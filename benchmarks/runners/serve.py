"""The serving runner: GenerationEngine over DecodeRuntime and the paged
pool, under an open loop from ONE thread.

The generator thread sends each request when it is due, and between
sends reads how many tokens every live stream has; a request's first and
last token times are the reads at which they were first seen (streams
deliver a decode window's tokens together, and the poll interval is a
millisecond or two).  Latency is counted from the time a request was DUE,
not from when it was sent.  Requests that arrive inside the window and
finish after it are drained and counted in the latency metrics;
`serve_tokens_per_s` counts only tokens seen inside the window.

The runtime's model dict and its weights' shapes come from
`model_dict(config, traffic)` and `weight_shapes(model)` of the
configuration's own builds/<config>.py where it has one (README, "Adding a
cell"); `model_dict` and `dense_weight_shapes` below are the
fall-back for a dense decoder that brings none.  Either way the PROGRAM
decides the weights' names: `make_weights` refuses shapes whose names are
not `weight_names(model)`.
"""
import shutil
import tempfile
import time

import numpy as np

from lib import memory as _memory
from lib import spans as _spans
from lib import traffic as _traffic
from lib import xplane as _xplane
from runners.train import _counters, _delta, load_build, load_reference

POLL_S = 0.002
MEMORY_EVERY = 64          # polls between two readings of device memory


def model_dict(config, traffic):
    """The fall-back: the program's model dict of a dense decoder from
    the configuration's published keys; `max_len` is one slot's share of
    the pool, from the traffic file."""
    return {'vocab': int(config['vocab_size']),
            'd_model': int(config['hidden_size']),
            'n_layer': int(config['num_hidden_layers']),
            'n_head': int(config['num_attention_heads']),
            'n_kv_head': int(config['num_key_value_heads']),
            'd_ffn': int(config['intermediate_size']),
            'theta': float(config['rope_theta']),
            'max_len': int(traffic['slot_tokens'])}


def dense_weight_shapes(model):
    """The fall-back: {weight name: shape} of a dense decoder."""
    d, v = model['d_model'], model['vocab']
    h, hkv, f = model['n_head'], model['n_kv_head'], model['d_ffn']
    dh = d // h
    per_layer = {'att_q_w': (d, h * dh), 'att_k_w': (d, hkv * dh),
                 'att_v_w': (d, hkv * dh), 'att_o_w': (h * dh, d),
                 'att_norm': (d,), 'ffn_norm': (d,), 'ffn_fc1_w': (d, f),
                 'ffn_fc3_w': (d, f), 'ffn_fc2_w': (f, d)}
    shapes = {'tok_emb': (v, d), 'final_norm': (d,), 'lm_proj_w': (d, v)}
    for i in range(model['n_layer']):
        for k, s in per_layer.items():
            shapes['layer_%d_%s' % (i, k)] = s
    return shapes


def make_weights(model, shapes, seed, std, dtype):
    """Every weight of `shapes` on the device, from the seed, in ONE
    jitted call, in the type it is served in: a norm's scale is ones,
    anything else normal with deviation `std`."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.generation import weight_names
    names = weight_names(model)
    if sorted(names) != sorted(shapes):
        raise ValueError(
            'weight layout drifted: the program names %s which the '
            'configuration gives no shape, and the configuration shapes %s '
            'which the program does not name'
            % (sorted(set(names) - set(shapes)) or 'nothing',
               sorted(set(shapes) - set(names)) or 'nothing'))
    dt = jnp.dtype(dtype)

    def init(key):
        out = {}
        for i, n in enumerate(names):
            if n.endswith('norm'):
                out[n] = jnp.ones(shapes[n], dt)
            else:
                out[n] = (std * jax.random.normal(
                    jax.random.fold_in(key, i), shapes[n],
                    jnp.float32)).astype(dt)
        return out

    key = jax.random.fold_in(jax.random.key(int(seed) & 0x7fffffff),
                             int(seed) >> 31)
    return jax.jit(init)(key)


def build_runtime(config, traffic, seed, spans, record):
    """The runtime, its weights and warm executables, with the
    benchmark's spans wrapped around its two launch calls."""
    from paddle_tpu.serving.generation import DecodeRuntime
    own = record['build'] = load_build(config)
    model = getattr(own, 'model_dict', model_dict)(config, traffic)
    shapes = getattr(own, 'weight_shapes', dense_weight_shapes)(model)
    weights = make_weights(model, shapes, seed,
                           float(config['initializer_range']),
                           config['torch_dtype'])
    rt = DecodeRuntime(weights, model, slots=int(traffic['slots']),
                       prefill_chunk=int(traffic['prefill_chunk']),
                       cache_dtype=config['torch_dtype'],
                       page_len=int(traffic['page_len']),
                       pages=int(traffic['pages']), kv_quant='none',
                       prefix_cache=bool(traffic['prefix_cache']))
    del weights
    t0 = time.perf_counter()
    rt.warmup(steps=int(traffic['decode_window']))
    record['warmup_s'] = time.perf_counter() - t0

    prefill, decode_window = rt.prefill, rt.decode_window

    def spanned_prefill(slot, tokens, offset, params):
        with spans.span('prefill'):
            return prefill(slot, tokens, offset, params)

    def spanned_window(steps, active, seeds, temps, topks):
        live = np.asarray(active, bool)
        record['windows'].append((int(live.sum()),
                                  int(rt.host_len[live].sum())))
        with spans.span('decode_window'):
            return decode_window(steps, active, seeds, temps, topks)

    rt.prefill, rt.decode_window = spanned_prefill, spanned_window
    return rt, model


def start_engine(rt, traffic):
    from paddle_tpu.serving.engine import ServingConfig
    from paddle_tpu.serving.generation import (GenerationConfig,
                                               GenerationEngine)
    return GenerationEngine(
        rt, config=ServingConfig(
            max_queue=int(traffic['max_queue']),
            drain_timeout_s=float(traffic['drain_seconds'])),
        gen_config=GenerationConfig(
            decode_window=int(traffic['decode_window']))).start()


class _Live(object):
    __slots__ = ('req', 'stream', 'sent', 'first', 'last', 'seen', 'in_window')

    def __init__(self, req, stream, sent):
        self.req, self.stream, self.sent = req, stream, sent
        self.first = self.last = None
        self.seen = self.in_window = 0


def open_loop(engine, requests, seconds, spans, drain_s, seed, memory=None):
    """Send `requests` (sorted by due time) on schedule, poll, drain.
    Returns (records, window_s, tokens_in_window, waiting_mid,
    waiting_end, drained_at)."""
    live, done = [], []
    t0 = time.perf_counter()
    nxt, waiting_mid, polls = 0, None, 0

    def poll(now):
        in_window = now - t0 <= seconds
        for lv in list(live):
            finished = lv.stream.done()     # read BEFORE the tokens: a
            n = len(lv.stream.tokens_so_far())   # finished count is final
            if n > lv.seen:
                if lv.first is None:
                    lv.first = now
                lv.last = now
                if in_window:
                    lv.in_window += n - lv.seen
                lv.seen = n
            if finished:
                live.remove(lv)
                done.append(lv)

    while True:
        now = time.perf_counter()
        if waiting_mid is None and now - t0 >= seconds / 2.0:
            waiting_mid = sum(1 for lv in live if lv.first is None)
        if now - t0 >= seconds:
            break
        while nxt < len(requests) and requests[nxt]['due'] <= now - t0:
            r = requests[nxt]
            with spans.span('submit'):
                stream = engine.generate(
                    r['prompt'], max_new=r['max_new'],
                    seed=(int(seed) + nxt) & 0x7fffffff,
                    timeout_s=seconds + drain_s)
            live.append(_Live(r, stream, time.perf_counter() - t0))
            nxt += 1
        poll(time.perf_counter())
        polls += 1
        if memory is not None and polls % MEMORY_EVERY == 0:
            memory.sample()
        due = requests[nxt]['due'] if nxt < len(requests) else seconds
        pause = min(POLL_S, max(0.0, t0 + due - time.perf_counter()))
        with spans.span('wait_request'):
            time.sleep(pause)
    window_s = time.perf_counter() - t0
    waiting_end = sum(1 for lv in live if lv.first is None)
    tokens_in_window = sum(lv.in_window for lv in live + done)
    limit = time.perf_counter() + drain_s
    while live and time.perf_counter() < limit:
        poll(time.perf_counter())
        time.sleep(POLL_S)
    drained_at = time.perf_counter() - t0
    records = []
    for lv in done + live:
        result = lv.stream.result(0) if lv.stream.done() else None
        ok = bool(result is not None and result.ok
                  and lv.seen == lv.req['max_new'])
        records.append({
            'due': lv.req['due'], 'sent': lv.sent, 'ok': ok,
            'first': None if lv.first is None else lv.first - t0,
            'last': None if lv.last is None else lv.last - t0,
            'tokens': lv.seen, 'prompt': int(lv.req['prompt'].size),
            'reason': getattr(result, 'reason', 'not_drained')})
    return (records, window_s, tokens_in_window, waiting_mid or 0,
            waiting_end, drained_at)


def compare(rt, model, config, traffic, seed, say):
    """Prefill, one decode window, one more chunk: the logits the pool
    gives at the last position against the reference's full forward, on a
    seeded sample of `compare_prompts` prompts from the traffic's pairs."""
    from paddle_tpu.serving.generation import SamplingParams
    ref = load_reference(config)
    K = int(traffic['decode_window'])
    pairs = _traffic.lognormal_pairs(
        int(traffic['pairs']), traffic['prompt'], traffic['output'],
        int(traffic['shared_prefix']))
    rng = _traffic.rng_for(seed, 5)
    lens = sorted(p for p, _ in pairs)
    picks = [lens[int(i)] for i in
             np.linspace(0, len(lens) * 0.75, int(traffic['compare_prompts']),
                         dtype=int)]
    rt.reset()
    worst, rows = 0.0, []
    for plen in picks:
        prompt = rng.integers(1, model['vocab'], plen, dtype=np.int32)
        slot = rt.alloc_slot()
        start = rt.try_begin(slot, prompt, K)
        assert start == 0, start
        for off in range(0, plen, rt.prefill_chunk):
            first, _ = rt.prefill(slot, prompt[off:off + rt.prefill_chunk],
                                  off, SamplingParams())
        active = np.zeros(rt.slots, bool)
        active[slot] = True
        zeros = np.zeros(rt.slots, np.int32)
        toks = rt.decode_window(K, active, zeros,
                                np.zeros(rt.slots, np.float32), zeros)[slot]
        assert rt.ensure_capacity(slot, plen + K + 1)
        _, logits = rt.prefill(slot, toks[-1:], plen + K, SamplingParams())
        rt.free_slot(slot)
        context = np.concatenate([prompt, [first], toks]).astype(np.int32)
        want = ref.last_logits(rt.w, model, context)
        got = np.asarray(logits, np.float32)
        err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        worst = max(worst, err)
        rows.append({'context': int(context.size), 'rel_err': err,
                     'max_err_over_max': float(np.max(np.abs(got - want))
                                               / np.max(np.abs(want))),
                     'finite': bool(np.isfinite(got).all())})
    rt.reset()
    ok = worst <= float(ref.LOGIT_RTOL) and all(r['finite'] for r in rows)
    say('compared', reference='benchmarks/references/%s.py'
        % config.get('reference', config['name']),
        comparing='logits at the last position after chunked prefill, one '
                  'decode window and one more chunk through the paged pool, '
                  "against the reference's full forward; float32, matmul "
                  'precision highest',
        rtol=float(ref.LOGIT_RTOL), worst_rel_err=worst, prompts=rows)
    return ok


def run(cell, config, traffic, seed, seconds, trace, t_start, device, say):
    import jax
    import paddle_tpu.observability as obs
    if not obs.enabled():
        raise RuntimeError('the benchmark reads obs counters: PT_OBS is off')
    if int(cell['chips']) != 1:
        raise ValueError('the serving runner drives one chip')
    c_start = _counters()
    spans, record = _spans.Spans(), {'windows': []}
    rt, model = build_runtime(config, traffic, seed, spans, record)
    drain_s = float(traffic['drain_seconds'])
    engine = start_engine(rt, traffic)
    trace_dir = None
    try:
        # first executions of the loaded executables, outside the window
        wrng = _traffic.rng_for(seed, 6)
        warm = [{'due': 0.0, 'max_new': int(traffic['decode_window']) + 1,
                 'prompt': wrng.integers(1, model['vocab'],
                                         int(traffic['prefill_chunk']) + 1,
                                         dtype=np.int32)}
                for _ in range(2)]
        open_loop(engine, warm, 0.05, spans, drain_s, seed)
        if rt.prefix is not None:
            rt.prefix.reset()
        c_warm = _counters()
        spans.reset()
        record['windows'] = []
        requests = _traffic.open_loop(traffic, model['vocab'], seed, seconds)
        if trace:
            trace_dir = tempfile.mkdtemp(prefix='bench_trace_')
            jax.profiler.start_trace(trace_dir)
            spans.annotate(True)
        memory = _memory.PeakSampler(jax.devices()[:1])
        setup_s = time.perf_counter() - t_start
        with spans.span('traced_window'):
            (records, window_s, tokens_in_window, waiting_mid, waiting_end,
             drained_at) = open_loop(engine, requests, seconds, spans,
                                     drain_s, seed, memory)
        memory_peak = memory.result()    # before the reference's own use
        say('memory', sampled_in_use_plus_reserved=memory.peak,
            samples=memory.samples, allocator_peak_in_use=memory.live_peak())
        summary = None
        if trace:
            spans.annotate(False)
            jax.profiler.stop_trace()
            path = _xplane.find_trace(trace_dir)
            summary = _xplane.summarize(_xplane.load(path)) if path else None
        c_end = _counters()
        drained = engine.drain(drain_s)
    finally:
        engine.stop()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    window = _delta(c_end, c_warm)
    compiles = int(window.get('generation.compiles', 0))
    failed = sum(1 for r in records if not r['ok'])
    say('requests', sent=len(records), failed=failed,
        waiting_mid=waiting_mid, waiting_end=waiting_end,
        drained_at_s=drained_at,
        tokens_per_s=tokens_in_window / window_s,
        ttft_ms=[None if r['first'] is None
                 else round((r['first'] - r['due']) * 1e3, 1)
                 for r in sorted(records, key=lambda r: r['due'])],
        reasons=sorted({str(r['reason']) for r in records}))
    logits_ok = compare(rt, model, config, traffic, seed, say)
    checks = {'logits_match_reference': logits_ok,
              'no_compile_in_window': compiles == 0,
              'engine_drained': bool(drained),
              'no_deadlock': window.get('serving.deadlocks', 0.0) == 0.0,
              'some_requests': len(records) > 0}
    say('checks', compiles_after_warmup=compiles, checks=checks)
    return {
        'correct': all(checks.values()),
        'attempted': len(records),
        'failed': failed,
        'setup_s': setup_s,
        'window_s': window_s,
        'requests': records,
        'tokens_in_window': tokens_in_window,
        'waiting_mid': waiting_mid,
        'waiting_end': waiting_end,
        'drained_at_s': drained_at,
        'windows': record['windows'],
        'warmup_s': record['warmup_s'],
        'model': model,
        'chips': 1,
        'memory_peak_bytes': memory_peak,
        'spans': dict(spans.seconds),
        'span_counts': dict(spans.counts),
        'counters': window,
        'setup_counters': _delta(c_warm, c_start),
        'trace': summary,
        'build': record['build'],
        'config': config,
        'traffic': traffic,
        'device': device,
    }


def sweep(cell, config, traffic, seed, seconds, rates, device, say):
    """One open-loop window per rate over one runtime.  The capacity is
    where completed tokens stop following the offer and the number waiting
    for a first token grows from the window's middle to its end; the knee
    is the highest rate at which the TTFT tail is still flat (README)."""
    from lib import stats
    spans, record = _spans.Spans(), {'windows': []}
    rt, model = build_runtime(config, traffic, seed, spans, record)
    drain_s = float(traffic['drain_seconds'])
    for rate in rates:
        rt.reset()
        record['windows'] = []
        engine = start_engine(rt, traffic)
        try:
            requests = _traffic.open_loop(traffic, model['vocab'], seed,
                                          seconds, rate=rate)
            (records, window_s, tokens, mid, end, drained_at) = open_loop(
                engine, requests, seconds, spans, drain_s, seed)
            engine.drain(drain_s)
        finally:
            engine.stop()
        ttft = [(r['first'] - r['due']) * 1e3 for r in records
                if r['first'] is not None]
        w = record['windows']
        say('sweep', rate_per_s=rate, sent=len(records),
            failed=sum(1 for r in records if not r['ok']),
            waiting_mid=mid, waiting_end=end, drained_at_s=drained_at,
            tokens_per_s=tokens / window_s,
            ttft_p50_ms=stats.quantile(ttft, 0.5) if ttft else None,
            ttft_p90_ms=stats.quantile(ttft, 0.9) if ttft else None,
            occupancy=(sum(n for n, _ in w) / len(w) / rt.slots) if w
            else None)
