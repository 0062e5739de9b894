#!/usr/bin/env python
"""Serving soak: a closed+open-loop load generator that drives the
ServingEngine under the armed PT_FAULT matrix and asserts the SLOs.

Traffic:
  * CLOSED loop — ``--clients`` threads, each submits one request with a
    generous deadline, waits for the terminal reply, repeats.  Models
    well-behaved callers and guarantees a stream of successes for the
    latency percentiles.
  * OPEN loop — the main thread fires ``--requests`` requests at
    ``--qps`` regardless of replies, each with a ``--deadline-ms``
    deadline.  Models the traffic that does NOT slow down when the
    server does — the load that admission control and shedding exist
    for.

Chaos (armed by the caller via PT_FAULT, see docs/serving.md):
  ``serve_slow_batch`` latency spikes, ``serve_dispatch`` batch failures
  (trips the breaker; it must also RECOVER), ``queue_overflow`` forced
  sheds, ``compile_storm`` cold-compile storms, and ``sigterm`` — the
  soak delivers a real SIGTERM to itself at open-loop request index
  ``at`` and the engine must drain: finish in-flight work, refuse new
  requests, reach STOPPED, with the process alive to report.

Asserted SLOs (--assert-slo), all from ``serving.*`` metrics:
  * every admitted request got a terminal reply; ``serving.deadlocks``
    == 0; counters reconcile (admitted == completed + errors +
    deadline_exceeded + shed)
  * p99 latency is finite (and there WERE successes)
  * shed rate <= --shed-ceiling
  * breaker tripped AND recovered (--expect-breaker)
  * SIGTERM drain observed: handler ran, engine STOPPED, post-drain
    submissions refused (--expect-drain)

Observability gates (docs/observability.md):
  * --trace-out PATH exports the Perfetto trace and VERIFIES it: a
    chosen successful request has exactly ONE `serving.request` root
    span, that root links (via its children's batch_span_id) to a
    `serving.batch` span whose `links` carry the request's trace id,
    and the queue_wait + dispatch + device child spans cover >= 90% of
    the root span's duration — the trace actually answers "why was
    this request slow".
  * --metrics-port N starts the engine-owned /metrics endpoint; the
    soak scrapes it mid-run (serving_admitted_total present) and again
    post-drain, asserting the scraped accounting identity
    admitted == completed + errors + deadline_exceeded + shed.
  * --expect-flight requires a flight-recorder dump in PT_FLIGHT_DIR
    containing at least one `serving.batch` span and a
    `fault.injected` serve_dispatch event (the mid-batch crash left a
    usable postmortem).

``--scenario decode`` switches to the streaming-generation soak
(`run_decode_scenario`): open-loop token-stream load over the PAGED KV
pool with mixed prompt lengths, mid-soak cancellations, overlong-prompt
refusals, the ``decode_step`` fault site, and token-level SLO gates
(TTFT/ITL histograms, bitwise greedy parity over the same page
geometry/quantization, zero post-warmup compiles, no KV slot OR page
leaks, prefix-cache hits when shared prompts flow, live draft/verify
acceptance when --speculative) — see docs/generation.md.
``--capacity-floor N`` appends the fixed-budget density gate
(`run_capacity_gate`): a hard KV byte budget, an oversubscribed slot
table, and a stream ramp that must queue at admission backpressure —
never die mid-stream — while sustaining >= N concurrent streams at SLO.

Prints one JSON line with the verdict and the metrics that prove it
(the serving block comes from observability.telemetry_snapshot, the
same schema fault_soak.py prints).
"""
import argparse
import json
import os
import signal
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _harness  # noqa: E402 - shared stage/watchdog/JSON-tail contract


def build_predictor_backend(tmpdir):
    """Tiny real model through the full stack: save_inference_model ->
    Predictor (per-bucket AOT executables, single-flight compiles)."""
    import numpy as np
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[8], dtype='float32')
            h = fluid.layers.fc(x, 16, act='relu')
            probs = fluid.layers.fc(h, 4, act='softmax')
    exe, scope = fluid.Executor(), fluid.Scope()
    model_dir = os.path.join(tmpdir, 'model')
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ['x'], [probs], exe, main)
    predictor = fluid.inference.Predictor(model_dir)
    return predictor.run


def build_stub_backend(latency_s):
    import numpy as np

    def backend(feed):
        if latency_s:
            time.sleep(latency_s)
        x = np.asarray(feed['x'])
        return [x.sum(axis=tuple(range(1, x.ndim)), keepdims=True)]
    return backend


def run_decode_scenario(args):
    """Streaming-decode soak (--scenario decode): open-loop generation
    load with mixed prompt lengths against a GenerationEngine, mid-soak
    client cancellations, and deliberately-overlong prompts that must be
    refused (never truncated).  Asserts, under the armed PT_FAULT matrix
    (``decode_step`` breaks one fused window mid-soak):

      * zero no-reply streams and ``serving.deadlocks == 0``; admitted
        == completed + errors + deadline_exceeded + shed
      * TTFT and ITL histograms populated (the telemetry quantiles are
        finite)
      * at least one mixed prefill+decode dispatch round
      * bitwise greedy parity: the engine's fused K-token stream equals
        a sequential (K=1) single-request reference
      * ZERO new executable compiles after warmup — batch composition,
        prompt length, and sampling params never retrace
      * every KV slot returned to the free list after drain
    """
    import numpy as np
    import paddle_tpu.observability as obs
    from paddle_tpu.observability import flight as _flight
    from paddle_tpu.serving.engine import ServingConfig
    from paddle_tpu.serving.generation import (DecodeRuntime,
                                               GenerationConfig,
                                               GenerationEngine)
    from paddle_tpu.serving.generation.decode import random_weights

    _flight.install()
    _harness.stage('decode_setup')
    cfg = dict(vocab=128, d_model=32, n_layer=2, n_head=4, n_kv_head=2,
               d_ffn=64, theta=10000.0, max_len=32)
    w = random_weights(cfg, seed=0)
    rt = DecodeRuntime(w, cfg, slots=args.slots, prefill_chunk=4,
                       page_len=args.page_len, pages=args.pages,
                       kv_quant=args.kv_quant)
    K = args.decode_window
    engine = GenerationEngine(
        rt, config=ServingConfig(max_queue=args.max_queue,
                                 drain_timeout_s=30.0),
        gen_config=GenerationConfig(
            decode_window=K,
            speculative=args.speculative)).start()

    # parity gate first (its executables land before the warmup
    # snapshot): fused engine stream == sequential K=1 reference over
    # the SAME page geometry and quantization (speculative decode, if
    # on, must also be bitwise-invisible here).  The PT_FAULT matrix is
    # disarmed for this pre-flight — fault fire counts (at=N) index
    # into SOAK traffic rounds, not the parity probe — and re-armed
    # from the environment before traffic starts
    from paddle_tpu.testing import faults as _faults
    _faults.configure('')
    ref_prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    ref_rt = DecodeRuntime(w, cfg, slots=1, prefill_chunk=4,
                           page_len=args.page_len, kv_quant=args.kv_quant)
    ref = ref_rt.generate(ref_prompt, 8, steps_per_window=1)
    got = engine.generate(ref_prompt, max_new=8).result(60)
    if not got.ok or list(got.outputs[0]) != ref:
        sys.exit('serve_soak[decode]: greedy parity broken: engine=%r '
                 'sequential=%r'
                 % (list(got.outputs[0]) if got.ok else got.status, ref))

    rt.warmup(steps=K, speculative=args.speculative)
    _faults.configure()
    compiles0 = obs.counters().get('generation.compiles') or 0

    _harness.stage('decode_traffic')
    streams, cancellers = [], []
    overlong = 0
    period = 1.0 / args.qps if args.qps > 0 else 0.0
    lengths = (2, 5, 9, 14, 20)
    # every well-formed prompt opens with one full page of shared
    # "system prefix" so the prefix cache has something real to hit
    shared = ([(3 + j) % (cfg['vocab'] - 1) + 1
               for j in range(rt.cache.page_len)]
              if rt.prefix is not None else [])
    for i in range(args.requests):
        if i % 11 == 10:
            prompt = list(range(1, 40))        # must be REFUSED, whole
            overlong += 1
        else:
            n = lengths[i % len(lengths)]
            prompt = shared + [(7 * i + j) % (cfg['vocab'] - 1) + 1
                               for j in range(n)]
        s = engine.generate(prompt,
                            max_new=min(8, cfg['max_len'] - min(
                                len(prompt), cfg['max_len'] - 1)),
                            temperature=0.8 if i % 3 else 0.0,
                            top_k=5 if i % 3 else 0, seed=i,
                            timeout_s=args.deadline_ms / 1e3)
        streams.append(s)
        if args.cancel_every and i % args.cancel_every \
                == args.cancel_every - 1:
            def canceller(stream=s):
                try:
                    next(stream.tokens(timeout=20.0))
                except (TimeoutError, StopIteration):
                    pass
                stream.cancel()                # mid-stream, after TTFT
            t = threading.Thread(target=canceller, daemon=True)
            t.start()
            cancellers.append(t)
        if period:
            time.sleep(period)
    for t in cancellers:
        t.join(timeout=30.0)
    engine.stop()

    _harness.stage('decode_audit')
    statuses, no_reply = {}, 0
    for s in streams:
        if not s.done():
            no_reply += 1
            continue
        res = s.result(0)
        key = (res.status if res.status != 'rejected'
               else 'rejected.%s' % res.reason)
        statuses[key] = statuses.get(key, 0) + 1

    tel = obs.telemetry_snapshot('serving')
    c = obs.counters()
    compiles_during = (c.get('generation.compiles') or 0) - compiles0
    if rt.prefix is not None:
        rt.prefix.reset()          # cached pages are holds, not leaks
    pages_leaked = int(rt.pool.in_use())
    rec = {
        'scenario': 'decode',
        'requests_submitted': len(streams),
        'statuses': statuses,
        'no_reply': no_reply,
        'cancels_requested': len(cancellers),
        'overlong_submitted': overlong,
        'compiles_after_warmup': compiles_during,
        'mixed_dispatches': int(c.get('generation.mixed_dispatches') or 0),
        'tokens': int(c.get('generation.tokens') or 0),
        'free_slots': rt.free_slots(),
        'kv_pages_leaked': pages_leaked,
        'prefix_hits': int(c.get('generation.prefix_hits') or 0),
        'spec_proposed': int(c.get('generation.spec_proposed') or 0),
        'spec_accepted': int(c.get('generation.spec_accepted') or 0),
        'kv_backpressure': int(c.get('generation.kv_backpressure') or 0),
        'kv_oom': int(c.get('generation.kv_oom') or 0),
        'kv_pool': rt.pool_snapshot(),
        'state': engine.state,
    }
    rec.update(tel)
    print(json.dumps(rec))

    if args.assert_slo:
        if no_reply:
            sys.exit('serve_soak[decode]: %d stream(s) never got a '
                     'terminal reply' % no_reply)
        if rec['deadlocks']:
            sys.exit('serve_soak[decode]: serving.deadlocks=%d'
                     % rec['deadlocks'])
        if rec['terminal_replies'] != rec['admitted']:
            sys.exit('serve_soak[decode]: terminal replies (%d) != '
                     'admitted (%d)' % (rec['terminal_replies'],
                                        rec['admitted']))
        if not statuses.get('ok'):
            sys.exit('serve_soak[decode]: zero successful streams')
        for q in ('ttft_p50_ms', 'ttft_p99_ms', 'itl_p50_ms',
                  'itl_p99_ms'):
            if rec[q] is None or not np.isfinite(rec[q]):
                sys.exit('serve_soak[decode]: %s is not finite: %r — '
                         'token-level SLO histogram unpopulated'
                         % (q, rec[q]))
        if rec['mixed_dispatches'] < 1:
            sys.exit('serve_soak[decode]: no mixed prefill+decode '
                     'dispatch round observed')
        if compiles_during:
            sys.exit('serve_soak[decode]: %d executable compile(s) after '
                     'warmup — decode loop retraced' % compiles_during)
        if overlong and not statuses.get('rejected.too_long'):
            sys.exit('serve_soak[decode]: overlong prompts were not '
                     'refused as too_long')
        if len(streams) > len(cancellers) + overlong \
                and not statuses.get('shed'):
            sys.exit('serve_soak[decode]: cancellations produced no shed '
                     'replies')
        if rec['free_slots'] != rt.slots:
            sys.exit('serve_soak[decode]: %d/%d KV slots leaked'
                     % (rt.slots - rec['free_slots'], rt.slots))
        if pages_leaked:
            sys.exit('serve_soak[decode]: %d KV pages still allocated '
                     'after drain (post prefix-cache reset)'
                     % pages_leaked)
        if rt.prefix is not None and rec['prefix_hits'] < 1:
            sys.exit('serve_soak[decode]: shared-prefix prompts produced '
                     'no prefix-cache hits')
        if args.speculative and (rec['spec_proposed'] < 1
                                 or rec['spec_accepted'] < 1):
            sys.exit('serve_soak[decode]: speculative decode proposed=%d '
                     'accepted=%d — draft/verify pipeline inert'
                     % (rec['spec_proposed'], rec['spec_accepted']))
        if rec['state'] != 'stopped':
            sys.exit('serve_soak[decode]: engine did not reach STOPPED '
                     '(state=%s)' % rec['state'])
    if args.capacity_floor:
        return run_capacity_gate(args, w, cfg)
    return 0


def run_capacity_gate(args, w, cfg):
    """Fixed-budget serving-density gate (--capacity-floor N): size the
    page pool to a hard byte budget, oversubscribe the slot table, and
    ram the engine with more streams than the pages can hold at once.
    The excess must queue at ADMISSION (generation.kv_backpressure > 0)
    — never die mid-stream with kv_oom — every stream must still finish
    OK, and the peak concurrency the budget sustained must beat the
    floor.  With int8 pages the floor is set at >= 4x the streams a
    dense PR-11 layout (one f32 max_len strip each) could reserve in
    the same bytes.  The verdict is ledgered as ``decode_capacity``.

    ``max_new = decode_window + 1`` keeps every stream inside its
    admission-time page span (one prefill token plus exactly one fused
    window), so admission is provably the only pressure path."""
    import numpy as np  # noqa: F401 - parity with sibling scenarios
    import paddle_tpu.observability as obs
    from paddle_tpu.serving.engine import ServingConfig
    from paddle_tpu.serving.generation import (CacheConfig, DecodeRuntime,
                                               GenerationConfig,
                                               GenerationEngine)
    from paddle_tpu.testing import faults as _faults

    _harness.stage('decode_capacity')
    _faults.configure('')   # density measurement, not chaos: run clean
    K = args.decode_window
    quant = args.kv_quant or 'int8'
    page_len = args.page_len or 4
    geom = CacheConfig(slots=1, layers=cfg['n_layer'],
                       kv_heads=cfg['n_kv_head'], max_len=cfg['max_len'],
                       head_dim=cfg['d_model'] // cfg['n_head'],
                       page_len=page_len, quant=quant)
    budget = args.capacity_budget
    pages = max(2, budget // geom.page_bytes() + 1)   # +1: garbage page
    dense_streams = max(1, budget // geom.dense_slot_bytes())
    # oversubscribed slot table: pages, not slots, must bind admission;
    # prefix cache off so every stream has identical page demand
    slots = 16
    rt = DecodeRuntime(w, cfg, slots=slots, prefill_chunk=4,
                       page_len=page_len, pages=pages, kv_quant=quant,
                       prefix_cache=False)
    engine = GenerationEngine(
        rt, config=ServingConfig(max_queue=256, drain_timeout_s=60.0),
        gen_config=GenerationConfig(decode_window=K,
                                    speculative=False)).start()
    rt.warmup(steps=K)
    bp0 = int(obs.counters().get('generation.kv_backpressure') or 0)

    peak = [0]
    done = threading.Event()

    def poll():
        while not done.is_set():
            peak[0] = max(peak[0], rt.allocator.in_use())
            time.sleep(0.001)

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    requests = 3 * slots
    streams = []
    for i in range(requests):
        n = 1 + (i % 3)
        prompt = ([(3 + j) % (cfg['vocab'] - 1) + 1
                   for j in range(page_len)] +
                  [(7 * i + j) % (cfg['vocab'] - 1) + 1 for j in range(n)])
        streams.append(engine.generate(prompt, max_new=K + 1, seed=i,
                                       timeout_s=120.0))
    ok = 0
    for s in streams:
        try:
            ok += 1 if s.result(120).ok else 0
        except Exception:
            pass
    done.set()
    poller.join(1.0)
    engine.stop()

    backpressure = (int(obs.counters().get('generation.kv_backpressure')
                        or 0) - bp0)
    pages_leaked = int(rt.pool.in_use())
    slo_held = (ok == requests and pages_leaked == 0
                and rt.free_slots() == rt.slots)
    streams_at_slo = int(peak[0]) if slo_held else 0
    floor = args.capacity_floor
    rec = {'scenario': 'decode_capacity', 'requests': requests,
           'streams_ok': ok, 'kv_budget_bytes': budget,
           'page_len': page_len, 'kv_quant': quant, 'pages': pages,
           'dense_streams_in_budget': dense_streams,
           'kv_backpressure': backpressure,
           'kv_pages_leaked': pages_leaked,
           'streams_at_slo': streams_at_slo,
           'density_x_vs_dense': streams_at_slo // dense_streams,
           'capacity_floor': floor}
    print(json.dumps(rec))
    if ok != requests:
        sys.exit('serve_soak[capacity]: %d/%d streams failed under the '
                 'page budget — backpressure must queue, never kill'
                 % (requests - ok, requests))
    if backpressure < 1:
        sys.exit('serve_soak[capacity]: the ramp never hit admission '
                 'backpressure — the budget was not binding, density '
                 'unproven')
    if pages_leaked:
        sys.exit('serve_soak[capacity]: %d KV pages still allocated '
                 'after drain' % pages_leaked)
    if streams_at_slo < floor:
        sys.exit('serve_soak[capacity]: %d concurrent streams at SLO '
                 'under a %d-byte budget — floor is %d'
                 % (streams_at_slo, budget, floor))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--scenario', default='oneshot',
                    choices=('oneshot', 'decode'),
                    help='oneshot: the PR-8 request/reply soak; decode: '
                         'streaming generation over the KV-cache runtime')
    ap.add_argument('--requests', type=int, default=80,
                    help='open-loop request count')
    ap.add_argument('--qps', type=float, default=120.0,
                    help='open-loop submission rate')
    ap.add_argument('--clients', type=int, default=3,
                    help='closed-loop client threads')
    ap.add_argument('--deadline-ms', type=float, default=2000.0,
                    help='open-loop per-request deadline')
    ap.add_argument('--max-queue', type=int, default=32)
    ap.add_argument('--policy', default='shed_oldest',
                    choices=('reject', 'block', 'shed_oldest'))
    ap.add_argument('--shed-ceiling', type=float, default=0.35,
                    help='max tolerated shed fraction of admitted')
    ap.add_argument('--stub', action='store_true',
                    help='stub backend (no compiles) instead of a real '
                         'Predictor')
    ap.add_argument('--stub-latency-ms', type=float, default=2.0)
    ap.add_argument('--assert-slo', action='store_true')
    ap.add_argument('--expect-breaker', action='store_true',
                    help='require breaker tripped AND recovered')
    ap.add_argument('--expect-drain', action='store_true',
                    help='require a SIGTERM-initiated drain was observed')
    ap.add_argument('--trace-out', default=None, metavar='PATH',
                    help='export the Perfetto trace here and verify a '
                         'request decomposes into queue/dispatch/device '
                         'child spans linked to its batch span')
    ap.add_argument('--metrics-port', type=int, default=None,
                    help='engine-owned /metrics port (0 = ephemeral); '
                         'the soak scrapes it mid-run and post-drain')
    ap.add_argument('--expect-flight', action='store_true',
                    help='require a flight dump with a serving.batch '
                         'span and a serve_dispatch fault event')
    ap.add_argument('--slots', type=int, default=4,
                    help='[decode] KV cache slots')
    ap.add_argument('--decode-window', type=int, default=4,
                    help='[decode] tokens per fused decode launch')
    ap.add_argument('--cancel-every', type=int, default=7,
                    help='[decode] cancel every Nth stream after its '
                         'first token (0 = never)')
    ap.add_argument('--kv-quant', default=None, choices=('none', 'int8'),
                    help='[decode] KV page quantization (default: none; '
                         'the --capacity-floor rerun defaults to int8)')
    ap.add_argument('--page-len', type=int, default=None,
                    help='[decode] tokens per KV page (default: largest '
                         'divisor of max_len that is <= 8)')
    ap.add_argument('--pages', type=int, default=None,
                    help='[decode] KV pool depth (default: enough for '
                         'every slot at max_len)')
    ap.add_argument('--speculative', action='store_true',
                    help='[decode] draft+verify speculative decoding')
    ap.add_argument('--capacity-floor', type=int, default=0,
                    help='[decode] after the soak, run the fixed-budget '
                         'capacity gate and require at least this many '
                         'concurrent streams at SLO (0 = skip)')
    ap.add_argument('--capacity-budget', type=int, default=16384,
                    help='[decode] KV byte budget for the capacity gate')
    args = ap.parse_args()
    if args.scenario == 'decode':
        return run_decode_scenario(args)

    import numpy as np
    import paddle_tpu.observability as obs
    from paddle_tpu import serving
    from paddle_tpu.data_feeder import FeedBucketer
    from paddle_tpu.observability import flight as _flight
    from paddle_tpu.testing import faults as _faults

    _flight.install()   # an uncaught crash still leaves a postmortem

    _harness.stage('setup')
    import tempfile
    tmpdir = tempfile.mkdtemp(prefix='pt_serve_soak.')
    backend = (build_stub_backend(args.stub_latency_ms / 1e3) if args.stub
               else build_predictor_backend(tmpdir))

    bucketer = FeedBucketer(boundaries=[1, 2, 4, 8, 16, 32])
    engine = serving.ServingEngine(
        backend, bucketer=bucketer,
        config=serving.ServingConfig(
            max_queue=args.max_queue, overflow_policy=args.policy,
            max_batch_rows=32, batch_linger_s=0.002,
            breaker_failure_threshold=3, breaker_storm_threshold=3,
            breaker_cooldown_s=0.2, drain_timeout_s=20.0,
            metrics_port=args.metrics_port))

    # the soak's own SIGTERM recorder goes in FIRST so the engine's
    # drain handler (installed second) chains to it — the process stays
    # alive to finish the drain and report, proving handler composition
    sigterm_seen = [False]
    signal.signal(signal.SIGTERM, lambda s, f: sigterm_seen.__setitem__(
        0, True))
    engine.install_signal_handlers()
    engine.start()

    futures = []
    fut_lock = threading.Lock()
    stop_clients = threading.Event()

    def feed_at(i):
        rows = 1 + (i % 3)
        rng = np.random.RandomState(2000 + i)
        return {'x': rng.rand(rows, 8).astype('float32')}

    def closed_loop(cid):
        i = 0
        while not stop_clients.is_set():
            fut = engine.submit(feed_at(10000 * (cid + 1) + i),
                                timeout_s=10.0)
            with fut_lock:
                futures.append(fut)
            try:
                res = fut.result(timeout=30.0)
            except TimeoutError:
                return
            if res.status == 'rejected' and res.reason in ('draining',
                                                           'not_ready'):
                return
            i += 1

    clients = [threading.Thread(target=closed_loop, args=(c,), daemon=True)
               for c in range(args.clients)]
    for t in clients:
        t.start()

    # open loop: fixed-rate fire-and-remember
    _harness.stage('traffic')
    period = 1.0 / args.qps if args.qps > 0 else 0.0
    for i in range(args.requests):
        if _faults.active('sigterm') and _faults.fire('sigterm', step=i):
            os.kill(os.getpid(), signal.SIGTERM)   # engine drains, we live
        fut = engine.submit(feed_at(i), timeout_s=args.deadline_ms / 1e3)
        with fut_lock:
            futures.append(fut)
        if period:
            time.sleep(period)
        if engine.breaker.state != 'closed':
            # stretch the tail while tripped so the cooldown elapses
            # with live traffic still flowing — the recovery probe needs
            # a real batch to run against
            time.sleep(0.05)

    def scrape(path='/metrics'):
        import urllib.request
        url = 'http://127.0.0.1:%d%s' % (engine.metrics_port, path)
        with urllib.request.urlopen(url, timeout=5.0) as resp:
            return resp.read().decode()

    def prom_values(text):
        out = {}
        for line in text.splitlines():
            if line.startswith('#') or not line.strip():
                continue
            parts = line.split()
            if len(parts) == 2 and '{' not in parts[0]:
                out[parts[0]] = float(parts[1])
        return out

    # mid-soak scrape: the endpoint must be live DURING traffic (an
    # exact accounting identity waits for the post-drain scrape —
    # in-flight requests make it inexact here)
    mid_scrape_ok = None
    if args.metrics_port is not None:
        if engine.metrics_port is None:
            sys.exit('serve_soak: --metrics-port set but the engine did '
                     'not start a metrics server (is PT_OBS=0?)')
        mid_scrape_ok = 'serving_admitted_total' in prom_values(scrape())

    _harness.stage('drain')
    drained = engine.drain()
    stop_clients.set()
    for t in clients:
        t.join(timeout=10.0)

    # ---------------------------------------------------------- audit
    statuses = {}
    no_reply = 0
    with fut_lock:
        all_futs = list(futures)
    for fut in all_futs:
        if not fut.done():
            no_reply += 1
            continue
        res = fut.result(0)
        statuses[res.status] = statuses.get(res.status, 0) + 1

    # the serving block comes straight from the shared schema; p50/p99
    # read the serving.latency_ms bounded histogram (observed only for
    # OK replies — the same population the old in-process list held)
    tel = obs.telemetry_snapshot('serving')
    admitted = tel['admitted']
    terminal = tel['terminal_replies']
    shed_rate = tel['shed_rate']
    p99 = tel['p99_ms']

    rec = {
        'requests_submitted': len(all_futs),
        'statuses': statuses,
        'no_reply': no_reply,
        'sigterm_seen': sigterm_seen[0],
        'drained': bool(drained),
        'state': engine.state,
        'mid_scrape_ok': mid_scrape_ok,
    }
    rec.update(tel)
    print(json.dumps(rec))

    if args.assert_slo:
        if no_reply:
            sys.exit('serve_soak: %d request(s) never got a terminal '
                     'reply' % no_reply)
        if rec['deadlocks']:
            sys.exit('serve_soak: serving.deadlocks=%d' % rec['deadlocks'])
        if terminal != admitted:
            sys.exit('serve_soak: terminal replies (%d) != admitted (%d) '
                     '— a request was dropped without a reply'
                     % (terminal, admitted))
        if not statuses.get('ok'):
            sys.exit('serve_soak: zero successful requests — no p99 to '
                     'measure')
        if p99 is None or not np.isfinite(p99):
            sys.exit('serve_soak: p99 is not finite: %r' % p99)
        if shed_rate > args.shed_ceiling:
            sys.exit('serve_soak: shed rate %.3f above the ceiling %.3f'
                     % (shed_rate, args.shed_ceiling))
        if not rec['state'] == 'stopped':
            sys.exit('serve_soak: engine did not reach STOPPED '
                     '(state=%s)' % rec['state'])
    if args.expect_breaker:
        if rec['breaker_trips'] < 1 or rec['breaker_recoveries'] < 1:
            sys.exit('serve_soak: breaker trips=%d recoveries=%d — '
                     'expected it to trip AND recover'
                     % (rec['breaker_trips'], rec['breaker_recoveries']))
    if args.expect_drain:
        if not sigterm_seen[0]:
            sys.exit('serve_soak: SIGTERM never chained to the soak '
                     'recorder — drain handler composition broken')
        if not rec['drained']:
            sys.exit('serve_soak: drain did not complete in budget')
        probe = engine.submit({'x': np.ones((1, 8), 'float32')}).result(1)
        if probe.status != 'rejected':
            sys.exit('serve_soak: post-drain submit was not refused '
                     '(%s)' % probe.status)

    # ------------------------------------------- /metrics scrape gate
    if args.metrics_port is not None:
        if not mid_scrape_ok:
            sys.exit('serve_soak: mid-soak /metrics scrape missing '
                     'serving_admitted_total')
        # post-drain the queue is empty, so the scraped identity must
        # be EXACT: every admitted request reached one terminal counter
        pv = prom_values(scrape())
        s_adm = pv.get('serving_admitted_total', -1)
        s_term = (pv.get('serving_completed_total', 0) +
                  pv.get('serving_errors_total', 0) +
                  pv.get('serving_deadline_exceeded_total', 0) +
                  pv.get('serving_shed_total', 0))
        if int(s_adm) != int(s_term):
            sys.exit('serve_soak: scraped accounting identity broken: '
                     'admitted=%d != terminal=%d' % (s_adm, s_term))

    # --------------------------------------------- trace export gate
    if args.trace_out:
        path = obs.export_chrome_trace(args.trace_out)
        with open(path) as f:
            events = json.load(f)['traceEvents']
        ok_tids = [f_.traceparent.split('-')[1] for f_ in all_futs
                   if f_.done() and f_.result(0).status == 'ok'
                   and f_.traceparent]
        if not ok_tids:
            sys.exit('serve_soak: --trace-out with zero ok requests')
        verified = None
        for tid in ok_tids:
            roots = [e for e in events
                     if e.get('name') == 'serving.request'
                     and e.get('args', {}).get('trace_id') == tid]
            if len(roots) != 1:
                sys.exit('serve_soak: trace %s has %d serving.request '
                         'root spans (want exactly 1)' % (tid, len(roots)))
            kids = {e['name']: e for e in events
                    if e.get('name') in ('serving.queue_wait',
                                         'serving.dispatch',
                                         'serving.device')
                    and e.get('args', {}).get('trace_id') == tid}
            if len(kids) != 3:
                continue   # ring may have evicted an early request
            batch_sid = kids['serving.queue_wait']['args']['batch_span_id']
            batches = [e for e in events if e.get('name') == 'serving.batch'
                       and e.get('args', {}).get('span_id') == batch_sid]
            if len(batches) != 1 or \
                    tid not in batches[0]['args'].get('links', ()):
                sys.exit('serve_soak: trace %s: batch span %s missing or '
                         'not linking the request' % (tid, batch_sid))
            covered = sum(k['dur'] for k in kids.values())
            if covered < 0.9 * roots[0]['dur']:
                sys.exit('serve_soak: trace %s: child spans cover %.1f%% '
                         'of the root span (want >= 90%%)'
                         % (tid, 100.0 * covered / max(roots[0]['dur'],
                                                       1e-9)))
            verified = tid
            break
        if verified is None:
            sys.exit('serve_soak: no ok request had a full '
                     'queue/dispatch/device decomposition in the trace')
        print('serve_soak: trace verified for request %s -> %s'
              % (verified, path), file=sys.stderr)

    # ------------------------------------------- flight recorder gate
    if args.expect_flight:
        fdir = _flight.flight_dir()
        if not fdir:
            sys.exit('serve_soak: --expect-flight needs PT_FLIGHT_DIR')
        dumps = sorted(fn for fn in os.listdir(fdir)
                       if fn.startswith('flight_') and fn.endswith('.json'))
        if not dumps:
            sys.exit('serve_soak: no flight dump in %s' % fdir)
        found_batch = found_fault = False
        for fn in dumps:
            with open(os.path.join(fdir, fn)) as f:
                art = json.load(f)
            evs = art.get('events', [])
            found_batch = found_batch or any(
                e.get('name') == 'serving.batch' for e in evs)
            found_fault = found_fault or any(
                e.get('name') == 'fault.injected'
                and e.get('args', {}).get('site') == 'serve_dispatch'
                for e in evs)
        if not (found_batch and found_fault):
            sys.exit('serve_soak: flight dump(s) missing %s' % ', '.join(
                n for n, ok in (('serving.batch span', found_batch),
                                ('serve_dispatch fault event', found_fault))
                if not ok))
    return 0


if __name__ == '__main__':
    _harness.set_tool('SERVE_SOAK')
    _harness.main_guard(main, flight_tag='serve_soak.watchdog')
