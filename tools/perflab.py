#!/usr/bin/env python
"""The continuous performance lab: a scenario-matrix bench runner with
an append-only ledger and a baseline regression gate.

Exactly one way to produce a perf number in this repo (ROADMAP item 5):

  run      execute the scenario matrix, each scenario in a
           SUBPROCESS-ISOLATED child with a hard budget — one hang
           kills one scenario, not the round — and append one
           schema-validated, provenance-stamped record per scenario to
           the ledger (PERF_HISTORY.jsonl by default).
  compare  diff the newest ledger record per scenario against the
           committed PERF_BASELINE.json: deterministic counters are
           zero-tolerance, timings are noise-bounded best-of-K, and a
           record from another platform than the baseline's is a
           structured REFUSAL, not a pass.
  check    assert every requested scenario has a schema-valid,
           non-error, provenance-complete ledger record (the ci gate).
  bless    write the newest ledger records out as the new baseline.
  list     print the scenario registry.
  probe    one-shot diagnostic harnesses (absorbed tools/measure.py).
  models   the reference model-matrix benchmark CLI (absorbed
           tools/fluid_benchmark.py).

Scenarios (geometry via the BENCH_* shrink knobs, see docs/perflab.md):

  train_transformer  fused train-step throughput (tokens/s, MFU) via
                     run_steps K-launches — the bench.py headline
  train_resnet       ResNet training throughput (img/s)
  decode_stream      GenerationEngine streaming decode: tokens/s/chip
                     + TTFT/ITL p99 under open-loop load
  pod_parallel       all-reduce bandwidth over the local mesh + 2-host
                     lockstep scaling of HOST-side step throughput
                     (subprocess workers pinned to the CPU)
  fused_adam_micro   the kernelgen tier's headline op: ms/step of the
                     fused-Adam update

Record + comparison semantics live in
paddle_tpu/observability/perflab.py; the per-scenario metric schemas in
observability/export.py (SCHEMA['perflab.*']).

One process for each chip: this parent never initialises JAX (importing
paddle_tpu starts no backend) and runs its children strictly one after
another, so each child has the chip to itself.  There is no backend
probe and no fall-back to the CPU: a child that wants the chip and
finds none fails; a deliberate ``JAX_PLATFORMS=cpu`` round is labelled
``cpu``.  Every child compiles into the one cache directory
(core/compile_cache.cache_dir()).
"""
import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _harness  # noqa: E402 - shared stage/watchdog machinery

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_LEDGER = os.path.join(REPO_ROOT, 'PERF_HISTORY.jsonl')
DEFAULT_BASELINE = os.path.join(REPO_ROOT, 'PERF_BASELINE.json')

# the scenario matrix `run` executes by default, in order (the ledger
# bridge sections — perflab.bench etc. — are written by those tools
# themselves, not by the lab)
MATRIX = ('train_transformer', 'train_resnet', 'decode_stream',
          'pod_parallel', 'fused_adam_micro')


def _env_int(name, default):
    return int(os.environ.get(name, str(default)))


def _on_tpu():
    import jax
    return jax.default_backend() not in ('cpu',)


def _best_of(fn, k):
    """Run ``fn`` k times; return (best implied by caller, samples).
    The caller picks best via max/min on the samples."""
    return [fn() for _ in range(max(1, k))]


# ------------------------------------------------------------ scenarios
def scenario_train_transformer(best_of):
    """The bench.py headline, lab-sized: fused run_steps launches of a
    transformer train step, best-of-K tokens/s, self-labeling counters
    snapshotted AFTER warmup so a retrace during the timed loop is a
    counter regression, not silent pollution."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as fluid
    import paddle_tpu.observability as obs
    from paddle_tpu.core import passes as pt_passes
    from paddle_tpu.models import transformer as tr
    from bench import peak_flops

    on_tpu = _on_tpu()
    B = _env_int('BENCH_B', 32 if on_tpu else 4)
    T = _env_int('BENCH_T', 256 if on_tpu else 64)
    vocab = _env_int('BENCH_VOCAB', 32000)
    n_layer = _env_int('BENCH_LAYERS', 6)
    n_head = _env_int('BENCH_HEADS', 8)
    d_model = _env_int('BENCH_DMODEL', 512)
    d_inner = _env_int('BENCH_DINNER', 2048)
    K = max(2, _env_int('BENCH_STEPS_PER_LAUNCH', 8))
    launches = _env_int('PERFLAB_LAUNCHES', 3 if on_tpu else 2)

    _harness.stage('build')
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            out = tr.build(src_vocab=vocab, trg_vocab=vocab, max_len=T,
                           n_layer=n_layer, n_head=n_head, d_model=d_model,
                           d_inner=d_inner, dropout=0.0, use_flash=True)
    main_prog.set_amp(True)
    exe, scope = fluid.Executor(), fluid.Scope()
    rng = np.random.RandomState(0)
    feed = tr.synthetic_batch(rng, B, T, vocab)
    tokens_per_step = float(np.sum(1.0 - feed['trg_pad']))
    n_params = sum(int(np.prod(v.shape)) for v in
                   main_prog.global_block().all_parameters() if v.shape)
    n_matmul = n_params - sum(
        int(np.prod(v.shape)) for v in
        main_prog.global_block().all_parameters()
        if v.shape and v.name.endswith('_emb'))

    with fluid.scope_guard(scope):
        _harness.stage('warmup')
        exe.run(startup)
        feed = {k: jax.device_put(v) for k, v in feed.items()}
        for _ in range(3):
            loss, = exe.run(main_prog, feed=feed, fetch_list=[out['loss']])
        np.asarray(loss)
        superfeed = {k: jnp.stack([v] * K) for k, v in feed.items()}
        exe.run_steps(main_prog, feed_list=superfeed, steps=K,
                      fetch_list=[out['loss']])
        _harness.stage('measure')
        c0 = obs.counters()
        blocked0 = float(c0.get('executor.host_blocked_s') or 0)

        def trial():
            t0 = time.perf_counter()
            for _ in range(launches):
                losses, = exe.run_steps(main_prog, feed_list=superfeed,
                                        steps=K, fetch_list=[out['loss']],
                                        return_numpy=False)
            np.asarray(losses)
            return launches * K * tokens_per_step / \
                (time.perf_counter() - t0)

        samples = _best_of(trial, best_of)
        c1 = obs.counters()

    tps = max(samples)
    attn_layers = 3 * n_layer
    flops_per_token = 6.0 * n_matmul + 12.0 * T * d_model * attn_layers
    peak = peak_flops(str(jax.devices()[0].device_kind)) if on_tpu else None
    mfu = round(flops_per_token * tps / peak, 4) if peak else None
    raw_ops = sum(len(b.ops) for b in main_prog.blocks)
    _, opt_stats = pt_passes.maybe_optimize(main_prog, (out['loss'].name,))
    metrics = {
        'program_op_count_opt': int(opt_stats['op_count_opt']
                                    if opt_stats else raw_ops),
        'compiles_after_warmup': int((c1.get('executor.compiles') or 0) -
                                     (c0.get('executor.compiles') or 0)),
        'retraces': int((c1.get('executor.retraces') or 0) -
                        (c0.get('executor.retraces') or 0)),
        'kernel_fallbacks': int(c1.get('kernel.fallbacks') or 0),
        'kernelgen_fallbacks': int(c1.get('kernelgen.fallbacks') or 0),
        'emitter_fallbacks': int(c1.get('emitter.fallbacks') or 0),
        'tokens_per_s': round(tps, 1),
        'mfu': mfu,
        'host_blocked_s': round(float(
            (c1.get('executor.host_blocked_s') or 0)) - blocked0, 3),
        'params_m': round(n_params / 1e6, 2),
        'batch': B, 'seq': T, 'steps_per_launch': K,
    }
    config = {'batch': B, 'seq': T, 'vocab': vocab, 'layers': n_layer,
              'heads': n_head, 'd_model': d_model, 'd_inner': d_inner,
              'steps_per_launch': K, 'launches': launches}
    return metrics, {'tokens_per_s': [round(s, 1) for s in samples]}, config


def scenario_train_resnet(best_of):
    import jax
    import numpy as np
    import paddle_tpu as fluid
    import paddle_tpu.observability as obs
    from paddle_tpu.models import resnet
    from bench import (peak_flops, RESNET50_TRAIN_FLOPS_PER_IMAGE)

    on_tpu = _on_tpu()
    B = _env_int('BENCH_RESNET_B', 128 if on_tpu else 2)
    depth = _env_int('BENCH_RESNET_DEPTH', 50)
    data_set = os.environ.get('BENCH_RESNET_SET',
                              'imagenet' if on_tpu else 'cifar10')
    side = 224 if data_set == 'imagenet' else 32
    classes = 1000 if data_set == 'imagenet' else 10
    steps = _env_int('PERFLAB_RESNET_STEPS', 20 if on_tpu else 3)

    _harness.stage('build')
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            out = resnet.build(data_shape=(3, side, side),
                               class_dim=classes, depth=depth, lr=0.1,
                               data_set=data_set)
    main_prog.set_amp(True)
    exe, scope = fluid.Executor(), fluid.Scope()
    rng = np.random.RandomState(0)
    feed = {'data': rng.rand(B, 3, side, side).astype('float32'),
            'label': rng.randint(0, classes, (B, 1)).astype('int64')}
    with fluid.scope_guard(scope):
        _harness.stage('warmup')
        exe.run(startup)
        feed = {k: jax.device_put(v) for k, v in feed.items()}
        for _ in range(3):
            loss, = exe.run(main_prog, feed=feed, fetch_list=[out['loss']])
        np.asarray(loss)
        _harness.stage('measure')
        c0 = obs.counters()

        def trial():
            t0 = time.perf_counter()
            for _ in range(steps):
                loss, = exe.run(main_prog, feed=feed,
                                fetch_list=[out['loss']],
                                return_numpy=False)
            np.asarray(loss)
            return steps * B / (time.perf_counter() - t0)

        samples = _best_of(trial, best_of)
        c1 = obs.counters()

    ips = max(samples)
    peak = peak_flops(str(jax.devices()[0].device_kind)) if on_tpu else None
    mfu = (round(RESNET50_TRAIN_FLOPS_PER_IMAGE * ips / peak, 4)
           if peak and depth == 50 else None)
    metrics = {
        'compiles_after_warmup': int((c1.get('executor.compiles') or 0) -
                                     (c0.get('executor.compiles') or 0)),
        'retraces': int((c1.get('executor.retraces') or 0) -
                        (c0.get('executor.retraces') or 0)),
        'kernel_fallbacks': int(c1.get('kernel.fallbacks') or 0),
        'emitter_fallbacks': int(c1.get('emitter.fallbacks') or 0),
        'images_per_s': round(ips, 1),
        'mfu': mfu,
        'batch': B, 'depth': depth,
    }
    config = {'batch': B, 'depth': depth, 'data_set': data_set,
              'steps': steps}
    return metrics, {'images_per_s': [round(s, 1) for s in samples]}, config


def scenario_decode_stream(best_of):
    """Streaming generation through the GenerationEngine over the PAGED
    KV pool: open-loop token-stream load under a FIXED page-budget
    (int8-quantized pages, shared-prefix caching, speculative decode
    all on), tokens/s/chip from the generation.tokens counter, TTFT/ITL
    p99 from the serving histograms, and the serving-density headline —
    peak concurrent streams the budget sustained at held SLOs
    (``streams_at_slo``) against the streams a dense PR-11 layout
    could have reserved in the same bytes (``density_x_vs_dense``)."""
    import threading

    import numpy as np
    import paddle_tpu.observability as obs
    from paddle_tpu.serving.engine import ServingConfig
    from paddle_tpu.serving.generation import (CacheConfig, DecodeRuntime,
                                               GenerationConfig,
                                               GenerationEngine)
    from paddle_tpu.serving.generation.decode import random_weights

    requests = _env_int('PERFLAB_DECODE_REQUESTS', 24)
    slots = _env_int('PERFLAB_DECODE_SLOTS', 10)
    K = _env_int('PERFLAB_DECODE_WINDOW', 4)
    budget = _env_int('PERFLAB_DECODE_KV_BUDGET', 16384)
    page_len = _env_int('PERFLAB_DECODE_PAGE_LEN', 4)
    quant = os.environ.get('PERFLAB_DECODE_KV_QUANT', 'int8')

    _harness.stage('build')
    cfg = dict(vocab=128, d_model=32, n_layer=2, n_head=4, n_kv_head=2,
               d_ffn=64, theta=10000.0, max_len=32)
    w = random_weights(cfg, seed=0)
    geom = CacheConfig(slots=slots, layers=cfg['n_layer'],
                       kv_heads=cfg['n_kv_head'], max_len=cfg['max_len'],
                       head_dim=cfg['d_model'] // cfg['n_head'],
                       page_len=page_len, quant=quant)
    # fixed byte budget -> pool depth; the same budget under the dense
    # PR-11 layout (one f32 max_len strip per stream) is the density
    # denominator
    pages = max(2, budget // geom.page_bytes() + 1)   # +1: garbage page
    dense_streams = max(1, budget // geom.dense_slot_bytes())
    rt = DecodeRuntime(w, cfg, slots=slots, prefill_chunk=4,
                       page_len=page_len, pages=pages, kv_quant=quant,
                       prefix_cache=True)
    engine = GenerationEngine(
        rt, config=ServingConfig(max_queue=max(64, 2 * requests),
                                 drain_timeout_s=60.0),
        gen_config=GenerationConfig(decode_window=K,
                                    speculative=True)).start()
    _harness.stage('warmup')
    rt.warmup(steps=K, speculative=True)
    engine.generate([3, 1, 4, 1, 5], max_new=4).result(120)
    c0 = obs.counters()
    compiles0 = int(c0.get('generation.compiles') or 0)
    tokens0 = int(c0.get('generation.tokens') or 0)

    _harness.stage('measure')
    # every prompt shares one FULL page of system prefix (prefix-cache
    # hits after the first stream publishes it) plus a distinct tail;
    # per-stream page demand stays within slots * worst-case even with
    # zero sharing, so the budget never kills a stream mid-flight
    shared = [(3 + j) % (cfg['vocab'] - 1) + 1 for j in range(page_len)]
    tails = (1, 2, 3)
    peak = [0]
    done = threading.Event()

    def poll_peak():
        while not done.is_set():
            peak[0] = max(peak[0], rt.allocator.in_use())
            time.sleep(0.001)

    poller = threading.Thread(target=poll_peak, daemon=True)
    poller.start()
    t0 = time.perf_counter()
    streams = []
    for i in range(requests):
        n = tails[i % len(tails)]
        prompt = shared + [(7 * i + j) % (cfg['vocab'] - 1) + 1
                           for j in range(n)]
        streams.append(engine.generate(
            prompt, max_new=6,
            temperature=0.8 if i % 3 else 0.0,
            top_k=5 if i % 3 else 0, seed=i, timeout_s=120.0))
    ok = failed = 0
    for s in streams:
        try:
            res = s.result(120)
            ok += 1 if res.ok else 0
            failed += 0 if res.ok else 1
        except Exception:
            failed += 1
    dt = time.perf_counter() - t0
    done.set()
    poller.join(1.0)
    engine.stop()

    _harness.stage('audit')
    c1 = obs.counters()
    tel = obs.telemetry_snapshot('serving')
    new_tokens = int(c1.get('generation.tokens') or 0) - tokens0
    tps = new_tokens / dt if dt > 0 else 0.0
    if rt.prefix is not None:
        rt.prefix.reset()          # cached pages are holds, not leaks
    pages_leaked = int(rt.pool.in_use())
    slots_leaked = int(rt.slots - rt.free_slots())
    slo_held = (failed == 0 and ok == requests
                and int(tel['deadlocks']) == 0 and slots_leaked == 0
                and pages_leaked == 0)
    streams_at_slo = int(peak[0]) if slo_held else 0

    def fin(v):
        return float(v) if v is not None and np.isfinite(v) else None

    metrics = {
        'compiles_after_warmup': int(c1.get('generation.compiles') or 0) -
        compiles0,
        'deadlocks': int(tel['deadlocks']),
        'kv_slots_leaked': slots_leaked,
        'kv_pages_leaked': pages_leaked,
        'streams_failed': failed,
        'streams_at_slo': streams_at_slo,
        'density_x_vs_dense': streams_at_slo // dense_streams,
        'tokens_per_s_per_chip': round(tps, 1),
        'ttft_p99_ms': fin(tel['ttft_p99_ms']),
        'itl_p99_ms': fin(tel['itl_p99_ms']),
        'requests': requests,
        'streams_ok': ok,
    }
    config = {'requests': requests, 'slots': slots, 'decode_window': K,
              'model': cfg, 'page_len': page_len, 'pages': pages,
              'kv_quant': quant, 'kv_budget_bytes': budget,
              'dense_streams_in_budget': dense_streams,
              'speculative': True, 'prefix_cache': True}
    # one open-loop pass is the sample — TTFT/ITL p99 already aggregate
    # per-token noise, and re-running would double-count warm KV state
    return metrics, {'tokens_per_s_per_chip': [round(tps, 1)]}, config


def _pod_shard_round():
    """Replicated-vs-ZeRO-sharded in one round on the local mesh: per-
    device persistable HBM (via addressable_shards, not the cost model)
    plus the shard pass's explicit-collective accounting.  Returns {}
    below 2 devices — the schema keys then stay absent, which the gate
    treats as not-measured rather than regressed."""
    import jax
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import passes
    from paddle_tpu.parallel.mesh import make_mesh

    if jax.local_device_count() < 2:
        return {}
    mesh = make_mesh(data=2, devices=jax.devices()[:2])

    def build():
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data('ps_x', shape=[64], dtype='float32')
                h = fluid.layers.fc(x, size=64, act='relu')
                y = fluid.layers.fc(h, size=64)
                loss = fluid.layers.reduce_mean(y * y)
                fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        main_prog.set_mesh_axes(mesh)
        x.sharding = (None, None)   # replicated feed: bitwise comparable
        return main_prog, startup, loss

    def dev0_bytes(scope, persist):
        total = 0
        for n in persist:
            arr = scope.vars.get(n)
            if arr is None or not hasattr(arr, 'addressable_shards'):
                continue
            total += sum(s.data.nbytes for s in arr.addressable_shards
                         if s.device == jax.devices()[0])
        return total

    feed = {'ps_x': np.random.RandomState(0).rand(16, 64).astype('float32')}
    out = {}
    for label, shard_on in (('replicated', '0'), ('sharded', '1')):
        old = os.environ.get('PT_SHARD')
        os.environ['PT_SHARD'] = shard_on
        try:
            main_prog, startup, loss = build()
            exe, scope = fluid.Executor(mesh=mesh), fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(startup)
                for _ in range(3):
                    exe.run(main_prog, feed=feed, fetch_list=[loss])
                persist = [v.name for v in main_prog.list_vars()
                           if v.persistable]
                out['hbm_params_bytes_%s' % label] = \
                    dev0_bytes(scope, persist)
            if shard_on == '1':
                _, stats = passes.optimize_program(main_prog, (loss.name,))
                sh = stats['passes'].get('shard') or {}
                out['reshards_inserted'] = int(
                    sh.get('reshards_inserted') or 0)
                out['collective_bytes'] = int(
                    sh.get('collective_bytes') or 0)
        finally:
            if old is None:
                os.environ.pop('PT_SHARD', None)
            else:
                os.environ['PT_SHARD'] = old
    rep = out.get('hbm_params_bytes_replicated') or 0
    shd = out.get('hbm_params_bytes_sharded') or 0
    out['hbm_sharded_ratio'] = round(shd / rep, 3) if rep else None
    return out


def scenario_pod_parallel(best_of):
    """Pod-story plumbing: psum bus bandwidth over the local mesh (null
    single-device), the shard pass's replicated-vs-sharded HBM round,
    and 2-worker lockstep scaling of HOST-side step throughput via
    subprocess workers — the shape the real pod gate grows into."""
    import jax
    from bench import allreduce_bw_gbps

    steps = _env_int('PERFLAB_POD_STEPS', 8)
    _harness.stage('shard_round')
    shard_metrics = _pod_shard_round()
    _harness.stage('allreduce')
    devices = jax.local_device_count()
    bw = allreduce_bw_gbps(n_iters=5, nbytes=8 * 1024 * 1024)

    def spawn():
        env = dict(os.environ)
        # this process has run JAX and holds whatever chip there is, so
        # the workers are pinned to the CPU: steps_per_s_1worker and
        # scaling_2worker_x are host-side numbers on every platform
        env['JAX_PLATFORMS'] = 'cpu'
        env.pop('XLA_FLAGS', None)
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), 'podworker',
             '--steps', str(steps)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)

    def finish(proc, timeout):
        try:
            out, err = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.communicate()
            return None
        if proc.returncode != 0:
            print('PERFLAB: podworker rc=%d: %s'
                  % (proc.returncode,
                     ' | '.join((err or '').strip().splitlines()[-4:])),
                  file=sys.stderr)
        for line in reversed((out or '').strip().splitlines()):
            if line.startswith('{'):
                try:
                    rec = json.loads(line)
                except ValueError:
                    return None
                return rec if proc.returncode == 0 else None
        return None

    budget = float(os.environ.get('PERFLAB_POD_WORKER_S', '240'))
    _harness.stage('single_worker')
    r1 = finish(spawn(), budget)
    _harness.stage('dual_worker')
    procs = [spawn(), spawn()]
    r2 = [finish(p, budget) for p in procs]

    completed = (1 if r1 else 0) + sum(1 for r in r2 if r)
    failures = 3 - completed
    single = r1['steps_per_s'] if r1 else None
    aggregate = (sum(r['steps_per_s'] for r in r2 if r)
                 if all(r2) else None)
    scaling = (round(aggregate / single, 3)
               if single and aggregate else None)
    metrics = {
        'workers_completed': completed,
        'worker_failures': failures,
        'allreduce_gbps': round(bw, 2) if bw is not None else None,
        'steps_per_s_1worker': round(single, 2) if single else None,
        'scaling_2worker_x': scaling,
        'devices': devices,
    }
    metrics.update(shard_metrics)
    config = {'steps': steps, 'workers': 2}
    return metrics, {}, config


def scenario_fused_adam_micro(best_of):
    """The kernelgen tier's headline op: ms/step of the fused-Adam
    update (ONE generated kernel when PT_KERNELGEN=1), with the tier's
    own counters as the zero-tolerance gate."""
    import numpy as np
    import paddle_tpu as fluid
    import paddle_tpu.observability as obs

    steps = _env_int('PERFLAB_ADAM_STEPS', 20)
    _harness.stage('build')
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('fa_x', shape=[64], dtype='float32')
            h = fluid.layers.fc(x, size=64, act='relu')
            y = fluid.layers.fc(h, size=64)
            loss = fluid.layers.reduce_mean(y * y)
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe, scope = fluid.Executor(), fluid.Scope()
    feed = {'fa_x': np.random.RandomState(0).rand(32, 64).astype('float32')}
    n_params = sum(int(np.prod(v.shape)) for v in
                   main_prog.global_block().all_parameters() if v.shape)
    with fluid.scope_guard(scope):
        _harness.stage('warmup')
        exe.run(startup)
        for _ in range(3):
            exe.run(main_prog, feed=feed, fetch_list=[loss])
        _harness.stage('measure')
        c0 = obs.counters()

        def trial():
            t0 = time.perf_counter()
            for _ in range(steps):
                exe.run(main_prog, feed=feed, fetch_list=[loss],
                        return_numpy=False)
            lv, = exe.run(main_prog, feed=feed, fetch_list=[loss])
            np.asarray(lv)
            return (time.perf_counter() - t0) / (steps + 1) * 1000.0

        samples = _best_of(trial, best_of)
        c1 = obs.counters()

    metrics = {
        'kernelgen_ops': int(c1.get('kernelgen.ops') or 0),
        'kernelgen_fallbacks': int(c1.get('kernelgen.fallbacks') or 0),
        'retraces': int((c1.get('executor.retraces') or 0) -
                        (c0.get('executor.retraces') or 0)),
        'fused_adam_ms': round(min(samples), 3),
        'params': n_params,
    }
    config = {'steps': steps}
    return metrics, {'fused_adam_ms': [round(s, 3) for s in samples]}, config


SCENARIOS = {
    'train_transformer': scenario_train_transformer,
    'train_resnet': scenario_train_resnet,
    'decode_stream': scenario_decode_stream,
    'pod_parallel': scenario_pod_parallel,
    'fused_adam_micro': scenario_fused_adam_micro,
}

# test-only scenarios (tests/test_perflab.py): a child that hangs past
# its budget and a near-instant one — enabled explicitly so the real
# matrix can't pick them up
if os.environ.get('PERFLAB_TEST_SCENARIOS') == '1':
    from paddle_tpu.observability.export import SCHEMA as _SCHEMA

    _SCHEMA.setdefault('perflab._quick', (
        ('widgets', ('counter', 'lower')),
        ('widget_ms', ('timing', 'lower', 'ms')),
        ('note', ('info',)),
    ))
    _SCHEMA.setdefault('perflab._sleep', (('widgets', ('counter',
                                                       'lower')),))

    def _scenario_quick(best_of):
        return ({'widgets': 1, 'widget_ms': 1.0, 'note': 'test'},
                {'widget_ms': [1.0, 1.1]}, {'kind': 'test'})

    def _scenario_sleep(best_of):
        _harness.stage('sleeping')
        time.sleep(3600)
        return ({'widgets': 0}, {}, {})

    SCENARIOS['_quick'] = _scenario_quick
    SCENARIOS['_sleep'] = _scenario_sleep


# ------------------------------------------------------------- plumbing
def _run_child(name, budget, best_of):
    """One subprocess-isolated scenario.  Returns a ledger record —
    success, or a structured {"error": "timeout"|...} record."""
    from paddle_tpu.observability import perflab as pl

    env = dict(os.environ)
    if name == 'pod_parallel' and _harness.cpu_requested():
        # give the allreduce microbench a 2-device mesh to measure
        flags = env.get('XLA_FLAGS', '')
        if 'xla_force_host_platform_device_count' not in flags:
            env['XLA_FLAGS'] = (
                flags + ' --xla_force_host_platform_device_count=2').strip()
    cmd = [sys.executable, os.path.abspath(__file__), 'child',
           '--scenario', name, '--best-of', str(best_of)]
    t0 = time.time()
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    try:
        out, err = proc.communicate(timeout=budget)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, err = proc.communicate()
        stage = 'unknown'
        for line in reversed((err or '').splitlines()):
            if ': stage=' in line:
                stage = line.split(': stage=', 1)[1].strip()
                break
        print('PERFLAB: scenario %s TIMED OUT after %.0fs (stage=%s)'
              % (name, budget, stage), file=sys.stderr)
        return pl.error_record(name, 'timeout', stage=stage,
                               detail='child exceeded %.0fs budget'
                                      % budget)
    dt = time.time() - t0
    rec = None
    for line in reversed((out or '').strip().splitlines()):
        if line.startswith('{'):
            try:
                rec = json.loads(line)
            except ValueError:
                pass
            break
    if rec is None:
        tail = (err or out or '').strip().splitlines()[-6:]
        return pl.error_record(name, 'crash',
                               detail='rc=%r: %s' % (proc.returncode,
                                                     ' | '.join(tail)))
    if 'schema' not in rec and 'error' in rec:
        # the _harness JSON tail from a crashed child — promote it to a
        # ledger failure record, keeping its stage attribution
        return pl.error_record(name, rec['error'], stage=rec.get('stage'),
                               detail=rec.get('detail'))
    try:
        pl.validate_record(rec)
    except ValueError as e:
        return pl.error_record(name, 'schema_violation', detail=e)
    if 'error' not in rec:
        print('PERFLAB: scenario %s ok in %.1fs' % (name, dt),
              file=sys.stderr)
    return rec


def cmd_run(args):
    from paddle_tpu.observability import perflab as pl

    names = ([s.strip() for s in args.scenarios.split(',') if s.strip()]
             if args.scenarios else list(MATRIX))
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        sys.exit('perflab: unknown scenario(s) %s (known: %s)'
                 % (unknown, ', '.join(sorted(SCENARIOS))))
    ledger = args.ledger
    records, failed = [], []
    for name in names:
        _harness.stage(name)
        rec = _run_child(name, args.budget_s, args.best_of)
        pl.append_record(ledger, rec)
        records.append(rec)
        if 'error' in rec:
            failed.append(name)
    summary = {
        'scenarios': len(records),
        'ok': len(records) - len(failed),
        'failed': failed,
        # what the children ran on, by their own report: this process
        # never opens a device
        'platform': sorted({r['provenance']['platform']
                            for r in records if 'error' not in r}),
        'ledger': ledger,
    }
    print(json.dumps(summary))
    return 1 if failed else 0


def cmd_child(args):
    from paddle_tpu.observability import perflab as pl

    name = args.scenario
    if name not in SCENARIOS:
        sys.exit('perflab child: unknown scenario %r' % name)
    _harness.stage('device')
    _harness.require_device()
    metrics, spread, config = SCENARIOS[name](args.best_of)
    _harness.stage('report')
    rec = pl.build_record(name, metrics, spread=spread, config=config)
    print(json.dumps(rec))
    return 0


def cmd_podworker(args):
    """Internal: one lockstep trainer for the pod_parallel scenario —
    the fault_soak tiny model, steps/s over a fixed step count."""
    import numpy as np
    import paddle_tpu as fluid
    import fault_soak

    main_prog, startup, loss = fault_soak.build_model(fluid)
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed = fault_soak.feed_at(0)
        for _ in range(2):
            out = exe.run(main_prog, feed=feed, fetch_list=[loss])
        np.asarray(out[0])
        t0 = time.perf_counter()
        for i in range(args.steps):
            out = exe.run(main_prog, feed=fault_soak.feed_at(i),
                          fetch_list=[loss], return_numpy=False)
        np.asarray(out[0])
        dt = time.perf_counter() - t0
    print(json.dumps({'steps_per_s': args.steps / dt}))
    return 0


def cmd_compare(args):
    from paddle_tpu.observability import perflab as pl

    if not os.path.exists(args.baseline):
        sys.exit('perflab compare: no baseline at %s (run `perflab '
                 'bless` to create one)' % args.baseline)
    with open(args.baseline) as f:
        doc = json.load(f)
    records = pl.read_ledger(args.ledger)
    names = ([s.strip() for s in args.scenarios.split(',') if s.strip()]
             if args.scenarios else None)
    fail_on = None if args.fail_on == 'none' else args.fail_on
    rc, reports = pl.compare_ledger(doc, records, fail_on=fail_on,
                                    scenarios=names)
    for rep in reports:
        print(json.dumps(rep))
    summary = {
        'compare': {s: sum(1 for r in reports if r['status'] == s)
                    for s in ('ok', 'regression', 'refused', 'missing')},
        'baseline_git_sha': doc.get('blessed_git_sha'),
        'rc': rc,
    }
    print(json.dumps(summary))
    if rc == 2:
        print('PERFLAB: comparison REFUSED — see reasons above '
              '(a mismatched-backend record cannot gate against this '
              'baseline)', file=sys.stderr)
    elif rc:
        print('PERFLAB: regression(s) detected', file=sys.stderr)
    return rc


def cmd_check(args):
    """The ci assertion: every requested scenario has a newest ledger
    record that is schema-valid, non-error, and provenance-complete."""
    from paddle_tpu.observability import perflab as pl

    names = ([s.strip() for s in args.scenarios.split(',') if s.strip()]
             if args.scenarios else list(MATRIX))
    latest = pl.latest_per_scenario(pl.read_ledger(args.ledger))
    bad = []
    for name in names:
        rec = latest.get(name)
        if rec is None:
            bad.append('%s: no ledger record' % name)
            continue
        if 'error' in rec:
            bad.append('%s: failure record (%s, stage=%s)'
                       % (name, rec.get('error'), rec.get('stage')))
            continue
        try:
            pl.validate_record(rec)
        except ValueError as e:
            bad.append(str(e))
    print(json.dumps({'checked': names, 'failures': bad}))
    if bad:
        for b in bad:
            print('PERFLAB: check FAILED: %s' % b, file=sys.stderr)
        return 1
    return 0


def cmd_bless(args):
    from paddle_tpu.observability import perflab as pl

    records = pl.read_ledger(args.ledger)
    names = ([s.strip() for s in args.scenarios.split(',') if s.strip()]
             if args.scenarios else None)
    if names:
        records = [r for r in records if r['scenario'] in names]
    doc = pl.bless(records,
                   default_timing_tolerance=args.timing_tolerance)
    with open(args.out, 'w') as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write('\n')
    print(json.dumps({'blessed': sorted(doc['scenarios']),
                      'out': args.out,
                      'git_sha': doc['blessed_git_sha']}))
    return 0


def cmd_list(args):
    from paddle_tpu.observability import perflab as pl

    for name in sorted(SCENARIOS):
        specs = pl.metric_specs(name)
        counters = [k for k, s in specs.items() if s[0] == 'counter']
        timings = [k for k, s in specs.items() if s[0] == 'timing']
        print(json.dumps({'scenario': name, 'counters': sorted(counters),
                          'timings': sorted(timings),
                          'in_matrix': name in MATRIX}))
    return 0


def main():
    ap = argparse.ArgumentParser(prog='perflab', description=__doc__)
    sub = ap.add_subparsers(dest='cmd', required=True)

    p = sub.add_parser('run', help='run the scenario matrix')
    p.add_argument('--scenarios', default=None,
                   help='comma list (default: the full matrix)')
    p.add_argument('--ledger', default=os.environ.get('PT_PERF_LEDGER',
                                                      DEFAULT_LEDGER))
    p.add_argument('--budget-s', type=float,
                   default=float(os.environ.get('PERFLAB_BUDGET_S',
                                                '600')),
                   help='per-scenario child budget; a child past it is '
                        'killed and gets a structured timeout record')
    p.add_argument('--best-of', type=int,
                   default=int(os.environ.get('PERFLAB_BEST_OF', '3')),
                   help='timing trials per scenario (spread is recorded)')
    p.set_defaults(fn=cmd_run)

    p = sub.add_parser('child', help='internal: run ONE scenario '
                                     'in-process and print its record')
    p.add_argument('--scenario', required=True)
    p.add_argument('--best-of', type=int, default=3)
    p.set_defaults(fn=cmd_child)

    p = sub.add_parser('podworker', help='internal: pod_parallel worker')
    p.add_argument('--steps', type=int, default=8)
    p.set_defaults(fn=cmd_podworker)

    p = sub.add_parser('compare', help='diff newest records vs baseline')
    p.add_argument('--baseline', default=DEFAULT_BASELINE)
    p.add_argument('--ledger', default=os.environ.get('PT_PERF_LEDGER',
                                                      DEFAULT_LEDGER))
    p.add_argument('--scenarios', default=None)
    p.add_argument('--fail-on', default='none',
                   choices=('regression', 'none'),
                   help='regression: exit 1 on any counter/timing '
                        'regression or missing scenario, exit 2 on a '
                        'structured refusal')
    p.set_defaults(fn=cmd_compare)

    p = sub.add_parser('check', help='assert schema-valid provenanced '
                                     'records exist per scenario')
    p.add_argument('--ledger', default=os.environ.get('PT_PERF_LEDGER',
                                                      DEFAULT_LEDGER))
    p.add_argument('--scenarios', default=None)
    p.set_defaults(fn=cmd_check)

    p = sub.add_parser('bless', help='write newest records as baseline')
    p.add_argument('--ledger', default=os.environ.get('PT_PERF_LEDGER',
                                                      DEFAULT_LEDGER))
    p.add_argument('--out', default=DEFAULT_BASELINE)
    p.add_argument('--scenarios', default=None)
    p.add_argument('--timing-tolerance', type=float, default=0.5)
    p.set_defaults(fn=cmd_bless)

    p = sub.add_parser('list', help='print the scenario registry')
    p.set_defaults(fn=cmd_list)

    p = sub.add_parser('probe', help='one-shot diagnostic harnesses '
                                     '(absorbed tools/measure.py)')
    p.add_argument('rest', nargs=argparse.REMAINDER)
    p.set_defaults(fn=None)

    p = sub.add_parser('models', help='reference model-matrix benchmark '
                                      '(absorbed tools/fluid_benchmark.py)')
    p.add_argument('rest', nargs=argparse.REMAINDER)
    p.set_defaults(fn=None)

    args = ap.parse_args()
    if args.cmd == 'probe':
        import _probes
        return _probes.probe_main(args.rest)
    if args.cmd == 'models':
        import _probes
        return _probes.models_main(args.rest)
    return args.fn(args)


if __name__ == '__main__':
    _harness.set_tool('PERFLAB')
    scenario = None
    if 'child' in sys.argv[1:2] and '--scenario' in sys.argv:
        scenario = sys.argv[sys.argv.index('--scenario') + 1]
    extra = {'scenario': scenario} if scenario else {}
    _harness.main_guard(main, watchdog_env='PERFLAB_WATCHDOG_S',
                        flight_tag='perflab.watchdog', **extra)
