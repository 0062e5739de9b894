"""Headline benchmark: Transformer-base training throughput on one TPU chip.

Mirrors the reference's benchmark/fluid/fluid_benchmark.py harness
(--model machine_translation reports words/sec); here the whole train step
(fwd + vjp bwd + Adam) is ONE XLA executable, run in bf16 AMP with the
fused flash-attention kernel.

One process, no probe: the bench runs on the device JAX gives it and
FAILS when that is not a TPU, unless ``JAX_PLATFORMS=cpu`` asked for a
plumbing run (labelled ``cpu``, no device metric is meaningful there).
Any failed phase raises — a bench that exits 0 measured every phase.

Prints ONE JSON line:
  {"metric": ..., "value": tok/s, "unit": "tokens/s", "vs_baseline": ...,
   "mfu": model-flops-utilization vs chip peak, "backend": ..., ...}

vs_baseline denominator: ~5100 tokens/s/GPU, the Fluid-era V100 fp32
transformer-base figure recorded in SURVEY.md §5 (BASELINE.json has no
published numbers).
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                'tools'))
import _harness  # noqa: E402 - shared stage/watchdog machinery
from _harness import stage  # noqa: E402

BASELINE_TOKENS_PER_SEC = 5100.0
# Fluid-era V100 fp32 ResNet-50 throughput stand-in (BASELINE.json has no
# published numbers; benchmark/fluid's README-era figure is ~360 img/s)
BASELINE_RESNET_IMAGES_PER_SEC = 360.0
# canonical ResNet-50 224x224 forward cost; training ~= 3x forward
RESNET50_TRAIN_FLOPS_PER_IMAGE = 3 * 4.1e9
# Peak rates of ONE chip, keyed by the exact ``device_kind`` string JAX
# reports for it.  A device that is not here is an error, not a default:
# add it with its source.
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s.  'TPU v5 lite' is what a v5e chip reports (chip run,
    # PR 21).
    'TPU v5 lite': {'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9},
}


def peak_flops(device_kind):
    if device_kind not in PEAKS:
        raise KeyError('no peak rates recorded for device_kind %r — add it '
                       'to bench.PEAKS with its source' % (device_kind,))
    return PEAKS[device_kind]['bf16_flops']


def allreduce_bw_gbps(n_iters=10, nbytes=64 * 1024 * 1024):
    """psum bandwidth across local devices (BASELINE.json headline metric).
    Only meaningful with >1 device; returns None single-chip."""
    import jax
    import jax.numpy as jnp
    devs = jax.devices()
    if len(devs) < 2:
        return None
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(devs), ('x',))
    n = nbytes // 4 // len(devs) * len(devs)
    x = jnp.ones((n,), jnp.float32)

    @jax.jit
    def ar(v):
        return jax.shard_map(lambda s: jax.lax.psum(s, 'x'), mesh=mesh,
                             in_specs=P('x'), out_specs=P(None))(v)

    ar(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(n_iters):
        out = ar(x)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    # ring allreduce moves 2*(n-1)/n of the buffer per device
    moved = 2 * (len(devs) - 1) / len(devs) * n * 4 * n_iters
    return moved / dt / 1e9


def bench_resnet50(on_tpu, device_kind):
    """ResNet-50 training throughput (BASELINE.json headline metric #1;
    reference harness: benchmark/fluid/fluid_benchmark.py --model resnet
    with --data_set imagenet, model at benchmark/fluid/models/resnet.py)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    # TPU v5 lite, conv flow-through AMP policy: 2233 img/s at B=128 vs
    # 2242 at B=256 (a tie); 128 keeps HBM headroom (PERF.md sweep)
    B = int(os.environ.get('BENCH_RESNET_B', 128 if on_tpu else 2))
    side = 224 if on_tpu else 32
    classes = 1000 if on_tpu else 10
    # same CPU-smoke story as the transformer dims: 25M resnet50 params
    # through the interpret-mode fused-optimizer kernel is minutes/step,
    # so CI drops to the 0.27M-param cifar10 variant
    depth = int(os.environ.get('BENCH_RESNET_DEPTH', '50'))
    data_set = os.environ.get('BENCH_RESNET_SET', 'imagenet')
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            out = resnet.build(data_shape=(3, side, side),
                               class_dim=classes, depth=depth, lr=0.1,
                               data_set=data_set)
    main_prog.set_amp(True)
    exe = fluid.Executor()
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    feed = {'data': rng.rand(B, 3, side, side).astype('float32'),
            'label': rng.randint(0, classes, (B, 1)).astype('int64')}
    with fluid.scope_guard(scope):
        t0 = time.perf_counter()
        exe.run(startup)
        import jax
        feed = {k: jax.device_put(v) for k, v in feed.items()}
        for _ in range(3):
            loss, = exe.run(main_prog, feed=feed,
                            fetch_list=[out['loss']])
        np.asarray(loss)  # block
        print('BENCH: resnet50 compile+warmup ok (%.1fs)'
              % (time.perf_counter() - t0), file=sys.stderr)
        steps = 20 if on_tpu else 3
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, = exe.run(main_prog, feed=feed,
                            fetch_list=[out['loss']],
                            return_numpy=False)
        np.asarray(loss)  # block
        dt = time.perf_counter() - t0
    ips = steps * B / dt
    peak = peak_flops(device_kind) if on_tpu else None
    mfu = (round(RESNET50_TRAIN_FLOPS_PER_IMAGE * ips / peak, 4)
           if peak else None)
    return {'resnet50_images_per_sec': round(ips, 1),
            'resnet50_vs_baseline': round(
                ips / BASELINE_RESNET_IMAGES_PER_SEC, 3),
            'resnet50_mfu': mfu, 'resnet50_batch': B}


def bench_fused_adam(fluid):
    """Micro-bench the fused-Adam update path: a tiny 2-layer model whose
    optimizer sub-program fuses into one fused_elementwise group (ONE
    generated Pallas kernel when PT_KERNELGEN=1).  Returns avg ms per
    train step — the ledger row for the kernelgen tier's headline op."""
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('fa_x', shape=[64], dtype='float32')
            h = fluid.layers.fc(x, size=64, act='relu')
            y = fluid.layers.fc(h, size=64)
            loss = fluid.layers.reduce_mean(y * y)
            opt = fluid.optimizer.Adam(learning_rate=1e-3)
            opt.minimize(loss)
    exe, scope = fluid.Executor(), fluid.Scope()
    feed = {'fa_x': np.random.RandomState(0)
            .rand(32, 64).astype('float32')}
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):  # compile + warmup
            exe.run(main_prog, feed=feed, fetch_list=[loss])
        steps = 20
        t0 = time.perf_counter()
        for _ in range(steps):
            exe.run(main_prog, feed=feed, fetch_list=[loss],
                    return_numpy=False)
        lv, = exe.run(main_prog, feed=feed, fetch_list=[loss])
        np.asarray(lv)  # block
        dt = time.perf_counter() - t0
    return round(dt / (steps + 1) * 1000.0, 3)


def main():
    stage('device')
    platform, device_kind = _harness.require_device()
    print('BENCH: device: %s (%s)' % (platform, device_kind),
          file=sys.stderr)

    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tr

    on_tpu = platform == 'tpu'
    # transformer-base; dropout off so training uses the fused flash kernel.
    # The model dims are overridable because the kernelgen interpret tier
    # pays per PARAMETER on CPU (the fused-Adam kernel walks every param
    # group through the Pallas interpreter, ~minutes/step at 25M params) —
    # CI smoke must shrink the model itself, not just B/T.
    B = int(os.environ.get('BENCH_B', 32 if on_tpu else 4))
    T = int(os.environ.get('BENCH_T', 256 if on_tpu else 64))
    vocab = int(os.environ.get('BENCH_VOCAB', '32000'))
    n_layer = int(os.environ.get('BENCH_LAYERS', '6'))
    n_head = int(os.environ.get('BENCH_HEADS', '8'))
    d_model = int(os.environ.get('BENCH_DMODEL', '512'))
    d_inner = int(os.environ.get('BENCH_DINNER', '2048'))

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            out = tr.build(src_vocab=vocab, trg_vocab=vocab, max_len=T,
                           n_layer=n_layer, n_head=n_head, d_model=d_model,
                           d_inner=d_inner, dropout=0.0, use_flash=True)
    main_prog.set_amp(True)

    # tiny-shape warmup first: a failure or hang surfaces on a 2s compile,
    # not after the full-size 30s one
    t0 = time.perf_counter()
    stage('tiny_warmup')
    _tiny_warmup(fluid, vocab)
    print('BENCH: tiny warmup ok (%.1fs)' % (time.perf_counter() - t0),
          file=sys.stderr)

    exe = fluid.Executor()
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    feed = tr.synthetic_batch(rng, B, T, vocab)
    tokens_per_step = float(np.sum(1.0 - feed['trg_pad']))

    n_params = sum(
        int(np.prod(v.shape)) for v in
        main_prog.global_block().all_parameters() if v.shape)
    # params that only feed lookup_table gathers do 0 matmul FLOPs — count
    # them out of the 6*P model-FLOPs term (the logit projection is a real
    # matmul and keeps its '...proj...' name, so it stays in)
    n_gather_params = sum(
        int(np.prod(v.shape)) for v in
        main_prog.global_block().all_parameters()
        if v.shape and v.name.endswith('_emb'))
    n_matmul_params = n_params - n_gather_params

    with fluid.scope_guard(scope):
        t0 = time.perf_counter()
        stage('startup')
        exe.run(startup)
        print('BENCH: startup ok (%.1fs)' % (time.perf_counter() - t0),
              file=sys.stderr)
        # upload the batch ONCE — steady-state training streams batches
        # asynchronously; re-uploading identical host arrays every step
        # would measure the host link, not the chip
        import jax
        feed = {k: jax.device_put(v) for k, v in feed.items()}
        t0 = time.perf_counter()
        stage('train_warmup')
        for _ in range(3):  # compile + warmup
            loss, = exe.run(main_prog, feed=feed, fetch_list=[out['loss']])
        np.asarray(loss)  # block
        print('BENCH: train-step compile+warmup ok (%.1fs)'
              % (time.perf_counter() - t0), file=sys.stderr)
        stage('measure')
        steps = 30 if on_tpu else 10
        t0 = time.perf_counter()
        for _ in range(steps):
            # async fetch: steps pipeline on device; one sync at the end
            loss, = exe.run(main_prog, feed=feed,
                            fetch_list=[out['loss']],
                            return_numpy=False)
        np.asarray(loss)  # block
        dt_single = time.perf_counter() - t0
        tps_single = steps * tokens_per_step / dt_single

        # multi-step fused loop (the headline): K iterations per device
        # launch via run_steps — one lax.scan executable, one dispatch
        K = max(2, int(os.environ.get('BENCH_STEPS_PER_LAUNCH', '8')))
        import jax.numpy as jnp
        superfeed = {k: jnp.stack([v] * K) for k, v in feed.items()}
        t0 = time.perf_counter()
        losses, = exe.run_steps(main_prog, feed_list=superfeed, steps=K,
                                fetch_list=[out['loss']])
        print('BENCH: %d-step fused compile+warmup ok (%.1fs)'
              % (K, time.perf_counter() - t0), file=sys.stderr)
        launches = max(1, steps // K)
        # telemetry: snapshot AFTER warmup so the measured window is
        # self-labeling — a retrace or pipeline stall during the timed
        # loop lands in the JSON instead of silently polluting the number
        import paddle_tpu.observability as obs
        snap0 = obs.counters()
        t0 = time.perf_counter()
        for _ in range(launches):
            losses, = exe.run_steps(main_prog, feed_list=superfeed,
                                    steps=K, fetch_list=[out['loss']],
                                    return_numpy=False)
        np.asarray(losses)  # block
        dt = time.perf_counter() - t0
        # ragged tail: a partial superbatch (steps=1 < K) must route
        # through the already-compiled single-step executable (tail
        # split) instead of lowering a fresh steps=1 scan — any trace
        # here lands in the retraces-after-warmup check below
        tailfeed = {k: v[:1] for k, v in superfeed.items()}
        exe.run_steps(main_prog, feed_list=tailfeed, steps=1,
                      fetch_list=[out['loss']], return_numpy=False)
        snap1 = obs.counters()

        # sync-mode comparison row: the SAME fused launches but with a
        # host fetch (return_numpy=True) after every one — what the
        # headline number would be if the host serialized the device
        stage('sync_compare')
        t0 = time.perf_counter()
        for _ in range(launches):
            exe.run_steps(main_prog, feed_list=superfeed, steps=K,
                          fetch_list=[out['loss']], return_numpy=True)
        dt_sync = time.perf_counter() - t0
        tps_sync = launches * K * tokens_per_step / dt_sync

        # deferred check_nan overhead: with nan_poll=8 the fused
        # all-finite verdict stays device-resident between polls, so the
        # guard should cost ~nothing vs the unguarded single-step loop
        # (PERF.md's old per-launch bool() sync made it ~4x)
        stage('check_nan')
        exe_nan = fluid.Executor(check_nan=True, nan_poll=8)
        for _ in range(2):  # compile + warmup for the guarded executable
            loss, = exe_nan.run(main_prog, feed=feed,
                                fetch_list=[out['loss']])
        exe_nan.poll_nan()
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, = exe_nan.run(main_prog, feed=feed,
                                fetch_list=[out['loss']],
                                return_numpy=False)
        exe_nan.poll_nan()
        np.asarray(loss)  # block
        dt_nan = time.perf_counter() - t0
        check_nan_overhead_x = dt_nan / dt_single

    tps = launches * K * tokens_per_step / dt

    # PT_OPT rewriter accounting (core/passes): raw vs optimized traced-op
    # counts for the headline program.  maybe_optimize is memoized per
    # (program version, fetch set), so this reads the stats of the exact
    # rewrite the executor lowered — no extra work.
    from paddle_tpu.core import passes as pt_passes
    raw_ops = sum(len(b.ops) for b in main_prog.blocks)
    _, opt_stats = pt_passes.maybe_optimize(main_prog, (out['loss'].name,))
    opt_ops = opt_stats['op_count_opt'] if opt_stats else raw_ops

    dev0 = jax.devices()[0]
    # one shared schema (observability/export.py SCHEMA['bench']) builds
    # the telemetry block — serve_soak/fault_soak read their sections from
    # the same table, and ci_smoke validates the key set once.  Warm-start
    # semantics (compile_s_cold = in-process compile seconds, _warm = AOT
    # cache load seconds, ci_smoke asserts the second run collapses) are
    # documented in the schema + docs/observability.md.
    stage('fused_adam')
    fused_adam_ms = bench_fused_adam(fluid)
    print('BENCH: fused-adam step ok: %.3f ms' % fused_adam_ms,
          file=sys.stderr)

    telemetry = obs.telemetry_snapshot(
        'bench', baseline=snap0, snapshot=snap1,
        extra={'platform': dev0.platform,
               'device_kind': str(dev0.device_kind),
               'program_op_count_raw': raw_ops,
               'program_op_count_opt': opt_ops,
               'fused_adam_ms': fused_adam_ms})
    if telemetry['emitter_fallbacks']:
        print('BENCH: WARNING — %d emitter fallback(s): the direct '
              'Program→jaxpr emitter degraded to traced lowering (run '
              'PT_STRICT_EMIT=1 to get the raw error)'
              % telemetry['emitter_fallbacks'], file=sys.stderr)
    if telemetry['retraces']:
        print('BENCH: WARNING — %d retrace(s) DURING the measured fused '
              'loop; the number below is compile-polluted'
              % telemetry['retraces'], file=sys.stderr)
        rep = obs.explainer().last_report()
        if rep:
            print('BENCH: last retrace cause: %s'
                  % '; '.join(rep['details']), file=sys.stderr)

    # model FLOPs (scaling-book accounting): 6*P per trained token for the
    # MATMUL params (embedding gathers excluded — they do no MXU work),
    # + 12*T*d per token per attention layer for the score / context
    # matmuls (fwd 4*T*d, bwd x2); enc self + dec self + dec cross
    attn_layers = 3 * n_layer
    flops_per_token = 6.0 * n_matmul_params + 12.0 * T * d_model * attn_layers
    model_flops_per_s = flops_per_token * tps
    peak = peak_flops(device_kind) if on_tpu else None
    mfu = round(model_flops_per_s / peak, 4) if peak else None

    stage('allreduce')
    ar_bw = allreduce_bw_gbps()

    stage('resnet50')
    resnet_rec = bench_resnet50(on_tpu, device_kind)
    print('BENCH: resnet50 ok: %.1f img/s' %
          resnet_rec['resnet50_images_per_sec'], file=sys.stderr)

    stage('report')
    rec = {
        'metric': 'transformer_base_tokens_per_sec_per_chip',
        'value': round(tps, 1),
        'unit': 'tokens/s',
        'vs_baseline': round(tps / BASELINE_TOKENS_PER_SEC, 3),
        'mfu': mfu,
        'model_tflops_per_s': round(model_flops_per_s / 1e12, 2),
        'params_m': round(n_params / 1e6, 1),
        'matmul_params_m': round(n_matmul_params / 1e6, 1),
        'backend': device_kind,
        'batch': B, 'seq': T, 'amp': True, 'flash': True,
        'steps_per_launch': K,
        'single_step_tokens_per_sec': round(tps_single, 1),
        'sync_mode_tokens_per_sec': round(tps_sync, 1),
        'check_nan_overhead_x': round(check_nan_overhead_x, 2),
        'telemetry': telemetry,
    }
    rec.update(resnet_rec)
    if ar_bw is not None:
        rec['allreduce_gbps'] = round(ar_bw, 1)
    print(json.dumps(rec))

    # feed the perf lab's append-only ledger when asked (PT_PERF_LEDGER):
    # the SAME record contract as a `perflab run` scenario, so bench rows
    # diff against blessed baselines with the same counter/timing rules
    from paddle_tpu.observability import perflab
    perflab.maybe_ledger(
        'bench',
        {'program_op_count_opt': int(opt_ops),
         'retraces': int(telemetry['retraces']),
         'kernel_fallbacks': int(telemetry['kernel_fallbacks']),
         'kernelgen_fallbacks': int(telemetry['kernelgen_fallbacks']),
         'emitter_fallbacks': int(telemetry['emitter_fallbacks']),
         'tokens_per_s': round(tps, 1),
         'mfu': mfu,
         'host_blocked_s': telemetry.get('host_blocked_s'),
         'fused_adam_ms': fused_adam_ms,
         'resnet50_images_per_s':
             resnet_rec.get('resnet50_images_per_sec'),
         'batch': B, 'seq': T},
        config={'steps_per_launch': K, 'vocab': vocab,
                'layers': n_layer, 'd_model': d_model})


def _tiny_warmup(fluid, vocab):
    """One 2-layer micro train step end-to-end: exercises the same lowering
    path at trivial size so backend trouble shows up fast."""
    from paddle_tpu.models import transformer as tr
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        with fluid.unique_name.guard():
            out = tr.build(src_vocab=128, trg_vocab=128, max_len=8,
                           n_layer=1, n_head=2, d_model=32, d_inner=64,
                           dropout=0.0, use_flash=False)
    prog.set_amp(True)
    exe = fluid.Executor()
    scope = fluid.Scope()
    rows = [(np.array([3, 4, 1]), np.array([0, 3, 4]), np.array([3, 4, 1]))]
    feed = tr.make_batch(rows, 8)
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(prog, feed=feed, fetch_list=[out['loss']])


if __name__ == '__main__':
    # a crashed bench still leaves a diagnosable artifact: the last
    # line is {"error": ..., "stage": ...} instead of a bare stack
    _harness.main_guard(main, flight_tag='bench.watchdog')
