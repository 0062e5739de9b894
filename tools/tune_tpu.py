"""One-shot TPU tuning sweep for the headline benchmarks.

Run on a live chip (`python tools/tune_tpu.py`); prints a table of
(batch, seq) configurations for the transformer and batch sizes for
ResNet-50, so the best one can be promoted to bench.py defaults.  MFU
accounting and the chip peak are imported from bench.py — one metric,
two tools.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from bench import peak_flops  # noqa: E402


def _peak():
    import jax
    return peak_flops(str(jax.devices()[0].device_kind))


def _sync(x):
    return float(np.asarray(x).ravel()[0])


def bench_transformer(B, T, steps=20):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tr

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            out = tr.build(src_vocab=32000, trg_vocab=32000, max_len=T,
                           n_layer=6, n_head=8, d_model=512,
                           d_inner=2048, dropout=0.0, use_flash=True)
    main.set_amp(True)
    exe = fluid.Executor()
    scope = fluid.Scope()
    feed = tr.synthetic_batch(np.random.RandomState(0), B, T)
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed = {k: jax.device_put(v) for k, v in feed.items()}
        for _ in range(3):
            loss, = exe.run(main, feed=feed, fetch_list=[out['loss']])
        _sync(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, = exe.run(main, feed=feed, fetch_list=[out['loss']],
                            return_numpy=False)
        _sync(loss)
        dt = time.perf_counter() - t0
    tps = steps * B * T / dt
    n_mm = sum(
        int(np.prod(v.shape)) for v in
        main.global_block().all_parameters()
        if v.shape and not v.name.endswith('_emb'))
    fpt = 6.0 * n_mm + 12.0 * T * 512 * (3 * 6)
    return tps, fpt * tps / _peak()


def bench_resnet(B, steps=10):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    main, startup, out, feed = resnet.bench_program(B=B)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed = {k: jax.device_put(v) for k, v in feed.items()}
        for _ in range(2):
            loss, = exe.run(main, feed=feed, fetch_list=[out['loss']])
        _sync(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, = exe.run(main, feed=feed, fetch_list=[out['loss']],
                            return_numpy=False)
        _sync(loss)
        dt = time.perf_counter() - t0
    ips = steps * B / dt
    from bench import RESNET50_TRAIN_FLOPS_PER_IMAGE
    return ips, RESNET50_TRAIN_FLOPS_PER_IMAGE * ips / _peak()


def main():
    import jax
    print('backend:', jax.default_backend(), jax.devices()[0].device_kind,
          flush=True)
    for B, T in ((32, 256), (64, 256), (128, 256), (64, 512)):
        try:
            t0 = time.time()
            tps, mfu = bench_transformer(B, T)
            print('transformer B=%-4d T=%-4d  %9.0f tok/s  mfu=%.3f  '
                  '(%.0fs)' % (B, T, tps, mfu, time.time() - t0),
                  flush=True)
        except Exception as e:
            print('transformer B=%d T=%d FAILED: %s' % (B, T, e),
                  flush=True)
    for B in (64, 128, 256):
        try:
            t0 = time.time()
            ips, mfu = bench_resnet(B)
            print('resnet50    B=%-4d         %9.1f img/s  mfu=%.3f  '
                  '(%.0fs)' % (B, ips, mfu, time.time() - t0), flush=True)
        except Exception as e:
            print('resnet50 B=%d FAILED: %s' % (B, e), flush=True)


if __name__ == '__main__':
    sys.exit(main())
