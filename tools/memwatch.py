#!/usr/bin/env python
"""Device/host memory report: run a workload, print what it cost.

Answers the two questions the HBM-bound fusion work (ROADMAP item 2)
keeps asking:

  * what does one training/serving launch hold on the DEVICE —
    ``exec.hbm_peak_bytes`` / ``exec.hbm_in_use_bytes`` where the
    backend reports memory stats (TPU/GPU), ``exec.live_buffers``
    everywhere (a monotonically-climbing live count is a buffer leak);
  * what does checkpointing hold on the HOST —
    ``ckpt.snapshot_host_bytes`` per snapshot (forced device->host
    copies pinned until the async writer drains) against the process
    high-water RSS;
  * (``--decode``) what does the paged KV pool of the streaming decode
    runtime reserve vs actually pin — ``generation.kv_bytes_reserved``
    (the fixed pool footprint) against ``generation.kv_bytes_live`` /
    ``kv_pages_in_use`` sampled while streams run, the serving-density
    counterpart of the HBM gauges (docs/generation.md).

Runs a small fused training loop with
periodic checkpoints, sampling after every launch, and prints one JSON
report.  ``--steps``/``--steps-per-launch``/``--hidden`` scale the
workload; on CPU the HBM gauges are absent by design (memory_stats()
is a TPU/GPU surface) and the report says so instead of printing
zeros that look like measurements.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _decode_report(args):
    """Run a few streams through a small paged DecodeRuntime and sample
    the KV pool gauges: reserved (fixed) vs live (pages in use) bytes —
    the number the serving-density work optimizes."""
    import paddle_tpu.observability as obs
    from paddle_tpu.serving.generation import (DecodeRuntime,
                                               SamplingParams,
                                               random_weights)
    cfg = dict(vocab=128, d_model=32, n_layer=2, n_head=4, n_kv_head=2,
               d_ffn=64, theta=10000.0, max_len=32)
    rt = DecodeRuntime(random_weights(cfg, seed=0), cfg, slots=4,
                       prefill_chunk=4, kv_quant=args.kv_quant)
    rt.warmup(steps=4)

    def kv_gauges():
        g = obs.metrics_snapshot().get('gauges', {})
        return {k: g.get('generation.' + k)
                for k in ('kv_bytes_reserved', 'kv_bytes_live',
                          'kv_pages_in_use', 'kv_slots_in_use')}

    peak = {}
    slots = [rt.alloc_slot() for _ in range(rt.slots)]
    try:
        for i, slot in enumerate(slots):
            prompt = [1 + i, 5, 9, 2, 7, 3]
            start = rt.try_begin(slot, prompt, 4)
            for off in range(start, len(prompt), rt.prefill_chunk):
                rt.prefill(slot, prompt[off:off + rt.prefill_chunk], off,
                           SamplingParams(seed=i))
        import numpy as np
        active = np.ones(rt.slots, bool)
        zeros = np.zeros(rt.slots, np.int32)
        for _ in range(4):
            ok = all(rt.ensure_capacity(s, int(rt.host_len[s]) + 4)
                     for s in slots)
            if not ok:
                break
            rt.decode_window(4, active, zeros, zeros.astype(np.float32),
                             zeros)
        peak = kv_gauges()
    finally:
        for slot in slots:
            rt.free_slot(slot)
        if rt.prefix is not None:
            rt.prefix.reset()
    drained = kv_gauges()
    return {'quant': rt.cache.quant,
            'page_len': rt.cache.page_len,
            'page_bytes': rt.cache.page_bytes(),
            'dense_slot_bytes': rt.cache.dense_slot_bytes(),
            'peak': peak, 'drained': drained,
            'pages_leaked': rt.pool.in_use()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=32)
    ap.add_argument('--steps-per-launch', type=int, default=4)
    ap.add_argument('--batch', type=int, default=16)
    ap.add_argument('--hidden', type=int, default=64)
    ap.add_argument('--ckpt-interval', type=int, default=8,
                    help='checkpoint every N steps (0 disables)')
    ap.add_argument('--decode', action='store_true',
                    help='also run a small paged decode workload and '
                         'report the KV pool gauges')
    ap.add_argument('--kv-quant', default='none', choices=['none', 'int8'],
                    help='KV quantization for the --decode workload')
    args = ap.parse_args()

    import numpy as np
    import paddle_tpu as fluid
    import paddle_tpu.observability as obs
    from paddle_tpu.observability import memory as obs_mem
    from paddle_tpu.train import CheckpointConfig, Checkpointer

    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = 11
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[8], dtype='float32')
            lbl = fluid.layers.data('lbl', shape=[1], dtype='int64')
            h = fluid.layers.fc(x, args.hidden, act='relu')
            logits = fluid.layers.fc(h, 4)
            loss = fluid.layers.reduce_mean(
                fluid.layers.softmax_with_cross_entropy(logits, lbl))
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)

    exe, scope = fluid.Executor(), fluid.Scope()
    rng = np.random.RandomState(5)
    K = max(1, args.steps_per_launch)

    def superfeed():
        return {'x': rng.rand(K, args.batch, 8).astype('float32'),
                'lbl': rng.randint(0, 4, (K, args.batch, 1)).astype('int64')}

    import tempfile
    ck = None
    samples = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        if args.ckpt_interval > 0:
            ck = Checkpointer(CheckpointConfig(
                checkpoint_dir=tempfile.mkdtemp(prefix='pt_memwatch.'),
                step_interval=args.ckpt_interval, handle_signals=False),
                exe)
        step = 0
        while step < args.steps:
            exe.run_steps(main_prog, feed_list=superfeed(), steps=K,
                          fetch_list=[loss.name], return_numpy=False)
            step += K
            if ck is not None:
                ck.maybe_save(0, step)
            g = obs.metrics_snapshot().get('gauges', {})
            samples.append({
                'step': step,
                'hbm_peak_bytes': g.get('exec.hbm_peak_bytes'),
                'hbm_in_use_bytes': g.get('exec.hbm_in_use_bytes'),
                'live_buffers': g.get('exec.live_buffers'),
                'ckpt_snapshot_host_bytes':
                    g.get('ckpt.snapshot_host_bytes'),
            })
        if ck is not None:
            ck.wait()

    g = obs.metrics_snapshot().get('gauges', {})
    c = obs.counters()
    hbm_samples = [s['hbm_peak_bytes'] for s in samples
                   if s['hbm_peak_bytes'] is not None]
    live = [s['live_buffers'] for s in samples
            if s['live_buffers'] is not None]
    report = {
        'device_stats_supported': bool(hbm_samples),
        'hbm_peak_bytes_max': max(hbm_samples) if hbm_samples else None,
        'hbm_limit_bytes': g.get('exec.hbm_limit_bytes'),
        'live_buffers_first': live[0] if live else None,
        'live_buffers_last': live[-1] if live else None,
        'ckpt_snapshot_host_bytes': g.get('ckpt.snapshot_host_bytes'),
        'ckpt_snapshot_bytes_total': int(
            c.get('ckpt.snapshot_bytes_total') or 0),
        'ckpt_saves': int(c.get('ckpt.saves') or 0),
        'host_rss_peak_bytes': obs_mem.host_rss_bytes(),
        'samples': samples,
    }
    if not hbm_samples:
        report['note'] = ('backend reports no memory_stats() (CPU): HBM '
                          'gauges are absent by design; live_buffers and '
                          'host accounting above are still real')
    if args.decode:
        report['kv'] = _decode_report(args)
    print(json.dumps(report))
    # a leak check cheap enough to always run: the live-buffer count at
    # the end of a steady-state loop should not have grown unboundedly
    if live and live[-1] > max(16, 4 * max(live[0], 1)):
        sys.exit('memwatch: live buffer count grew %d -> %d over the '
                 'run — buffer leak' % (live[0], live[-1]))
    return 0


if __name__ == '__main__':
    sys.exit(main())
