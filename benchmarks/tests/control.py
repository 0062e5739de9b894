#!/usr/bin/env python3
"""The control of a training cell's comparison, and the readings its
limits are set from.  The control is the plain reference put in the
program's place and computed a precision BELOW the one the configuration
states.  The training configurations state AMP (bf16 matmul operands);
the step below, and the one that would tempt a later PR, is 8-bit
weights: every matrix, filter and table rounded to float8_e4m3 before the
reference's own f32 forward and backward pass.  The control must come
out NOT correct, or the comparison cannot tell a program that stores its
weights in eight bits from a sound one.

    python3 benchmarks/tests/control.py --workload tbase.train_dp4 \\
        --seeds 12 --control-seeds 3

builds the cell's program ONCE and then, seed after seed, sets the
weights, runs the first launch on the seed's first batches and compares
what its first step fetched (the loss, every item's own loss, the probed
gradients) with the reference's `probes` on the same weights and batch:
the sound readings.  On the first `--control-seeds` of them the reference
with 8-bit weights is compared the same way: the control's readings.
Nothing is timed.  One `reading:` line a seed, one `readings:` line with
the largest sound and the smallest control reading of each number and
their ratio, and the same under chiprun_out/.  It is never part of a
cell's run; tests/test_control.py keeps it at a tiny size on the CPU.
"""
import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

NUMBERS = ('loss', 'per_item', 'grads')


def eight_bit(params):
    """The parameters with every array of two or more dimensions rounded
    to float8_e4m3fn and back; vectors (norm scales, biases) stay, as in a
    weight-only 8-bit deployment."""
    import jax.numpy as jnp
    return {n: p.astype(jnp.float8_e4m3fn).astype(p.dtype) if p.ndim >= 2
            else p for n, p in params.items()}


def seeds_from(first, count):
    """`count` seeds from `first` on, a large odd stride apart and all
    under 2**31 + 2**20: small ones, large ones, and past 32 signed bits."""
    return [(first + i * 357913951) % (2 ** 31 + 2 ** 20)
            for i in range(count)]


def readings(workload, seeds, control_seeds, allow_cpu=False, root=run.ROOT):
    """[{seed, sound: {...}, control: {...} or None}] and the limits."""
    from lib import peaks, traffic as _traffic
    from runners.train import Trainer, probe_errors
    _, cell, config, traffic, _ = run.load_cell(root, workload)
    peaks.require_device(cell['chips'], allow_cpu=allow_cpu)
    trainer = Trainer(cell, config, traffic)
    ref = trainer.ref
    base = trainer.params()
    rows = []
    for i, seed in enumerate(seeds):
        init = trainer.set_weights(seed, base)
        reader, _ = _traffic.TRAIN_GENERATORS[traffic['generator']](
            traffic, config[trainer.size_key], seed)
        group = [next(reader) for _ in range(trainer.K)]
        feed = {k: np.stack([f[k] for f in group]) for k in group[0]}
        loss, got = trainer.first_step(trainer.launch(feed))
        first = {k: v[0] for k, v in feed.items()}
        want = ref.probes(init, first, config)

        def against(loss, got):
            out = probe_errors(got, want)
            out['loss'] = abs(loss - want['loss']) / abs(want['loss'])
            return out
        row = {'seed': seed, 'sound': against(loss, got), 'control': None}
        if i < control_seeds:
            low = ref.probes(eight_bit(init), first, config)
            row['control'] = against(low['loss'], low)
        run.say('reading', workload=workload, **row)
        rows.append(row)
    return rows, {'loss': float(ref.LOSS_RTOL),
                  'per_item': float(ref.ITEM_TOL),
                  'grads': float(ref.GRAD_TOL)}


def summary(rows, limits):
    """For each number compared: the largest sound reading, the smallest
    control reading, their ratio and the limit; `sound_pass`: every sound
    run is within every limit; `control_fails`: every control run is
    beyond one of them."""
    out = {}
    for k in NUMBERS:
        sound = [r['sound'][k] for r in rows]
        control = [r['control'][k] for r in rows if r['control']]
        out[k] = {'sound_max': max(sound), 'sound_median':
                  float(np.median(sound)), 'limit': limits[k],
                  'control_min': min(control) if control else None,
                  'ratio': min(control) / max(sound)
                  if control and max(sound) > 0 else None}
    out['sound_pass'] = all(r['sound'][k] <= limits[k]
                            for r in rows for k in NUMBERS)
    out['control_fails'] = all(any(r['control'][k] > limits[k]
                                   for k in NUMBERS)
                               for r in rows if r['control'])
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--first-seed', type=int, default=2 ** 31 + 77)
    ap.add_argument('--seeds', type=int, default=12)
    ap.add_argument('--control-seeds', type=int, default=3)
    args = ap.parse_args(argv)
    rows, limits = readings(args.workload,
                            seeds_from(args.first_seed, args.seeds),
                            args.control_seeds)
    fields = summary(rows, limits)
    run.say('readings', workload=args.workload, **fields)
    out = os.path.join(run.ROOT, 'chiprun_out')
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, 'control.%s.json' % args.workload), 'w') as f:
        json.dump({'rows': rows, 'summary': fields}, f, indent=1,
                  default=float)
    return 0 if fields['sound_pass'] and fields['control_fails'] else 1


if __name__ == '__main__':
    sys.exit(main())
