"""The `lfm2_8b_a1b` comparison's controls, run by hand on the chip at the
cell's own size (as tests/kimi_linear_control.py is for `kimi_linear`):

    chiprun --timeout 3000 -- \
        python3 benchmarks/tests/lfm2_8b_a1b_control.py --seed <n>

One runtime at the published widths (runners/serve.py:build_runtime, the
weights from --seed), then for each of the cell's `compare_prompts` contexts
(chosen as runners/serve.py:compare chooses them) the logits the program
gives at the last position after chunked prefill, one decode window and one
more chunk through the K/V pool and the convolutions' tails, compared in the
2-norm with

  * the sound reference (what decides `correct`), and
  * the reference made wrong in each way of references/lfm2_8b_a1b.py:
    CONTROLS, which must read ABOVE `LOGIT_RTOL` (the tail zeroed where a
    chunk begins, the query/key head norms left out, the experts chosen
    without the bias, the rotation left out, every matrix rounded to
    float8), and its two READINGS, which nobody judges (the residual stream
    rounded to bfloat16; no operand rounded at all),

the sound reference and every control handed those logits (``got=``) as the
cell's run hands them: a control is held to the same near-tie rule as the
sound reference;

and, for routing (which is discontinuous), the program's OWN picks for every
prompt token and for the compared position in every expert layer, taken out
of a prefill executable rebuilt with a `jax.debug.callback` on
`experts.route` (the timed executables are not touched), against the
reference's: the share of (token, layer) pairs whose pick sets differ (every
expert is held here, so every one of them matters), and the reference's
margin at the pairs that differ: what NEAR_TIE of references/lfm2_8b_a1b.py
is set from.  The reference's own `routing:` line is kept with each prompt.
`--initializer-range` overrides the configuration's assumed value (how it
was chosen: PERF.md, Findings of PR 63).  Everything is
printed as JSON lines and written to chiprun_out/lfm2_8b_a1b_control.<seed>.
<range>.json.  Nothing here is part of a cell's run.
"""
import argparse
import contextlib
import io
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

CELL = 'lfm2_8b_a1b.many_streams_medium_prompts'


def say(what, **fields):
    print('%s: %s' % (what, json.dumps(fields, sort_keys=True, default=str)),
          flush=True)


def reference_logits(ref, *args, **kwargs):
    """(`ref.last_logits(...)`, what its `routing:` line said)."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        logits = ref.last_logits(*args, **kwargs)
    print(out.getvalue(), end='', flush=True)
    said = [ln for ln in out.getvalue().splitlines()
            if ln.startswith('routing: ')]
    return logits, json.loads(said[-1][len('routing: '):]) if said else None


def contexts(rt, model, traffic, seed, picked):
    """(context, logits, prompt length) per compare prompt, as
    runners/serve.py:compare takes them; ``picked`` receives the program's
    picks of every chunk."""
    from lib import traffic as _traffic
    from paddle_tpu.serving.generation import SamplingParams
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
    out = []
    for plen in picks:
        prompt = rng.integers(1, model['vocab'], plen, dtype=np.int32)
        slot = rt.alloc_slot()
        assert rt.try_begin(slot, prompt, K) == 0
        picked.append([])
        for off in range(0, plen, rt.prefill_chunk):
            first, _ = rt.prefill(slot, prompt[off:off + rt.prefill_chunk],
                                  off, SamplingParams())
            int(first)                      # the chunk's callbacks have run
        active = np.zeros(rt.slots, bool)
        active[slot] = True
        zeros = np.zeros(rt.slots, np.int32)
        toks = rt.decode_window(K, active, zeros,
                                np.zeros(rt.slots, np.float32), zeros)[slot]
        assert rt.ensure_capacity(slot, plen + K + 1)
        _, logits = rt.prefill(slot, toks[-1:], plen + K, SamplingParams())
        got = np.asarray(logits, np.float32)
        rt.free_slot(slot)
        out.append((np.concatenate([prompt, [int(first)], toks])
                    .astype(np.int32), got, plen))
    rt.reset()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--seed', type=int, default=2 ** 31 + 61)
    ap.add_argument('--controls', default='all')
    ap.add_argument('--initializer-range', type=float, default=None)
    ap.add_argument('--no-picks', action='store_true',
                    help='leave the prefill executable as it is: no record '
                         'of the program\'s own picks')
    ap.add_argument('--prompts', default=None,
                    help='which of the compare prompts, e.g. 1,3 (all)')
    ap.add_argument('--allow-cpu', action='store_true',
                    help='the tiny-size rehearsal of tests/'
                         'test_lfm2_8b_a1b.py')
    args = ap.parse_args(argv)
    import run
    _, cell, config, traffic, runner = run.load_cell(ROOT, CELL)
    if args.initializer_range is not None:
        config['initializer_range'] = args.initializer_range
    from lib import peaks, spans as _spans
    device = peaks.require_device(cell['chips'], allow_cpu=args.allow_cpu)
    say('device', **device)
    from runners.train import load_reference
    ref = load_reference(config)
    rt, model = runner.build_runtime(config, traffic, args.seed,
                                     _spans.Spans(), {'windows': []})
    wanted = ref.CONTROLS if args.controls == 'all' else tuple(
        c for c in args.controls.split(',') if c)
    # read, not judged: the reference with no operand rounded
    readings = ref.READINGS if args.controls == 'all' else ()
    # the program's own picks: a prefill executable rebuilt with a callback
    import jax
    from paddle_tpu.core import compile_cache as _cc
    from paddle_tpu.serving.generation import experts
    picked, route = [], experts.route

    def recorded(h, router_w, moe, *bias):
        picks, wts = route(h, router_w, moe, *bias)
        jax.debug.callback(lambda p: picked[-1].append(np.asarray(p)), picks,
                           ordered=True)
        return picks, wts

    disk = _cc.disk_enabled
    if not args.no_picks:
        experts.route, _cc.disk_enabled = recorded, lambda: False
        rt._execs.pop(('prefill', rt.prefill_chunk))
    try:
        runs = contexts(rt, model, traffic, args.seed, picked)
    finally:
        experts.route, _cc.disk_enabled = route, disk
    n_moe = model['ffn'].count('experts')
    n_held = model['moe']['n_routed'] // model['moe']['ranks']
    held = set(range(model['moe']['rank'] * n_held,
                     (model['moe']['rank'] + 1) * n_held))
    chosen = range(len(runs)) if not args.prompts else [
        int(i) for i in args.prompts.split(',')]
    rows = []
    for context, got, plen, chunks in [runs[i] + (picked[i],)
                                       for i in chosen]:
        ref_picks = []
        want, said = reference_logits(ref, rt.w, model, context, got=got,
                                      picks_out=ref_picks)
        row = {'context': int(context.size),
               'sound': float(np.linalg.norm(got - want)
                              / np.linalg.norm(want)),
               'sound_plain': said['plain_from_compared'],
               'margins': said['margin_at_compared_position'],
               'weighed': said['weighed'], 'taken': said['taken']}
        for control in wanted + readings:
            wrong, _ = reference_logits(
                ref, rt.w, model, context, control=control,
                got=got,
                chunk=int(traffic['prefill_chunk']))
            row[control] = float(np.linalg.norm(got - wrong)
                                 / np.linalg.norm(wrong))
        if args.no_picks:
            say('prompt', **row)
            rows.append(row)
            continue
        # chunk c's callbacks came layer by layer; the last one-token chunk
        # (the compared position) is the last n_moe entries
        mine = [np.concatenate(chunks[j:-n_moe:n_moe])[:plen]
                for j in range(n_moe)]
        differ = held_differ = 0
        margins_differ = []
        for layer, (theirs, margin) in zip(mine, ref_picks):
            for t in range(plen):
                a, b = set(layer[t].tolist()), set(theirs[t].tolist())
                if a != b:
                    differ += 1
                    if (a ^ b) & held:
                        held_differ += 1
                        margins_differ.append(float(margin[t]))
        last = [set(c[0].tolist()) for c in chunks[-n_moe:]]
        row['routing'] = {
            'pairs': n_moe * plen, 'picks_differ_share': differ / (n_moe * plen),
            'differ_in_a_held_expert_share': held_differ / (n_moe * plen),
            'margin_where_they_differ_in_a_held_expert': {
                'median': float(np.median(margins_differ)),
                'p99': float(np.quantile(margins_differ, 0.99)),
                'max': max(margins_differ)} if margins_differ else None,
            'compared_position': [
                {'layer': j, 'margin': float(m[-1]),
                 'program_not_reference': sorted((a - b) & held),
                 'reference_not_program': sorted((b - a) & held)}
                for j, (a, (p, m)) in enumerate(zip(last, ref_picks))
                for b in [set(p[-1].tolist())] if (a ^ b) & held]}
        say('prompt', **row)
        rows.append(row)
    summary = {'seed': args.seed, 'rtol': float(ref.LOGIT_RTOL),
               'initializer_range': config['initializer_range'],
               'sound_worst': max(r['sound'] for r in rows),
               'sound_plain_worst': max(r['sound_plain'] for r in rows),
               'prompts_that_took_an_alternative':
                   sum(bool(r['taken']) for r in rows),
               'selections_weighed': sum(len(r['weighed']) for r in rows)}
    for control in wanted:
        summary[control + '_worst'] = max(r[control] for r in rows)
        summary[control + '_least'] = min(r[control] for r in rows)
        summary[control + '_not_correct'] = bool(
            summary[control + '_worst'] > ref.LOGIT_RTOL)
    for reading in readings:
        summary[reading + '_worst'] = max(r[reading] for r in rows)
        summary[reading + '_least'] = min(r[reading] for r in rows)
    say('summary', **summary)
    os.makedirs(os.path.join(ROOT, 'chiprun_out'), exist_ok=True)
    name = 'lfm2_8b_a1b_control.%d.%g.json' % (
        args.seed, config['initializer_range'])
    with open(os.path.join(ROOT, 'chiprun_out', name), 'w') as f:
        json.dump({'summary': summary, 'prompts': rows}, f, indent=1)
    return 0


if __name__ == '__main__':
    sys.exit(main())
