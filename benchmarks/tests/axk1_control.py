"""The `axk1` comparison's controls, run by hand on the chip at the cell's own
size (as tests/control.py is for training):

    chiprun --timeout 3000 -- \
        python3 benchmarks/tests/axk1_control.py --seed <n>

One runtime at the published widths (runners/serve.py:build_runtime, the
weights from --seed), then for each of the cell's `compare_prompts` contexts
(chosen as runners/serve.py:compare chooses them) the logits the program
gives at the last position after chunked prefill, one decode window and one
more chunk through the latent pool, compared in the 2-norm with

  * the sound reference (what decides `correct`), and
  * the reference made wrong in each way of references/axk1.py:CONTROLS,
    which must read ABOVE `LOGIT_RTOL` (the rope part of the score dropped,
    the top-8 weights left unnormalised, the cached row rounded to int8,
    every matrix rounded to float8),

each handed those logits (``got=``) as the cell's run hands them, so that
each resolves a near-tie at the compared position by the one rule;

and, for routing (which is discontinuous), the program's OWN picks for
every prompt token in every expert layer, taken out of a prefill
executable rebuilt with a `jax.debug.callback` on `experts.route` (the
timed executables are not touched), against the reference's: the share of
(token, layer) pairs whose pick sets differ, how many of those differ in a
HELD expert (one whose contribution is then in one result and not in the
other), and the reference's margin (the router logit of the 8th pick less
that of the 9th) at the pairs that differ and overall: what NEAR_TIE of
references/axk1.py is set from.  The reference's own `routing:` line is kept
with each prompt: the alternatives its margins admitted at the compared
position, how far each lies from the plain selection's logits (what one
near-tie costs where it is not resolved) and which one it took.  Everything
is printed as JSON lines and written to
chiprun_out/axk1_control.<seed>.json.  Nothing here is part of a cell's run.
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

CELL = 'axk1.shared_context_answers'


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
    """(context, logits) per compare prompt, as runners/serve.py:compare
    takes them; ``picked`` receives the program's picks of every chunk."""
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
    ap.add_argument('--seed', type=int, default=2 ** 31 + 47)
    ap.add_argument('--controls', default='all')
    ap.add_argument('--allow-cpu', action='store_true',
                    help='the tiny-size rehearsal of tests/test_axk1.py')
    args = ap.parse_args(argv)
    import run
    _, cell, config, traffic, runner = run.load_cell(ROOT, CELL)
    from lib import peaks, spans as _spans
    device = peaks.require_device(cell['chips'], allow_cpu=args.allow_cpu)
    say('device', **device)
    import jax
    from paddle_tpu.core import compile_cache as _cc
    from paddle_tpu.serving.generation import experts
    from runners.train import load_reference
    ref = load_reference(config)
    rt, model = runner.build_runtime(config, traffic, args.seed,
                                     _spans.Spans(), {'windows': []})

    # the program's own picks: a prefill executable rebuilt with a callback
    picked, route = [], experts.route

    def recorded(h, router_w, moe):
        picks, wts = route(h, router_w, moe)
        jax.debug.callback(lambda p: picked[-1].append(np.asarray(p)), picks,
                           ordered=True)
        return picks, wts

    experts.route = recorded
    disk, _cc.disk_enabled = _cc.disk_enabled, lambda: False
    rt._execs.pop(('prefill', rt.prefill_chunk))
    try:
        runs = contexts(rt, model, traffic, args.seed, picked)
    finally:
        experts.route, _cc.disk_enabled = route, disk
    n_moe = sum(1 for kind in model['ffn'] if kind == 'experts')
    first = model['moe']['rank'] * (model['moe']['n_routed']
                                    // model['moe']['ranks'])
    held = set(range(first, first + model['moe']['n_routed']
                     // model['moe']['ranks']))
    wanted = ref.CONTROLS if args.controls == 'all' else tuple(
        c for c in args.controls.split(',') if c)
    rows = []
    for (context, got, plen), chunks in zip(runs, picked):
        ref_picks = []
        want, said = reference_logits(ref, rt.w, model, context, got=got,
                                      picks_out=ref_picks)
        row = {'context': int(context.size),
               'sound': float(np.linalg.norm(got - want)
                              / np.linalg.norm(want)),
               'sound_plain': said['plain_from_compared'],
               'alternatives': said['alternatives'], 'taken': said['taken'],
               'position_alone_from_plain':
                   said['position_alone_from_plain']}
        # chunk c's callbacks came layer by layer; the last one-token chunk
        # (the compared position) is the last n_moe entries
        per_layer = [np.concatenate(chunks[j:-n_moe:n_moe])[:plen]
                     for j in range(n_moe)]
        differ = held_differ = pairs = 0
        margins_all, margins_differ = [], []
        # a token is CLEAN in a layer while no earlier layer gave it a
        # pick that differs in a held expert (after one, its stream differs
        # by a whole contribution and later layers' picks cascade; at the
        # compared position the reference carries each alternative on
        # layer by layer, so only clean differences matter there)
        clean = np.ones(plen, bool)
        clean_pairs = clean_held_differ = clean_held_outside = 0
        margins_clean = []
        for mine, (theirs, margin) in zip(per_layer, ref_picks):
            theirs, margin = theirs[:plen], margin[:plen]
            dirty = np.zeros(plen, bool)
            for t in range(plen):
                a, b = set(mine[t].tolist()), set(theirs[t].tolist())
                if a != b:
                    differ += 1
                    margins_differ.append(float(margin[t]))
                    in_held = bool((a ^ b) & held)
                    held_differ += in_held
                    dirty[t] = in_held
                    if clean[t]:
                        margins_clean.append(float(margin[t]))
                        clean_held_differ += in_held
                        clean_held_outside += in_held and bool(
                            margin[t] > ref.NEAR_TIE)
            clean_pairs += int(clean.sum())
            clean &= ~dirty
            pairs += plen
            margins_all.append(margin)
        last = [set(c[0].tolist()) for c in chunks[-n_moe:]]
        last_ref = [set(p[-1].tolist()) for p, _ in ref_picks]
        margins_all = np.concatenate(margins_all)
        row['routing'] = {
            'pairs': pairs, 'picks_differ_share': differ / pairs,
            'differ_in_a_held_expert_share': held_differ / pairs,
            'reference_margin_median': float(np.median(margins_all)),
            'reference_margin_where_they_differ_max':
                max(margins_differ) if margins_differ else None,
            'reference_margin_where_they_differ_p999':
                float(np.quantile(margins_differ, 0.999))
                if margins_differ else None,
            'reference_margin_where_they_differ_median':
                float(np.median(margins_differ)) if margins_differ else None,
            'share_of_pairs_with_margin_under_near_tie':
                float(np.mean(margins_all < ref.NEAR_TIE)),
            'clean_pairs': clean_pairs,
            'clean_margin_where_they_differ_p99':
                float(np.quantile(margins_clean, 0.99))
                if margins_clean else None,
            'clean_margin_where_they_differ_p999':
                float(np.quantile(margins_clean, 0.999))
                if margins_clean else None,
            'clean_margin_where_they_differ_max':
                max(margins_clean) if margins_clean else None,
            'clean_differ_in_a_held_expert_share':
                clean_held_differ / max(1, clean_pairs),
            'clean_differ_in_a_held_expert_outside_near_tie_share':
                clean_held_outside / max(1, clean_pairs),
            'compared_position_layers_that_differ_in_a_held_expert':
                sum(bool((a ^ b) & held) for a, b in zip(last, last_ref))}
        for control in wanted:
            wrong, _ = reference_logits(ref, rt.w, model, context,
                                        control=control, got=got)
            row[control] = float(np.linalg.norm(got - wrong)
                                 / np.linalg.norm(wrong))
        say('prompt', **row)
        rows.append(row)
    summary = {'seed': args.seed, 'rtol': float(ref.LOGIT_RTOL),
               'initializer_range': config['initializer_range'],
               'sound_worst': max(r['sound'] for r in rows),
               'sound_plain_worst': max(r['sound_plain'] for r in rows),
               'prompts_that_took_an_alternative':
                   sum(bool(r['taken']) for r in rows),
               'alternatives_from_plain': [a['from_plain'] for r in rows
                                           for a in r['alternatives']]}
    for control in wanted:
        summary[control + '_worst'] = max(r[control] for r in rows)
        summary[control + '_least'] = min(r[control] for r in rows)
        summary[control + '_not_correct'] = bool(
            summary[control + '_worst'] > ref.LOGIT_RTOL)
    say('summary', **summary)
    os.makedirs(os.path.join(ROOT, 'chiprun_out'), exist_ok=True)
    with open(os.path.join(ROOT, 'chiprun_out',
                           'axk1_control.%d.json' % args.seed), 'w') as f:
        json.dump({'summary': summary, 'prompts': rows}, f, indent=1)
    return 0


if __name__ == '__main__':
    sys.exit(main())
