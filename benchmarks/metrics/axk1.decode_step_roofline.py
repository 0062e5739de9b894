"""axk1.decode_step_roofline

The least time a decode step of the A.X-K1 rank could take, the bytes it
must move (builds/axk1.py:bytes_per_decode_step: the resident weights once,
the routed experts TOUCHED in the step, the fed tokens' embedding rows, the
live latent rows at 1,152 B a token a layer) over the HBM bandwidth, as a
share of decode.step_ms.  Memory-bound: at a dozen live rows the operations'
time is a small part of the bytes'.  The experts touched are the program's
own count, generation.window_moe_experts_touched (held experts with at least
one token, summed over the expert layers and the windows' steps): not all
held ones, or a step that skips idle experts would read over 100 %.  None
where the build file counts no such bytes or the program has no such
counter (the parent of PR 47).
"""
from lib import xplane

META = {'name': 'axk1.decode_step_roofline', 'unit': '%',
        'better': 'higher', 'source': 'device_trace',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    found = xplane.module_time(ctx.get('trace'), 'window')
    w = ctx.get('windows')
    count = getattr(ctx.get('build'), 'bytes_per_decode_step', None)
    touched = (ctx.get('counters') or {}).get(
        'generation.window_moe_experts_touched')
    if not found or not w or not ctx.get('peaks') or count is None \
            or touched is None or 'moe' not in (ctx.get('model') or {}):
        return None
    K = ctx['traffic']['decode_window']
    step_s = found[0] / (found[1] * K)
    live = sum(n for n, _ in w) / len(w)
    # cached tokens at a window's start, plus its own growth on average
    kv_tokens = sum(t for _, t in w) / len(w) + live * (K - 1) / 2.0
    least_s = count(ctx['model'], live, kv_tokens, touched / (len(w) * K)) \
        / ctx['peaks']['hbm_bytes_per_s']
    return 100.0 * least_s / step_s
