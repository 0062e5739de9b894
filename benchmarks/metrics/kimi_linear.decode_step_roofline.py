"""kimi_linear.decode_step_roofline

The least time a decode step of the Kimi-Linear rank could take, the bytes it
must move (builds/kimi_linear.py:bytes_per_decode_step: the resident weights
once, the routed experts TOUCHED in the step, the fed tokens' embedding rows,
every LIVE stream's matrix state and convolution tails in the six KDA layers
read once and written once, the live latent rows of the two latent layers)
over the HBM bandwidth, as a share of decode.step_ms.  Memory-bound: at a
hundred live rows the operations' time is a small part of the bytes'.  The
experts touched are the program's own count,
generation.window_moe_experts_touched (a mean over every window to the
drain's end; at this cell's 128 slots the expert layer takes its batched
route and READS all 64 held experts a step, so the share says what a step
that read the touched ones alone would gain).  The live streams and their
cached tokens are means over the windows the trace holds, the first of the
runner's record (which runs on through the drain).  None where the build file
counts no such bytes or the program has no such counter (the parent of
PR 61).
"""
from lib import xplane

META = {'name': 'kimi_linear.decode_step_roofline', 'unit': '%',
        'better': 'higher', 'source': 'device_trace',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    found = xplane.module_time(ctx.get('trace'), 'window')
    w = ctx.get('windows')
    count = getattr(ctx.get('build'), 'bytes_per_decode_step', None)
    touched = (ctx.get('counters') or {}).get(
        'generation.window_moe_experts_touched')
    if not found or not w or not ctx.get('peaks') or count is None \
            or touched is None or 'kda' not in (ctx.get('model') or {}):
        return None
    K = ctx['traffic']['decode_window']
    step_s = found[0] / (found[1] * K)
    traced = w[:int(found[1])]
    live = sum(n for n, _ in traced) / len(traced)
    # cached tokens at a window's start, plus its own growth on average
    kv_tokens = sum(t for _, t in traced) / len(traced) \
        + live * (K - 1) / 2.0
    least_s = count(ctx['model'], live, kv_tokens, touched / (len(w) * K)) \
        / ctx['peaks']['hbm_bytes_per_s']
    return 100.0 * least_s / step_s
