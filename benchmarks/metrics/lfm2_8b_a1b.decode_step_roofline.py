"""lfm2_8b_a1b.decode_step_roofline

The least time a decode step of the LFM2 stage could take, the bytes it must
move (builds/lfm2_8b_a1b.py:bytes_per_decode_step: the resident weights once
without the embedding table, the routed experts TOUCHED in the step, the fed
tokens' embedding rows, every LIVE stream's convolution tails in the twelve
convolution layers read once and written once, the live K and V rows of the
four attention layers at the published 64-wide head) over the HBM bandwidth,
as a share of decode.step_ms.  Memory-bound: at forty live rows the
operations' time is a small part of the bytes'.  It is the WHOLE step's
share, so a padded pool or a route that reads every expert lowers it.  The
experts touched are the program's own count,
generation.window_moe_experts_touched (a mean over every window to the
drain's end).  The live streams and their cached tokens are means over the
windows the trace holds, the first of the runner's record (which runs on
through the drain).  None where the build file counts no such bytes, the
model has no convolution mixer or the program no such counter (the parent of
PR 63).
"""
from lib import xplane

META = {'name': 'lfm2_8b_a1b.decode_step_roofline', 'unit': '%',
        'better': 'higher', 'source': 'device_trace',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    found = xplane.module_time(ctx.get('trace'), 'window')
    w = ctx.get('windows')
    count = getattr(ctx.get('build'), 'bytes_per_decode_step', None)
    touched = (ctx.get('counters') or {}).get(
        'generation.window_moe_experts_touched')
    if not found or not w or not ctx.get('peaks') or count is None \
            or touched is None or 'conv' not in (ctx.get('model') or {}):
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
