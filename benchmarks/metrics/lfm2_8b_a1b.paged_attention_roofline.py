"""lfm2_8b_a1b.paged_attention_roofline

The least time the decode steps' attention over the head-64 pool could take
over the time the `paged_attention` kernel's own calls took (one call an
attention layer a step: four of the sixteen layers).  Least: every live row's
key and value, 2 x 8 heads x 64 bfloat16 values a token an attention layer
(builds/lfm2_8b_a1b.py:attention_bytes), over the HBM bandwidth; one query a
stream, so the bytes bound it.  Took: xplane.op_seconds of `custom-call
paged_attention`.  Both over the SAME windows: the live cached tokens are
summed over as many of the runner's recorded windows as the trace holds
launches of `jit_window`, the first ones, each step of a window attending its
start's tokens plus the window's own growth.  The kernel fetches whole pages
(the tail page's padding) and two kv heads a 128-lane row, each read once.
None where the step takes the composed path (a mesh, the parent of PR 63: no
such call) or the build file counts no such bytes.
"""
from lib import xplane

META = {'name': 'lfm2_8b_a1b.paged_attention_roofline', 'unit': '%',
        'better': 'higher', 'source': 'device_trace',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    t = ctx.get('trace')
    seconds = xplane.op_seconds(t, 'custom-call paged_attention')
    count = getattr(ctx.get('build'), 'attention_bytes', None)
    found = xplane.module_time(t, 'window')
    w = ctx.get('windows')
    if not seconds or count is None or not found or not w \
            or not ctx.get('peaks'):
        return None
    K = ctx['traffic']['decode_window']
    traced = w[:int(found[1])]
    # step j of a window attends its start's tokens and j + 1 more a stream
    kv_token_steps = sum(K * tokens + live * K * (K + 1) / 2.0
                         for live, tokens in traced)
    return 100.0 * count(ctx['model'], kv_token_steps) \
        / ctx['peaks']['hbm_bytes_per_s'] / seconds
