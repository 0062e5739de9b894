"""axk1.latent_attention_roofline

The least time the `latent_attention` kernel's calls of the traced window
could take over the time they took (own device time of its custom calls,
from EVERY operation of the window).  The rows are the program's count of
what the kernel fetched (generation.window_latent_rows_read: per step and
layer the whole pages a live slot's positions cover); per row the kernel
needs 2 x 64 heads x (2 x 512 + 64) = 139 kFLOP and the row's 1,152 B
(builds/axk1.py:latent_attention_cost), 121 FLOP a byte where the chip's
ridge is 240: it sits between the two roofs, and the least time is the
LARGER of operations over the bf16 peak and bytes over the HBM bandwidth
(the bytes').  None where the trace has no such call or the program no such
counter (the composed route, the parent of PR 47).
"""
from lib import xplane

META = {'name': 'axk1.latent_attention_roofline', 'unit': '%',
        'better': 'higher', 'source': 'device_trace',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    t = ctx.get('trace')
    seconds = xplane.op_seconds(t, 'custom-call latent_attention')
    cost = getattr(ctx.get('build'), 'latent_attention_cost', None)
    rows = (ctx.get('counters') or {}).get(
        'generation.window_latent_rows_read')
    if not seconds or cost is None or not rows or not ctx.get('peaks'):
        return None
    ops, nbytes = cost(ctx['model'], rows)
    least_s = max(ops / ctx['peaks']['bf16_flops'],
                  nbytes / ctx['peaks']['hbm_bytes_per_s'])
    return 100.0 * least_s / seconds
