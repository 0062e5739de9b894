"""lfm2_8b_a1b.attention_share

Own device time of the `paged_attention` kernel's custom calls (the decode
step's attention over the head-64 pool in place, one call an attention layer
a step: four of the sixteen layers) over the chip's busy time, from EVERY
operation of the traced window.  The gathered attention of a prefill chunk is
not in it.  None where the model has no convolution mixer (another model's
cell) or the decode step takes the composed route (a mesh, the parent of
PR 63): the trace then has no such call.
"""
from lib import xplane

META = {'name': 'lfm2_8b_a1b.attention_share', 'unit': '%',
        'better': 'lower', 'source': 'device_trace',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    t = ctx.get('trace')
    seconds = xplane.op_seconds(t, 'custom-call paged_attention')
    if seconds is None or 'conv' not in (ctx.get('model') or {}):
        return None
    return 100.0 * seconds / t['busy_s']
