"""decode.paged_attention_share

Own device time of the `paged_attention` kernel's custom calls over the
chip's busy time, from EVERY operation of the traced window
(ctx['trace']['ops']: the ten costliest of `device_ops` need not hold
it).  None where the decode step takes the composed path (an int8 pool, a
mesh, the parent of PR 25): the trace then has no such call.
"""
from lib import xplane

META = {'name': 'decode.paged_attention_share', 'unit': '%', 'better': 'lower', 'source': 'device_trace',
        'layer': 'decode (runtime and paged cache)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    t = ctx.get('trace')
    seconds = xplane.op_seconds(t, 'custom-call paged_attention')
    if 'windows' not in ctx or seconds is None:
        return None
    return 100.0 * seconds / t['busy_s']
