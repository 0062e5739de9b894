"""kimi_linear.latent_attention_share

Own device time of the `latent_attention` kernel's custom calls (the decode
step's attention over the latent pool in place, one call a latent layer a
step: two of the eight layers) over the chip's busy time, from EVERY
operation of the traced window.  The expanded attention of a prefill chunk is
not in it.  None where the decode step takes the composed route (a mesh, the
parent of PR 61): the trace then has no such call.
"""
from lib import xplane

META = {'name': 'kimi_linear.latent_attention_share', 'unit': '%',
        'better': 'lower', 'source': 'device_trace',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    t = ctx.get('trace')
    seconds = xplane.op_seconds(t, 'custom-call latent_attention')
    if seconds is None:
        return None
    return 100.0 * seconds / t['busy_s']
