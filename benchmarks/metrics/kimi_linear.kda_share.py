"""kimi_linear.kda_share

Own device time of the delta rule's operations, the decode step's and the
chunk scan's, over the chip's busy time, from EVERY operation of the traced
window (lib/kda_ops.py: counted by the extents in their labels).  The mixer's
projections, convolutions and gates are not in it.  None where the model has
no such layer or the trace no such operation.
"""
from lib import kda_ops

META = {'name': 'kimi_linear.kda_share', 'unit': '%', 'better': 'lower',
        'source': 'device_trace', 'layer': 'decode (runtime and paged cache)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    parts = [kda_ops.step_seconds(ctx), kda_ops.scan_seconds(ctx)]
    if all(p is None for p in parts):
        return None
    return 100.0 * sum(p or 0.0 for p in parts) / ctx['trace']['busy_s']
