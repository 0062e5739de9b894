"""kimi_linear.kda_step_roofline

The least time the delta rule's decode steps of the traced window could take
over the time their operations took.  Least: every LIVE stream's matrix
state, [32, 128, 128] float32 in each of the six KDA layers, read once and
written once a step (builds/kimi_linear.py:kda_step_bytes) over the HBM
bandwidth; the step is elementwise over the state, so the bytes bound it.
Took: own device time of the operations of `kda.step`
(lib/kda_ops.py:step_seconds, by the extents in their labels).  Both over the
SAME windows: the trace ends with the measured window while the runner's
record of launches (and the program's counters) runs on through the drain, so
the live slot-steps are summed over as many of the recorded windows as the
trace holds launches of `jit_window`, the first ones.  Whatever implements the
step, this is what it is held to: the composed step reads every slot's state
twice and writes it once, live or not.  None where the trace has no such
operation (the parent of PR 61) or the build file counts no such bytes.
"""
from lib import xplane
from lib import kda_ops

META = {'name': 'kimi_linear.kda_step_roofline', 'unit': '%',
        'better': 'higher', 'source': 'device_trace',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    seconds = kda_ops.step_seconds(ctx)
    count = getattr(ctx.get('build'), 'kda_step_bytes', None)
    found = xplane.module_time(ctx.get('trace'), 'window')
    w = ctx.get('windows')
    if not seconds or count is None or not found or not w \
            or not ctx.get('peaks'):
        return None
    traced = w[:int(found[1])]
    live_steps = sum(n for n, _ in traced) * ctx['traffic']['decode_window']
    return 100.0 * count(ctx['model'], live_steps) \
        / ctx['peaks']['hbm_bytes_per_s'] / seconds
