"""decode.step_ms

Device time of the decode-window executable per token step, from the trace's
module line.
"""
from lib import xplane

META = {'name': 'decode.step_ms', 'unit': 'ms', 'better': 'lower', 'source': 'device_trace',
        'layer': 'decode (runtime and paged cache)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    found = xplane.module_time(ctx.get('trace'), 'window')
    if 'windows' not in ctx or not found:
        return None
    seconds, launches = found
    return 1e3 * seconds / (launches * ctx['traffic']['decode_window'])
