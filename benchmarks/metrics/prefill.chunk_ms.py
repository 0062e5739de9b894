"""prefill.chunk_ms

Device time of the prefill executable per chunk, from the trace's module
line.
"""
from lib import xplane

META = {'name': 'prefill.chunk_ms', 'unit': 'ms', 'better': 'lower', 'source': 'device_trace',
        'layer': 'prefill (chunked)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    found = xplane.module_time(ctx.get('trace'), 'prefill')
    if 'windows' not in ctx or not found:
        return None
    seconds, launches = found
    return 1e3 * seconds / launches
