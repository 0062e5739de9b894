"""decode.host_ms_per_window

Host time of DecodeRuntime.decode_window per window outside the blocking
fetch: the block-table and vector uploads and the dispatch
((generation.window_s - window_fetch_s) over generation.decode_windows).
"""
from lib.program import ratio

META = {'name': 'decode.host_ms_per_window', 'unit': 'ms', 'better': 'lower', 'source': 'program_counter',
        'layer': 'decode (runtime and paged cache)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    busy = c.get('generation.window_s', 0.0)
    if not busy:
        # the scheduler counts the launches; a program without the
        # runtime's own clock has nothing to divide by them
        return None
    return ratio(1e3 * (busy - c.get('generation.window_fetch_s', 0.0)),
                 c.get('generation.decode_windows', 0.0))
