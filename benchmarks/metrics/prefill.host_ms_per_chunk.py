"""prefill.host_ms_per_chunk

Host time of DecodeRuntime.prefill per chunk outside the blocking fetch:
padding, the ten argument uploads, the dispatch ((generation.prefill_s -
prefill_fetch_s) over generation.prefill_chunks).
"""
from lib.program import ratio

META = {'name': 'prefill.host_ms_per_chunk', 'unit': 'ms', 'better': 'lower', 'source': 'program_counter',
        'layer': 'prefill (chunked)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    busy = c.get('generation.prefill_s', 0.0)
    if not busy:
        # the scheduler counts the launches; a program without the
        # runtime's own clock has nothing to divide by them
        return None
    return ratio(1e3 * (busy - c.get('generation.prefill_fetch_s', 0.0)),
                 c.get('generation.prefill_chunks', 0.0))
