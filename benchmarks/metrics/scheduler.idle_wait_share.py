"""scheduler.idle_wait_share

Share of the scheduler thread's time spent waiting with an empty queue and
no live stream (generation.idle_wait_s over round_s + idle_wait_s): the chip
idle because nobody asked.  It holds the wait before the first request too
(the profiler starting, in a traced run).
"""
from lib.program import ratio

META = {'name': 'scheduler.idle_wait_share', 'unit': '%', 'better': 'lower', 'source': 'program_counter',
        'layer': 'scheduler (continuous batching)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    idle = c.get('generation.idle_wait_s', 0.0)
    return ratio(100.0 * idle, c.get('generation.round_s', 0.0) + idle)
