"""scheduler.boundary_dry_share

Share of the scheduler thread's time in which the chip is dry by the
host's doing: generation.boundary_dry_s over round_s + idle_wait_s (the
counters' own clock, as scheduler.host_gap_share).  Beside
serve.device_idle_share - scheduler.idle_wait_share it says how much of
the chip's idle time with work pending the boundary explains; what is
left is the runtime's (dispatch to start) or lies between two launches.
No reading where no boundary was counted (a program without the counter).
"""
from lib.program import ratio

META = {'name': 'scheduler.boundary_dry_share', 'unit': '%', 'better': 'lower', 'source': 'program_counter',
        'layer': 'scheduler (continuous batching)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    if not c.get('generation.boundaries', 0.0):
        return None
    return ratio(100.0 * c.get('generation.boundary_dry_s', 0.0),
                 c.get('generation.round_s', 0.0)
                 + c.get('generation.idle_wait_s', 0.0))
