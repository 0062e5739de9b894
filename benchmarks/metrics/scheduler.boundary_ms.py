"""scheduler.boundary_ms

Milliseconds the chip has nothing at a boundary of the serving round, by
the host's clock: from the return of the read of window N's tokens to the
end of the dispatch of the round's first launch behind it (the chunk's
when there is one, else window N+1's): generation.boundary_dry_s over
generation.boundaries.  The scheduler's check, then the launch call's
look-up of the staged arguments, whatever upload differs, and the
dispatch.  A lower bound where scheduler.boundary_late_share is not 0.
"""
from lib.program import ratio

META = {'name': 'scheduler.boundary_ms', 'unit': 'ms', 'better': 'lower', 'source': 'program_counter',
        'layer': 'scheduler (continuous batching)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    return ratio(1e3 * c.get('generation.boundary_dry_s', 0.0),
                 c.get('generation.boundaries', 0.0))
