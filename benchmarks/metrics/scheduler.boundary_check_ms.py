"""scheduler.boundary_check_ms

Of scheduler.boundary_ms, the milliseconds before the first launch call:
the end check of the landed window (EOS rows, cancels, deadlines), the
plan's repair, at a boundary that keeps the serial order the emit, admit
and re-plan, a late arrival's chunk (generation.boundary_check_s over
generation.boundaries).  The rest of boundary_ms is the launch call's.
"""
from lib.program import ratio

META = {'name': 'scheduler.boundary_check_ms', 'unit': 'ms', 'better': 'lower', 'source': 'program_counter',
        'layer': 'scheduler (continuous batching)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    return ratio(1e3 * c.get('generation.boundary_check_s', 0.0),
                 c.get('generation.boundaries', 0.0))
