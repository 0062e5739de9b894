"""scheduler.boundary_late_share

Share of the boundaries at which window N's tokens had ALREADY landed when
the host came to read them (generation.boundary_late over
generation.boundaries): the host's work under the window outlasted the
window, the chip went dry before the boundary began, and boundary_ms is a
lower bound there.
"""
from lib.program import ratio

META = {'name': 'scheduler.boundary_late_share', 'unit': '%', 'better': 'lower', 'source': 'program_counter',
        'layer': 'scheduler (continuous batching)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    return ratio(100.0 * c.get('generation.boundary_late', 0.0),
                 c.get('generation.boundaries', 0.0))
