"""scheduler.live_slot_share

Live slot-steps over slot-steps run by the decode windows
(generation.decode_live_slot_steps over generation.decode_slot_steps, counted
in DecodeRuntime where the window is launched): what
scheduler.batch_occupancy takes from the benchmark's wrapper.
"""
from lib.program import ratio

META = {'name': 'scheduler.live_slot_share', 'unit': '%', 'better': 'higher', 'source': 'program_counter',
        'layer': 'scheduler (continuous batching)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    return ratio(100.0 * c.get('generation.decode_live_slot_steps', 0.0),
                 c.get('generation.decode_slot_steps', 0.0))
