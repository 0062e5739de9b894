"""scheduler.queue_wait_ms

Mean wait of an admitted request for its KV slot: generate() to the slot
granted in the scheduler's round (generation.queue_wait_s over
generation.admitted, both counted where the slot is granted).
"""
from lib.program import ratio

META = {'name': 'scheduler.queue_wait_ms', 'unit': 'ms', 'better': 'lower', 'source': 'program_counter',
        'layer': 'scheduler (continuous batching)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    return ratio(1e3 * c.get('generation.queue_wait_s', 0.0),
                 c.get('generation.admitted', 0.0))
