"""scheduler.rounds_to_first_token

Mean scheduler rounds from a request's slot to its first token, the round
of the grant counted (generation.rounds_to_first_token over
generation.first_tokens): its prompt's chunks plus the rounds another
request's chunk took its place.
"""
from lib.program import ratio

META = {'name': 'scheduler.rounds_to_first_token', 'unit': 'count', 'better': 'lower', 'source': 'program_counter',
        'layer': 'scheduler (continuous batching)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    return ratio(c.get('generation.rounds_to_first_token', 0.0),
                 c.get('generation.first_tokens', 0.0))
