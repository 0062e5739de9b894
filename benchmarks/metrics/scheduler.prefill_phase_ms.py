"""scheduler.prefill_phase_ms

Mean time from a request's slot to its first token: its own chunks and the
rounds it waited for its turn at the one chunk a round
(generation.prefill_phase_s over generation.first_tokens, both counted where
the first token is emitted).
"""
from lib.program import ratio

META = {'name': 'scheduler.prefill_phase_ms', 'unit': 'ms', 'better': 'lower', 'source': 'program_counter',
        'layer': 'scheduler (continuous batching)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    return ratio(1e3 * c.get('generation.prefill_phase_s', 0.0),
                 c.get('generation.first_tokens', 0.0))
