"""prefill.useful_token_share

Real prompt tokens over the chunk positions computed
(generation.prefill_tokens over prefill_tokens + prefill_pad_tokens): a short
last chunk is padded to the executable's width.
"""
from lib.program import ratio

META = {'name': 'prefill.useful_token_share', 'unit': '%', 'better': 'higher', 'source': 'program_counter',
        'layer': 'prefill (chunked)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    real = c.get('generation.prefill_tokens', 0.0)
    return ratio(100.0 * real,
                 real + c.get('generation.prefill_pad_tokens', 0.0))
