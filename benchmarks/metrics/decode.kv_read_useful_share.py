"""decode.kv_read_useful_share

KV positions the live streams attended over the rows of K (or V) per layer
the window executables gathered (generation.kv_tokens_live over
generation.kv_rows_read).  The denominator is carried on the compiled
entry (DecodeRuntime._window_exec): batch x positions of what the step's
gather (_logical_rows) returns for the structs that executable was built
over, x steps; today that is every slot's max_len rows a step, and a
gather of live pages only changes it with the executable.
"""
from lib.program import ratio

META = {'name': 'decode.kv_read_useful_share', 'unit': '%', 'better': 'higher', 'source': 'program_counter',
        'layer': 'decode (runtime and paged cache)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    return ratio(100.0 * c.get('generation.kv_tokens_live', 0.0),
                 c.get('generation.kv_rows_read', 0.0))
