"""decode.kv_read_useful_share

KV positions the live streams attended over the rows of K (or V) per layer
that the window executables READ (generation.kv_tokens_live over
generation.kv_rows_read).  The program counts the rows by the path the
runtime takes (DecodeRuntime._window_rows_read).  Paged path (a floating
pool on one device, since PR 25): what the kernel fetches, which for step
j of an active slot of length n is the whole pages that its n + j + 1
positions cover, and nothing for an inactive slot
(ops.attention.paged_attention_rows); the share is then 99 %, the tail
page's padding.  Composed path (an int8 pool, or a mesh): every slot's
max_len rows a step, read off the shapes the step's gather
(_logical_rows) returns for the executable's structs; 3 % in
mistral7b.chat_steady before PR 25.
"""
from lib.program import ratio

META = {'name': 'decode.kv_read_useful_share', 'unit': '%', 'better': 'higher', 'source': 'program_counter',
        'layer': 'decode (runtime and paged cache)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    return ratio(100.0 * c.get('generation.kv_tokens_live', 0.0),
                 c.get('generation.kv_rows_read', 0.0))
