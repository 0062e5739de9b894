"""kimi_linear.expert_touched_share

Held experts with at least one token in a decode step over the experts held,
summed over the windows' steps and the seven expert layers: the program's
generation.window_moe_experts_touched over (steps x expert layers x 64).  It
is what the cell's steadiness rests on: near 100 a step reads nearly every
held expert whoever is live, so its bytes do not follow the number of live
streams; at a handful of streams (a quarter, axk1's cell) they do.  None for
a program without the counter (no experts, or the parent of PR 61).
"""
from lib.program import ratio

META = {'name': 'kimi_linear.expert_touched_share', 'unit': '%',
        'better': 'higher', 'source': 'program_counter',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    model = ctx.get('model') or {}
    moe, w = model.get('moe'), ctx.get('windows')
    touched = c.get('generation.window_moe_experts_touched')
    if not moe or not w or touched is None:
        return None
    steps = len(w) * int(ctx['traffic']['decode_window'])
    held = moe['n_routed'] // moe['ranks']
    return ratio(100.0 * touched,
                 steps * model['ffn'].count('experts') * held)
