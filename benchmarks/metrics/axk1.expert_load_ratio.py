"""axk1.expert_load_ratio

The busiest held expert's tokens over the mean over the held experts, summed
over every expert layer of every launch of the window: the program's
generation.moe_busiest_expert_tokens over generation.moe_assignments /
(experts held a layer).  1 is an even load; with about half an assignment a
stream a layer over 12 experts, a decode step's busiest expert has one token
where the mean is a fraction of one, so the ratio reads well above 1 there
and falls toward 1 in a chunk of 512 tokens.  None for a program without the
counters (no experts, or the parent of PR 47).
"""
from lib.program import ratio

META = {'name': 'axk1.expert_load_ratio', 'unit': 'ratio', 'better': 'lower',
        'source': 'program_counter',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    moe = (ctx.get('model') or {}).get('moe')
    if not moe:
        return None
    held = moe['n_routed'] // moe['ranks']
    return ratio(held * c.get('generation.moe_busiest_expert_tokens', 0.0),
                 c.get('generation.moe_assignments', 0.0))
