"""lfm2_8b_a1b.expert_touched_share

Experts with at least one token in a decode step over the experts a layer
has, summed over the windows' steps and the fourteen expert layers: the
program's generation.window_moe_experts_touched over (steps x expert layers x
32); the arithmetic of metrics/kimi_linear.expert_touched_share.py.  It is
what the cell's steadiness rests on: with 32 experts top-4 a step of B live
streams touches 1 - (7/8)^B of them, 98 % at 30, so a step's bytes do not
follow the number of live streams.  None for a model without a convolution
mixer (another model's cell) or a program without the counter (the parent of
PR 63).
"""
import run

META = {'name': 'lfm2_8b_a1b.expert_touched_share', 'unit': '%',
        'better': 'higher', 'source': 'program_counter',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}

_read = run.load_module('metrics', 'kimi_linear.expert_touched_share').read


def read(ctx):
    return _read(ctx) if 'conv' in (ctx.get('model') or {}) else None
