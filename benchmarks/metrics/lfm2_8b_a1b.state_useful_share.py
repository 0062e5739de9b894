"""lfm2_8b_a1b.state_useful_share

Slot-steps of LIVE streams over the slot-steps whose convolution tails the
decode windows read and wrote (generation.state_live_slot_steps over
generation.state_slot_steps).  The tails have no in-place kernel: a step
reads every slot's two rows a convolution layer and writes them back, a dead
slot's unchanged (shortconv.step_mixer), so the share is the batch's live
share; the tails are 196 kB a stream, 18.9 MB for all 96 slots, 0.2 % of a
step's bytes.  It is here so that a change to how the tails are carried
shows.  None for a model without a convolution mixer (another model's cell)
or a program without the counters (the parent of PR 63).
"""
from lib.program import ratio

META = {'name': 'lfm2_8b_a1b.state_useful_share', 'unit': '%',
        'better': 'higher', 'source': 'program_counter',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    if 'conv' not in (ctx.get('model') or {}):
        return None
    return ratio(100.0 * c.get('generation.state_live_slot_steps', 0.0),
                 c.get('generation.state_slot_steps', 0.0))
