"""kimi_linear.state_useful_share

Slot-steps of LIVE streams over the slot-steps whose matrix state the decode
windows read and wrote (generation.state_live_slot_steps over
generation.state_slot_steps).  On one chip the step is the kernel `kda_step`,
which touches the live slots' state alone, so the share reads 100 (as
falconh1_34b.state_useful_share does over `ssm_step`); under a mesh the
composed step advances every slot's state and keeps the dead ones', and the
share is the batch's live share.  It is here so that a step that falls back to
the composed route shows.  None for a program without the counters (no
recurrent state, or the parent of PR 61).
"""
from lib.program import ratio

META = {'name': 'kimi_linear.state_useful_share', 'unit': '%',
        'better': 'higher', 'source': 'program_counter',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    return ratio(100.0 * c.get('generation.state_live_slot_steps', 0.0),
                 c.get('generation.state_slot_steps', 0.0))
