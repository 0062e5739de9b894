"""falconh1_34b.state_useful_share

Slot-steps of LIVE streams over the slot-steps whose recurrent state the
decode windows read and wrote (generation.state_live_slot_steps over
generation.state_slot_steps).  The plain step advances every slot's scan
state and masks the dead ones' result, so the share is the batch's live
share; a step that skipped dead slots would read 100.  None for a program
without the counters (no recurrent state, or the parent of PR 32).
"""
from lib.program import ratio

META = {'name': 'falconh1_34b.state_useful_share', 'unit': '%',
        'better': 'higher', 'source': 'program_counter',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    return ratio(100.0 * c.get('generation.state_live_slot_steps', 0.0),
                 c.get('generation.state_slot_steps', 0.0))
