"""train_rate

Target tokens (transformer: non-pad) or images per second per chip: ALL the
window's items over ALL its time.  The window is a whole number of segments
of equal work, each closed by block_until_ready, so a stall inside it
lowers this rate.
"""
from lib import stats

META = {'name': 'train_rate', 'unit': 'items/s/chip', 'better': 'higher', 'source': 'host_clock'}


def read(ctx):
    if 'segments' not in ctx:
        return None
    return stats.segment_rate(ctx['segments'],
                              ctx['items_per_segment'])[0] / ctx['chips']
