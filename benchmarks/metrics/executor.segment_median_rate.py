"""executor.segment_median_rate

Items over the MEDIAN segment time, per chip: the pace between stalls, kept
beside the whole-window train_rate.  The two differ by executor.stall_share.
"""
from lib import stats

META = {'name': 'executor.segment_median_rate', 'unit': 'items/s/chip', 'better': 'higher', 'source': 'host_clock',
        'layer': 'entry: executor and parallel executor',
        'moves': 'train_rate'}


def read(ctx):
    if 'segments' not in ctx:
        return None
    return stats.segment_rate(ctx['segments'],
                              ctx['items_per_segment'])[1] / ctx['chips']
