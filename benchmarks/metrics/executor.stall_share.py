"""executor.stall_share

The share of the window lost to segments slower than the median one:
1 - (segments x median segment time) / window time.  It is what separates
train_rate from executor.segment_median_rate; periodic stalls show here.
"""
from lib import stats

META = {'name': 'executor.stall_share', 'unit': '%', 'better': 'lower', 'source': 'host_clock',
        'layer': 'entry: executor and parallel executor',
        'moves': 'train_rate'}


def read(ctx):
    if 'segments' not in ctx:
        return None
    return 100.0 * stats.segment_rate(ctx['segments'],
                                      ctx['items_per_segment'])[2]
