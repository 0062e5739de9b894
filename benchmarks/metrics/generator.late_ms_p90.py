"""generator.late_ms_p90

90th percentile of (time sent - time due): how late the benchmark's one
generator thread ran.
"""
from lib import stats

META = {'name': 'generator.late_ms_p90', 'unit': 'ms', 'better': 'lower', 'source': 'host_clock',
        'layer': "generator (the benchmark's own)",
        'moves': 'tpot_p50_ms'}


def read(ctx):
    if not ctx.get('requests'):
        return None
    return stats.quantile([(r['sent'] - r['due']) * 1e3
                           for r in ctx['requests']], 0.90)
