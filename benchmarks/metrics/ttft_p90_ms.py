"""ttft_p90_ms

90th percentile over ALL the window's requests of first token minus the time
the request was DUE; a failed or refused request counts as the longest wait
of the run.  A per-layer metric without a bound: over seeds, which permute
the arrivals, it spreads by tens of percent at any rate (PERF.md, Findings
of PR 23), so it can judge nothing at the 10 % a bound may have.
"""
from lib import stats

META = {'name': 'ttft_p90_ms', 'unit': 'ms', 'better': 'lower', 'source': 'host_clock',
        'layer': 'scheduler (continuous batching)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    if not ctx.get('requests'):
        return None
    lat, worst = stats.ttft_ms(ctx['requests'], ctx['drained_at_s'])
    return stats.tail_with_failures(lat, worst, 0.90)
