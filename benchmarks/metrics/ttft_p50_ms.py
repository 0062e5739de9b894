"""ttft_p50_ms

Median over the window's requests of first token minus due time; failures
count as the worst.
"""
from lib import stats

META = {'name': 'ttft_p50_ms', 'unit': 'ms', 'better': 'lower', 'source': 'host_clock',
        'layer': 'scheduler (continuous batching)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    if not ctx.get('requests'):
        return None
    lat, worst = stats.ttft_ms(ctx['requests'], ctx['drained_at_s'])
    return stats.tail_with_failures(lat, worst, 0.50)
