"""tpot_p50_ms

Median over requests of (last token - first token) / (tokens - 1): the pace
of a stream. A failed request counts as the slowest pace of the run.
"""
from lib import stats

META = {'name': 'tpot_p50_ms', 'unit': 'ms', 'better': 'lower', 'source': 'host_clock'}


def read(ctx):
    if not ctx.get('requests'):
        return None
    pace = [None if (not r['ok'] or r['tokens'] < 2)
            else (r['last'] - r['first']) * 1e3 / (r['tokens'] - 1)
            for r in ctx['requests']]
    seen = [x for x in pace if x is not None]
    if not seen:
        return None
    return stats.tail_with_failures(pace, max(seen), 0.50)
