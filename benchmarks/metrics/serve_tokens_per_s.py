"""serve_tokens_per_s

Output tokens seen inside the window over the window's length.  Under the
knee an open loop completes what is offered, so this is the offered rate
less what the window's last arrivals leave unfinished: a guard against
collapse, without a bound.  A cell above the knee would judge it.
"""
META = {'name': 'serve_tokens_per_s', 'unit': 'tokens/s', 'better': 'higher', 'source': 'host_clock',
        'layer': 'scheduler (continuous batching)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    if 'tokens_in_window' not in ctx:
        return None
    return ctx['tokens_in_window'] / ctx['window_s']
