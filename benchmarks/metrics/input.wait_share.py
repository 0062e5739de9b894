"""input.wait_share

Share of the window the training loop spent waiting for the next superbatch
from data_feeder.FeedPrefetcher.
"""
META = {'name': 'input.wait_share', 'unit': '%', 'better': 'lower', 'source': 'host_clock',
        'layer': 'input: reader and feed prefetch',
        'moves': 'train_rate'}


def read(ctx):
    if 'segments' not in ctx:
        return None
    return 100.0 * ctx['spans'].get('feed', 0.0) / ctx['window_s']
