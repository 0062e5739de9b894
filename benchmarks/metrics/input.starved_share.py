"""input.starved_share

Share of the window the training loop waited on an EMPTY prefetch queue,
counted where the wait happens ((prefetch.starvation_s +
prefetch.upload_wait_s) over the window): reader too slow, or an upload in
flight.  0 where the cell feeds without the prefetcher.
"""
from lib.program import ratio

META = {'name': 'input.starved_share', 'unit': '%', 'better': 'lower', 'source': 'program_counter',
        'layer': 'input: reader and feed prefetch',
        'moves': 'train_rate'}


def read(ctx):
    c = ctx['counters']
    return ratio(100.0 * (c.get('prefetch.starvation_s', 0.0)
                          + c.get('prefetch.upload_wait_s', 0.0)),
                 ctx.get('window_s', 0.0))
