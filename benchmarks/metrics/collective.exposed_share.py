"""collective.exposed_share

Time the TensorCore spent IN all-reduce, all-gather and the like (operations
on its own line of the trace, so nothing else computes meanwhile) over the
traced window.
"""
META = {'name': 'collective.exposed_share', 'unit': '%', 'better': 'lower', 'source': 'device_trace',
        'layer': 'mesh: shard pass and interconnect',
        'moves': 'train_rate'}


def read(ctx):
    t = ctx.get('trace')
    if not t or 'segments' not in ctx:
        return None
    return 100.0 * t['collective_s'] / t['window_s']
