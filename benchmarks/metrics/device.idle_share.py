"""device.idle_share

1 - union of the chip's operation intervals over the traced window, averaged
over chips.
"""
META = {'name': 'device.idle_share', 'unit': '%', 'better': 'lower', 'source': 'device_trace',
        'layer': 'device',
        'moves': 'train_rate'}


def read(ctx):
    t = ctx.get('trace')
    if not t or 'segments' not in ctx:
        return None
    return 100.0 * (1.0 - t['busy_s'] / t['window_s'])
