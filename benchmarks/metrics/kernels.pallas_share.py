"""kernels.pallas_share

Device time of custom calls (Mosaic kernels) over the chip's busy time, from
the trace.
"""
META = {'name': 'kernels.pallas_share', 'unit': '%', 'better': 'higher', 'source': 'device_trace',
        'layer': 'kernel tier (Pallas)',
        'moves': 'train_rate'}


def read(ctx):
    t = ctx.get('trace')
    if not t or 'segments' not in ctx:
        return None
    return 100.0 * t['custom_call_s'] / t['busy_s']
