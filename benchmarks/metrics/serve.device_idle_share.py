"""serve.device_idle_share

1 - union of the chip's operation intervals over the traced serving window.
"""
META = {'name': 'serve.device_idle_share', 'unit': '%', 'better': 'lower', 'source': 'device_trace',
        'layer': 'device',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    t = ctx.get('trace')
    if not t or 'windows' not in ctx:
        return None
    return 100.0 * (1.0 - t['busy_s'] / t['window_s'])
