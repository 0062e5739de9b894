"""decode_step_roofline

The least time a decode step could take, (weight bytes + live KV bytes) /
HBM bandwidth (lib/flops.decode_step_bytes; memory-bound: the operations'
time is a tenth of it), over decode.step_ms.
"""
from lib import flops, xplane

META = {'name': 'decode_step_roofline', 'unit': '%', 'better': 'higher', 'source': 'device_trace',
        'layer': 'decode (runtime and paged cache)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    found = xplane.module_time(ctx.get('trace'), 'window')
    w = ctx.get('windows')
    if not found or not w or not ctx.get('peaks'):
        return None
    m, K = ctx['model'], ctx['traffic']['decode_window']
    step_s = found[0] / (found[1] * K)
    live = sum(n for n, _ in w) / len(w)
    # cached tokens at a window's start, plus its own growth on average
    kv_tokens = sum(t for _, t in w) / len(w) + live * (K - 1) / 2.0
    args = (m['n_layer'], m['d_model'], m['n_head'], m['n_kv_head'],
            m['d_model'] // m['n_head'], m['d_ffn'], m['vocab'])
    least_bytes = flops.decode_step_bytes(*args, 2, 2, kv_tokens, live) \
        / ctx['peaks']['hbm_bytes_per_s']
    least_flops = flops.decode_step_flops(*args, kv_tokens, live) \
        / ctx['peaks']['bf16_flops']
    return 100.0 * max(least_bytes, least_flops) / step_s
