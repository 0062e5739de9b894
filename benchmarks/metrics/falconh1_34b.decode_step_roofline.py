"""falconh1_34b.decode_step_roofline

The least time a decode step of the Falcon-H1 block could take, the bytes
it must move (builds/falconh1_34b.py:bytes_per_decode_step: six blocks'
matrices and the head, the live K/V rows, and every LIVE stream's
recurrent state read and written) over the HBM bandwidth, as a share of
decode.step_ms.  Memory-bound: at 32 rows the operations' time is a
twentieth of the bytes'.  The program's step moves every slot's state,
live or not, so the share falls as slots stand empty.  None where the
configuration's build file counts no such bytes.
"""
from lib import xplane

META = {'name': 'falconh1_34b.decode_step_roofline', 'unit': '%',
        'better': 'higher', 'source': 'device_trace',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    found = xplane.module_time(ctx.get('trace'), 'window')
    w = ctx.get('windows')
    count = getattr(ctx.get('build'), 'bytes_per_decode_step', None)
    if not found or not w or not ctx.get('peaks') or count is None:
        return None
    K = ctx['traffic']['decode_window']
    step_s = found[0] / (found[1] * K)
    live = sum(n for n, _ in w) / len(w)
    # cached tokens at a window's start, plus its own growth on average
    kv_tokens = sum(t for _, t in w) / len(w) + live * (K - 1) / 2.0
    least_s = count(ctx['model'], live, kv_tokens) \
        / ctx['peaks']['hbm_bytes_per_s']
    return 100.0 * least_s / step_s
