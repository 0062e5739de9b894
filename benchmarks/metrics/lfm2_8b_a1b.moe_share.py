"""lfm2_8b_a1b.moe_share

Own device time of the expert layers' operations over the chip's busy time,
from EVERY operation of the traced window (chunks and windows alike): the
arithmetic of metrics/kimi_linear.moe_share.py, which takes its marks from
the model dict, so here

  * the grouped products themselves, custom calls whose label holds
    `ragged-dot`, `ragged_dot` or `gmm`;
  * the batched route's products (experts.py:routed, one batch entry an
    expert, each group padded to 64 rows): the extents `[32, 64, d_model]`;
  * the expert width as the last extent, `,1792]`: the gate's elementwise
    pass;
  * the router's width as the last extent, `,32]`: scores, top-4.

Left out, because its label `[slots, d_model]` is any row-wise operation's:
the scatter of the experts' rows back to their tokens.  None where the model
has no experts or no convolution mixer (another model's cell) or the trace no
such operation.
"""
import run

META = {'name': 'lfm2_8b_a1b.moe_share', 'unit': '%', 'better': 'lower',
        'source': 'device_trace', 'layer': 'decode (runtime and paged cache)',
        'moves': 'tpot_p50_ms'}

_read = run.load_module('metrics', 'kimi_linear.moe_share').read


def read(ctx):
    return _read(ctx) if 'conv' in (ctx.get('model') or {}) else None
