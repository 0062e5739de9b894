"""kimi_linear.moe_share

Own device time of the expert layers' operations over the chip's busy time,
from EVERY operation of the traced window (chunks and windows alike), counted
by what an operation's label carries (lib/xplane.py hands out no
`jax.named_scope`, so `moe.experts` cannot be summed by name):

  * the grouped products themselves, custom calls whose label holds
    `ragged-dot`, `ragged_dot` or `gmm`;
  * the batched route's products (experts.py:routed, one batch entry an
    expert held, each group padded to `GROUP_ROWS` rows): the extents
    `[held, GROUP_ROWS, d_model]` of the product back into the stream and
    `[held, GROUP_ROWS, d_expert]` of the two into the expert's width; at
    this cell's 128 slots a decode step takes this route too, and these two
    are most of the layer's time;
  * the expert width as the last extent, `,1024]`: the shared expert's
    products and the gate's elementwise pass;
  * the router's width as the last extent, `,256]`: scores, top-8.

Left out, because its label `[slots, d_model]` is any row-wise operation's:
the scatter of the experts' rows back to their tokens.  None where the model
has no experts or the trace no such operation.
"""
GROUP_ROWS = 64             # experts.py:_GROUP_ROWS

META = {'name': 'kimi_linear.moe_share', 'unit': '%', 'better': 'lower',
        'source': 'device_trace', 'layer': 'decode (runtime and paged cache)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    t = ctx.get('trace')
    model = ctx.get('model') or {}
    moe = model.get('moe')
    if not t or not moe or not t.get('ops'):
        return None
    held = moe['n_routed'] // moe['ranks']
    marks = (',%d]' % moe['d_expert'], ',%d]' % moe['n_routed'],
             '[%d,%d,%d]' % (held, GROUP_ROWS, model['d_model']))
    hits = [op['seconds'] for label, op in t['ops'].items()
            if 'ragged-dot' in label or 'ragged_dot' in label
            or 'gmm' in label
            or any(mark in label for mark in marks)]
    return 100.0 * sum(hits) / t['busy_s'] if hits else None
