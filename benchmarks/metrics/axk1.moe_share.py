"""axk1.moe_share

Own device time of the expert layers' operations over the chip's busy
time, from EVERY operation of the traced window (chunks and windows
alike).  The expert layer is plain jax.numpy around `jax.lax.ragged_dot`,
so an operation is counted by what its label carries:

  * the grouped products themselves, custom calls whose label holds
    `ragged-dot` (the chip's traces: `custom-call ragged-dot-none
    f32[64,2048]` into the expert width, `... f32[64,7168]` back out of
    it), `ragged_dot` or `gmm`;
  * the expert width as the last extent, `,2048]`: the products into it
    of the shared expert and of a chunk's batched groups, and the gate's
    elementwise pass (no other array of the model ends in 2048);
  * the router's width as the last extent, `,192]`: scores, top-8.

The gather that orders the tokens by expert and the scatter that adds the
rows back carry the model width like the rest of the block and are not
counted.  None where the model has no experts or the trace no such
operation.
"""
META = {'name': 'axk1.moe_share', 'unit': '%', 'better': 'lower',
        'source': 'device_trace', 'layer': 'decode (runtime and paged cache)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    t = ctx.get('trace')
    moe = (ctx.get('model') or {}).get('moe')
    if not t or not moe:
        return None
    marks = (',%d]' % moe['d_expert'], ',%d]' % moe['n_routed'])
    hits = [op['seconds'] for label, op in t['ops'].items()
            if 'ragged-dot' in label or 'ragged_dot' in label
            or 'gmm' in label
            or any(mark in label for mark in marks)]
    return 100.0 * sum(hits) / t['busy_s'] if hits else None
