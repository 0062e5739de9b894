"""falconh1_34b.ssm_share

Own device time of the operations of the Mamba-2 recurrence over the
chip's busy time, from EVERY operation of the traced window
(ctx['trace']['ops']).  The mixer is plain jax.numpy, so there is no
kernel name to look for; an operation is counted by the extents its label
(its output's shape) carries:

  * `heads,head_dim,d_state]`, the scan state's: the decode step's fused
    in-place update over `[slots, layers, ...]`, the chunk scan's over one
    slot;
  * `,heads,head_dim]`, the per-head rows the recurrence reads and gives:
    the step's read-out `y = S C` (XLA runs it as a second pass over the
    state, so it is the state's second READ), `dt * x`, the chunk scan's
    outputs.  Attention's rows are `[.., 20, 128]` and `[.., 4, 128]`.

The mixer's projections, convolution and gated norm are matrix and vector
work like the rest of the block and are not counted.  None where the model
has no such state or the trace no such operation.
"""
META = {'name': 'falconh1_34b.ssm_share', 'unit': '%', 'better': 'lower',
        'source': 'device_trace', 'layer': 'decode (runtime and paged cache)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    t = ctx.get('trace')
    ssm = (ctx.get('model') or {}).get('ssm')
    if not t or not ssm:
        return None
    heads, head_dim = ssm['n_heads'], ssm['d_ssm'] // ssm['n_heads']
    marks = ('%d,%d,%d]' % (heads, head_dim, ssm['d_state']),
             ',%d,%d]' % (heads, head_dim))
    hits = [op['seconds'] for label, op in t['ops'].items()
            if any(mark in label for mark in marks)]
    return 100.0 * sum(hits) / t['busy_s'] if hits else None
