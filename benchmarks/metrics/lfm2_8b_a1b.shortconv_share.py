"""lfm2_8b_a1b.shortconv_share

Own device time of the gated short convolution's operations over the chip's
busy time, from EVERY operation of the traced window (chunks and windows
alike), counted by the extents an operation's label carries (lib/xplane.py
hands out no `jax.named_scope`, so `shortconv.project` cannot be summed by
name):

  * the projection into B, C and x and whatever is fused behind it: the
    last extent `,6144]` = 3 x d_model;
  * the taps and the tail: an extent of `taps` or `taps - 1` rows before the
    channels, `,3,2048]` and `,2,2048]` (the stacked [tail ; u] rows and the
    tail written back; in a chunk `[514,2048]`, tail and chunk together).

Left out, because its label `[rows, d_model]` is any row-wise product's: the
output projection W_out (a third of the projection's bytes).  None where the
model has no such mixer or the trace no such operation.
"""
META = {'name': 'lfm2_8b_a1b.shortconv_share', 'unit': '%',
        'better': 'lower', 'source': 'device_trace',
        'layer': 'decode (runtime and paged cache)', 'moves': 'tpot_p50_ms'}


def read(ctx):
    t = ctx.get('trace')
    model = ctx.get('model') or {}
    conv = model.get('conv')
    if not t or not conv or not t.get('ops'):
        return None
    d, taps = model['d_model'], conv['taps']
    chunk = ctx['traffic']['prefill_chunk']
    marks = (',%d]' % (3 * d), ',%d,%d]' % (taps, d),
             ',%d,%d]' % (taps - 1, d), '[%d,%d]' % (chunk + taps - 1, d))
    hits = [op['seconds'] for label, op in t['ops'].items()
            if any(mark in label for mark in marks)]
    return 100.0 * sum(hits) / t['busy_s'] if hits else None
