"""Which operations of a traced window are the delta rule's: what the
readers metrics/kimi_linear.kda_share.py and kimi_linear.kda_step_roofline.py
share.

The decode step (`kda.step`) is ONE kernel, the custom call `kda_step`
(kda.py: the matrix state of the live slots updated in place), and is found
by that name and by nothing else; under a mesh the step is composed of plain
jax.numpy operations with no name, and both step readings are silent.

The chunk scan (`kda.scan`) is plain jax.numpy, and lib/xplane.py hands out
no `jax.named_scope`, so its operations are counted by the extents their
label (the output's shape) carries, float32 with H heads of d (a latent
layer's bfloat16 `[H, nope, v]` has the same extents and is not the scan's):

  * `H,d,d]` behind anything else: one slot's state carried over the
    sub-chunks;
  * `H,sub,sub]`, `H,sub,d]` with sub = 64 (kda.py:_SUB): the pair-decay
    matrices, (I + A)^-1, W, U and the scan's outputs;
  * `slots,layers,H,d,d]`: the chunk's end state written into the state
    array in place (an operation of the prefill executable; the kernel's own
    label carries the same extents and is left out by its name).

The projections, the convolutions and the gates are matrix and vector work
like the rest of the block and are not counted.  Both return None where the
model has no such layer or the trace no such operation.
"""
import re

from lib import xplane

SUB = 64                  # kda.py:_SUB, the chunk form's sub-chunk
STEP = 'custom-call kda_step'


def step_seconds(ctx):
    if not (ctx.get('model') or {}).get('kda'):
        return None
    return xplane.op_seconds(ctx.get('trace'), STEP)


def scan_seconds(ctx):
    kda = (ctx.get('model') or {}).get('kda')
    t = ctx.get('trace')
    if not t or not kda or not t.get('ops'):
        return None
    H, d = kda['n_heads'], kda['head_dim']
    marks = re.compile(r'f32\[(?:\d+,)*%d,(?:%d,%d|%d,%d|%d,%d)\]'
                       % (H, d, d, SUB, SUB, SUB, d))
    hits = [op['seconds'] for label, op in t['ops'].items()
            if marks.search(label) and not label.startswith(STEP)]
    return sum(hits) if hits else None
