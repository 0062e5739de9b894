"""The gated short convolution as the serving path runs it: a mixer
whose whole state is the last rows of its own input.

A ``latent_moe`` model (decode.py) may name ``'conv'`` as a layer's mixer
(``cfg['mixer']``).  For the layer's normalised input ``h`` ``[T, D]``
and ``conv = cfg['conv']`` (``taps`` L, the source's ``conv_L_cache``), no
biases, no activation and no norm inside:

    [B ; C ; x] = h W_in                     W_in [D, 3 D], split in that order
    u_t         = B_t * x_t
    c_t         = sum_j k_j * u_{t - (L - 1) + j}    j = 0 .. L - 1, causal:
                  the last tap multiplies the current row, u is zero
                  before the stream's start
    y_t         = (C_t * c_t) W_out          W_out [D, D]

``k`` is one filter a channel, stored ``[L, D]`` (the source's depthwise
``Conv1d`` weight ``[D, 1, L]`` with the channels along the lanes).

What a stream KEEPS a layer is the convolution's tail, ``u_{t-1} ..
u_{t-L+1}``: ``[L - 1, D]`` float32 in the recurrent array ``conv`` of
the state dict (kv_cache.py), and nothing else: no scan state, no
matrix state (ssm.py's and kda.py's convolutions are parts of another
recurrence; this one is the mixer).  Nothing grows with the context.
This module only maps (input, tail) to (output, tail).

`prefill_mixer` advances one slot over one prefill chunk: ONE shifted
multiply-add over ``[tail ; u]``, no loop over tokens; the chunk starts
from the slot's tail (zeros where the prompt begins) and leaves it as
position ``true_count - 1`` does, not as the padded end would.
`step_mixer` is the single step of a decode window over every slot: a
live slot's tail advances, a dead slot's stays bit for bit what it was.

``u``, ``c`` and the tail are float32; the two products take their
inputs in the weights' dtype and accumulate in float32.
"""
from .latent import dot as _dot
from .mixer import Mixer

__all__ = ['SLOTS', 'weight_shapes', 'state_shapes', 'prefill_mixer',
           'step_mixer']

# the mixer's weights of one layer, after `layer_<i>_`
SLOTS = ('conv_in_w', 'conv_taps', 'conv_out_w')


def weight_shapes(d_model, conv):
    """{slot: shape} of one layer's mixer weights, public layout (a
    projection is ``[in, out]``, the filter ``[taps, channels]``: tap j
    multiplies the input ``taps - 1 - j`` positions back)."""
    return {'conv_in_w': (d_model, 3 * d_model),
            'conv_taps': (int(conv['taps']), d_model),
            'conv_out_w': (d_model, d_model)}


def state_shapes(d_model, conv):
    """(scan state, convolution tail) of ONE slot in ONE layer: no scan
    state at all, and the ``taps - 1`` rows of ``u`` a step reads."""
    return (None, (int(conv['taps']) - 1, d_model))


def _project(w, p, h):
    """h [T, D] normalised -> (u = B * x, the gate C), each [T, D]
    float32."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope('shortconv.project'):
        b, c, x = jnp.split(_dot(h, w[p + 'conv_in_w']), 3, axis=-1)
        return b * x, c


def _out(w, p, gated):
    import jax
    with jax.named_scope('shortconv.out'):
        return _dot(gated, w[p + 'conv_out_w'])


def prefill_mixer(w, p, cfg, h, tail, true_count):
    """One slot, one prefill chunk: h [C, D] normalised, tail [taps - 1,
    D] the slot's of this layer (zeros where the prompt begins).
    Returns (out [C, D] float32, tail), the tail as position
    ``true_count - 1`` leaves it; rows of ``out`` past it are
    padding's."""
    import jax
    import jax.numpy as jnp
    C = h.shape[0]
    u, gate = _project(w, p, h)
    with jax.named_scope('shortconv.taps'):
        taps = w[p + 'conv_taps'].astype(jnp.float32)
        full = jnp.concatenate([tail, u], axis=0)          # [L-1+C, D]
        c = sum(full[j:j + C] * taps[j] for j in range(taps.shape[0]))
        tail = jax.lax.dynamic_slice_in_dim(full, true_count,
                                            taps.shape[0] - 1)
    return _out(w, p, gate * c), tail


def step_mixer(w, p, cfg, h, tail, active):
    """Every slot, one decode step: h [slots, D] normalised, tail
    [slots, taps - 1, D] (this layer's), active [slots] bool.  Returns
    (out [slots, D] float32, tail): a live slot's tail advanced by its
    row, a dead slot's as it was, bit for bit."""
    import jax
    import jax.numpy as jnp
    u, gate = _project(w, p, h)
    with jax.named_scope('shortconv.taps'):
        full = jnp.concatenate([tail, u[:, None]], axis=1)    # [S, L, D]
        c = jnp.sum(full * w[p + 'conv_taps'].astype(jnp.float32), axis=1)
        tail = jnp.where(active[:, None, None], full[:, 1:], tail)
    return _out(w, p, gate * c), tail


def _prefill_layer(w, cfg, cache, kernels, lay, h, st, at):
    """`prefill_mixer` as a layer of a chunk (mixer.py): from the slot's
    tail as the last chunk left it (zeros where the prompt begins)."""
    import jax.numpy as jnp
    j = lay.state
    out, tail = prefill_mixer(
        w, 'layer_%d_' % lay.index, cfg, h,
        jnp.where(at.offset > 0, st['conv'][at.slot, j], 0.0),
        at.true_count)
    return out, dict(st, conv=st['conv'].at[at.slot, j].set(tail))


def _step_layer(w, cfg, cache, kernels, lay, h, st, at):
    """`step_mixer` as a layer of a step (mixer.py)."""
    j = lay.state
    out, tail = step_mixer(w, 'layer_%d_' % lay.index, cfg, h,
                           st['conv'][:, j], at.active)
    return out, dict(st, conv=st['conv'].at[:, j].set(tail))


MIXER = Mixer(
    weight_shapes=lambda cfg: weight_shapes(int(cfg['d_model']),
                                            cfg['conv']),
    recurrent=lambda cfg: state_shapes(int(cfg['d_model']), cfg['conv']),
    wide=(_prefill_layer, _step_layer))
