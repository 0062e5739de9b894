"""The Mamba-2 mixer of a Falcon-H1 block, as the serving path runs it.

A block of kind ``falcon_h1`` (decode.py) feeds the same normalised
input to grouped-query attention and to this mixer and adds both to the
residual stream.  The mixer is a recurrence over time, so a stream owns
RECURRENT state beside its pages: per layer the scan state ``S``
``[heads, head_dim, d_state]`` (float32: a half-width state would round
at every step of the recurrence) and the convolution's last ``d_conv -
1`` inputs.  Both live in the runtime's donated state dict
(kv_cache.init_state); this module only maps (input, state) to (output,
state):

    p          = ((h * ssm_in) @ W_in) * m       m piecewise constant over
    z, xBC, dt = split(p)                        the parts z, x, B, C, dt
    x, B, C    = split(silu(conv1d(xBC) + b))    causal, depthwise, d_conv taps
    dt_t       = softplus(dt_t + dt_bias)        per head j, group g = j // (H/G)
    S_t        = exp(dt_t A_j) S_{t-1} + dt_t x_t (x) B_t[g]      A_j = -exp(A_log_j)
    y_t        = S_t C_t[g] + D_j x_t
    out        = (w * rmsnorm_per_group(y * silu(z))) @ W_out

`prefill_mixer` advances one slot over one prefill chunk with the
chunked form of the scan (`scan_chunk`: within a block of
``ssm['chunk']`` positions the recurrence is a masked matrix product,
between blocks the state is carried), starting FROM the slot's state
and leaving it at ``true_count``: a pad position gets dt = 0, so it
neither decays the state nor feeds it, and the convolution's tail is
cut at ``true_count``.  `step_mixer` is the single recurrence step of a
decode window, every slot at once; which slots keep the result is the
caller's mask.  Everything is plain jax.numpy; the state's products run
at `highest` precision (they are a few per cent of a chunk's matrix
work) so that the chunked and the stepwise form agree to float32.
"""
import math

import numpy as np

__all__ = ['SLOTS', 'part_sizes', 'conv_channels', 'weight_shapes',
           'state_shapes', 'prefill_mixer', 'step_mixer', 'scan_chunk',
           'scan_step']

# the mixer's weights of one layer, after `layer_<i>_`.  The gated
# norm's scale ends in `norm`: whoever draws weights makes such a name
# ones (decode.random_weights, the benchmark's runner).
SLOTS = ('ssm_in_w', 'ssm_conv_w', 'ssm_conv_b', 'ssm_dt_bias', 'ssm_A_log',
         'ssm_D', 'ssm_gate_norm', 'ssm_out_w')


def part_sizes(ssm):
    """Widths of the input projection's five parts: z, x, B, C, dt."""
    d, gn = int(ssm['d_ssm']), int(ssm['n_groups']) * int(ssm['d_state'])
    return (d, d, gn, gn, int(ssm['n_heads']))


def conv_channels(ssm):
    """Channels the convolution runs over: x, B and C."""
    return sum(part_sizes(ssm)[1:4])


def weight_shapes(d_model, ssm):
    """{slot: shape} of one layer's mixer weights."""
    d, h, ch = int(ssm['d_ssm']), int(ssm['n_heads']), conv_channels(ssm)
    return {'ssm_in_w': (d_model, sum(part_sizes(ssm))),
            'ssm_conv_w': (int(ssm['d_conv']), ch), 'ssm_conv_b': (ch,),
            'ssm_dt_bias': (h,), 'ssm_A_log': (h,), 'ssm_D': (h,),
            'ssm_gate_norm': (d,), 'ssm_out_w': (d, d_model)}


def state_shapes(ssm):
    """(scan state, convolution tail) of ONE slot in ONE layer."""
    h = int(ssm['n_heads'])
    return ((h, int(ssm['d_ssm']) // h, int(ssm['d_state'])),
            (int(ssm['d_conv']) - 1, conv_channels(ssm)))


def _in_proj(h, w, p, ssm, mult):
    """h [..., D] normalised -> z [..., d_ssm], xBC [..., channels],
    dt [..., H], float32 (the product accumulates in f32 either way)."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope('ssm.in_proj'):
        h = h * mult['ssm_in']
        proj = jnp.dot(h, w[p + 'ssm_in_w'],
                       preferred_element_type=jnp.float32)
        proj = proj * np.repeat(np.asarray(mult['ssm'], np.float32),
                                part_sizes(ssm))
        d, ch = int(ssm['d_ssm']), conv_channels(ssm)
        return proj[..., :d], proj[..., d:d + ch], proj[..., d + ch:]


def _heads(xbc, dt, w, p, ssm):
    """Convolved xBC and raw dt -> x [..., H, P], B, C [..., G, N],
    softplus'ed dt [..., H], and the per-head A (negative) and D."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    _, d, gn, _, h = part_sizes(ssm)
    g = int(ssm['n_groups'])
    lead = xbc.shape[:-1]
    x = xbc[..., :d].reshape(lead + (h, d // h))
    B = xbc[..., d:d + gn].reshape(lead + (g, gn // g))
    C = xbc[..., d + gn:].reshape(lead + (g, gn // g))
    dt = jax.nn.softplus(dt + w[p + 'ssm_dt_bias'].astype(f32))
    A = -jnp.exp(w[p + 'ssm_A_log'].astype(f32))
    return x, B, C, dt, A, w[p + 'ssm_D'].astype(f32)


def _gate_out(y, z, w, p, ssm, eps):
    """y, z [..., d_ssm] f32 -> the mixer's output [..., D]: the gate
    first, then an RMS norm over each group's share of the channels."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope('ssm.gate_out'):
        g = int(ssm['n_groups'])
        gated = (y * jax.nn.silu(z)).reshape(y.shape[:-1] + (g, -1))
        var = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
        normed = (gated * jax.lax.rsqrt(var + eps)).reshape(y.shape)
        out_w = w[p + 'ssm_out_w']
        normed = normed * w[p + 'ssm_gate_norm'].astype(jnp.float32)
        return normed.astype(out_w.dtype) @ out_w


def scan_chunk(x, dt, A, B, C, D, S0, block):
    """The recurrence over T positions in blocks of ``block`` (T a
    multiple of it), from state S0.

    x [T, H, P], dt [T, H] (after softplus; 0 where a position is
    padding), A, D [H], B, C [T, G, N], S0 [H, P, N]; returns (y [T, H,
    P], the state after position T - 1).  Inside a block position t
    reads position s <= t through exp(sum of dt A over (s, t]), a lower
    triangular matrix, and the incoming state through exp(sum over [0,
    t]); every exponent is <= 0.
    """
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    T, H, _ = x.shape
    G = B.shape[1]
    nb, rep = T // block, H // G
    xd = x * dt[..., None]

    def blocks(a):
        return a.reshape((nb, block) + a.shape[1:])

    causal = jnp.tril(jnp.ones((block, block), bool))

    def body(S, blk):
        xb, ab, Bb, Cb = blk
        cum = jnp.cumsum(ab, axis=0)                       # [Q, H]
        seg = cum[:, None, :] - cum[None, :, :]            # [t, s, H]
        decay = jnp.exp(jnp.where(causal[..., None], seg, -jnp.inf))
        cb = jnp.einsum('tgn,sgn->gts', Cb, Bb, precision=hi)
        mix = jnp.repeat(cb, rep, axis=0) * decay.transpose(2, 0, 1)
        y = jnp.einsum('hts,shp->thp', mix, xb, precision=hi)
        Ch = jnp.repeat(Cb, rep, axis=1)                   # [Q, H, N]
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            'thn,hpn->thp', Ch, S, precision=hi)
        to_end = jnp.exp(cum[-1][None] - cum)              # [Q, H]
        Bh = jnp.repeat(Bb, rep, axis=1)
        S = jnp.exp(cum[-1])[:, None, None] * S + jnp.einsum(
            'shp,shn->hpn', xb * to_end[..., None], Bh, precision=hi)
        return S, y

    S, y = jax.lax.scan(body, S0, (blocks(xd), blocks(dt * A), blocks(B),
                                   blocks(C)))
    return y.reshape(x.shape) + D[:, None] * x, S


def scan_step(x, dt, A, B, C, D, S):
    """One step of the recurrence for a batch of slots: x [S, H, P],
    dt [S, H], B, C [S, G, N], state [S, H, P, N] -> (y [S, H, P], the
    new state).  Elementwise over the state: it is read once and
    written once, and nothing is rounded to a matrix unit's width."""
    import jax.numpy as jnp
    rep = x.shape[1] // B.shape[1]
    Bh = jnp.repeat(B, rep, axis=1)[:, :, None, :]         # [S, H, 1, N]
    Ch = jnp.repeat(C, rep, axis=1)[:, :, None, :]
    S = jnp.exp(dt * A)[..., None, None] * S \
        + (dt[..., None] * x)[..., None] * Bh
    return jnp.sum(S * Ch, axis=-1) + D[:, None] * x, S


def prefill_mixer(w, p, cfg, h, S0, tail, true_count):
    """One slot, one prefill chunk: h [C, D] normalised, S0 and tail the
    slot's state of this layer (zeros where the prompt begins).  Returns
    (out [C, D], S, tail), the state as position ``true_count - 1``
    leaves it; rows of ``out`` past it are padding's."""
    import jax
    import jax.numpy as jnp
    ssm = cfg['ssm']
    C = h.shape[0]
    z, xbc, dt = _in_proj(h, w, p, ssm, cfg['multipliers'])
    with jax.named_scope('ssm.conv'):
        taps = w[p + 'ssm_conv_w'].astype(jnp.float32)
        full = jnp.concatenate([tail, xbc], axis=0)        # [K-1+C, ch]
        conv = w[p + 'ssm_conv_b'].astype(jnp.float32) + sum(
            full[k:k + C] * taps[k] for k in range(taps.shape[0]))
        tail = jax.lax.dynamic_slice_in_dim(full, true_count,
                                            taps.shape[0] - 1)
        xbc = jax.nn.silu(conv)
    with jax.named_scope('ssm.scan'):
        x, B, Cm, dt, A, D = _heads(xbc, dt, w, p, ssm)
        dt = jnp.where((jnp.arange(C) < true_count)[:, None], dt, 0.0)
        y, S = scan_chunk(x, dt, A, B, Cm, D, S0,
                          math.gcd(C, int(ssm['chunk'])))
    out = _gate_out(y.reshape(C, -1), z, w, p, ssm, float(cfg['rms_eps']))
    return out, S, tail


def step_mixer(w, p, cfg, h, S, tail):
    """Every slot, one decode step: h [slots, D] normalised, S [slots,
    H, P, N], tail [slots, K-1, ch].  Returns (out [slots, D], S, tail)
    for ALL slots; the caller keeps an inactive slot's old state."""
    import jax
    import jax.numpy as jnp
    ssm = cfg['ssm']
    z, xbc, dt = _in_proj(h, w, p, ssm, cfg['multipliers'])
    with jax.named_scope('ssm.conv'):
        full = jnp.concatenate([tail, xbc[:, None]], axis=1)  # [S, K, ch]
        conv = w[p + 'ssm_conv_b'].astype(jnp.float32) + jnp.sum(
            full * w[p + 'ssm_conv_w'].astype(jnp.float32), axis=1)
        tail = full[:, 1:]
        xbc = jax.nn.silu(conv)
    with jax.named_scope('ssm.step'):
        x, B, Cm, dt, A, D = _heads(xbc, dt, w, p, ssm)
        y, S = scan_step(x, dt, A, B, Cm, D, S)
    out = _gate_out(y.reshape(h.shape[0], -1), z, w, p, ssm,
                    float(cfg['rms_eps']))
    return out, S, tail
