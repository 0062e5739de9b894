"""The Mamba-2 mixer of a Falcon-H1 block, as the serving path runs it.

A block of kind ``falcon_h1`` (decode.py) feeds the same normalised
input to grouped-query attention and to this mixer and adds both to the
residual stream.  The mixer is a recurrence over time, so a stream owns
RECURRENT state beside its pages: per layer the scan state ``S``
``[heads, head_dim, d_state]`` (float32: a half-width state would round
at every step of the recurrence) and the convolution's last ``d_conv -
1`` inputs.  Both live in the runtime's donated state dict
(kv_cache.init_state); this module's two halves map (input, the dict) to
(output, the dict):

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
decode window over the WHOLE state array ``[slots, layers, ...]``: a
live slot's state of that layer advances, a dead slot's stays bit for
bit what it was.

The step's recurrence has two routes.  `ssm_step` is a Pallas kernel
that updates the state array IN PLACE: it walks the live slots only
(their indices are scalar prefetch), reads each block of a live slot's
state once, writes it once, and takes ``y = S C`` from the registers
that hold the new state.  `scan_step` is the same arithmetic in plain
jax.numpy over every slot, masked afterwards: the route under a mesh or
for a state the kernel cannot tile (`ssm_step_eligible`, a static rule
on the state's shape, dtype and the mesh), and the reference the kernel
is tested against.  The chunk scan and everything around the recurrence
are plain jax.numpy; the state's products run at `highest` precision
(they are a few per cent of a chunk's matrix work) so that the chunked
and the stepwise form agree to float32.
"""
import functools
import math

import numpy as np

from ...ops import _pallas
from .mixer import Mixer

__all__ = ['SLOTS', 'part_sizes', 'conv_channels', 'weight_shapes',
           'state_shapes', 'prefill_mixer', 'step_mixer', 'scan_chunk',
           'scan_step', 'ssm_step', 'ssm_step_eligible']

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


# ------------------------------------------------ the step, in place

# bytes of one [head block, head_dim, d_state] tile of the state.  The
# kernel holds four (in and out, double buffered)
_STEP_BLOCK_BYTES = 1 << 20


def _head_block(H, P, N):
    """Heads one tile of `ssm_step` holds: the largest divisor of H
    whose tile fits _STEP_BLOCK_BYTES and whose rows of x tile ([hb, P]
    with hb whole sublanes, or every head).  None where nothing does."""
    fits = [hb for hb in range(1, H + 1)
            if H % hb == 0 and (hb % 8 == 0 or hb == H)
            and hb * P * N * 4 <= _STEP_BLOCK_BYTES]
    return max(fits, default=None)


def ssm_step_eligible(state_shape, dtype, mesh=None):
    """Static rule for `ssm_step` over a ``[slots, layers, heads,
    head_dim, d_state]`` state: float32 on a single device; on an
    accelerator a head's ``[head_dim, d_state]`` must be whole tiles
    and some block of heads must fit the kernel's buffers."""
    import jax.numpy as jnp
    if jnp.dtype(dtype) != jnp.float32 or not _pallas.single_device(mesh):
        return False
    if _pallas.interpret():
        return True
    _slots, _layers, H, P, N = state_shape
    return N % 128 == 0 and P % 8 == 0 and _head_block(H, P, N) is not None


def _live_slots(active):
    """active [S] bool -> (order [S] int32, count [1] int32): the live
    slots' indices compacted to the front in slot order (zeros behind
    them) and how many there are.  Plain data for `ssm_step`'s scalar
    prefetch; a comparison and a sum, no sort.  Every layer of a step
    asks with the same mask: XLA keeps one copy."""
    import jax.numpy as jnp
    S = active.shape[0]
    ids = jnp.arange(S, dtype=jnp.int32)
    rank = jnp.cumsum(active.astype(jnp.int32)) - 1
    here = active[None, :] & (rank[None, :] == ids[:, None])
    order = jnp.sum(jnp.where(here, ids[None, :], 0), axis=1)
    return order, jnp.sum(active.astype(jnp.int32)).reshape(1)


def _grid_block(pos, hblk, order_ref, count_ref, nh):
    """(slot, head block) that grid position (pos, hblk) of `ssm_step`
    works on.  A position past the live count names the LAST live
    block again (with no live slot at all: one block of slot
    ``order[0]``), so the pipeline neither fetches nor writes anything
    new for it."""
    import jax.numpy as jnp
    count = count_ref[0]
    return (order_ref[jnp.clip(pos, 0, jnp.maximum(count - 1, 0))],
            jnp.where(pos < count, hblk, nh - 1))


def _ssm_step_kernel(order_ref, count_ref, layer_ref, decay_ref, dtx_ref,
                     dx_ref, bc_ref, s_ref, o_ref, y_ref, *, heads, groups):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    del layer_ref                         # the index maps read it
    pos, hblk = pl.program_id(0), pl.program_id(1)
    count = count_ref[0]
    hb, P, _ = s_ref.shape
    rep = heads // groups

    @pl.when(pos < count)
    def _():
        slot = order_ref[pos]
        # [P, P] identity: turns a row of P lanes into a column of P
        # sublanes and back with one select and one sum, exactly
        eye = jax.lax.broadcasted_iota(jnp.int32, (P, P), 0) \
            == jax.lax.broadcasted_iota(jnp.int32, (P, P), 1)
        for j in range(hb):
            head = hblk * hb + j
            g = head // rep
            b = bc_ref[pl.ds(g, 1), :]                       # [1, N]
            c = bc_ref[pl.ds(groups + g, 1), :]
            dtx = jnp.sum(jnp.where(eye, dtx_ref[j:j + 1, :], 0.0),
                          axis=1, keepdims=True)             # [P, 1]
            S = decay_ref[slot * heads + head] * s_ref[j] + dtx * b
            o_ref[j] = S
            y = jnp.sum(S * c, axis=1, keepdims=True)        # [P, 1]
            y_ref[j:j + 1, :] = dx_ref[j:j + 1, :] + jnp.sum(
                jnp.where(eye, y, 0.0), axis=0, keepdims=True)

    # no live slot: every grid position maps to ONE block, which goes
    # back as it came (an output block the body left alone is written
    # back as whatever its buffer held)
    @pl.when((count == 0) & (pos == 0) & (hblk == 0))
    def _():
        o_ref[...] = s_ref[...]


def ssm_step(x, dt, A, B, C, D, state, layer, active):
    """One step of the recurrence for the LIVE slots, on the state array
    in place.

    x [S, H, P], dt [S, H] (after softplus), A, D [H], B, C [S, G, N],
    float32; state [S, layers, H, P, N] float32, WHOLE: it is aliased
    to the first result, so under donation XLA neither slices nor
    copies it; layer an int32 scalar; active [S] bool, the live slots.
    Returns (y [S, H, P], state): `scan_step`'s arithmetic (only the
    order of the sum over N may differ) for the live slots; every other
    slot's state, and every other layer's, is not touched, and a slot
    that is not live gets zeros for y.

    The grid walks the live slots' indices, compacted to the front
    (scalar prefetch), x blocks of heads.  A position past the live
    count repeats the last live block's index, so nothing is fetched or
    written for it.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, _layers, H, P, N = state.shape
    G = B.shape[1]
    hb = _head_block(H, P, N) or H
    nh = H // hb

    def rows(pos, hblk, order_ref, count_ref, layer_ref):
        return _grid_block(pos, hblk, order_ref, count_ref, nh) + (0,)

    def slot_only(pos, hblk, order_ref, count_ref, layer_ref):
        return (_grid_block(pos, hblk, order_ref, count_ref, nh)[0], 0, 0)

    def tile(pos, hblk, order_ref, count_ref, layer_ref):
        slot, hblk = _grid_block(pos, hblk, order_ref, count_ref, nh)
        return (slot, layer_ref[0], hblk, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, nh),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # decay
            pl.BlockSpec((None, hb, P), rows),                # dt x
            pl.BlockSpec((None, hb, P), rows),                # D x
            pl.BlockSpec((None, 2 * G, N), slot_only),        # B over C
            pl.BlockSpec((None, None, hb, P, N), tile),
        ],
        out_specs=[pl.BlockSpec((None, None, hb, P, N), tile),
                   pl.BlockSpec((None, hb, P), rows)],
    )
    order, count = _live_slots(active)
    state, y = pl.pallas_call(
        functools.partial(_ssm_step_kernel, heads=H, groups=G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((S, H, P), jnp.float32)],
        # operand 7 (after the three prefetched scalars): the state
        input_output_aliases={7: 0},
        name='ssm_step',
        interpret=_pallas.interpret(),
    )(order, count, jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.exp(dt * A).reshape(-1), dt[..., None] * x, D[:, None] * x,
      jnp.concatenate([B, C], axis=1), state)
    # a dead slot's rows of y were never written
    return jnp.where(active[:, None, None], y, 0.0), state


def prefill_mixer(w, cfg, cache, kernels, lay, h, st, at):
    """One slot, one prefill chunk of a layer (mixer.py): h [1, C, D]
    normalised; the slot's scan state and tail of this layer as the last
    chunk left them (zeros where the prompt begins, whoever held the slot
    before).  Returns (what the layer adds to the stream [1, C, D], the
    state dict with the slot's state as position ``true_count - 1``
    leaves it); rows of the output past it are padding's."""
    import jax
    import jax.numpy as jnp
    ssm, p, j = cfg['ssm'], 'layer_%d_' % lay.index, lay.state
    carried, h, true_count = at.offset > 0, h[0], at.true_count
    S0 = jnp.where(carried, st['ssm'][at.slot, j], 0.0)
    tail = jnp.where(carried, st['conv'][at.slot, j], 0.0)
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
    st = dict(st, ssm=st['ssm'].at[at.slot, j].set(S),
              conv=st['conv'].at[at.slot, j].set(tail))
    return out[None] * cfg['multipliers']['ssm_out'], st


def step_mixer(w, cfg, cache, kernels, lay, h, st, at):
    """Every slot, one decode step of a layer (mixer.py): h [slots, 1, D]
    normalised, over the WHOLE scan state [slots, layers, H, P, N] and
    this layer's tails [slots, K-1, ch].  Returns (what the layer adds to
    the stream [slots, 1, D], the state dict): the scan state and the
    tail of the live slots advanced in this layer, an inactive slot's
    kept, both kinds, and nothing else changed.

    ``kernels.state`` (`ssm_step_eligible`, static) runs the recurrence
    in place over the live slots (`ssm_step`); otherwise every slot steps
    (`scan_step`) and the dead ones' result is masked away."""
    import jax
    import jax.numpy as jnp
    from ... import observability as _obs
    ssm, p, j = cfg['ssm'], 'layer_%d_' % lay.index, lay.state
    h, state, active = h[:, 0], st['ssm'], at.active
    tail = st['conv'][:, j]
    z, xbc, dt = _in_proj(h, w, p, ssm, cfg['multipliers'])
    with jax.named_scope('ssm.conv'):
        full = jnp.concatenate([tail, xbc[:, None]], axis=1)  # [S, K, ch]
        conv = w[p + 'ssm_conv_b'].astype(jnp.float32) + jnp.sum(
            full * w[p + 'ssm_conv_w'].astype(jnp.float32), axis=1)
        tail = full[:, 1:]
        xbc = jax.nn.silu(conv)
    with jax.named_scope('ssm.step'):
        x, B, Cm, dt, A, D = _heads(xbc, dt, w, p, ssm)
        if kernels.state:
            _obs.metrics.counter('ssm.step_kernel').inc()
            y, state = ssm_step(x, dt, A, B, Cm, D, state, j, active)
        else:
            _obs.metrics.counter('ssm.step_composed').inc()
            y, S = scan_step(x, dt, A, B, Cm, D, state[:, j])
            state = state.at[:, j].set(jnp.where(
                active[:, None, None, None], S, state[:, j]))
    out = _gate_out(y.reshape(h.shape[0], -1), z, w, p, ssm,
                    float(cfg['rms_eps']))
    st = dict(st, ssm=state,
              conv=st['conv'].at[:, j].set(jnp.where(
                  active[:, None, None], tail, st['conv'][:, j])))
    return out[:, None] * cfg['multipliers']['ssm_out'], st


# the runtime's entry (mixer.py)
MIXER = Mixer(
    weight_shapes=lambda cfg: weight_shapes(int(cfg['d_model']), cfg['ssm']),
    recurrent=lambda cfg: state_shapes(cfg['ssm']),
    kernels=lambda cfg, cache, chunk, mesh: {'state': ssm_step_eligible(
        cache.recurrent_shapes()['ssm'], 'float32', mesh)},
    narrow=(prefill_mixer, step_mixer))
