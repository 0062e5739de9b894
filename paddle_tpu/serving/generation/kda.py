"""Kimi Delta Attention (KDA) as the serving path runs it: a gated
delta rule over a MATRIX state a head, with a decay a CHANNEL.

A ``latent_moe`` model (decode.py) may name ``'kda'`` as a layer's mixer
(``cfg['mixer']``).  For the layer's normalised input ``h`` ``[T, D]``
and ``kda = cfg['kda']`` (``n_heads`` H, ``head_dim`` d for keys and
values alike, ``d_conv`` taps, ``gate_rank``), no biases:

    q^, k^, v = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h W_v))
    q, k      = l2norm(q^) * d^(-1/2), l2norm(k^)            a head
    g         = -exp(A_log[head]) * softplus((h W_fa) W_fb + dt_bias + dt_shift)
    beta      = sigmoid(h W_beta)                            [T, H]
    S         = Diag(exp(g_t)) S;  u_t = beta_t (v_t - S^T k_t)
    S         = S + k_t u_t^T;     o_t = S^T q_t             S [d_k, d_v]
    y         = concat_heads(rmsnorm_head(o_t) * sigmoid((h W_ga) W_gb)) W_o

``conv`` is a causal depthwise convolution over time, one filter a
column, ``d_conv`` taps.  ``g <= 0`` is a log-decay a channel of the
key dimension: what separates this layer from a gated delta rule with
one gate a head (and from ssm.py, whose recurrence has a scalar decay a
head and no delta correction).  ``dt_shift`` (``kda['dt_shift']``, 0
unless the model dict says otherwise) is a constant beside ``dt_bias``:
the mean of a bias whose values are drawn about zero; a checkpoint's own
``dt_bias`` carries its mean and leaves it 0.  It is NOT the source's
and goes when drawn weights can be given a mean where they are drawn
(PERF.md, section 7).

What a stream KEEPS a layer is recurrent state beside its pages
(kv_cache.py): ``S`` ``[H, d, d]`` float32 and the last ``d_conv - 1``
rows of the three convolutions' inputs, the TAIL, ``[d_conv - 1, 3 H,
d]`` float32: a kept row is q's heads, k's, v's as ``3 H`` rows of
``d`` (`state_shapes`), so that one slot's tail of one layer is whole
``(8, 128)`` tiles, one DMA, and a head's part of it a row of lanes.
Nothing grows with the context.  This module only maps (input, state)
to (output, state).

`prefill_mixer` advances one slot over one prefill chunk in the CHUNK
FORM (`chunk_scan`).  Within a sub-chunk of ``_SUB`` tokens, with ``G``
the running sum of ``g`` and ``S_0`` the state it starts from:

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)   i > j
    W, U = (I + A)^-1 (beta K exp(G)),  (I + A)^-1 (beta V)
    U'   = U - W S_0
    P_ij = sum_c q_ic k_jc exp(G_ic - G_jc)          i >= j
    O    = (Q exp(G)) S_0 + P U'
    S_C  = Diag(exp(G_C)) S_0 + sum_j (k_j exp(G_C - G_j)) u'_j^T

Everything but the last three lines is the same for every sub-chunk
whatever state it meets, so it is computed for all of a chunk's
sub-chunks at once; only those three are a scan over the sub-chunks.
Only differences ``G_i - G_j`` with ``i >= j`` are exponentiated, so
nothing overflows however negative ``g``: inside a block of ``_BLOCK``
positions directly (a ``[block, block, d]`` tensor), between blocks as
the product of two factors each at most 1, ``exp(G_i - r)`` and
``exp(r - G_j)`` with ``r`` the running sum where i's block begins.
``(I + A)^-1`` is forward substitution inside a block and the block
formula ``[[X, 0], [-Z M X, Z]]`` between them: no power of ``A`` is
formed.  A pad position (past ``true_count``) gets ``g = 0`` and
``beta = 0``: it neither decays the state nor writes it, and the
convolution's tail is cut at ``true_count``.

`step_mixer` is the single step of a decode window over the WHOLE state
and tail arrays ``[slots, layers, ...]``: a live slot's state and tail
of that layer advance, a dead slot's stay bit for bit what they were.
The matrix products (the projections, the gates' four, the output's)
are XLA's on every slot.  What lies between them has two routes, as
ssm.py's step has.  `kda_step` is a Pallas kernel that updates both
arrays IN PLACE: it walks the live slots only (their indices are scalar
prefetch, `ssm._live_slots`) and takes the step's raw projections; for
a live slot it reads the ``d_conv - 1`` kept rows and ``[H, d, d]`` of
that layer once, convolves, writes the tail back a row further, applies
silu and the unit norms, turns a, k and q into columns (one transpose a
slot: they scale ROWS of the state), and takes ``r = S^T k`` and ``o =
S^T q`` from the registers that hold the state, which it writes once.
So a step touches 2 x (``4 H d d`` + ``4 (d_conv - 1) 3 H d``) bytes a
live slot a layer and nothing of any other slot
(`generation.kda_state_bytes`, `generation.kda_tail_bytes`).  The
composed step is the same arithmetic in plain jax.numpy over every slot
(the tail rejoined with the new row and sliced; ``S^T (a k)`` and ``S^T
(a q)`` in one pass, since ``o = (a S)^T q + (k . q) u``; then the
update; a dead slot's state and tail kept by a select): the route under
a mesh or for extents the kernel cannot tile (`kda_step_eligible`, a
static rule on the state's and the tails' shape, the dtype and the
mesh), and what the kernel is tested against.

Everything of the recurrence is float32 and its products run at
`highest` precision (a few per cent of a chunk's matrix work), so that
the chunk form and the stepwise form agree to float32; the projections
take their inputs in the weights' dtype and accumulate in float32.
"""
import math

from ...ops import _pallas
from .latent import dot as _dot, rms as _rms
from .mixer import Mixer
from .ssm import _live_slots

__all__ = ['SLOTS', 'weight_shapes', 'state_shapes', 'conv_channels',
           'prefill_mixer', 'step_mixer', 'chunk_scan', 'token_scan',
           'state_bytes', 'tail_bytes', 'kda_step', 'kda_step_eligible']

# the mixer's weights of one layer, after `layer_<i>_`.  The head
# norm's scale ends in `norm`: whoever draws weights makes such a name
# ones (decode.random_weights, the benchmark's runner).
SLOTS = ('kda_q_w', 'kda_k_w', 'kda_v_w', 'kda_q_conv', 'kda_k_conv',
         'kda_v_conv', 'kda_fa_w', 'kda_fb_w', 'kda_A_log', 'kda_dt_bias',
         'kda_beta_w', 'kda_ga_w', 'kda_gb_w', 'kda_o_norm', 'kda_o_w')

_SUB = 64       # positions of one sub-chunk of the chunk form
_BLOCK = 16     # positions whose decays are exponentiated pair by pair


def _dims(kda):
    return int(kda['n_heads']), int(kda['head_dim'])


def conv_channels(kda):
    """Channels the three convolutions run over: q, k and v side by
    side."""
    H, d = _dims(kda)
    return 3 * H * d


def weight_shapes(d_model, kda):
    """{slot: shape} of one layer's mixer weights, public layout (a
    projection is ``[in, out]``, a filter ``[taps, columns]``: tap k
    multiplies the input ``d_conv - 1 - k`` positions back)."""
    H, d = _dims(kda)
    n, taps, r = H * d, int(kda['d_conv']), int(kda['gate_rank'])
    return {'kda_q_w': (d_model, n), 'kda_k_w': (d_model, n),
            'kda_v_w': (d_model, n), 'kda_q_conv': (taps, n),
            'kda_k_conv': (taps, n), 'kda_v_conv': (taps, n),
            'kda_fa_w': (d_model, r), 'kda_fb_w': (r, n),
            'kda_A_log': (H,), 'kda_dt_bias': (n,),
            'kda_beta_w': (d_model, H), 'kda_ga_w': (d_model, r),
            'kda_gb_w': (r, n), 'kda_o_norm': (d,), 'kda_o_w': (n, d_model)}


def state_shapes(kda):
    """(matrix state, convolution tail) of ONE slot in ONE layer.  A
    tail row is the three convolutions' input of one position, q's heads,
    k's, v's, as ``3 H`` rows of ``d``: whole tiles a slot, where ``[K-1,
    3 H d]`` would be ``K-1`` sublanes of a tile of eight."""
    H, d = _dims(kda)
    return ((H, d, d), (int(kda['d_conv']) - 1, 3 * H, d))


def state_bytes(kda):
    """Bytes of the matrix state of one slot in one layer (float32): what
    a step must read once and write once for a live stream."""
    H, d = _dims(kda)
    return 4 * H * d * d


def tail_bytes(kda):
    """Bytes of the convolution tail of one slot in one layer (float32):
    what a step must read once and write once for a live stream."""
    return 4 * (int(kda['d_conv']) - 1) * conv_channels(kda)


def _project(w, p, h):
    """h [T, D] normalised -> the three convolutions' inputs side by
    side [T, 3 H d], float32."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope('kda.proj'):
        return jnp.concatenate([_dot(h, w[p + 'kda_q_w']),
                                _dot(h, w[p + 'kda_k_w']),
                                _dot(h, w[p + 'kda_v_w'])], axis=-1)


def _taps(w, p):
    """The three filters side by side [taps, 3 H d], float32."""
    import jax.numpy as jnp
    return jnp.concatenate([w[p + 'kda_q_conv'], w[p + 'kda_k_conv'],
                            w[p + 'kda_v_conv']],
                           axis=-1).astype(jnp.float32)


def _gates(w, p, kda, h):
    """h [T, D] normalised -> (g [T, H, d] log-decay, <= 0; beta [T, H];
    the output gate [T, H, d]), float32."""
    import jax
    import jax.numpy as jnp
    H, d = _dims(kda)
    f32 = jnp.float32
    with jax.named_scope('kda.gate'):
        raw = _dot(_dot(h, w[p + 'kda_fa_w']), w[p + 'kda_fb_w']) \
            + w[p + 'kda_dt_bias'].astype(f32) \
            + float(kda.get('dt_shift', 0.0))
        g = -jnp.exp(w[p + 'kda_A_log'].astype(f32))[:, None] \
            * jax.nn.softplus(raw).reshape(raw.shape[:-1] + (H, d))
        beta = jax.nn.sigmoid(_dot(h, w[p + 'kda_beta_w']))
        gate = jax.nn.sigmoid(_dot(_dot(h, w[p + 'kda_ga_w']),
                                   w[p + 'kda_gb_w']))
        return g, beta, gate.reshape(gate.shape[:-1] + (H, d))


def _heads(conv, kda):
    """The convolutions' outputs [..., 3 H d] -> q, k, v [..., H, d]:
    silu, then q and k to unit length a head and q by ``d^(-1/2)``."""
    import jax
    import jax.numpy as jnp
    H, d = _dims(kda)
    x = jax.nn.silu(conv).reshape(conv.shape[:-1] + (3, H, d))
    q, k, v = x[..., 0, :, :], x[..., 1, :, :], x[..., 2, :, :]

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                                 + 1e-6)

    return unit(q) * d ** -0.5, unit(k), v


def _out(w, p, kda, o, gate, eps):
    """o, gate [T, H, d] float32 -> the mixer's output [T, D]: an RMS
    norm a head, the gate, the output projection."""
    import jax
    with jax.named_scope('kda.out'):
        normed = _rms(o, w[p + 'kda_o_norm'], eps)
        return _dot((normed * gate).reshape(o.shape[0], -1),
                    w[p + 'kda_o_w'])


# ------------------------------------------------------ the recurrence

def token_scan(q, k, v, g, beta, S0):
    """The recurrence as it is defined, a token at a time: q, k, v, g
    [T, H, d], beta [T, H], S0 [H, d, d] -> (o [T, H, d], the state
    after position T - 1).  What `chunk_scan` is tested against."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def body(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[..., None] * S
        u = bt[:, None] * (vt - jnp.einsum('hkv,hk->hv', S, kt,
                                           precision=hi))
        S = S + kt[..., None] * u[:, None, :]
        return S, jnp.einsum('hkv,hk->hv', S, qt, precision=hi)

    S, o = jax.lax.scan(body, S0, (q, k, v, g, beta))
    return o, S


def _inverse(A, block):
    """(I + A)^-1 for A [..., c, c] strictly lower triangular, c a power
    of two times ``block``: forward substitution inside the diagonal
    blocks, then pairs of blocks merged by ``[[X, 0], [-Z M X, Z]]``
    until one is left."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    c = A.shape[-1]
    lead = A.shape[:-2]
    n = c // block
    diag = jnp.einsum('...nimj,nm->...nij',
                      A.reshape(lead + (n, block, n, block)),
                      jnp.eye(n, dtype=A.dtype))          # [..., n, b, b]
    rows = [jnp.broadcast_to(jnp.eye(block, dtype=A.dtype)[0],
                             diag.shape[:-2] + (block,))]
    for i in range(1, block):
        done = jnp.stack(rows, axis=-2)                   # [..., i, b]
        rows.append(jnp.eye(block, dtype=A.dtype)[i] - jnp.einsum(
            '...j,...jc->...c', diag[..., i, :i], done, precision=hi))
    inv = jnp.stack(rows, axis=-2)                        # [..., n, b, b]
    size = block
    while size < c:
        n = c // (2 * size)
        pairs = inv.reshape(lead + (n, 2, size, size))
        X, Z = pairs[..., 0, :, :], pairs[..., 1, :, :]
        M = jnp.einsum(
            '...nimj,nm->...nij',
            A.reshape(lead + (n, 2, size, n, 2, size))[..., 1, :, :, 0, :],
            jnp.eye(n, dtype=A.dtype))
        low = -jnp.einsum('...ij,...jk,...kl->...il', Z, M, X, precision=hi)
        top = jnp.concatenate([X, jnp.zeros_like(X)], axis=-1)
        inv = jnp.concatenate(
            [top, jnp.concatenate([low, Z], axis=-1)], axis=-2)
        size *= 2
    return inv.reshape(lead + (c, c))


def _pair_decays(x, k, G, block):
    """``sum_c x_ic k_jc exp(G_ic - G_jc)`` for i >= j, 0 above the
    diagonal: x, k, G [..., c, d] -> [..., c, c].  Pairs inside a block
    of ``block`` positions are exponentiated one by one; a pair of two
    blocks is the product of ``exp(G_i - r)`` and ``exp(r - G_j)``, r the
    running sum before i's block: each at most 1."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    c, d = x.shape[-2:]
    lead = x.shape[:-2]
    n = c // block

    def blocks(a):
        return a.reshape(lead + (n, block, d))

    xb, kb, Gb = blocks(x), blocks(k), blocks(G)
    lower = jnp.tril(jnp.ones((block, block), bool))
    diff = Gb[..., :, None, :] - Gb[..., None, :, :]      # [.., n, i, j, d]
    near = jnp.sum(xb[..., :, None, :] * kb[..., None, :, :] * jnp.exp(
        jnp.where(lower[..., None], diff, -jnp.inf)), axis=-1)
    eye = jnp.eye(n, dtype=x.dtype)
    out = jnp.einsum('...nij,nm->...nimj', near, eye)     # [.., n, b, n, b]
    if n > 1:
        # r of block I: the running sum at the last position before it
        r = jnp.concatenate([jnp.zeros_like(Gb[..., :1, 0, :]),
                             Gb[..., :-1, -1, :]], axis=-2)   # [.., n, d]
        left = xb * jnp.exp(Gb - r[..., None, :])             # <= 1
        # [.., n (row block), c (column), d]: 0 from the row block on
        before = (jnp.arange(c)[None, :] // block
                  < jnp.arange(n)[:, None])[..., None]
        right = jnp.where(before, jnp.exp(jnp.where(
            before, r[..., :, None, :] - G[..., None, :, :], 0.0)), 0.0) \
            * k[..., None, :, :]
        far = jnp.einsum('...nid,...ncd->...nic', left, right, precision=hi)
        out = out + far.reshape(lead + (n, block, n, block))
    return out.reshape(lead + (c, c))


def chunk_scan(q, k, v, g, beta, S0, sub=_SUB, block=_BLOCK):
    """The recurrence over T positions in the chunk form, sub-chunks of
    ``sub`` positions (T a multiple of it; ``sub`` a power of two times
    ``block``): q, k, v, g [T, H, d], beta [T, H] (0, with g = 0, where
    a position is padding), S0 [H, d, d]; returns (o [T, H, d], the
    state after position T - 1).  `token_scan`'s result; the module's
    docstring has the algebra."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    T, H, d = q.shape
    n = T // sub

    def heads_first(a):                                   # [n, H, sub, ..]
        return jnp.moveaxis(a.reshape((n, sub) + a.shape[1:]), 2, 1)

    q, k, v, g = (heads_first(a) for a in (q, k, v, g))
    beta = heads_first(beta)[..., None]                   # [n, H, sub, 1]
    G = jnp.cumsum(g, axis=2)
    A = beta * jnp.tril(_pair_decays(k, k, G, block), -1)
    P = _pair_decays(q, k, G, block)
    inv = _inverse(A, block)
    W = jnp.einsum('nhij,nhjd->nhid', inv, beta * k * jnp.exp(G),
                   precision=hi)
    U = jnp.einsum('nhij,nhjd->nhid', inv, beta * v, precision=hi)
    last = G[:, :, -1:, :]
    to_end = k * jnp.exp(last - G)
    q_in = q * jnp.exp(G)

    def body(S, x):
        Wn, Un, Pn, Qn, Kn, end = x
        Un = Un - jnp.einsum('hik,hkv->hiv', Wn, S, precision=hi)
        o = jnp.einsum('hik,hkv->hiv', Qn, S, precision=hi) \
            + jnp.einsum('hij,hjv->hiv', Pn, Un, precision=hi)
        S = jnp.exp(end)[..., None] * S + jnp.einsum(
            'hjk,hjv->hkv', Kn, Un, precision=hi)
        return S, o

    S, o = jax.lax.scan(body, S0, (W, U, P, q_in, to_end, last[:, :, 0]))
    return jnp.moveaxis(o, 1, 2).reshape(T, H, d), S


def _sizes(C):
    """(sub-chunk, block) for a chunk of C positions: `_SUB` and `_BLOCK`
    where they divide it, else the largest powers of two that do."""
    sub = math.gcd(C, _SUB)
    return sub, math.gcd(sub, _BLOCK)


def prefill_mixer(w, cfg, cache, kernels, lay, h, st, at):
    """One slot, one prefill chunk of a layer (mixer.py): h [C, D]
    normalised; the slot's matrix state [H, d, d] and tail [K-1, 3 H, d]
    of this layer as the last chunk left them (zeros where the prompt
    begins).  Returns (out [C, D] float32, the state dict with the slot's
    state as position ``true_count - 1`` leaves it); rows of ``out`` past
    it are padding's."""
    import jax
    import jax.numpy as jnp
    kda, p, j = cfg['kda'], 'layer_%d_' % lay.index, lay.state
    carried, true_count = at.offset > 0, at.true_count
    S0 = jnp.where(carried, st['ssm'][at.slot, j], 0.0)
    tail = jnp.where(carried, st['conv'][at.slot, j], 0.0)
    C = h.shape[0]
    x = _project(w, p, h)
    g, beta, gate = _gates(w, p, kda, h)
    with jax.named_scope('kda.conv'):
        taps = _taps(w, p)
        full = jnp.concatenate([tail.reshape(tail.shape[0], -1), x],
                               axis=0)                     # [K-1+C, ch]
        conv = sum(full[t:t + C] * taps[t] for t in range(taps.shape[0]))
        tail = jax.lax.dynamic_slice_in_dim(
            full, true_count, taps.shape[0] - 1).reshape(tail.shape)
    with jax.named_scope('kda.scan'):
        q, k, v = _heads(conv, kda)
        real = jnp.arange(C) < true_count
        g = jnp.where(real[:, None, None], g, 0.0)
        beta = jnp.where(real[:, None], beta, 0.0)
        o, S = chunk_scan(q, k, v, g, beta, S0, *_sizes(C))
    out = _out(w, p, kda, o, gate, float(cfg.get('rms_eps', 1e-6)))
    return out, dict(st, ssm=st['ssm'].at[at.slot, j].set(S),
                     conv=st['conv'].at[at.slot, j].set(tail))


# ------------------------------------------------ the step, in place

# bytes of one slot's [H, d, d] state of one layer, the kernel's largest
# tile: it holds four (in and out, double buffered), and four of the
# slot-layer's tail beside them
_STEP_TILE_BYTES = 2 << 20


def kda_step_eligible(state_shape, tails_shape, dtype, mesh=None):
    """Static rule for `kda_step` over a ``[slots, layers, H, d, d]``
    state and its ``[slots, layers, K-1, 3 H, d]`` tails: float32 on a
    single device; on an accelerator a head's ``[d, d]``, the heads' ``[H,
    d]`` and so a tail row's ``[3 H, d]`` must be whole tiles, and one
    slot's heads (with its tail, which must be the smaller) must fit the
    kernel's buffers."""
    import jax.numpy as jnp
    if jnp.dtype(dtype) != jnp.float32 or not _pallas.single_device(mesh):
        return False
    if _pallas.interpret():
        return True
    _slots, _layers, H, d, _ = state_shape
    return d % 128 == 0 and H % 8 == 0 \
        and math.prod(tails_shape[2:]) <= H * d * d <= _STEP_TILE_BYTES // 4


def _kda_step_kernel(order_ref, count_ref, layer_ref, x_ref, taps_ref, a_ref,
                     beta_ref, s_ref, tail_ref, s_out, tail_out, o_ref,
                     cols_ref, v_ref):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    del order_ref, layer_ref              # the index maps read them
    H, d = a_ref.shape
    K = taps_ref.shape[0]
    pos, count = pl.program_id(0), count_ref[0]

    @pl.when(pos < count)
    def _():
        # the three convolutions over the slot's K-1 kept rows and the new
        # one, q's heads, k's, v's down the sublanes [3 H, d]; the tail
        # moves up a row in place
        x = x_ref[...]
        conv = x * taps_ref[K - 1]
        for j in range(K - 1):
            conv = conv + tail_ref[j] * taps_ref[j]
        for j in range(K - 2):
            tail_out[j] = tail_ref[j + 1]
        tail_out[K - 2] = x
        y = conv * jax.nn.sigmoid(conv)                      # silu

        def unit(r):
            return r * jax.lax.rsqrt(
                jnp.sum(r * r, axis=-1, keepdims=True) + 1e-6)

        # a head's decay, key and query scale ROWS of its state: turned
        # once a slot so that a head's is a column down the sublanes
        cols_ref[0] = a_ref[...].T
        cols_ref[1] = unit(y[H:2 * H]).T
        cols_ref[2] = (unit(y[:H]) * d ** -0.5).T
        v_ref[...] = y[2 * H:]
        for j in range(H):
            a, k, q = (cols_ref[c, :, j:j + 1] for c in range(3))   # [d, 1]
            S = a * s_ref[j]
            r = jnp.sum(k * S, axis=0, keepdims=True)        # [1, d]
            u = beta_ref[:, j:j + 1] * (v_ref[j:j + 1, :] - r)
            S = S + k * u
            s_out[j] = S
            o_ref[j:j + 1, :] = jnp.sum(q * S, axis=0, keepdims=True)

    # no live slot: every grid position maps to ONE block of the state and
    # one of the tails, which go back as they came
    @pl.when((count == 0) & (pos == 0))
    def _():
        s_out[...] = s_ref[...]
        tail_out[...] = tail_ref[...]


def kda_step(x, taps, a, beta, state, tails, layer, active):
    """One step of a `kda` layer's recurrence for the LIVE slots, from
    the projections on, on the state and the tails in place.

    x [S, 3 H d] the step's projections (`_project`), taps [K, 3 H d]
    (`_taps`), a (the decay, ``exp(g)``) [S, H, d], beta [S, H], float32;
    state [S, layers, H, d, d] and tails [S, layers, K-1, 3 H, d] float32,
    WHOLE: each is aliased to a result, so under donation XLA neither
    slices nor copies them; layer an int32 scalar; active [S] bool, the
    live slots.  Returns (o [S, H, d], state, tails).  For a live slot
    the kernel reads the ``K-1`` kept rows of that layer, convolves them
    with the new row, writes the tail back a row further, applies silu,
    brings q and k to unit length a head (q by ``d^(-1/2)``) and runs the
    delta rule on the heads' state: `step_mixer`'s composed arithmetic.
    Every other slot's state and tail, and every other layer's, is not
    touched, and a slot that is not live gets zeros for o.

    The grid walks the live slots' indices, compacted to the front
    (scalar prefetch); a position past the live count repeats the last
    live slot's index, so nothing is fetched or written for it.  The
    filters are one block for every position, fetched once.  The
    vectors that scale ROWS of a head's state (a, k, q: one value a key
    channel) are transposed in the kernel, ``[H, d]`` to ``[d, H]`` once
    a slot, so that a head's is a column down the sublanes; v and beta
    stay rows."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, _layers, H, d, _ = state.shape
    K = taps.shape[0]

    def slot_of(pos, order_ref, count_ref):
        return order_ref[jnp.clip(pos, 0, jnp.maximum(count_ref[0] - 1, 0))]

    def rows(pos, order_ref, count_ref, layer_ref):
        return (slot_of(pos, order_ref, count_ref), 0, 0)

    def filters(pos, order_ref, count_ref, layer_ref):
        return (0, 0, 0)

    def tile(pos, order_ref, count_ref, layer_ref):
        return (slot_of(pos, order_ref, count_ref), layer_ref[0], 0, 0, 0)

    kept = pl.BlockSpec((None, None, H, d, d), tile), \
        pl.BlockSpec((None, None, K - 1, 3 * H, d), tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec((None, 3 * H, d), rows),
                  pl.BlockSpec((K, 3 * H, d), filters),
                  pl.BlockSpec((None, H, d), rows),
                  pl.BlockSpec((None, 1, H), rows), *kept],
        out_specs=[*kept, pl.BlockSpec((None, H, d), rows)],
        scratch_shapes=[pltpu.VMEM((3, d, H), jnp.float32),
                        pltpu.VMEM((H, d), jnp.float32)],
    )
    order, count = _live_slots(active)
    state, tails, o = pl.pallas_call(
        _kda_step_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(tails.shape, tails.dtype),
                   jax.ShapeDtypeStruct((S, H, d), jnp.float32)],
        # operands 7 and 8 (after the three prefetched scalars): the state
        # and the tails
        input_output_aliases={7: 0, 8: 1},
        name='kda_step',
        interpret=_pallas.interpret(),
    )(order, count, jnp.asarray(layer, jnp.int32).reshape(1),
      x.reshape(S, 3 * H, d), taps.reshape(K, 3 * H, d), a,
      beta[:, None, :], state, tails)
    # a dead slot's rows of o were never written
    return jnp.where(active[:, None, None], o, 0.0), state, tails


def step_mixer(w, p, cfg, h, state, layer, tails, active, kernel):
    """Every slot, one decode step of recurrent layer ``layer``: h
    [slots, D] normalised, state [slots, layers, H, d, d] and tails
    [slots, layers, K-1, 3 H, d] (the WHOLE arrays), active [slots] bool.
    Returns (out [slots, D] float32, state, tails): the state and the
    tail of the live slots advanced in this layer and nothing else of
    either changed.

    ``kernel`` (`kda_step_eligible`, static) runs the step from the
    projections on in place over the live slots (`kda_step`); otherwise
    every slot steps and the dead ones' state and tail are kept by a
    select."""
    import jax
    import jax.numpy as jnp
    from ... import observability as _obs
    kda = cfg['kda']
    x = _project(w, p, h)
    g, beta, gate = _gates(w, p, kda, h)
    a = jnp.exp(g)
    if kernel:
        with jax.named_scope('kda.step'):
            _obs.metrics.counter('kda.step_kernel').inc()
            o, state, tails = kda_step(x, _taps(w, p), a, beta, state,
                                       tails, layer, active)
    else:
        _obs.metrics.counter('kda.step_composed').inc()
        with jax.named_scope('kda.conv'):
            old = tails[:, layer]                      # [S, K-1, 3 H, d]
            full = jnp.concatenate([old.reshape(old.shape[:2] + (-1,)),
                                    x[:, None]], axis=1)      # [S, K, ch]
            conv = jnp.sum(full * _taps(w, p), axis=1)
            tails = tails.at[:, layer].set(jnp.where(
                active[:, None, None, None],
                full[:, 1:].reshape(old.shape), old))
        with jax.named_scope('kda.step'):
            q, k, v = _heads(conv, kda)
            S = state[:, layer]                        # [S, H, d, d]
            # S^T (a k) and S^T (a q) in ONE pass over the state
            both = jnp.sum(
                S[:, :, None] * (a[:, :, None]
                                 * jnp.stack([k, q], axis=2))[..., None],
                axis=-2)                               # [S, H, 2, d]
            u = beta[..., None] * (v - both[:, :, 0])
            o = both[:, :, 1] + jnp.sum(k * q, axis=-1, keepdims=True) * u
            new = a[..., None] * S + k[..., None] * u[:, :, None, :]
            state = state.at[:, layer].set(
                jnp.where(active[:, None, None, None], new, S))
    return _out(w, p, kda, o, gate, float(cfg.get('rms_eps', 1e-6))), \
        state, tails


def _step_layer(w, cfg, cache, kernels, lay, h, st, at):
    """`step_mixer` as a layer of a step (mixer.py): an inactive slot
    keeps both kinds of state."""
    out, matrices, tails = step_mixer(
        w, 'layer_%d_' % lay.index, cfg, h, st['ssm'], lay.state,
        st['conv'], at.active, kernels.state)
    return out, dict(st, ssm=matrices, conv=tails)


def _chunk_counted(n, cache, new_len, true_count):
    import jax.numpy as jnp
    return [jnp.stack([jnp.int32(0), true_count.astype(jnp.int32),
                       jnp.int32(0)])]


def _step_counted(n, cache, kernels, at):
    """Slot-layers whose state and tail the step moved: the kernel moves
    the live slots' in every layer, the composed step every slot's."""
    import jax.numpy as jnp
    moved = jnp.asarray(n * (
        jnp.sum(at.active, dtype=jnp.int32) if kernels.state
        else at.active.shape[0]), jnp.int32)
    return [jnp.stack([moved, jnp.int32(0), moved])]


def _kernels(cfg, cache, chunk, mesh):
    shapes = cache.recurrent_shapes()
    return {'state': kda_step_eligible(shapes['ssm'], shapes['conv'],
                                       'float32', mesh)}


MIXER = Mixer(
    weight_shapes=lambda cfg: weight_shapes(int(cfg['d_model']), cfg['kda']),
    recurrent=lambda cfg: state_shapes(cfg['kda']),
    kernels=_kernels,
    # counted in slot-layers whose state, and tails, a window's steps read
    # once and wrote once (bytes); and the tokens a chunk's scan took
    stats=lambda cfg: {'kda_state_bytes': 2 * state_bytes(cfg['kda']),
                       'kda_chunk_tokens': 1,
                       'kda_tail_bytes': 2 * tail_bytes(cfg['kda'])},
    counted=(_chunk_counted, _step_counted),
    wide=(prefill_mixer, _step_layer))
