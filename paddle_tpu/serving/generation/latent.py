"""Multi-head LATENT attention (MLA) as the serving path runs it.

A block of kind ``latent_moe`` (decode.py) attends through a compressed
key/value vector.  For a layer's normalised input ``h`` ``[T, D]``, with
``lat = cfg['latent']`` (``q_rank``, ``kv_rank``, ``nope``, ``rope``,
``v`` and the ``yarn`` numbers) and H heads, no biases:

    c_q            = rmsnorm(h W_qa)                     [T, q_rank]
    q_nope, q_rope = split(c_q W_qb)                     H x (nope ; rope)
    c_kv ; k_r     = split(h W_kva)                      kv_rank ; rope
    c_kv           = rmsnorm(c_kv)
    k_r, q_r       = rope(k_r), rope(q_rope)             ONE k_r for all heads
    k_nope ; v     = c_kv W_kvb                          H x (nope ; v)
    score          = (q_nope . k_nope + q_r . k_r) * s   causal, softmax in f32
    out            = concat_heads(P v) W_o

What a token leaves in the CACHE is ``[c_kv ; k_r]`` and nothing else:
``kv_rank + rope`` values a layer (`row_width`), one row of the latent
pool (kv_cache.py), stored `stored_width` wide: whole lane tiles, the
columns behind the row zeros (a DMA moves whole tiles).  `prefill`
writes a chunk's rows, gathers the slot's logical rows and EXPANDS
``k_nope`` and ``v`` from them, a block of cached positions at a time
under an online softmax (the plain form over 64 heads and 7 k positions
at once would hold a gigabyte of scores, and blocks past the context
are not visited).  `step` uses the ABSORBED form:

    q_lat = q_nope W_kvb^K[head]                         nope -> kv_rank
    score = (q_lat . c_kv + q_r . k_r) * s
    o     = (P c_kv) W_kvb^V[head]                       kv_rank -> v

so that every head reads the one row as it lies in the pool
(`ops.attention.latent_attention`, in place through the block table;
`latent_attention_composed` on gathered rows under a mesh).

The rotation is YaRN's (`yarn_inv_freq`: every pair's frequency blended
between ``theta^(-2i/rope)`` and that over ``factor`` by where it falls
in the correction range of ``beta_fast`` and ``beta_slow``), computed
once in numpy, and ``s`` carries its ``mscale`` (`score_scale`).

Weights as the launches read them (`prepare`, undone exactly by
`public`): ``W_qb`` split into its nope and its rope columns, ``W_kva``
whole, the rope columns of both in ROTATED-HALF order (a head's even
columns, then its odd ones: decode.py's `_rope_at` convention, so a
cached row's rope part lies in that order too); ``W_kvb`` split into
its key half ``[H, nope, kv_rank]`` and its value half ``[H, kv_rank,
v]``, the operands of the two absorptions.

TWO STEPS ARE OPTIONAL, read off ``lat``.  ``q_rank: None`` is a model
whose queries have no low-rank step: ``q = h W_q``, ONE weight
``att_q_w`` ``[D, H (nope + rope)]`` in place of ``att_qa_w``,
``att_qa_norm`` and ``att_qb_w`` (`slots`, `prepared`: it is prepared
as ``W_qb`` is).  ``rotate: False`` is a model that gives its latent
layers no positions: ``q_rope`` and ``k_r`` enter the score as they
come out of their projections.  Without ``lat['yarn']`` the score scale
is ``(nope + rope)^(-1/2)`` alone (the only form served without a
rotation).  The cache row, the absorbed step and the expanded chunk
are the same either way.

The residual stream of this block is float32 and so is every norm and
every softmax; a product takes its inputs in the weights' dtype and
accumulates in float32.
"""
import math

import numpy as np

from ...ops.attention import (latent_attention, latent_attention_composed,
                              latent_attention_eligible,
                              latent_prefill, latent_prefill_eligible)
from .mixer import Mixer

__all__ = ['SLOTS', 'PREPARED', 'slots', 'prepared', 'weight_shapes',
           'row_width', 'stored_width',
           'yarn_inv_freq', 'score_scale', 'prepare', 'public', 'prefill',
           'prefill_kernel', 'prefill_rows', 'step', 'public_rows', 'rms',
           'dot']

# the attention weights of one layer, after `layer_<i>_`.  The two inner
# norms' scales end in `norm`: whoever draws weights makes such a name
# ones (decode.random_weights, the benchmark's runner).
SLOTS = ('att_qa_w', 'att_qa_norm', 'att_qb_w', 'att_kva_w', 'att_kva_norm',
         'att_kvb_w', 'att_o_w')

# public slot -> the names its prepared parts go by among the
# executables' parameters
PREPARED = {'att_qb_w': ('att_qb_nope', 'att_qb_rope'),
            'att_kva_w': ('att_kva_wp',),
            'att_kvb_w': ('att_kvb_k', 'att_kvb_v')}

_PREFILL_KEY_BLOCK = 1024   # cached positions one pass of a chunk expands


def _low_rank_q(lat):
    return lat.get('q_rank') is not None


def slots(lat):
    """`SLOTS` of a model with ``lat``: ``att_q_w`` alone for the queries
    where ``q_rank`` is None."""
    return SLOTS if _low_rank_q(lat) else ('att_q_w',) + SLOTS[3:]


def prepared(lat):
    """`PREPARED` of a model with ``lat``: where ``q_rank`` is None the
    one query weight ``att_q_w`` takes ``att_qb_w``'s place (and its
    prepared parts' names)."""
    if _low_rank_q(lat):
        return PREPARED
    return {('att_q_w' if slot == 'att_qb_w' else slot): parts
            for slot, parts in PREPARED.items()}


def weight_shapes(d_model, n_head, lat):
    """{slot: shape} of one layer's attention weights, public layout
    (a projection is ``[in, out]``; a head's columns are nope then rope,
    nope then v)."""
    kr = int(lat['kv_rank'])
    nope, rope, v = int(lat['nope']), int(lat['rope']), int(lat['v'])
    if _low_rank_q(lat):
        qr = int(lat['q_rank'])
        queries = {'att_qa_w': (d_model, qr), 'att_qa_norm': (qr,),
                   'att_qb_w': (qr, n_head * (nope + rope))}
    else:
        queries = {'att_q_w': (d_model, n_head * (nope + rope))}
    return dict(queries,
                **{'att_kva_w': (d_model, kr + rope), 'att_kva_norm': (kr,),
                   'att_kvb_w': (kr, n_head * (nope + v)),
                   'att_o_w': (n_head * v, d_model)})


def row_width(lat):
    """Values a token leaves in the cache a layer: ``[c_kv ; k_r]``."""
    return int(lat['kv_rank']) + int(lat['rope'])


def stored_width(lat):
    """Columns a row takes in the pool: `row_width` up to whole lane
    tiles of 128, zeros behind the row."""
    return -(-row_width(lat) // 128) * 128


def yarn_inv_freq(lat, theta):
    """[rope / 2] float32: pair i's angle per position."""
    rope, yarn = int(lat['rope']), lat['yarn']
    f = float(theta) ** (-np.arange(0, rope, 2, dtype=np.float64) / rope)
    factor, orig = float(yarn['factor']), float(yarn['original_max_len'])

    def correction(rotations):
        return rope * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(float(theta)))

    lo = max(math.floor(correction(float(yarn['beta_fast']))), 0)
    hi = min(math.ceil(correction(float(yarn['beta_slow']))), rope - 1)
    # 0 below the range (the pair keeps its frequency), 1 above it (the
    # pair is slowed by ``factor``), a ramp between
    ramp = np.clip((np.arange(rope // 2, dtype=np.float64) - lo)
                   / max(hi - lo, 1e-3), 0.0, 1.0)
    return (f / factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def score_scale(lat):
    """``(nope + rope)^(-1/2)`` times YaRN's ``mscale(factor,
    mscale_all_dim)`` squared; cos and sin stay unscaled where ``mscale
    == mscale_all_dim`` (the only case served).  Without ``lat['yarn']``
    the first factor alone."""
    if 'yarn' not in lat:
        return (int(lat['nope']) + int(lat['rope'])) ** -0.5
    yarn = lat['yarn']
    if float(yarn['mscale']) != float(yarn['mscale_all_dim']):
        raise ValueError('latent attention serves mscale == '
                         'mscale_all_dim (unscaled cos and sin)')
    return (int(lat['nope']) + int(lat['rope'])) ** -0.5 * (
        0.1 * float(yarn['mscale_all_dim'])
        * math.log(float(yarn['factor'])) + 1.0) ** 2


# ---------------------------------------- weights as the launches read them

def _halves(w, rope, forward):
    """w [..., n * rope]: every group of ``rope`` trailing columns from
    interleaved pairs to rotated halves (``forward``) or back."""
    inner = (rope // 2, 2) if forward else (2, rope // 2)
    lead = w.shape[:-1]
    return w.reshape(lead + (-1,) + inner).swapaxes(-1, -2).reshape(w.shape)


def prepare(qb, kva, kvb, n_head, nope, rope, v):
    """One layer's public ``W_qb``, ``W_kva``, ``W_kvb`` -> the five
    arrays of `PREPARED`, in its order."""
    import jax.numpy as jnp
    qr, kr = qb.shape[0], kvb.shape[0]
    q = qb.reshape(qr, n_head, nope + rope)
    q_nope = q[..., :nope].reshape(qr, n_head * nope)
    q_rope = _halves(q[..., nope:].reshape(qr, n_head * rope), rope, True)
    kva_p = jnp.concatenate([kva[:, :kr], _halves(kva[:, kr:], rope, True)],
                            axis=1)
    b = kvb.reshape(kr, n_head, nope + v)
    return (q_nope, q_rope, kva_p, b[..., :nope].transpose(1, 2, 0),
            b[..., nope:].transpose(1, 0, 2))


def public(slot, parts, n_head, nope, rope, v):
    """`prepare` undone for ONE public slot from its prepared parts:
    bitwise the weight they were made from."""
    import jax.numpy as jnp
    if slot in ('att_qb_w', 'att_q_w'):
        q_nope, q_rope = parts
        qr = q_nope.shape[0]
        return jnp.concatenate(
            [q_nope.reshape(qr, n_head, nope),
             _halves(q_rope, rope, False).reshape(qr, n_head, rope)],
            axis=-1).reshape(qr, n_head * (nope + rope))
    if slot == 'att_kva_w':
        kva_p, = parts
        kr = kva_p.shape[1] - rope
        return jnp.concatenate(
            [kva_p[:, :kr], _halves(kva_p[:, kr:], rope, False)], axis=1)
    wk, wv = parts                         # [H, nope, kr], [H, kr, v]
    kr = wk.shape[2]
    return jnp.concatenate([wk.transpose(2, 0, 1), wv.transpose(1, 0, 2)],
                           axis=-1).reshape(kr, n_head * (nope + v))


def public_rows(rows, lat):
    """Cached rows [..., stored_width] as the pool holds them -> [...,
    row_width] in the public order: the pad dropped, the rope part from
    rotated halves back to interleaved pairs (numpy)."""
    kr, rope = int(lat['kv_rank']), int(lat['rope'])
    return np.concatenate(
        [rows[..., :kr], _halves(rows[..., kr:kr + rope], rope, False)],
        axis=-1)


# ------------------------------------------------------- forward pieces

def rms(x, scale, eps):
    """RMSNorm in float32, whatever comes in."""
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def dot(x, w):
    """x @ w: x in w's dtype, float32 out."""
    import jax.numpy as jnp
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _rotate(x, pos, inv_freq):
    """x [..., T, rope] in rotated-half order, pos [T] (or broadcastable
    to x's leading axes + [T]): pair i = (x[i], x[rope/2 + i]) turns by
    ``pos * inv_freq[i]``.  float32."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    ang = pos[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _turned(x, pos, cfg):
    """`_rotate` by the model's angles; x itself for a model whose
    latent layers take no positions (``rotate: False``)."""
    lat = cfg['latent']
    if not lat.get('rotate', True):
        return x
    return _rotate(x, pos, yarn_inv_freq(lat, cfg['theta']))


def _queries(w, p, cfg, h):
    """h [T, D] normalised -> q_nope [T, H, nope], q_rope [T, H, rope]
    (before the rotation), float32.  Through the low-rank step where the
    model has one (``q_rank``), else straight from ``h``."""
    import jax
    lat, H = cfg['latent'], int(cfg['n_head'])
    with jax.named_scope('attn.latent.q'):
        c_q = h if not _low_rank_q(lat) else rms(
            dot(h, w[p + 'att_qa_w']), w[p + 'att_qa_norm'],
            float(cfg.get('rms_eps', 1e-6)))
        T = h.shape[0]
        return (dot(c_q, w[p + 'att_qb_nope']).reshape(T, H, -1),
                dot(c_q, w[p + 'att_qb_rope']).reshape(T, H,
                                                        int(lat['rope'])))


def _row(w, p, cfg, h, pos, dtype):
    """h [T, D] normalised, pos [T] -> the rows [T, stored_width] these
    tokens leave in the cache, in the pool's ``dtype``: normalised c_kv,
    the rotated shared key, zeros."""
    import jax
    import jax.numpy as jnp
    lat = cfg['latent']
    kr = int(lat['kv_rank'])
    with jax.named_scope('attn.latent.kv'):
        ckv_kr = dot(h, w[p + 'att_kva_wp'])
        c_kv = rms(ckv_kr[:, :kr], w[p + 'att_kva_norm'],
                    float(cfg.get('rms_eps', 1e-6)))
        k_r = _turned(ckv_kr[:, kr:], pos, cfg)
        pad = stored_width(lat) - row_width(lat)
        return jnp.concatenate(
            [c_kv, k_r, jnp.zeros((h.shape[0], pad), jnp.float32)],
            axis=1).astype(dtype)


def _padded(q_r, lat):
    """q_r [..., rope] -> [..., stored_width - kv_rank]: zeros against
    the row's pad columns."""
    import jax.numpy as jnp
    pad = stored_width(lat) - row_width(lat)
    return jnp.pad(q_r, [(0, 0)] * (q_r.ndim - 1) + [(0, pad)])


def _key_block(table_rows):
    """Cached positions one pass of a chunk takes, on either route."""
    return min(_PREFILL_KEY_BLOCK, table_rows)


def prefill_kernel(cfg, cache, chunk, mesh=None):
    """Whether `prefill` attends through `ops.attention.latent_prefill`
    for chunks of ``chunk`` tokens over ``cache`` (a `CacheConfig` with a
    latent pool): the kernel's static rule of shapes, dtype and mesh."""
    lat = cfg['latent']
    return latent_prefill_eligible(
        cache.pool_shape, cache.store_dtype, chunk,
        _key_block(cache.max_pages * cache.page_len), int(lat['kv_rank']),
        int(lat['nope']), int(lat['v']), mesh)


def prefill_rows(n_keys, table_rows):
    """Cached rows a layer of `prefill` visits for a chunk that leaves
    ``n_keys`` positions written, of a slot whose block table maps
    ``table_rows``: whole blocks of ``_PREFILL_KEY_BLOCK``, on either
    route."""
    BK = _key_block(table_rows)
    return (n_keys + BK - 1) // BK * BK


def _block_loop(q, rows, wk, wv, pos, n_keys, scale, BK, kr, rope):
    """The chunk's attention composed of XLA operations: q [H, C, nope +
    rope] against rows [Tk, W] (whole blocks of ``BK``), a block at a
    time under an online softmax.  Returns [H, C, v] float32."""
    import jax
    import jax.numpy as jnp
    H, C, _ = q.shape
    dt = rows.dtype

    def block(b, carry):
        m, l, acc = carry
        blk = jax.lax.dynamic_slice_in_dim(rows, b * BK, BK)
        ckv = blk[:, :kr]
        k_nope = jnp.einsum('tc,hnc->htn', ckv, wk,
                            preferred_element_type=jnp.float32)
        vals = jnp.einsum('tc,hcv->htv', ckv, wv,
                          preferred_element_type=jnp.float32)
        k = jnp.concatenate(
            [k_nope.astype(dt), jnp.broadcast_to(
                blk[None, :, kr:kr + rope], (H, BK, rope))], axis=-1)
        s = jnp.einsum('hqd,hkd->hqk', q, k,
                       preferred_element_type=jnp.float32) * scale
        kpos = b * BK + jnp.arange(BK)
        s = jnp.where(kpos[None, :] <= pos[:, None], s, -1e30)
        # key 0 is visible to every query, so from the first block on
        # m is a real score and a masked key's exp is 0
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        prob = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(prob, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            'hqk,hkv->hqv', prob.astype(dt), vals.astype(dt),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(
        0, (n_keys + BK - 1) // BK, block,
        (jnp.full((H, C, 1), -1e30, jnp.float32),
         jnp.zeros((H, C, 1), jnp.float32),
         jnp.zeros((H, C, wv.shape[2]), jnp.float32)))
    return acc / jnp.maximum(l, 1e-30)


def prefill(w, p, cfg, h, pos, n_keys, pool, layer, pg, rw, bt_row, kernel):
    """One slot, one prefill chunk of layer ``layer``: h [C, D]
    normalised, pos [C] absolute positions, n_keys the positions written
    once the chunk is (offset + true_count), pg / rw [C] the page and
    the in-page row of each position (page 0 for padding), bt_row
    [max_pages].  Writes the chunk's rows, gathers the slot's logical
    rows and attends in the EXPANDED form, ``_PREFILL_KEY_BLOCK`` keys
    at a time: each block's ``k_nope`` and ``v`` are expanded for every
    head from its cached rows and enter an online softmax (float32
    statistics), and blocks past ``n_keys`` are not visited, so the
    work follows the context and not ``max_len``.

    ``kernel`` (`prefill_kernel`, static) runs those passes as ONE
    Pallas kernel whose scores never leave the chip
    (`ops.attention.latent_prefill`); otherwise they are a loop of XLA
    operations (`_block_loop`), a block's float32 scores an array in
    HBM: the composed form the kernel is tested against, and the route
    under a mesh.
    Returns (the attention's output [C, D] float32, the pool)."""
    import jax
    import jax.numpy as jnp
    from ... import observability as _obs
    lat, H = cfg['latent'], int(cfg['n_head'])
    kr, rope = int(lat['kv_rank']), int(lat['rope'])
    v = int(lat['v'])
    C = h.shape[0]
    q_nope, q_rope = _queries(w, p, cfg, h)
    pool = pool.at[pg, layer, rw].set(_row(w, p, cfg, h, pos, pool.dtype))
    with jax.named_scope('attn.latent.scores'):
        dt = pool.dtype
        q_r = _turned(q_rope.transpose(1, 0, 2), pos, cfg)    # [H, C, rope]
        q_nope = q_nope.transpose(1, 0, 2)                    # [H, C, nope]
        rows = pool[bt_row, layer].reshape(-1, pool.shape[-1])   # [Tk, W]
        BK = _key_block(rows.shape[0])
        rows = jnp.pad(rows, ((0, -rows.shape[0] % BK), (0, 0)))
        wk, wv = w[p + 'att_kvb_k'].astype(dt), w[p + 'att_kvb_v'].astype(dt)
        scale = score_scale(lat)
        if kernel:
            _obs.metrics.counter('latent.prefill_kernel').inc()
            q = jnp.concatenate([q_nope, _padded(q_r, lat)],
                                axis=-1).astype(dt)
            out = latent_prefill(q, rows, wk, wv, pos, n_keys, scale, BK)
        else:
            _obs.metrics.counter('latent.prefill_composed').inc()
            q = jnp.concatenate([q_nope, q_r], axis=-1).astype(dt)
            out = _block_loop(q, rows, wk, wv, pos, n_keys, scale, BK,
                              kr, rope)
        att = out.transpose(1, 0, 2)
        return dot(att.reshape(C, H * v), w[p + 'att_o_w']), pool


def step(w, cfg, cache, kernels, lay, h, st, at):
    """Every slot, one decode step of a layer (mixer.py): h [S, D]
    normalised, ``at.pos`` [S] the write positions, ``at.pg`` / ``at.rw``
    [S] their page (0 for a slot that rides along) and in-page row,
    ``at.n_attend`` [S] positions each slot attends (0: none).  Writes
    the step's rows and attends in the ABSORBED form: over the pool in
    place where ``kernels.paged`` (`latent_attention`), else on gathered
    rows.  Returns (the attention's output [S, D] float32, the state dict
    with the pool written)."""
    import jax
    import jax.numpy as jnp
    lat, p, layer = cfg['latent'], 'layer_%d_' % lay.index, lay.pool
    pos, bt, n_attend = at.pos, at.bt, at.n_attend
    S = h.shape[0]
    q_nope, q_rope = _queries(w, p, cfg, h)
    pool = st['k'].at[at.pg, layer, at.rw].set(
        _row(w, p, cfg, h, pos, st['k'].dtype))
    with jax.named_scope('attn.latent.scores'):
        q_r = _turned(q_rope, pos[:, None], cfg)              # [S, H, rope]
        wk = w[p + 'att_kvb_k']
        q_lat = jnp.einsum('shn,hnc->shc', q_nope.astype(wk.dtype), wk,
                           preferred_element_type=jnp.float32)
        scale = score_scale(lat)
        if kernels.paged:
            o_lat = latent_attention(q_lat, _padded(q_r, lat), pool, bt,
                                     n_attend, layer, scale)
        else:
            rows = pool[bt, layer].reshape(S, -1, pool.shape[-1])
            o_lat = latent_attention_composed(
                q_lat[:, :, None], _padded(q_r, lat)[:, :, None], rows,
                (n_attend - 1)[:, None], scale)[:, :, 0]
        wv = w[p + 'att_kvb_v']
        o = jnp.einsum('shc,hcv->shv', o_lat.astype(wv.dtype), wv,
                       preferred_element_type=jnp.float32)
        return dot(o.reshape(S, -1), w[p + 'att_o_w']), dict(st, k=pool)


def _prefill_layer(w, cfg, cache, kernels, lay, h, st, at):
    """`prefill` as a layer of a chunk (mixer.py)."""
    out, pool = prefill(w, 'layer_%d_' % lay.index, cfg, h, at.p_abs,
                        at.offset + at.true_count, st['k'], lay.pool, at.pg,
                        at.rw, at.bt_row, kernels.prefill)
    return out, dict(st, k=pool)


def _chunk_counted(n, cache, new_len, true_count):
    """The blocks of cached rows the chunk visited, in every layer that
    attends."""
    import jax.numpy as jnp
    rows = n * prefill_rows(new_len, cache.max_len)
    return [rows.astype(jnp.int32).reshape(1)]


def _step_counted(n, cache, kernels, at):
    """Rows a layer reads: in place, the whole pages a live slot's
    positions cover (`paged_attention_rows`); gathered, every slot's
    ``max_len``."""
    import jax.numpy as jnp
    PL = cache.page_len
    rows = jnp.sum(-(-at.n_attend // PL) * PL) if kernels.paged \
        else at.bt.shape[0] * cache.max_len
    return [jnp.asarray(n * rows, jnp.int32).reshape(1)]


def _kernels(cfg, cache, chunk, mesh):
    return {'paged': latent_attention_eligible(
                cache.pool_shape, cache.store_dtype, cache.latent, mesh),
            'prefill': prefill_kernel(cfg, cache, chunk, mesh)}


MIXER = Mixer(
    weight_shapes=lambda cfg: weight_shapes(
        int(cfg['d_model']), int(cfg['n_head']), cfg['latent']),
    # the second pool geometry: one row a token a layer
    pool=lambda cfg, wide: dict(
        kv_heads=1, head_dim=stored_width(cfg['latent']),
        latent=int(cfg['latent']['kv_rank'])),
    prepared=lambda cfg: prepared(cfg['latent']),
    dims=lambda cfg: dict(
        n_head=int(cfg['n_head']), nope=int(cfg['latent']['nope']),
        rope=int(cfg['latent']['rope']), v=int(cfg['latent']['v'])),
    prepare=prepare, public=public,
    public_rows=lambda cfg, k, v: (public_rows(k, cfg['latent']), None),
    kernels=_kernels,
    stats=lambda cfg: {'latent_rows_read': 1},
    counted=(_chunk_counted, _step_counted),
    wide=(_prefill_layer, step))
