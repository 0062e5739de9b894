"""Fused attention kernels (pallas) + the `flash_attention` op.

TPU-native replacement for the reference's unfused softmax(QK^T)V op chain
(there is no fused attention in the reference — this is where we beat it).
Online-softmax flash attention: one pass over K/V blocks with running
max/sum, O(T) memory instead of the T×T score matrix.  Padding is handled
with a per-row valid-K-length vector (pad is always a suffix in the padded
batch layout), causal masking with block-level position comparison.

Shapes the kernels cannot tile, and short contexts where the composed
einsum path measured faster, take the composed jnp implementation by a
static shape rule; an eligible shape runs the kernel or raises.  On the
CPU backend (tests) the kernels run in Pallas interpret mode.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..core.registry import register
from . import _pallas
from .math import mul

_NEG_INF = -1e30
_LSE_LANES = 8   # trailing broadcast dim that makes (1, bq) rows tileable


def _ref_attention(q, k, v, causal, scale, k_len=None):
    """q: [B, H, Tq, D]; k/v: [B, Hkv, Tk, D] with H % Hkv == 0 (GQA —
    each kv head serves H/Hkv query heads without materializing copies).

    Matches the pallas kernel's precision contract under AMP: the
    einsums run in the input dtype on the MXU but accumulate/emit f32
    (preferred_element_type), so masking and softmax statistics are
    always f32 even for bf16 activations; the output returns in the
    input dtype.  For f32 inputs every step is the plain f32 path."""
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, Tq, D)
    scores = jnp.einsum('bhgqd,bhkd->bhgqk', qg, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = np.tril(np.ones((Tq, Tk), np.bool_), k=Tk - Tq)
        scores = jnp.where(mask[None, None, None], scores, _NEG_INF)
    if k_len is not None:
        kmask = jnp.arange(Tk)[None, :] < k_len[:, None]   # [B, Tk]
        scores = jnp.where(kmask[:, None, None, None, :], scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)                    # f32
    out = jnp.einsum('bhgqk,bhkd->bhgqd', w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, Tq, D).astype(q.dtype)


def _flash_kernel(klen_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k,
                  causal, scale, q_block, seq_len, causal_offset=0):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # [block_q, d]
    block_q = q.shape[0]
    d = q.shape[-1]
    klen = klen_ref[b]                                  # SMEM scalar prefetch
    m = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, d), jnp.float32)
    # skip K blocks that are entirely invalid: past the padded length, and
    # (causal) past the last query row of this block
    num_k = jax.lax.div(klen + block_k - 1, block_k)
    if causal:
        q_end = causal_offset + (qi + 1) * q_block
        num_k = jnp.minimum(num_k,
                            jax.lax.div(q_end + block_k - 1, block_k))
    num_k = jnp.minimum(num_k, seq_len // block_k)

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(ki * block_k, block_k)].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block_k, block_k)].astype(jnp.float32)
        s = q @ k.T                                      # [bq, bk]
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < klen
        if causal:
            # end-aligned (matches _ref_attention's tril(k=Tk-Tq)): the last
            # query sees all keys when Tq < Tk (cached decode)
            q_pos = causal_offset + qi * q_block + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid = valid & (q_pos >= k_pos)
        s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_k, body, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    # logsumexp of the (masked, scaled) score rows — the softmax statistic
    # the backward kernels need to rebuild P = exp(S - LSE) blockwise.
    # Stored broadcast along an 8-lane trailing dim: TPU refuses (1, bq)
    # blocks (sublane 1), and 8 lanes is the cheapest legal layout.
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), _NEG_INF)
    lse_ref[0] = jnp.broadcast_to(lse[:, None], (block_q, _LSE_LANES))


def _masked_p_ds(q, do, k, v, lse, delta, k_base, q_base, klen, causal):
    """Rebuild the softmax block P = exp(S - LSE) under padding/causal
    masking, plus dS = P * (dO V^T - delta) — the math shared by all
    three backward kernels.  exp(-inf - -inf) is NaN for fully-masked
    rows, hence the explicit where."""
    block_q, block_k = q.shape[0], k.shape[0]
    s = q @ k.T                                          # [bq, bk]
    k_pos = k_base + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = k_pos < klen
    if causal:
        q_pos = q_base + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        valid = valid & (q_pos >= k_pos)
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    ds = p * (do @ v.T - delta)
    return p, ds


def _flash_dq_kernel(klen_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, dq_ref, *, block_k, causal, scale, q_block,
                     seq_len, causal_offset=0):
    """dQ = scale * sum_k [P * (dO V^T - delta)] K, one q block per step."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # [bq, d]
    do = do_ref[0].astype(jnp.float32)                  # [bq, d]
    lse = lse_ref[0][:, :1]                             # [bq, 1]
    delta = delta_ref[0][:, :1]                         # [bq, 1]
    block_q, d = q.shape
    klen = klen_ref[b]
    num_k = jax.lax.div(klen + block_k - 1, block_k)
    if causal:
        q_end = causal_offset + (qi + 1) * q_block
        num_k = jnp.minimum(num_k,
                            jax.lax.div(q_end + block_k - 1, block_k))
    num_k = jnp.minimum(num_k, seq_len // block_k)

    def body(ki, dq):
        k = k_ref[0, pl.ds(ki * block_k, block_k)].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block_k, block_k)].astype(jnp.float32)
        _, ds = _masked_p_ds(q, do, k, v, lse, delta, ki * block_k,
                             causal_offset + qi * q_block, klen, causal)
        return dq + ds @ k

    dq = jax.lax.fori_loop(0, num_k, body, jnp.zeros((block_q, d),
                                                     jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


# Up to this many query rows the dK/dV kernel keeps the whole q/do/lse/
# delta rows VMEM-resident and accumulates in registers (faster: no
# output read-modify-write per q block — llama T=4096 measured 36.3k vs
# 30.2k tok/s).  Above it, the full-row block specs overflow the 16 MB
# scoped-vmem limit (hard compile OOM in the T=8192 llama train step),
# so the streamed variant grids over q blocks instead.
_DKV_RESIDENT_MAX_T = 4096


def _flash_dkv_kernel_resident(klen_ref, q_ref, k_ref, v_ref, do_ref,
                               lse_ref, delta_ref, dk_ref, dv_ref, *,
                               block_q, causal, scale, q_len,
                               causal_offset=0):
    """dK/dV for one k block, looping over VMEM-resident q blocks; the
    GQA group axis is the innermost grid dim, accumulating into the
    kv-head-resident output block (init at gi==0, add after)."""
    from jax.experimental import pallas as pl

    bkv = pl.program_id(0)
    ki = pl.program_id(1)
    gi = pl.program_id(2)
    k = k_ref[0].astype(jnp.float32)                    # [bk, d]
    v = v_ref[0].astype(jnp.float32)
    block_k, d = k.shape
    klen = klen_ref[bkv]
    num_q = q_len // block_q
    if causal:
        # first q block whose last row can see this k block's first key
        q_start = jnp.maximum(
            0, jax.lax.div(ki * block_k - causal_offset, block_q))
    else:
        q_start = 0

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * block_q, block_q)].astype(
            jnp.float32) * scale                        # [bq, d]
        do = do_ref[0, pl.ds(qi * block_q, block_q)].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qi * block_q, block_q)][:, :1]   # [bq, 1]
        delta = delta_ref[0, pl.ds(qi * block_q, block_q)][:, :1]
        p, ds = _masked_p_ds(q, do, k, v, lse, delta, ki * block_k,
                             causal_offset + qi * block_q, klen, causal)
        dv = dv + p.T @ do
        dk = dk + ds.T @ q                               # q pre-scaled
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        q_start, num_q, body,
        (jnp.zeros((block_k, d), jnp.float32),
         jnp.zeros((block_k, d), jnp.float32)))

    @pl.when(gi == 0)
    def _init():
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(gi > 0)
    def _accum():
        dk_ref[0] += dk.astype(dk_ref.dtype)
        dv_ref[0] += dv.astype(dv_ref.dtype)


def _flash_dkv_kernel(klen_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dk_ref, dv_ref, *, block_q, causal, scale,
                      causal_offset=0):
    """dK/dV for one k block.  The GQA group axis AND the q-block axis are
    the two innermost (sequential) grid dims, accumulating into the
    kv-head-resident output block — q/do/lse/delta stream through VMEM in
    (1, block_q, d) tiles, so VMEM stays O(block) at any sequence length
    (a full-Tq block spec overflowed the 16 MB scoped-vmem limit at
    T=8192, measured on TPU v5 lite)."""
    from jax.experimental import pallas as pl

    bkv = pl.program_id(0)
    ki = pl.program_id(1)
    gi = pl.program_id(2)
    qi = pl.program_id(3)
    k = k_ref[0].astype(jnp.float32)                    # [bk, d]
    v = v_ref[0].astype(jnp.float32)
    block_k, d = k.shape
    klen = klen_ref[bkv]

    @pl.when((gi == 0) & (qi == 0))
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    # whole-block skip: k block entirely past the valid length, or
    # (causal) entirely above this q block's last row
    needed = ki * block_k < klen
    if causal:
        needed &= causal_offset + (qi + 1) * block_q - 1 >= ki * block_k

    @pl.when(needed)
    def _accum():
        q = q_ref[0].astype(jnp.float32) * scale        # [bq, d]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]                         # [bq, 1]
        delta = delta_ref[0][:, :1]
        p, ds = _masked_p_ds(q, do, k, v, lse, delta, ki * block_k,
                             causal_offset + qi * block_q, klen, causal)
        dv_ref[0] += (p.T @ do).astype(dv_ref.dtype)
        dk_ref[0] += (ds.T @ q).astype(dk_ref.dtype)     # q pre-scaled


# Above this many bytes of would-be score matrix (B*H*Tq*Tk*2, bf16), the
# backward runs the blockwise pallas kernels; below it, the composed
# einsum backward.  Measured END-TO-END (fwd+grad, causal, B=2 H=8 D=64
# bf16, TPU v5 lite): composed 5.7 ms vs pallas 9.0 ms at T=2048
# (134 MB scores), pallas 16.1 vs 18.7 at T=4096 (537 MB), pallas 44 ms
# vs composed 486 ms at T=8192 (2.1 GB — XLA starts thrashing HBM long
# before the hard capacity wall).  Crossover ~T=4096, so the gate sits
# at 256 MiB of bf16 scores; the pallas kernels own the long-context
# regime, XLA's fused batched matmuls own the short one.
_BWD_PALLAS_SCORE_BYTES = 256 << 20

# Below this key length the FORWARD also routes to the composed einsum
# path, with the crossover put at T=512.  The numbers that set it
# (transformer-base training at B*T = 8k tokens: composed 211.8k tok/s
# at T=256 against 182.1k through the pallas forward; 146.2k flash
# against 145.6k composed at T=512) are from an installation that no
# longer exists (PERF.md section 6: "PRs 1-20: not verified"); no cell
# has re-read them.  `flash_attention` is fused-attention SEMANTICS;
# the op picks the fastest lowering per shape.
_FWD_PALLAS_MIN_T = 512

# Above this many bytes of f32 scores (B*H*Tq*Tk*4) the composed path
# runs over tiles of the batch (_composed_attention): XLA:TPU keeps the
# score matrices of a tile in on-chip memory across the fusions of the
# block, and writes a whole batch's to HBM and reads them back.  Set on
# the v5e in tbase.train_1chip (96 x 8 heads x 256 x 256: 2 MiB a
# sequence, 192 MiB a block; PERF.md section 6, my chip runs, PR 31),
# train_rate in items/s/chip by tile: whole batch 191,451; 2 sequences
# 224,915; 4: 229,080; 8: 226,675; 12: 231,195; 16: 230,618; 24:
# 229,417; 32: 225,713.  Every tile is 17 to 21 % ahead of the whole
# batch and the best are 24 to 48 MiB, so the constant sits inside:
# 32 MiB, 16 sequences there.  (Compiled ALONE the block keeps tiles of
# 48 MiB resident and not of 96; inside the step the on-chip memory is
# shared.)
_COMPOSED_TILE_BYTES = 32 << 20


def _composed_tile(B, H, Tq, Tk):
    """Sequences a tile of the composed path holds: the largest divisor
    of B whose f32 scores fit _COMPOSED_TILE_BYTES.  B itself (no loop)
    when the whole batch fits, and when the best divisor's scores are
    under a quarter of that budget (a quarter measured within 1 % of
    the best tile, an eighth 2.5 % behind it, less is unmeasured)."""
    per_seq = H * Tq * Tk * 4
    fit = _COMPOSED_TILE_BYTES // per_seq
    if fit >= B:
        return B
    c = max((d for d in range(1, fit + 1) if B % d == 0), default=0)
    return c if 4 * c * per_seq >= _COMPOSED_TILE_BYTES else B


def _composed_plan(B, H, Tq, Tk, mesh):
    """(sequences a device holds, sequences a tile holds) on the composed
    route; equal where there is no loop."""
    shards = 1 if mesh is None else mesh.size
    local = B // shards
    if shards == 1 or (
            mesh.shape.get('data') == shards and B % shards == 0):
        return local, _composed_tile(local, H, Tq, Tk)
    return local, local


def _takes_pallas(Tq, Tk, D, block_q, block_k, mesh):
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    return not (Tq % bq or Tk % bk or D % 8 or Tk < _FWD_PALLAS_MIN_T
                or not _pallas.single_device(mesh))


def takes_tile_loop(B, H, Tq, Tk, D, mesh=None, block_q=128, block_k=128):
    """Whether `flash_attention` at these extents lowers to the loop over
    tiles of the batch: the ONE rule the op, and the projections beside
    it that the rewriter marked (core/passes/attn_layout.py), decide
    their lowering by."""
    if _takes_pallas(Tq, Tk, D, block_q, block_k, mesh):
        return False
    local, c = _composed_plan(B, H, Tq, Tk, mesh)
    return c != local


def _unfilled(like):
    """A result buffer of `like`'s shape and dtype that nothing fills
    before the tile loop writes it: `AllocateBuffer` on the TPU, an
    allocation and no pass, where `jnp.zeros` (and the stacked output
    of a `scan`) is a pass over HBM the loop then overwrites.  The value
    is whatever the memory held: every caller writes each index of the
    leading axis before anyone reads it.  (An allocation has no operand,
    so XLA may schedule it long before its loop: docs/kernels.md.)"""
    from ..observability import metrics
    metrics.counter('attention.tile_buffers_unfilled').inc()
    return jax.lax.empty(like.shape, like.dtype)


def _tile_loop(causal, scale):
    """`_ref_attention` over operands `[tiles, c, ...]`, one tile of `c`
    sequences an iteration, with the loops of both directions written by
    hand: the forward keeps q, k, v and the lengths (no score matrix, no
    softmax), the backward recomputes tile `i` and pulls its cotangent
    back through it.  Each loop carries its results and writes tile `i`
    in place; iteration `i` of `range(tiles)` writes index `i` of every
    carried buffer and no other, so all of a buffer is written exactly
    once before the loop hands it on, and it may start uninitialised."""

    def tile(q, k, v, k_len):
        with jax.named_scope('attn.tile'):
            return _ref_attention(q, k, v, causal, scale, k_len)

    def at(i, *xs):
        return (jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False)
                for x in xs)

    def put(buf, i, x):
        # not `.at[i].set`: a scatter drops an index out of range, which
        # XLA lowers to a select that READS the buffer
        return jax.lax.dynamic_update_index_in_dim(buf, x, i, 0)

    def forward(q, k, v, k_len):
        def body(i, out):
            return put(out, i, tile(*at(i, q, k, v, k_len)))
        return jax.lax.fori_loop(0, q.shape[0], body, _unfilled(q))

    def fwd(q, k, v, k_len):
        return forward(q, k, v, k_len), (q, k, v, k_len)

    def bwd(res, g):
        q, k, v, k_len = res

        def body(i, grads):
            *qkv, kl, gi = at(i, q, k, v, k_len, g)
            _, pullback = jax.vjp(lambda *qkv: tile(*qkv, kl), *qkv)
            return tuple(put(buf, i, d)
                         for buf, d in zip(grads, pullback(gi)))
        return jax.lax.fori_loop(
            0, q.shape[0], body,
            (_unfilled(q), _unfilled(k), _unfilled(v))) + (None,)

    loop = jax.custom_vjp(forward)
    loop.defvjp(fwd, bwd)
    return loop


def _composed_attention(q, k, v, causal, scale, k_len, mesh=None):
    """`_ref_attention`, run over tiles of the batch where its scores
    would not stay on chip.  Attention is independent per sequence, so a
    tile is the same mathematics; the backward pass keeps q, k, v and
    recomputes a tile's scores (`_tile_loop`) where plain AD would stack
    every tile's softmax into a full-size residual.
    Under a mesh that shards the batch over 'data' (and nothing else)
    each device tiles its LOCAL batch inside a shard_map: no collective,
    and the loop never runs over a sharded dimension.  Any other mesh
    of several devices keeps the whole-batch path."""
    from ..observability import metrics
    B, H, Tq, _ = q.shape
    Tk = k.shape[2]
    shards = 1 if mesh is None else mesh.size
    local, c = _composed_plan(B, H, Tq, Tk, mesh)
    if c == local:
        metrics.counter('attention.composed_whole').inc()
        return _ref_attention(q, k, v, causal, scale, k_len)
    metrics.counter('attention.composed_tiled').inc()
    loop = _tile_loop(causal, scale)

    def tiled(*qkvl):
        out = loop(*(x.reshape((local // c, c) + x.shape[1:])
                     for x in qkvl))
        return out.reshape(qkvl[0].shape)

    if shards > 1:
        from jax.sharding import PartitionSpec as P
        from ..parallel.mesh import shard_map
        # every axis named (the others have one device): an axis left
        # out would be summed over in the backward pass
        spec = P(mesh.axis_names)
        tiled = shard_map(tiled, mesh=mesh, in_specs=spec, out_specs=spec)
    return tiled(q, k, v, k_len)


def flash_attention(q, k, v, causal=False, scale=None, k_len=None,
                    block_q=128, block_k=128, mesh=None):
    """q: [B, H, T, D]; k/v: [B, Hkv, T, D] (Hkv may divide H — GQA/MQA,
    served without repeating K/V); k_len: optional int32 [B] valid lengths.

    Differentiable end to end in pallas: the forward kernel saves the
    per-row logsumexp, and the VJP runs two flash backward kernels (dQ over
    q blocks; dK/dV over k blocks with GQA group accumulation) — O(T)
    memory in both directions, no T×T score matrix ever materializes.
    For sequence lengths whose score matrix comfortably fits in HBM the
    VJP instead uses the composed einsum gradient, which is faster there
    (see _BWD_PALLAS_SCORE_BYTES)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    if k_len is None:
        k_len = jnp.full((q.shape[0],), Tk, jnp.int32)
    k_len = k_len.astype(jnp.int32)
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    if not _takes_pallas(Tq, Tk, D, block_q, block_k, mesh):
        # shapes the kernel can't tile, short-context sizes where the
        # composed path measures faster, or a launch over several
        # devices — composed (jax AD backward), tiled over the batch
        # where the scores would not stay on chip
        return _composed_attention(q, k, v, causal, scale, k_len, mesh)
    pallas_bwd = B * H * Tq * Tk * 2 > _BWD_PALLAS_SCORE_BYTES

    @jax.custom_vjp
    def _attn(q, k, v, kl):
        out, _ = _flash_forward(q, k, v, kl, causal, scale, bq, bk)
        return out

    def _fwd(q, k, v, kl):
        out, lse = _flash_forward(q, k, v, kl, causal, scale, bq, bk)
        return out, (q, k, v, kl, out, lse)

    def _bwd(res, g):
        q, k, v, kl, out, lse = res
        if pallas_bwd:
            return _flash_backward(q, k, v, kl, out, lse, g, causal,
                                   scale, bq, bk) + (None,)
        _, pullback = jax.vjp(
            lambda q, k, v: _ref_attention(q, k, v, causal, scale, kl),
            q, k, v)
        dq, dk, dv = pullback(g)
        return dq, dk, dv, None

    _attn.defvjp(_fwd, _bwd)
    return _attn(q, k, v, k_len)


def _kv_row_map(H, Hkv, g):
    def kv_row(b, i, kl):
        # GQA: query row b = bi*H + h reads kv row bi*Hkv + h//g, so
        # K/V stay at Hkv width in HBM — no materialized head copies
        return (b // H) * Hkv + (b % H) // g, 0, 0
    return kv_row


def _flash_forward(q, k, v, k_len, causal, scale, block_q, block_k):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    g = H // Hkv
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * Hkv, Tk, D)
    vr = v.reshape(B * Hkv, Tk, D)
    klr = jnp.repeat(k_len, H)                           # [B*H]
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, causal=causal, scale=scale,
        q_block=block_q, seq_len=Tk, causal_offset=Tk - Tq)
    kv_row = _kv_row_map(H, Hkv, g)

    # k-lengths ride SMEM scalar prefetch (a (1,1) VMEM block would
    # violate the TPU (8,128) tiling minimum and refuse to lower)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, Tq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, kl: (b, i, 0)),
            pl.BlockSpec((1, Tk, D), kv_row),
            pl.BlockSpec((1, Tk, D), kv_row),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, kl: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LSE_LANES),
                         lambda b, i, kl: (b, i, 0)),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
                   jax.ShapeDtypeStruct((B * H, Tq, _LSE_LANES),
                                        jnp.float32)],
        interpret=_pallas.interpret(),
    )(klr, qr, kr, vr)
    return out.reshape(B, H, Tq, D), lse


def _flash_backward(q, k, v, k_len, out, lse, g_out, causal, scale,
                    block_q, block_k):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    g = H // Hkv
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * Hkv, Tk, D)
    vr = v.reshape(B * Hkv, Tk, D)
    dor = g_out.reshape(B * H, Tq, D)
    # delta_i = <dO_i, O_i> — the softmax-jacobian rank-1 correction term;
    # a fused elementwise reduce, no kernel needed.  Broadcast to the same
    # 8-lane layout the kernels read lse in.
    delta = jnp.sum(dor.astype(jnp.float32) *
                    out.reshape(B * H, Tq, D).astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (B * H, Tq, _LSE_LANES))
    kv_row = _kv_row_map(H, Hkv, g)
    causal_offset = Tk - Tq

    dq_kernel = functools.partial(
        _flash_dq_kernel, block_k=block_k, causal=causal, scale=scale,
        q_block=block_q, seq_len=Tk, causal_offset=causal_offset)
    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, Tq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, kl: (b, i, 0)),
            pl.BlockSpec((1, Tk, D), kv_row),
            pl.BlockSpec((1, Tk, D), kv_row),
            pl.BlockSpec((1, block_q, D), lambda b, i, kl: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LSE_LANES),
                         lambda b, i, kl: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LSE_LANES),
                         lambda b, i, kl: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, kl: (b, i, 0)),
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        interpret=_pallas.interpret(),
    )(jnp.repeat(k_len, H), qr, kr, vr, dor, lse, delta)

    # dK/dV: grid over kv rows × k blocks with the GQA group innermost.
    # Short Tq: whole q rows stay VMEM-resident, register accumulation
    # (faster).  Long Tq: q blocks join the grid as a 4th sequential dim
    # and stream through VMEM in (1, block_q, D) tiles (O(block) VMEM at
    # any Tq).  See _DKV_RESIDENT_MAX_T.
    if Tq <= _DKV_RESIDENT_MAX_T:
        def q_row(b, ki, gi, kl):
            return b // Hkv * H + (b % Hkv) * g + gi, 0, 0

        dkv_kernel = functools.partial(
            _flash_dkv_kernel_resident, block_q=block_q, causal=causal,
            scale=scale, q_len=Tq, causal_offset=causal_offset)
        dkv_grid = (B * Hkv, Tk // block_k, g)
        dkv_in_specs = [
            pl.BlockSpec((1, Tq, D), q_row),
            pl.BlockSpec((1, block_k, D), lambda b, ki, gi, kl: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, ki, gi, kl: (b, ki, 0)),
            pl.BlockSpec((1, Tq, D), q_row),
            pl.BlockSpec((1, Tq, _LSE_LANES), q_row),
            pl.BlockSpec((1, Tq, _LSE_LANES), q_row),
        ]
        dkv_out_specs = [
            pl.BlockSpec((1, block_k, D), lambda b, ki, gi, kl: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, ki, gi, kl: (b, ki, 0)),
        ]
    else:
        def q_blk(b, ki, gi, qi, kl):
            return b // Hkv * H + (b % Hkv) * g + gi, qi, 0

        dkv_kernel = functools.partial(
            _flash_dkv_kernel, block_q=block_q, causal=causal, scale=scale,
            causal_offset=causal_offset)
        dkv_grid = (B * Hkv, Tk // block_k, g, Tq // block_q)
        dkv_in_specs = [
            pl.BlockSpec((1, block_q, D), q_blk),
            pl.BlockSpec((1, block_k, D),
                         lambda b, ki, gi, qi, kl: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, ki, gi, qi, kl: (b, ki, 0)),
            pl.BlockSpec((1, block_q, D), q_blk),
            pl.BlockSpec((1, block_q, _LSE_LANES), q_blk),
            pl.BlockSpec((1, block_q, _LSE_LANES), q_blk),
        ]
        dkv_out_specs = [
            pl.BlockSpec((1, block_k, D),
                         lambda b, ki, gi, qi, kl: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, ki, gi, qi, kl: (b, ki, 0)),
        ]
    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=dkv_grid,
        in_specs=dkv_in_specs,
        out_specs=dkv_out_specs,
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid_spec=dkv_spec,
        out_shape=[jax.ShapeDtypeStruct((B * Hkv, Tk, D), jnp.float32),
                   jax.ShapeDtypeStruct((B * Hkv, Tk, D), jnp.float32)],
        interpret=_pallas.interpret(),
    )(jnp.repeat(k_len, Hkv), qr, kr, vr, dor, lse, delta)
    return (dq.reshape(B, H, Tq, D),
            dk.reshape(B, Hkv, Tk, D).astype(k.dtype),
            dv.reshape(B, Hkv, Tk, D).astype(v.dtype))




@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _column_slices(w, pieces):
    """`w [M, pieces * N]` as `pieces` arrays `[M, N]`.  The rule below
    joins their cotangents in ONE concatenation; plain AD pads each to
    the weight's extent and adds them, three passes over a fused q/k/v
    weight's gradient where there was none."""
    n = w.shape[1] // pieces
    return tuple(w[:, i * n:(i + 1) * n] for i in range(pieces))


def _column_slices_fwd(w, pieces):
    return _column_slices(w, pieces), None


def _column_slices_bwd(pieces, _, cts):
    return (jnp.concatenate(cts, axis=1),)


_column_slices.defvjp(_column_slices_fwd, _column_slices_bwd)


def heads_in_loop_layout(x, w, heads):
    """`x [B, T, M]` times `w [M, heads * D]`, as heads `[B, heads, T,
    D]`, the way the tile loop reads them: XLA keeps T on the lanes there
    (a 64-wide head would fill half a lane tile), and the `while` lets no
    neighbour fuse a relayout, so the dot itself is written `[B, heads *
    D, T]` and the head split and the last two axes' swap are bitcasts.
    Same contraction as `mul` followed by the program's reshape and
    transpose."""
    y = jnp.einsum('btm,mn->bnt', x, w)
    B, N, T = y.shape
    return jnp.swapaxes(y.reshape(B, heads, N // heads, T), 2, 3)


def _merged(o):
    B, H, T, D = o.shape
    return jnp.swapaxes(o, 2, 3).reshape(B, H * D, T)


@jax.custom_vjp
def project_from_loop_layout(o, w):
    """The output projection `[B, T, H*D] x [H*D, M]` read from the tile
    loop's own result `o [B, H, T, D]`, which lies `[B, H*D, T]` in
    memory.  Plain AD would write this product's cotangent `[B, T, H*D]`
    (the cotangent's free axes first), a copy away from the backward
    loop; the rule below puts the weight first, so the dot writes
    `[H*D, B, T]` with T on the lanes, as `heads_in_loop_layout` does."""
    return jnp.einsum('bnt,nm->btm', _merged(o), w)


def _project_fwd(o, w):
    return project_from_loop_layout(o, w), (o, w)


def _project_bwd(res, g):
    o, w = res
    B, H, T, D = o.shape
    do = jax.lax.dot_general(w.astype(g.dtype), g,
                             (((1,), (2,)), ((), ())))        # [N, B, T]
    do = jnp.swapaxes(do.transpose(1, 0, 2).reshape(B, H, D, T), 2, 3)
    dw = jnp.einsum('bnt,btm->nm', _merged(o), g)
    return do.astype(o.dtype), dw.astype(w.dtype)


project_from_loop_layout.defvjp(_project_fwd, _project_bwd)


def _op_takes_tile_loop(ctx, out, k):
    """`takes_tile_loop` for an attention op whose result is `out [B, H,
    Tq, D]` over keys `k`, as the executor lowers it: asked by the op and
    by the projection behind it, so both take one route."""
    B, H, Tq, D = out.shape
    return takes_tile_loop(B, H, Tq, k.shape[2], D,
                           getattr(ctx, 'mesh', None))


@register('flash_attention')
def flash_attention_op(ctx, ins, attrs):
    """`ProjX`, `ProjW` and attr `proj` (core/passes/attn_layout.py) name
    the projections Q, K and V come from; the tiled route computes them
    itself, in the loop's layout, and leaves `Q`, `K`, `V` unread."""
    q, k, v = ins['Q'], ins['K'], ins['V']
    k_len = ins.get('KLength')
    if k_len is not None and k_len.ndim > 1:
        k_len = k_len.reshape(-1)
    proj = attrs.get('proj')
    if proj and _op_takes_tile_loop(ctx, q, k):
        from ..observability import metrics
        metrics.counter('attention.operands_in_loop_layout').inc()
        # a fused weight is sliced (free), not its product's activations
        # (a pass through HBM)
        sliced, operands = {}, []
        for t, i in zip((q, k, v), (0, 4, 8)):
            xi, wi, piece, pieces = proj[i:i + 4]
            if xi < 0:
                operands.append(t)
                continue
            if wi not in sliced:
                sliced[wi] = _column_slices(ins['ProjW'][wi], pieces)
            operands.append(heads_in_loop_layout(
                ins['ProjX'][xi], sliced[wi][piece], t.shape[1]))
        q, k, v = operands
    return {'Out': flash_attention(
        q, k, v, causal=attrs.get('causal', False),
        scale=attrs.get('scale', None), k_len=k_len,
        mesh=getattr(ctx, 'mesh', None))}


@register('attn_out_proj')
def attn_out_proj_op(ctx, ins, attrs):
    """The `mul` behind a `flash_attention` (core/passes/attn_layout.py
    retypes it): `X [B, T, H*D]` is the program's transpose and reshape
    of `AttnOut [B, H, T, D]`, the attention's result over keys `AttnK`.
    Where that attention ran its tile loop the product reads `AttnOut` in
    the loop's layout and leaves `X` unread; elsewhere it is `mul`."""
    if _op_takes_tile_loop(ctx, ins['AttnOut'], ins['AttnK']):
        return {'Out': project_from_loop_layout(ins['AttnOut'], ins['Y'])}
    return mul(ctx, {'X': ins['X'], 'Y': ins['Y']}, attrs)


@register('ring_attention')
def ring_attention_op(ctx, ins, attrs):
    """Sequence-parallel exact attention (long-context path).

    When the executor runs with a mesh whose 'seq' axis is >1, the op runs
    the ppermute ring from parallel/ring_attention.py — each device holds
    T/n_seq of K/V, so context length scales with the ring size.  On a
    single chip (or no seq axis) it lowers to flash attention: the SAME
    program serves both, chosen at lowering time from ctx.mesh."""
    q, k, v = ins['Q'], ins['K'], ins['V']
    causal = attrs.get('causal', False)
    scale = attrs.get('scale', None)
    mesh = getattr(ctx, 'mesh', None)
    axis = attrs.get('axis_name', 'seq')
    if mesh is not None and axis in mesh.axis_names and \
            mesh.shape[axis] > 1 and q.shape[2] % mesh.shape[axis] == 0:
        from ..parallel.ring_attention import ring_attention
        return {'Out': ring_attention(q, k, v, mesh, axis_name=axis,
                                      causal=causal, scale=scale)}
    return {'Out': flash_attention(q, k, v, causal=causal, scale=scale,
                                   mesh=mesh)}


# --------------------------------------------------- KV-cache read path

def cached_attention(q, kcache, vcache, qpos, scale=None):
    """Attention of new-position queries against a KV cache row.

    q: [B, H, Tq, D] — the Tq new positions (a prefill chunk, or Tq=1
    for one decode step); kcache/vcache: [B, Hkv, Tmax, D] with the new
    positions' K/V already written; qpos: [B, Tq] int32 ABSOLUTE
    positions of the queries.  Masking is positional — key position
    kpos is visible iff ``kpos <= qpos`` — so mid-prompt chunk offsets
    and per-slot decode lengths share one rule, and garbage beyond a
    row's true length is never attended (unlike `_ref_attention`'s
    end-aligned causal mask, which assumes the query block sits at the
    END of the key range).  GQA-native and f32-accumulating, matching
    the `_ref_attention` precision contract.
    """
    B, H, Tq, D = q.shape
    Hkv, Tmax = kcache.shape[1], kcache.shape[2]
    if scale is None:
        scale = D ** -0.5
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, Tq, D)
    s = jnp.einsum('bhgqd,bhkd->bhgqk', qg, kcache,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(Tmax)
    mask = kpos[None, None, :] <= qpos[:, :, None]        # [B, Tq, Tmax]
    s = jnp.where(mask[:, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum('bhgqk,bhkd->bhgqd', p.astype(vcache.dtype), vcache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, Tq, D).astype(q.dtype)


# ------------------------------------------- paged decode-step attention

_PAGED_BLOCK_TOKENS = 128   # key positions one double-buffered block holds


def paged_pool_heads(kv_heads, head_dim):
    """(kv heads, head width) of the POOL that holds ``kv_heads`` heads of
    ``head_dim``: themselves for a head of whole lane tiles.  A narrower
    head that divides 128 (64: two, 32: four) lies as many kv heads side
    by side as fill a 128-lane row, where the kv heads come in such
    groups: ``(kv_heads / pack, pack * head_dim)``, the same bytes in the
    same order, so that a page of one layer is whole tiles
    (`paged_attention_eligible`) and no lane of a row is padding.  Any
    other head (96, say) has no such layout and keeps its own."""
    pack = 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 1
    if kv_heads % pack:
        pack = 1
    return kv_heads // pack, head_dim * pack


def paged_attention_eligible(pool_shape, dtype, mesh=None):
    """Static rule for `paged_attention` over a ``[pages, layers, page_len,
    kv_heads, head_dim]`` pool: a floating pool (an int8 pool dequantizes
    in the composed gather) on a single device; on an accelerator one
    page of one layer, ``[page_len * kv_heads, head_dim]``, must be whole
    tiles of the pool's dtype where it lies.  A head of 64 meets the rule
    as the pool `paged_pool_heads` lays out, two kv heads side by side
    in a 128-lane row (``[.., kv_heads / 2, 128]``); a head with no such
    layout (96) answers False and the step takes the composed path."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) \
            or not _pallas.single_device(mesh):
        return False
    if _pallas.interpret():
        return True
    _pages, _layers, page_len, hkv, dh = pool_shape
    sublanes = 8 * (4 // dtype.itemsize)
    return dh % 128 == 0 and (hkv * page_len) % sublanes == 0


def paged_attention_rows(n_attend, page_len):
    """Key positions `paged_attention` fetches for slots attending
    ``n_attend`` positions each (numpy, host side): whole pages up to
    the one holding the last position, nothing for a slot that attends
    nothing.  `generation.kv_rows_read` counts with it."""
    n = np.asarray(n_attend, np.int64)
    return int((-(-n // int(page_len)) * int(page_len)).sum())


def _paged_kernel(bt_ref, n_ref, layer_ref, q_ref, koff_ref, hbias_ref,
                  k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, *, pages_per_block,
                  page_rows, page_len, max_pages, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    n = n_ref[s]                          # positions this slot attends
    layer = layer_ref[0]
    P, PR = pages_per_block, page_rows
    block_tokens = P * page_len
    n_blocks = jax.lax.div(n + block_tokens - 1, block_tokens)
    n_pages = jax.lax.div(n + page_len - 1, page_len)

    def page_copies(blk, buf):
        """(covered, K copy, V copy) per page of block ``blk``: one
        page of one layer is ``[page_len * kv_heads, head_dim]``,
        contiguous in the pool, and lands at its rows of the buffer."""
        out = []
        for p in range(P):
            idx = blk * P + p
            page = bt_ref[s * max_pages + jnp.minimum(idx, max_pages - 1)]
            rows = pl.ds(p * PR, PR)
            out.append((idx < n_pages,
                        pltpu.make_async_copy(k_hbm.at[page, layer],
                                              kbuf.at[buf, rows],
                                              sem.at[0, buf]),
                        pltpu.make_async_copy(v_hbm.at[page, layer],
                                              vbuf.at[buf, rows],
                                              sem.at[1, buf])))
        return out

    def start(blk, buf):
        for covered, ck, cv in page_copies(blk, buf):
            @pl.when(covered)
            def _():
                ck.start()
                cv.start()

    def wait(blk, buf):
        for covered, ck, cv in page_copies(blk, buf):
            @pl.when(covered)
            def _():
                ck.wait()
                cv.wait()

    @pl.when(s == 0)
    def _():
        # rows no copy ever fills meet a probability of exactly 0 in the
        # value product; whatever VMEM held there must not be a NaN
        vbuf[...] = jnp.zeros_like(vbuf)

    @pl.when(n > 0)
    def _():
        start(0, 0)

    H, D = q_ref.shape[1], q_ref.shape[2]
    q = q_ref[0]
    ctype = jnp.promote_types(q.dtype, kbuf.dtype)

    def body(blk, carry):
        m, l, acc = carry
        buf = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            start(blk + 1, 1 - buf)

        wait(blk, buf)
        k, v = kbuf[buf], vbuf[buf]                     # [R, D]
        # every query head against every row of the block; the rows of
        # other kv heads are masked out with the positions past n
        sc = jax.lax.dot_general(
            q.astype(ctype), k.astype(ctype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, R]
        kpos = blk * block_tokens + koff_ref[...]        # [1, R]
        sc = jnp.where(kpos < n, sc + hbias_ref[...], _NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=1, keepdims=True))
        # a block the loop reaches holds a visible position of every
        # head, so m_new is a real score and a masked row's exp is 0
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((H, 1), _NEG_INF, jnp.float32),
         jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, D), jnp.float32)))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_attention(q, kpool, vpool, block_tables, n_attend, layer,
                    scale=None, pages_per_block=None):
    """One decode step's attention over the page pool IN PLACE.

    q: [S, H, D], one query per slot at position ``n_attend - 1``;
    kpool/vpool: [pages, layers, page_len, Hkv, D] with the step's own
    K/V already written; block_tables: [S, max_pages] int32; n_attend:
    [S] int32 positions each slot attends (its length after the write; 0
    for a slot that rides along: it reads nothing and returns zeros);
    layer: int32 scalar.  Returns [S, H, D] in q's dtype.

    The pools stay in HBM and XLA never slices them: per slot the
    kernel copies the pages its length covers, ``pages_per_block`` at a
    time and double-buffered, through the block table (scalar prefetch)
    and runs an online softmax with f32 statistics over them.  Key
    position kpos is visible iff ``kpos < n_attend`` (`cached_attention`'s
    ``kpos <= qpos``); probabilities are cast to the pool's dtype for the
    value product, as there.  No dense ``[S, Hkv, max_len, D]`` exists.

    A pool whose rows are WIDER than the queries' head (`paged_pool_heads`:
    a head of 64 lies two kv heads to a 128-lane row, ``[.., Hkv / pack,
    pack * D]``) runs the SAME kernel over the same bytes: each query sits
    in the lanes of its own kv head beside zeros (so a row's score is that
    head's alone, and the rows of other kv-head groups are masked as other
    kv heads' always were), and of the ``pack * D`` lanes of a query
    head's result its own kv head's are kept.  The kernel reads the pages
    a live slot's length covers and nothing for a dead one, exactly as at
    128.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    if scale is None:
        scale = q.shape[-1] ** -0.5
    pack = kpool.shape[-1] // q.shape[-1]
    if pack > 1:
        S, H, D = q.shape
        # query head h reads kv head h // g of Hkv = pack * (the pool's):
        # lanes [lane * D, lane * D + D) of row group h // g // pack
        lane = np.arange(H) // (H // (kpool.shape[3] * pack)) % pack
        mine = jnp.asarray(lane[:, None] == np.arange(pack)[None])  # [H, pack]
        wide = jnp.where(mine[None, :, :, None], q[:, :, None, :],
                         jnp.zeros((), q.dtype)).reshape(S, H, pack * D)
        out = paged_attention(wide, kpool, vpool, block_tables, n_attend,
                              layer, scale, pages_per_block)
        return jnp.sum(jnp.where(mine[None, :, :, None],
                                 out.reshape(S, H, pack, D),
                                 jnp.zeros((), out.dtype)), axis=2)
    S, H, D = q.shape
    pages, layers, PL, Hkv, _ = kpool.shape
    M = block_tables.shape[1]
    P = pages_per_block or max(1, _PAGED_BLOCK_TOKENS // PL)
    P = min(int(P), M)
    PR = Hkv * PL
    R = P * PR
    # row r of a block is (page r // PR, token (r % PR) // Hkv, kv head
    # r % Hkv): its position within the block, and which query heads it
    # belongs to (GQA: head h reads kv head h // g)
    r = np.arange(R)
    koff = ((r // PR) * PL + (r % PR) // Hkv).astype(np.int32)[None]
    hbias = np.where(np.arange(H)[:, None] // (H // Hkv)
                     == (r % Hkv)[None], 0.0, _NEG_INF).astype(np.float32)
    kernel = functools.partial(
        _paged_kernel, pages_per_block=P, page_rows=PR, page_len=PL,
        max_pages=M, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec((1, R), lambda s, *_: (0, 0)),
            pl.BlockSpec((H, R), lambda s, *_: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, R, D), kpool.dtype),
                        pltpu.VMEM((2, R, D), vpool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))],
    )
    n = jnp.clip(n_attend.astype(jnp.int32), 0, M * PL)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, D), q.dtype),
        name='paged_attention',
        interpret=_pallas.interpret(),
    )(block_tables.reshape(-1).astype(jnp.int32), n,
      jnp.asarray(layer, jnp.int32).reshape(1), q, jnp.asarray(koff),
      jnp.asarray(hbias), kpool.reshape(pages, layers, PR, D),
      vpool.reshape(pages, layers, PR, D))


# -------------------------------- latent decode-step attention, in place

_LATENT_BLOCK_TOKENS = 256  # key positions one double-buffered block holds


def latent_attention_eligible(pool_shape, dtype, v_dim, mesh=None):
    """Static rule for `latent_attention` over a ``[pages, layers,
    page_len, width]`` pool whose rows serve as keys (all ``width``
    columns) and as values (the first ``v_dim``): a floating pool on a
    single device; on an accelerator the value columns and the rest of
    the row must each start on a lane tile, and one page of one layer,
    ``[page_len, width]``, must be whole sublane tiles of the pool's
    dtype."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) \
            or not _pallas.single_device(mesh):
        return False
    if _pallas.interpret():
        return True
    _pages, _layers, page_len, width = pool_shape
    sublanes = 8 * (4 // dtype.itemsize)
    return int(v_dim) % 128 == 0 and width % 128 == 0 \
        and page_len % sublanes == 0


def latent_attention_composed(q_lat, q_rope, rows, qpos, scale):
    """The absorbed form on GATHERED rows: q_lat [B, H, Tq, v_dim] and
    q_rope [B, H, Tq, r] against rows [B, Tk, v_dim + r] that every
    head shares; key position kpos is visible iff ``kpos <= qpos`` (qpos
    [B, Tq]).  Returns [B, H, Tq, v_dim] float32: the probabilities'
    sum over the rows' first ``v_dim`` columns.  What `latent_attention`
    is tested against, and the step's route under a mesh."""
    v_dim = q_lat.shape[-1]
    ckv, kr = rows[..., :v_dim], rows[..., v_dim:]
    s = (jnp.einsum('bhqc,bkc->bhqk', q_lat.astype(rows.dtype), ckv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum('bhqr,bkr->bhqk', q_rope.astype(rows.dtype), kr,
                      preferred_element_type=jnp.float32)) * scale
    mask = jnp.arange(rows.shape[1])[None, None, :] <= qpos[:, :, None]
    s = jnp.where(mask[:, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhqk,bkc->bhqc', p.astype(rows.dtype), ckv,
                      preferred_element_type=jnp.float32)


def _latent_kernel(bt_ref, n_ref, layer_ref, ql_ref, qr_ref, pool_hbm, o_ref,
                   buf, sem, *, pages_per_block, page_len, max_pages, v_dim,
                   scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    n = n_ref[s]                          # positions this slot attends
    layer = layer_ref[0]
    P, PL = pages_per_block, page_len
    block_tokens = P * PL
    n_blocks = jax.lax.div(n + block_tokens - 1, block_tokens)
    n_pages = jax.lax.div(n + PL - 1, PL)

    def page_copies(blk, slot):
        """(covered, copy) per page of block ``blk``: one page of one
        layer is ``[page_len, width]``, contiguous in the pool, and
        lands at its rows of the buffer."""
        out = []
        for p in range(P):
            idx = blk * P + p
            page = bt_ref[s * max_pages + jnp.minimum(idx, max_pages - 1)]
            out.append((idx < n_pages, pltpu.make_async_copy(
                pool_hbm.at[page, layer], buf.at[slot, pl.ds(p * PL, PL)],
                sem.at[slot])))
        return out

    def start(blk, slot):
        for covered, copy in page_copies(blk, slot):
            @pl.when(covered)
            def _():
                copy.start()

    def wait(blk, slot):
        for covered, copy in page_copies(blk, slot):
            @pl.when(covered)
            def _():
                copy.wait()

    @pl.when(s == 0)
    def _():
        # rows no copy ever fills meet a probability of exactly 0 in the
        # value product; whatever VMEM held there must not be a NaN
        buf[...] = jnp.zeros_like(buf)

    @pl.when(n > 0)
    def _():
        start(0, 0)

    H = ql_ref.shape[1]
    ql, qr = ql_ref[0], qr_ref[0]                       # [H, v_dim], [H, r]

    def body(blk, carry):
        m, l, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            start(blk + 1, 1 - slot)

        wait(blk, slot)
        ckv = buf[slot, :, :v_dim]                      # [R, v_dim]
        kr = buf[slot, :, v_dim:]                       # [R, r]
        # every head against the one row a token has: the compressed
        # part and the rotated key are two products over one score
        sc = (jax.lax.dot_general(
            ql, ckv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) + jax.lax.dot_general(
            qr, kr, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)) * scale  # [H, R]
        kpos = blk * block_tokens + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1)
        sc = jnp.where(kpos < n, sc, _NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=1, keepdims=True))
        # a block the loop reaches holds a visible position, so m_new
        # is a real score and a masked row's exp is 0
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(
            p.astype(ckv.dtype), ckv, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, body,
        (jnp.full((H, 1), _NEG_INF, jnp.float32),
         jnp.zeros((H, 1), jnp.float32),
         jnp.zeros((H, v_dim), jnp.float32)))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def latent_attention(q_lat, q_rope, pool, block_tables, n_attend, layer,
                     scale, pages_per_block=None):
    """One decode step's latent attention over the page pool IN PLACE.

    q_lat: [S, H, v_dim], each head's query carried into the compressed
    space (absorbed through the key half of the up-projection); q_rope:
    [S, H, r], its rotated part; pool: [pages, layers, page_len, v_dim +
    r], ONE row a token a layer that every head shares, the step's own
    row already written: a row's ``v_dim + r`` columns are the key, its
    first ``v_dim`` the value; block_tables: [S, max_pages] int32;
    n_attend: [S] int32 positions each slot attends (0 for a slot that
    rides along: it reads nothing and returns zeros); layer: int32
    scalar.  Returns [S, H, v_dim] float32.

    The pool stays in HBM and XLA never slices it: per slot the kernel
    copies the pages its length covers ONCE, ``pages_per_block`` at a
    time and double-buffered, through the block table (scalar
    prefetch), and all H heads read that one copy: scores over the
    whole row, an online softmax with f32 statistics, probabilities
    cast to the pool's dtype for the value product (as
    `latent_attention_composed`).  There is no V pool and no per-head
    key anywhere.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, H, v_dim = q_lat.shape
    pages, layers, PL, width = pool.shape
    M = block_tables.shape[1]
    P = pages_per_block or max(1, _LATENT_BLOCK_TOKENS // PL)
    P = min(int(P), M)
    R = P * PL
    kernel = functools.partial(
        _latent_kernel, pages_per_block=P, page_len=PL, max_pages=M,
        v_dim=v_dim, scale=float(scale))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, H, v_dim), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec((1, H, width - v_dim), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, v_dim), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, R, width), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    n = jnp.clip(n_attend.astype(jnp.int32), 0, M * PL)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, v_dim), jnp.float32),
        name='latent_attention',
        interpret=_pallas.interpret(),
    )(block_tables.reshape(-1).astype(jnp.int32), n,
      jnp.asarray(layer, jnp.int32).reshape(1), q_lat.astype(pool.dtype),
      q_rope.astype(pool.dtype), pool)


# ---------------------- latent prefill-chunk attention, scores on chip

# one block's scores and probabilities in float32 and their copy in the
# rows' dtype are 5 MB at 512 x 1024, beside the double-buffered blocks:
# more than the 16 MiB a Mosaic kernel gets unasked, of a v5e's 128
_LATENT_PREFILL_VMEM = 64 << 20


def latent_prefill_eligible(pool_shape, dtype, chunk, key_block, kv_rank,
                            nope, v_dim, mesh=None):
    """Static rule for `latent_prefill`: a chunk of ``chunk`` queries
    (a head's ``nope`` un-rotated columns, its values ``v_dim`` wide)
    over rows gathered from a ``[pages, layers, page_len, width]`` pool
    whose first ``kv_rank`` columns are the compressed part, ``key_block``
    rows a grid step.  A floating pool on a single device; on an
    accelerator every slice the kernel takes must be whole tiles: the
    compressed part, the rest of a row, a head's un-rotated columns and
    its values each whole lane tiles, the key block too (it is the
    scores' lane axis), and the chunk whole sublane tiles of the pool's
    dtype."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) \
            or not _pallas.single_device(mesh):
        return False
    if _pallas.interpret():
        return True
    width = pool_shape[-1]
    sublanes = 8 * (4 // dtype.itemsize)
    return all(int(n) % 128 == 0
               for n in (kv_rank, width, nope, v_dim, key_block)) \
        and width > kv_rank and int(chunk) % sublanes == 0


def _latent_prefill_kernel(q_ref, rows_ref, wk_ref, wv_ref, pos_ref, o_ref,
                           m_sc, l_sc, acc_sc, *, block_k, kv_rank, nope,
                           scale):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    dt = rows_ref.dtype

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    ckv = rows_ref[:, :kv_rank]                           # [BK, kv_rank]
    # this head's keys and values of the block, expanded from the rows
    # every head shares and rounded to the pool's dtype
    k_nope = jax.lax.dot_general(
        ckv, wk_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dt)
    vals = jnp.dot(ckv, wv_ref[0],
                   preferred_element_type=jnp.float32).astype(dt)
    q = q_ref[0]
    s = (jax.lax.dot_general(
        q[:, :nope], k_nope, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) + jax.lax.dot_general(
        q[:, nope:], rows_ref[:, kv_rank:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)) * scale      # [C, BK]
    kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos <= pos_ref[...], s, _NEG_INF)
    # key 0 is visible to every query, so from the first block on m is a
    # real score and a masked key's exp is 0
    m = m_sc[...]
    m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_sc[...] = alpha * l_sc[...] + p.sum(axis=1, keepdims=True)
    acc_sc[...] = acc_sc[...] * alpha + jnp.dot(
        p.astype(dt), vals, preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    # a head's last step: there is always one, and an output block that
    # is visited and not written goes back as whatever VMEM held
    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)


def latent_prefill(q, rows, wk, wv, pos, n_keys, scale, block_k):
    """One prefill chunk's latent attention in the EXPANDED form with the
    scores kept on chip.

    q: [H, C, nope + r], the chunk's queries: a head's un-rotated columns,
    then its rotated part padded with zeros to the columns a row has
    behind its compressed part; rows: [Tk, kv_rank + r], the slot's
    gathered logical rows, the chunk's own already among them, Tk whole
    blocks of ``block_k`` (the caller's: what it counts as visited); wk: [H, nope, kv_rank] and wv: [H, kv_rank, v], the two
    halves of the up-projection; pos: [C] int32, the queries' absolute
    positions (key ``kpos`` is visible iff ``kpos <= pos``); n_keys:
    int32 scalar, the positions written (the last query's + 1).  Returns
    [H, C, v] float32.

    The grid is (heads, the key blocks ``n_keys`` covers): its second
    extent is a traced value, so blocks past the context are not steps
    at all, neither copied nor computed.  A step expands the block's
    ``k_nope`` and values for its head on chip (rounded to the rows'
    dtype, as the composed block loop of `latent.prefill` does), takes
    the scores over both parts of the key, and folds them into an online
    softmax whose float32 statistics and accumulator live in VMEM: no
    array of scores or probabilities exists in HBM.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    H, C, d = q.shape
    Tk, width = rows.shape
    nope, kv_rank = wk.shape[1], wk.shape[2]
    v_dim = wv.shape[2]
    BK = int(block_k)
    if Tk % BK or d != nope + width - kv_rank:
        raise ValueError('latent_prefill: rows %r are not whole blocks of '
                         '%d, or q %r does not match them'
                         % (rows.shape, BK, q.shape))
    kernel = functools.partial(
        _latent_prefill_kernel, block_k=BK, kv_rank=kv_rank, nope=nope,
        scale=float(scale))
    n_blocks = jnp.clip((jnp.asarray(n_keys, jnp.int32) + BK - 1) // BK,
                        1, Tk // BK)
    dt = rows.dtype
    return pl.pallas_call(
        kernel,
        grid=(H, n_blocks),
        in_specs=[
            pl.BlockSpec((1, C, d), lambda h, j: (h, 0, 0)),
            pl.BlockSpec((BK, width), lambda h, j: (j, 0)),
            pl.BlockSpec((1, nope, kv_rank), lambda h, j: (h, 0, 0)),
            pl.BlockSpec((1, kv_rank, v_dim), lambda h, j: (h, 0, 0)),
            pl.BlockSpec((C, 1), lambda h, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C, v_dim), lambda h, j: (h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((C, 1), jnp.float32),
                        pltpu.VMEM((C, 1), jnp.float32),
                        pltpu.VMEM((C, v_dim), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((H, C, v_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'),
            vmem_limit_bytes=_LATENT_PREFILL_VMEM),
        name='latent_prefill',
        interpret=_pallas.interpret(),
    )(q.astype(dt), rows, wk.astype(dt), wv.astype(dt),
      pos.astype(jnp.int32).reshape(C, 1))
