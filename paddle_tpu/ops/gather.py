"""Pallas DMA row-gather for embedding lookups.

XLA's TPU row gather runs far below HBM bandwidth: 8192 x 512 f32 rows
from a 32000 x 512 table measure 1.50 ms via `jnp.take` but 0.865 ms
(1.7x) as per-row async DMA copies (TPU v5 lite; all jnp formulations —
take, fancy-index, 2-D ids — measure the same, see PERF.md).  The
kernel: ids ride SMEM scalar prefetch; the table stays in HBM
([V, 1, D] so each row is a leading-dim slice — dynamic sublane slicing
of a (8,128)-tiled HBM memref does not lower); each grid step DMAs
`block` rows into its VMEM output block.

Only the FORWARD gather runs in pallas; the backward stays XLA's
scatter-add, which measured identical across every formulation
(pre-sorted, segment_sum — PERF.md) and is duplicate-index-correct.

Parity: reference lookup_table_op.cu row gather (the reference's
CUDA kernel solves the same your-compiler-won't-do-it problem).
"""
import functools
import os

import numpy as np
import jax
import jax.numpy as jnp

from . import _pallas

_BLOCK = 256
# Measured gate (TPU v5 lite, end-to-end A/B): at 8192 rows the kernel
# is 1.7x in isolation and ~+0.7% end-to-end on the transformer bench;
# at 4096 rows it is 3% SLOWER end-to-end on word2vec — the serial
# per-row DMA-issue loop stops amortizing.  Engage only at large N.
_MIN_ROWS = 8192


def _gather_kernel(ids_ref, tbl_ref, out_ref, sem, *, block):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    i = pl.program_id(0)

    def issue(j, _):
        row = ids_ref[i * block + j]
        pltpu.make_async_copy(tbl_ref.at[row], out_ref.at[j], sem).start()
        return 0

    jax.lax.fori_loop(0, block, issue, 0)

    def wait(j, _):
        row = ids_ref[i * block + j]
        pltpu.make_async_copy(tbl_ref.at[row], out_ref.at[j], sem).wait()
        return 0

    jax.lax.fori_loop(0, block, wait, 0)


def _pallas_gather(tbl, ids):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    N = ids.shape[0]
    V, D = tbl.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N // _BLOCK,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((_BLOCK, 1, D), lambda i, ids: (i, 0, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, block=_BLOCK),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, 1, D), tbl.dtype),
        interpret=_pallas.interpret(),
    )(ids, tbl.reshape(V, 1, D))
    return out.reshape(N, D)


def _eligible(w, idx_flat):
    # float32 tables only: a bf16 [V, 1, D] table packs two rows into one
    # sublane, and Mosaic refuses the one-row slice ("Slice shape along
    # dimension 1 must be aligned to tiling (2), but is 1", PERF.md PR 21).
    # PT_PALLAS_GATHER=0 keeps XLA's gather for A/B runs.
    return (os.environ.get('PT_PALLAS_GATHER', '1') != '0' and
            idx_flat.shape[0] >= _MIN_ROWS and
            idx_flat.shape[0] % _BLOCK == 0 and
            w.shape[1] % 128 == 0 and
            w.dtype == jnp.float32)


@functools.lru_cache(maxsize=None)
def _make_kernel_gather(V, D, dtype_name):
    """Per-(shape, dtype) custom_vjp gather.  The table shape/dtype are
    closed over as STATIC values so the vjp residuals hold only arrays —
    a dtype object in residuals is not a valid JAX type and would make
    tracing under jax.grad raise (and silently reroute every training
    step to the jnp.take fallback)."""
    w_dtype = jnp.dtype(dtype_name)

    @jax.custom_vjp
    def kernel_gather(w, idx_flat):
        return _pallas_gather(w, idx_flat)

    def fwd(w, idx_flat):
        return kernel_gather(w, idx_flat), (idx_flat,)

    def bwd(res, g):
        (idx_flat,) = res
        dw = jnp.zeros((V, D), w_dtype).at[idx_flat].add(g.astype(w_dtype))
        return dw, np.zeros(idx_flat.shape, jax.dtypes.float0)

    kernel_gather.defvjp(fwd, bwd)
    return kernel_gather


def _kernel_gather(w, idx_flat):
    V, D = w.shape
    return _make_kernel_gather(V, D, jnp.dtype(w.dtype).name)(w, idx_flat)


def embedding_gather(w, idx):
    """rows of `w` at `idx` (any idx shape): the DMA kernel when the
    shapes qualify (`_eligible`), jnp.take otherwise.  An eligible
    gather runs the kernel or raises — there is no reroute."""
    idx_flat = idx.reshape(-1).astype(jnp.int32)
    if _eligible(w, idx_flat):
        # match jnp.take's semantics exactly: negative ids wrap (numpy
        # style), truly out-of-range ids fill with NaN (so corruption
        # SURFACES via executor check_nan).  The raw DMA would read
        # unchecked HBM addresses for either.
        V = w.shape[0]
        wrapped = jnp.where(idx_flat < 0, idx_flat + V, idx_flat)
        oob = (wrapped < 0) | (wrapped >= V)
        safe = jnp.clip(wrapped, 0, V - 1)
        out = _kernel_gather(w, safe)
        out = jnp.where(oob[:, None], jnp.nan, out)
        return out.reshape(tuple(idx.shape) + (w.shape[1],))
    return jnp.take(w, idx, axis=0)
