"""Pallas DMA embedding-gather kernel: parity + gradient vs jnp.take
(interpret mode on CPU; the kernel engages for real on TPU at the
measured _MIN_ROWS gate)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.gather import embedding_gather, _eligible, _BLOCK


@pytest.fixture(autouse=True)
def _force_kernel(monkeypatch):
    """The N >= _MIN_ROWS gate reflects TPU measurement; these are
    KERNEL parity tests, so lower it to test at small sizes."""
    from paddle_tpu.ops import gather
    monkeypatch.setattr(gather, '_MIN_ROWS', _BLOCK)


def test_pallas_gather_kernel_direct():
    """Drive the pallas kernel DIRECTLY (interpret mode is the CPU
    backend's): an API drift in pallas fails HERE."""
    from paddle_tpu.ops.gather import _pallas_gather
    rng = np.random.RandomState(7)
    w = jnp.asarray(rng.randn(512, 128), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 512, (_BLOCK,)), jnp.int32)
    out = _pallas_gather(w, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(w)[idx],
                               rtol=1e-6)


def test_eligible_gather_runs_the_kernel_or_raises(monkeypatch):
    """No reroute: a failure inside an eligible gather propagates to the
    caller instead of degrading to jnp.take."""
    from paddle_tpu.ops import gather

    def _boom(*a, **k):
        raise ValueError('induced kernel failure')

    monkeypatch.setattr(gather, '_kernel_gather', _boom)
    rng = np.random.RandomState(4)
    w = jnp.asarray(rng.randn(640, 128), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 640, (_BLOCK,)), jnp.int32)
    with pytest.raises(ValueError, match='induced kernel failure'):
        embedding_gather(w, idx)


def test_bf16_table_is_not_eligible():
    """Mosaic refuses the one-row slice of a packed bf16 table (PERF.md
    PR 21), so bf16 takes jnp.take by the static rule."""
    w = jnp.zeros((640, 128), jnp.bfloat16)
    idx = jnp.zeros((_BLOCK,), jnp.int32)
    assert not _eligible(w, idx)


def test_gather_parity_and_grad(monkeypatch):
    from paddle_tpu.ops import gather
    calls = []
    real = gather._pallas_gather
    monkeypatch.setattr(gather, '_pallas_gather',
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(640, 128), jnp.float32)
    idx = jnp.asarray(rng.randint(0, 640, (_BLOCK * 2,)), jnp.int32)
    assert _eligible(w, idx)
    out = embedding_gather(w, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(w)[idx],
                               rtol=1e-6)
    # gradient: scatter-add with duplicate indices.  The kernel must
    # actually engage under jax.grad.
    n_fwd_calls = len(calls)
    assert n_fwd_calls > 0
    g = jax.grad(lambda w: (embedding_gather(w, idx) ** 2).sum())(w)
    assert len(calls) > n_fwd_calls, 'kernel path did not run under grad'
    gr = jax.grad(lambda w: (jnp.take(w, idx, axis=0) ** 2).sum())(w)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=1e-5)


def test_gather_multi_dim_ids_and_ineligible_shapes():
    rng = np.random.RandomState(1)
    w = jnp.asarray(rng.randn(64, 128), jnp.float32)
    idx2d = jnp.asarray(rng.randint(0, 64, (2, _BLOCK)), jnp.int32)
    out = embedding_gather(w, idx2d)
    assert out.shape == (2, _BLOCK, 128)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(w)[np.asarray(idx2d)], rtol=1e-6)
    # ineligible (tiny / misaligned) shapes take jnp.take
    small = jnp.asarray([3, 1], jnp.int32)
    np.testing.assert_allclose(np.asarray(embedding_gather(w, small)),
                               np.asarray(w)[[3, 1]], rtol=1e-6)


def test_gather_oob_ids_nan_fill_like_take():
    """Out-of-range ids must NaN-fill (jnp.take's default OOB
    semantics, which check_nan surfaces), not read unchecked HBM
    addresses."""
    rng = np.random.RandomState(2)
    w = jnp.asarray(rng.randn(64, 128), jnp.float32)
    idx = np.asarray(rng.randint(0, 64, (_BLOCK,)), np.int32)
    idx[0], idx[1] = 1000, -5  # OOV fills NaN; -5 wraps to row 59
    out = embedding_gather(w, jnp.asarray(idx))
    ref = jnp.take(w, jnp.asarray(idx), axis=0)
    assert np.isnan(np.asarray(out)[0]).all()
    np.testing.assert_allclose(np.asarray(out)[1], np.asarray(w)[59],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)
