"""flash_attention kernel parity vs composed attention."""
import numpy as np
import pytest

import jax

from paddle_tpu.ops.attention import flash_attention, _ref_attention


@pytest.fixture(autouse=True)
def _force_kernel_path(monkeypatch):
    """flash_attention routes short-T shapes to the composed path
    (measured faster on TPU below T=512 — see ops/attention.py); these
    are KERNEL parity tests, so force the kernel on at any size."""
    from paddle_tpu.ops import attention as att
    monkeypatch.setattr(att, '_FWD_PALLAS_MIN_T', 0)


def _rand(shape, seed):
    return np.random.RandomState(seed).normal(size=shape).astype('float32')


def test_forward_parity():
    q, k, v = (_rand((2, 2, 128, 16), i) for i in range(3))
    out = flash_attention(q, k, v)
    ref = _ref_attention(q, k, v, False, 16 ** -0.5)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_causal_parity():
    q, k, v = (_rand((2, 2, 128, 16), i + 3) for i in range(3))
    out = flash_attention(q, k, v, causal=True)
    ref = _ref_attention(q, k, v, True, 16 ** -0.5)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_causal_decode_shape_end_aligned():
    # Tq=1, Tk=128 (cached decode): last query must see ALL keys
    q = _rand((1, 2, 1, 16), 0)
    k, v = _rand((1, 2, 128, 16), 1), _rand((1, 2, 128, 16), 2)
    out = flash_attention(q, k, v, causal=True, block_q=1)
    ref = _ref_attention(q, k, v, True, 16 ** -0.5)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_k_length_masks_padding():
    q, k, v = (_rand((2, 2, 128, 16), i + 7) for i in range(3))
    k_len = np.array([60, 128], np.int32)
    out = flash_attention(q, k, v, k_len=k_len)
    ref = _ref_attention(q, k, v, False, 16 ** -0.5, k_len)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    # row 0 must be invariant to garbage in the padded K/V tail
    k2, v2 = k.copy(), v.copy()
    k2[0, :, 60:] = 99.0
    v2[0, :, 60:] = -99.0
    out2 = flash_attention(q, k2, v2, k_len=k_len)
    np.testing.assert_allclose(out[0], out2[0], atol=2e-5)


def test_gradient_parity():
    q, k, v = (_rand((1, 2, 128, 16), i + 11) for i in range(3))
    k_len = np.array([100], np.int32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, k_len=k_len).sum()

    def loss_ref(q, k, v):
        return _ref_attention(q, k, v, True, 16 ** -0.5, k_len).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_short_t_routes_to_composed_path(monkeypatch):
    """Default dispatch (no kernel forcing): below _FWD_PALLAS_MIN_T the
    op must lower to the composed path; at/above it, the pallas kernel.
    Also pins the AMP precision contract on the composed route: bf16
    in/out with f32 softmax internals (matches the kernel)."""
    from paddle_tpu.ops import attention as att
    monkeypatch.setattr(att, '_FWD_PALLAS_MIN_T', 512)  # the default
    calls = []
    real_ref = att._ref_attention

    def spy(*a, **kw):
        calls.append(a[0].shape)
        return real_ref(*a, **kw)

    monkeypatch.setattr(att, '_ref_attention', spy)
    import jax.numpy as jnp
    q, k, v = (jnp.asarray(_rand((1, 2, 256, 16), i), jnp.bfloat16)
               for i in range(3))
    out = flash_attention(q, k, v, causal=True)
    assert len(calls) == 1, 'T=256 must route to the composed path'
    assert out.dtype == jnp.bfloat16
    # f32-softmax internals: close to the all-f32 reference within
    # bf16 input-rounding error only
    ref = real_ref(*(x.astype(jnp.float32) for x in (q, k, v)),
                   True, 16 ** -0.5)
    np.testing.assert_allclose(np.asarray(out, dtype='float32'),
                               np.asarray(ref), atol=2e-2)
    calls.clear()
    q2, k2, v2 = (_rand((1, 2, 512, 16), i + 3) for i in range(3))
    flash_attention(q2, k2, v2)  # interpret-mode kernel on CPU
    assert not calls, 'T=512 must route to the pallas kernel'


@pytest.mark.parametrize('cfg', [
    dict(B=2, H=4, Hkv=4, Tq=128, Tk=128, D=32, causal=False, klen=False),
    dict(B=2, H=4, Hkv=4, Tq=128, Tk=128, D=32, causal=True, klen=True),
    dict(B=2, H=8, Hkv=2, Tq=128, Tk=128, D=32, causal=True, klen=False),
    dict(B=2, H=8, Hkv=2, Tq=128, Tk=256, D=32, causal=True, klen=True),
])
@pytest.mark.parametrize('dkv_variant', ['resident', 'streamed'])
def test_pallas_backward_kernels_gradient_parity(cfg, dkv_variant,
                                                 monkeypatch):
    """The pallas dq/dkv kernels normally engage only above the HBM score
    threshold (long-T); force them on so regressions surface here, not on
    a long-sequence TPU run.  Both dK/dV variants are exercised: the
    VMEM-resident register-accumulation one (short Tq) and the q-streaming
    4-D-grid one (long Tq)."""
    from paddle_tpu.ops import attention as att
    monkeypatch.setattr(att, '_BWD_PALLAS_SCORE_BYTES', 0)
    if dkv_variant == 'streamed':
        monkeypatch.setattr(att, '_DKV_RESIDENT_MAX_T', 0)
    # guard against the gates silently vacating this test (it happened:
    # _FWD_PALLAS_MIN_T was added after this test and routed its shapes
    # away from the kernels until the autouse fixture above restored them)
    engaged = {}
    real_bwd = att._flash_backward

    def spy_bwd(*a, **kw):
        engaged['bwd'] = True
        return real_bwd(*a, **kw)

    monkeypatch.setattr(att, '_flash_backward', spy_bwd)
    rng = np.random.RandomState(9)
    B, H, Hkv, Tq, Tk, D = (cfg[k] for k in 'B H Hkv Tq Tk D'.split())
    q = rng.randn(B, H, Tq, D).astype('float32')
    k = rng.randn(B, Hkv, Tk, D).astype('float32')
    v = rng.randn(B, Hkv, Tk, D).astype('float32')
    kl = (np.asarray(rng.randint(Tk // 2, Tk + 1, B), np.int32)
          if cfg['klen'] else None)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=cfg['causal'], k_len=kl,
                                block_q=64, block_k=64) ** 2).sum()

    def loss_ref(q, k, v):
        return (_ref_attention(q, k, v, cfg['causal'], D ** -0.5,
                               kl) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert engaged.get('bwd'), \
        'pallas backward never engaged — a routing gate vacated this test'
    for a, b, n in zip(gf, gr, 'dq dk dv'.split()):
        np.testing.assert_allclose(a, b, atol=5e-4, err_msg=n)


# --------------------------------- the composed path over tiles of the batch

def _composed_case(monkeypatch, cfg, tile_seqs=5):
    """Inputs of one case, with the real routing (T under the Pallas
    crossover) and the byte constant patched down so that `tile_seqs`
    sequences of CPU-sized scores are a tile's budget."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention as att
    B, H, Hkv, Tq, Tk, D = (cfg[k] for k in 'B H Hkv Tq Tk D'.split())
    monkeypatch.setattr(att, '_FWD_PALLAS_MIN_T', 512)
    monkeypatch.setattr(att, '_COMPOSED_TILE_BYTES',
                        tile_seqs * H * Tq * Tk * 4)
    rng = np.random.RandomState(31)
    dt = jnp.dtype(cfg['dtype'])
    q = jnp.asarray(rng.randn(B, H, Tq, D), dt)
    k = jnp.asarray(rng.randn(B, Hkv, Tk, D), dt)
    v = jnp.asarray(rng.randn(B, Hkv, Tk, D), dt)
    kl = (jnp.asarray(rng.randint(Tk // 2, Tk + 1, B), jnp.int32)
          if cfg['klen'] else None)
    return att, q, k, v, kl


def _counters():
    from paddle_tpu.observability import metrics
    return tuple(metrics.counter('attention.' + n).value for n in (
        'composed_tiled', 'composed_whole', 'tile_buffers_unfilled'))


def _moved(before):
    return tuple(a - b for a, b in zip(_counters(), before))


def _mapped_tiles(att, c, causal, scale):
    """The tiled route as it was before its loops were written by hand:
    `lax.map` over a checkpointed tile, differentiated by plain AD (its
    stacked results start as zeros).  The oracle of the route's loops."""
    import jax.numpy as jnp

    @jax.checkpoint
    def tile(qkvl):
        return att._ref_attention(*qkvl[:3], causal, scale, qkvl[3])

    def mapped(q, k, v, kl):
        if kl is None:
            kl = jnp.full((q.shape[0],), k.shape[2], jnp.int32)
        out = jax.lax.map(tile, tuple(
            x.reshape((x.shape[0] // c, c) + x.shape[1:])
            for x in (q, k, v, kl)))
        return out.reshape(q.shape)
    return mapped


_BASE = dict(B=8, H=4, Hkv=4, Tq=32, Tk=32, D=16, causal=False, klen=False,
             dtype='float32')


@pytest.mark.parametrize('cfg', [
    dict(_BASE),
    dict(_BASE, causal=True),
    dict(_BASE, klen=True),
    dict(_BASE, causal=True, klen=True),
    dict(_BASE, H=8, Hkv=2, causal=True),                 # GQA
    dict(_BASE, Tq=16, Tk=48, causal=True, klen=True),    # Tq != Tk
    dict(_BASE, dtype='bfloat16', causal=True),
    dict(_BASE, dtype='bfloat16', H=8, Hkv=2, klen=True),
    dict(_BASE, B=12, causal=True, klen=True),            # tiles of 4
    dict(_BASE, B=7, causal=True, klen=True),             # prime: whole
    dict(_BASE, B=4, causal=True),                        # fits: whole
], ids=lambda c: '-'.join('%s%s' % kv for kv in sorted(c.items())
                          if _BASE[kv[0]] != kv[1]) or 'base')
def test_composed_tiles_equal_the_whole_batch(cfg, monkeypatch):
    """The composed route over tiles of the batch is `_ref_attention` in
    output and in dq, dk, dv, and the `lax.map` over checkpointed tiles
    it was before its two loops were written by hand; a batch that is
    one tile (it fits, or its only useful divisor is itself) lowers to
    the jaxpr it lowered to before there were tiles; and the counters
    say which ran, and that the tiled one allocated its four result
    buffers (the forward's, dq, dk, dv) without a fill."""
    import jax.numpy as jnp
    att, q, k, v, kl = _composed_case(monkeypatch, cfg)
    B, D, causal = cfg['B'], cfg['D'], cfg['causal']
    c = att._composed_tile(B, cfg['H'], cfg['Tq'], cfg['Tk'])
    tiles = c != B
    assert tiles == (B in (8, 12))

    def loss(fn):
        def f(q, k, v):
            out = fn(q, k, v)
            return (out.astype(jnp.float32) ** 2).sum(), out
        return f

    def ours(q, k, v):
        return att.flash_attention(q, k, v, causal=causal, k_len=kl)

    def ref(q, k, v):
        return att._ref_attention(q, k, v, causal, D ** -0.5, kl)

    before = _counters()
    (_, out), grads = jax.value_and_grad(
        loss(ours), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert _moved(before) == ((1, 0, 4) if tiles else (0, 1, 0))
    (_, want), want_grads = jax.value_and_grad(
        loss(ref), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert out.dtype == q.dtype
    # f32: the same operations on the same rows; bf16: one rounding of
    # the output and of each gradient, whatever the order of the sums
    tol = dict(atol=1e-5, rtol=1e-5) if cfg['dtype'] == 'float32' \
        else dict(atol=5e-2, rtol=2e-2)

    def same(got, want, exact=False):
        for a, b, n in zip(got, want, 'out dq dk dv'.split()):
            assert a.dtype == b.dtype
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            if exact:
                np.testing.assert_array_equal(a, b, err_msg=n)
            else:
                np.testing.assert_allclose(a, b, err_msg=n, **tol)

    same((out,) + grads, (want,) + want_grads)

    text = str(jax.make_jaxpr(jax.grad(lambda *a: loss(ours)(*a)[0]))(
        q, k, v))
    if tiles:
        # tile by tile the same operations on the same rows as the
        # mapped form's, in either direction: bit for bit
        mapped = _mapped_tiles(att, c, causal, D ** -0.5)
        (_, old), old_grads = jax.value_and_grad(
            loss(lambda q, k, v: mapped(q, k, v, kl)), argnums=(0, 1, 2),
            has_aux=True)(q, k, v)
        same((out,) + grads, (old,) + old_grads, exact=True)
        # two loops, four buffers nothing fills, nothing stacked by a
        # scan and no residual but q, k, v and the lengths
        assert 'while' in text or 'scan' in text
        assert text.count(' empty[') == 4
        assert 'checkpoint' not in text and 'remat' not in text
    else:
        def before_tiles(q, k, v):
            # what flash_attention's composed branch was: the lengths
            # filled in, then `_ref_attention` on the whole batch
            full = jnp.full((B,), cfg['Tk'], jnp.int32) if kl is None \
                else kl
            return att._ref_attention(q, k, v, causal, D ** -0.5,
                                      full.astype(jnp.int32))

        old = str(jax.make_jaxpr(jax.grad(
            lambda *a: loss(before_tiles)(*a)[0]))(q, k, v))
        assert 'while' not in text and 'scan' not in text
        assert text == old


def test_composed_tile_follows_the_shapes(monkeypatch):
    """The largest divisor of the batch whose f32 scores fit the byte
    constant; the whole batch when it fits, when no divisor holds a
    quarter of the budget, and when one sequence is over it."""
    from paddle_tpu.ops import attention as att
    monkeypatch.setattr(att, '_COMPOSED_TILE_BYTES',
                        24 * 8 * 256 * 256 * 4)
    tile = att._composed_tile
    assert tile(96, 8, 256, 256) == 24          # tbase.train_1chip
    assert tile(24, 8, 256, 256) == 24          # fits: no loop
    assert tile(100, 8, 256, 256) == 20
    assert tile(94, 8, 256, 256) == 94          # 2 x 47: 2 is no tile
    assert tile(97, 8, 256, 256) == 97          # prime
    assert tile(96, 8, 128, 128) == 96          # a quarter the scores: fits
    assert tile(8, 8, 2048, 2048) == 8          # one sequence is over it
    assert tile(96, 16, 256, 256) == 12         # twice the heads


def test_the_lengths_of_the_tiled_route_take_no_cotangent(monkeypatch):
    """`k_len` is an integer operand of the route's `custom_vjp`:
    differentiating with respect to q, k, v while the lengths are traced
    raises nothing, and asked for, their cotangent is `float0`."""
    att, q, k, v, kl = _composed_case(
        monkeypatch, dict(_BASE, causal=True, klen=True))

    def f(q, k, v, kl):
        return (att.flash_attention(q, k, v, causal=True, k_len=kl)
                ** 2).sum()

    want = jax.grad(lambda q, k, v: (att._ref_attention(
        q, k, v, True, 16 ** -0.5, kl) ** 2).sum(), argnums=(0, 1, 2))(
            q, k, v)
    before = _counters()
    got = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v, kl)
    assert _moved(before) == (1, 0, 4)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    dq, dkl = jax.grad(f, argnums=(0, 3), allow_int=True)(q, k, v, kl)
    assert dkl.dtype == jax.dtypes.float0 and dkl.shape == kl.shape
    np.testing.assert_allclose(dq, want[0], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize('shards', [2, 4])
def test_composed_tiles_the_local_batch_under_a_data_mesh(shards,
                                                          monkeypatch):
    """Under a mesh that shards the batch over 'data' alone each device
    tiles its own share inside a shard_map (no loop over the sharded
    dimension); a mesh with another axis in use keeps the whole batch."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.parallel import make_mesh
    cfg = dict(_BASE, B=16, causal=True, klen=True)
    att, q, k, v, kl = _composed_case(monkeypatch, cfg, tile_seqs=2)
    want = att._ref_attention(q, k, v, True, 16 ** -0.5, kl)

    def grad_of(mesh):
        def f(q, k, v, kl):
            out = att.flash_attention(q, k, v, causal=True, k_len=kl,
                                      mesh=mesh)
            return (out ** 2).sum(), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    want_g = jax.grad(lambda q, k, v: (att._ref_attention(
        q, k, v, True, 16 ** -0.5, kl) ** 2).sum(), argnums=(0, 1, 2))(
            q, k, v)
    data4 = make_mesh(data=shards, devices=jax.devices()[:shards])
    sh = NamedSharding(data4, P('data'))
    before = _counters()
    (_, out), grads = grad_of(data4)(*(jax.device_put(x, sh)
                                       for x in (q, k, v, kl)))
    assert _moved(before) == (1, 0, 4)
    assert out.sharding.is_equivalent_to(sh, out.ndim)
    for a, b in zip((out,) + grads, (want,) + want_g):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    lowered = grad_of(data4).lower(*(jax.device_put(x, sh)
                                     for x in (q, k, v, kl)))
    text = lowered.as_text()
    # scores [c, Hkv, g, Tq, Tk]: 16 / shards sequences a device in
    # tiles of 2
    assert 'tensor<2x4x1x32x32xf32>' in text
    assert 'tensor<%dx4x1x32x32xf32>' % (16 // shards) not in text
    assert 'tensor<16x4x1x32x32xf32>' not in text

    def pulled_back(q, k, v, kl, g):
        # no loss to sum over the devices: out and its pullback alone
        out, pull = jax.vjp(lambda q, k, v: att.flash_attention(
            q, k, v, causal=True, k_len=kl, mesh=data4), q, k, v)
        return out, pull(g)

    compiled = jax.jit(pulled_back).lower(*(
        jax.device_put(x, sh) for x in (q, k, v, kl, q))).compile().as_text()
    assert ' while(' in compiled
    assert not [w for w in ('all-reduce', 'all-gather', 'all-to-all',
                            'collective-permute') if w in compiled]

    mixed = make_mesh(data=2, model=2, devices=jax.devices()[:4])
    before = _counters()
    (_, out), _ = grad_of(mixed)(q, k, v, kl)
    assert _moved(before) == (0, 1, 0)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
