"""The decode step's attention over the page pool in place
(`ops.attention.paged_attention`, Pallas in interpret mode on the CPU)
against the composed path it replaces on one pool: `_logical_rows` +
`cached_attention`.  Ragged lengths, page and block boundaries, a full
slot, inactive slots with stale tables, shared prefix pages, an idle
batch, GQA and MHA, f32 and bf16; and that pages no live length covers
are not READ, not merely masked.  Toy geometry: counts and values, never
a time."""
import re

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.attention import (cached_attention, paged_attention,
                                      paged_attention_eligible,
                                      paged_attention_rows, paged_pool_heads)
from paddle_tpu.serving.generation import CacheConfig
from paddle_tpu.serving.generation.decode import _logical_rows

PL, M, LAYERS, DH = 4, 6, 2, 8           # max_len 24
MAX_LEN = PL * M


def _pool(slots, hkv, dtype, seed=0):
    cache = CacheConfig(slots=slots, layers=LAYERS, kv_heads=hkv,
                        max_len=MAX_LEN, head_dim=DH, dtype=dtype,
                        page_len=PL)
    rng = np.random.RandomState(seed)
    st = {n: jnp.asarray(rng.randn(*cache.pool_shape), jnp.dtype(dtype))
          for n in ('k', 'v')}
    return cache, st


def _tables(slots, pages, seed=1):
    """Every slot maps distinct random pages (never the garbage page)."""
    rng = np.random.RandomState(seed)
    return rng.permutation(np.arange(1, pages))[:slots * M].reshape(
        slots, M).astype(np.int32)


def _composed(st, bt, cache, layer, q, n):
    """What the step computed before: gather every slot's logical row,
    attend with the positional mask kpos <= qpos = n - 1."""
    kl, vl = _logical_rows(st, jnp.asarray(bt), layer, cache)
    qpos = jnp.asarray(n, jnp.int32)[:, None] - 1
    return cached_attention(q[:, :, None, :], kl, vl, qpos)[:, :, 0, :]


def _both(lengths, heads, hkv, dtype, pages_per_block, bt=None, st=None,
          layer=1):
    slots = len(lengths)
    cache, fresh = _pool(slots, hkv, dtype)
    st = st or fresh
    if bt is None:
        bt = _tables(slots, cache.pages)
    q = jnp.asarray(np.random.RandomState(2).randn(slots, heads, DH),
                    jnp.dtype(dtype))
    n = np.asarray(lengths, np.int32)
    got = paged_attention(q, st['k'], st['v'], jnp.asarray(bt),
                          jnp.asarray(n), layer,
                          pages_per_block=pages_per_block)
    want = _composed(st, bt, cache, layer, q, n)
    return np.asarray(got, np.float32), np.asarray(want, np.float32), n


def _assert_close(got, want, n, dtype):
    live = n > 0
    assert np.isfinite(got).all()
    # bf16: both sides round probabilities and the output to 8 bits of
    # mantissa, in another order of summation
    tol = 1e-5 if dtype == 'float32' else 2e-2
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
    assert not got[~live].any()          # a slot that rides along: zeros


RAGGED = {
    'ragged_incl_1': [1, 7, 13, 24],
    'page_boundary': [PL, PL - 1, PL + 1, 3 * PL],
    'block_boundary': [2 * PL, 2 * PL + 1, 4 * PL - 1, 4 * PL + 1],
    'slot_at_max_len': [MAX_LEN, 2, MAX_LEN - 1, MAX_LEN],
    'inactive_among_live': [0, 9, 0, 17],
}


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('heads,hkv', [(4, 1), (2, 2)],
                         ids=['gqa4to1', 'mha'])
@pytest.mark.parametrize('case', sorted(RAGGED))
def test_paged_equals_gather_then_attend(case, heads, hkv, dtype):
    """Blocks of two pages, so lengths cross block boundaries and the
    double buffer turns over; an inactive slot's table maps live pages
    of others (stale) and must contribute nothing."""
    got, want, n = _both(RAGGED[case], heads, hkv, dtype, 2)
    _assert_close(got, want, n, dtype)


@pytest.mark.parametrize('pages_per_block', [1, 3, M, None])
def test_block_size_does_not_change_the_result(pages_per_block):
    got, want, n = _both([5, 24, 0, 12], 4, 2, 'float32', pages_per_block)
    _assert_close(got, want, n, 'float32')


def test_two_slots_sharing_prefix_pages():
    """Slots 0 and 1 map the same first two pages (a prefix-cache hit)
    and their own tails; slot 2 is inactive with slot 0's table."""
    cache, _ = _pool(3, 2, 'float32')
    bt = _tables(3, cache.pages)
    bt[1, :2] = bt[0, :2]
    bt[2] = bt[0]
    got, want, n = _both([11, 14, 0], 4, 2, 'float32', 2, bt=bt)
    _assert_close(got, want, n, 'float32')
    # the shared pages really carried weight in both results
    assert np.abs(got[0]).max() > 0 and np.abs(got[1]).max() > 0


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_all_inactive_batch_is_zero_and_reads_nothing(dtype):
    cache, st = _pool(3, 2, dtype)
    poisoned = {k: jnp.full_like(v, jnp.nan) for k, v in st.items()}
    got, _want, n = _both([0, 0, 0], 4, 2, dtype, 2, st=poisoned)
    assert np.isfinite(got).all() and not got.any()
    assert paged_attention_rows(n, PL) == 0


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_pages_no_live_length_covers_are_not_read(dtype):
    """NaN in every page that no ACTIVE slot's length covers: the
    garbage page, unmapped pool pages, the pages past each live length
    and every page of the inactive slot's stale table.  Masking alone
    would not do: 0 * NaN in the value product is NaN."""
    lengths = [1, 9, 0, 2 * PL]
    cache, st = _pool(len(lengths), 2, dtype)
    bt = _tables(len(lengths), cache.pages)
    covered = np.zeros(cache.pages, bool)
    for s, n in enumerate(lengths):
        covered[bt[s, :cache.pages_for(n)]] = True
    assert not covered[0] and covered.sum() == 1 + 3 + 0 + 2
    dead = jnp.asarray(~covered)[:, None, None, None, None]
    poisoned = {k: jnp.where(dead, jnp.nan, v) for k, v in st.items()}
    got, _, n = _both(lengths, 4, 2, dtype, 2, bt=bt, st=poisoned)
    clean, want, _ = _both(lengths, 4, 2, dtype, 2, bt=bt, st=st)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    _assert_close(got, want, n, dtype)
    assert paged_attention_rows(n, PL) == (1 + 3 + 0 + 2) * PL


# ----------------------------------- a head narrower than the 128 lanes

def _narrow(lengths, heads, hkv, dh, dtype, poison=False):
    """(paged over the pool `paged_pool_heads` lays out, gather then
    attend with the kv heads apart, n) for one layer of a pool whose kv
    heads lie side by side in a row."""
    slots = len(lengths)
    hp, wide = paged_pool_heads(hkv, dh)
    cache = CacheConfig(slots=slots, layers=LAYERS, kv_heads=hp,
                        max_len=MAX_LEN, head_dim=wide, dtype=dtype,
                        page_len=PL)
    rng = np.random.RandomState(3)
    st = {n: jnp.asarray(rng.randn(*cache.pool_shape), jnp.dtype(dtype))
          for n in ('k', 'v')}
    bt = _tables(slots, cache.pages)
    n = np.asarray(lengths, np.int32)
    if poison:
        covered = np.zeros(cache.pages, bool)
        for s, length in enumerate(lengths):
            covered[bt[s, :cache.pages_for(length)]] = True
        dead = jnp.asarray(~covered)[:, None, None, None, None]
        st = {k: jnp.where(dead, jnp.nan, v) for k, v in st.items()}
    q = jnp.asarray(rng.randn(slots, heads, dh), jnp.dtype(dtype))
    got = paged_attention(q, st['k'], st['v'], jnp.asarray(bt),
                          jnp.asarray(n), 1, pages_per_block=2)

    def apart(pool):
        rows = jnp.nan_to_num(pool)[jnp.asarray(bt), 1]  # [S, M, PL, hp, w]
        return rows.reshape(slots, MAX_LEN, hkv, dh).transpose(0, 2, 1, 3)

    want = cached_attention(q[:, :, None], apart(st['k']), apart(st['v']),
                            jnp.asarray(n)[:, None] - 1)[:, :, 0]
    return np.asarray(got, np.float32), np.asarray(want, np.float32), n


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('heads,hkv,dh', [(32, 8, 64), (8, 2, 64),
                                          (8, 4, 32), (4, 4, 32)],
                         ids=['lfm2_32q8kv64', 'gqa4to1_64', 'gqa2to1_32',
                              'mha_32'])
@pytest.mark.parametrize('case', sorted(RAGGED))
def test_a_narrow_head_attends_over_kv_heads_side_by_side(case, heads, hkv,
                                                          dh, dtype):
    """A head of 64 (of 32) lies two (four) kv heads to a 128-lane row of
    the pool; the kernel is the same, each query beside zeros in the
    lanes of the other kv heads of its row."""
    assert paged_pool_heads(hkv, dh) == (hkv * dh // 128, 128)
    got, want, n = _narrow(RAGGED[case], heads, hkv, dh, dtype)
    assert got.shape == (len(n), heads, dh)
    _assert_close(got, want, n, dtype)


def test_a_narrow_head_reads_no_page_a_live_length_does_not_cover():
    got, want, n = _narrow([1, 9, 0, 2 * PL], 32, 8, 64, 'float32',
                           poison=True)
    clean, _, _ = _narrow([1, 9, 0, 2 * PL], 32, 8, 64, 'float32')
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    _assert_close(got, want, n, 'float32')


def test_the_pool_layout_is_read_off_the_head(monkeypatch):
    """Whole lane tiles keep their own geometry; 64 and 32 lie side by
    side where the kv heads come in such groups; a head of 96 has no
    layout: on an accelerator the rule answers False and the step takes
    the composed path."""
    from paddle_tpu.ops import _pallas
    assert paged_pool_heads(8, 128) == (8, 128)
    assert paged_pool_heads(4, 256) == (4, 256)
    assert paged_pool_heads(8, 64) == (4, 128)
    assert paged_pool_heads(8, 32) == (2, 128)
    assert paged_pool_heads(3, 64) == (3, 64)        # no pair to lie beside
    assert paged_pool_heads(8, 96) == (8, 96)
    monkeypatch.setattr(_pallas, 'interpret', lambda: False)
    for dtype in ('bfloat16', 'float32'):
        assert paged_attention_eligible(
            (9, 2, 16) + paged_pool_heads(8, 64), dtype)
        assert not paged_attention_eligible((9, 2, 16, 8, 64), dtype)
        assert not paged_attention_eligible(
            (9, 2, 16) + paged_pool_heads(8, 96), dtype)
        assert not paged_attention_eligible(
            (9, 2, 16) + paged_pool_heads(3, 64), dtype)


def test_a_head_of_96_takes_the_composed_path(monkeypatch):
    """A `gqa` mixer whose head has no layout: the runtime reads the
    rule, the step gathers, and `generation.kv_rows_read` counts every
    slot's ``max_len``."""
    from paddle_tpu.ops import _pallas
    from paddle_tpu.serving.generation import DecodeRuntime, random_weights
    cfg = {'block': 'latent_moe', 'vocab': 61, 'd_model': 32, 'n_layer': 2,
           'n_head': 2, 'n_kv_head': 1, 'head_dim': 96, 'd_ffn': 48,
           'theta': 1e4, 'max_len': 16, 'mixer': ['conv', 'gqa'],
           'ffn': ['dense', 'dense'], 'conv': {'taps': 3}}
    w = random_weights(cfg, seed=1, scale=0.2)
    paged = DecodeRuntime(w, cfg, slots=2, prefill_chunk=4, page_len=4)
    assert paged.paged and paged._gathered is None     # interpret mode
    monkeypatch.setattr(_pallas, 'interpret', lambda: False)
    composed = DecodeRuntime(w, cfg, slots=2, prefill_chunk=4, page_len=4)
    monkeypatch.undo()
    assert not composed.paged and composed._gathered == 2 * 16
    assert composed.cache.pool_shape == (9, 1, 4, 1, 96)
    prompt = np.arange(1, 8)
    assert composed.generate(prompt, 5, steps_per_window=2) \
        == paged.generate(prompt, 5, steps_per_window=2)


def test_rows_fetched_are_whole_pages_of_active_slots():
    assert paged_attention_rows([0, 1, 8, 9], 8) == 0 + 8 + 8 + 16
    assert paged_attention_rows(np.zeros(4, int), 8) == 0
    # a window: step j of a slot of length n attends n + j + 1
    lens = np.array([7, 20])[:, None] + np.arange(1, 3)
    assert paged_attention_rows(lens, 8) == (8 + 16) + (24 + 24)


def test_eligibility_is_read_off_the_pool():
    """A floating pool on one device; the int8 pool and a mesh of
    several devices keep the composed gather."""
    import jax
    from jax.sharding import Mesh
    shape = (9, 2, 8, 2, 8)
    assert paged_attention_eligible(shape, 'float32')
    assert paged_attention_eligible(shape, 'bfloat16')
    assert not paged_attention_eligible(shape, 'int8')
    one = Mesh(np.array(jax.devices()[:1]), ('seq',))
    assert paged_attention_eligible(shape, 'float32', one)
    if len(jax.devices()) > 1:
        two = Mesh(np.array(jax.devices()[:2]), ('seq',))
        assert not paged_attention_eligible(shape, 'float32', two)


# ------------------------------------ the chip's compiler, without the chip

@pytest.mark.parametrize('dtype,heads,kv_heads,layers,slots,max_len,pages', [
    ('bfloat16', 32, 8, 16, 32, 1280, 5121),   # mistral7b.chat_steady's pool
    ('float32', 16, 8, 16, 8, 2048, None),     # chip_smoke's llama_1b widths
    # falconh1_34b.chat_long_answers: 20 query heads, five a kv head, are
    # no multiple of the 8 sublanes but the array's own extent
    ('bfloat16', 20, 4, 6, 32, 1544, 6177),
])
def test_mosaic_compiles_the_kernel_at_real_widths(
        one_v5e_chip, monkeypatch, dtype, heads, kv_heads, layers, slots,
        max_len, pages):
    """Interpret mode cannot see a refused tiling or DMA; the compiler
    can, and the pool must reach the kernel as a bitcast, not a copy."""
    import jax
    from paddle_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, 'interpret', lambda: False)
    cache = CacheConfig(slots=slots, layers=layers, kv_heads=kv_heads,
                        max_len=max_len, head_dim=128, dtype=dtype,
                        page_len=8, pages=pages)
    assert paged_attention_eligible(cache.pool_shape, dtype)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                    sharding=one_v5e_chip)

    compiled = jax.jit(
        lambda q, k, v, bt, n: paged_attention(q, k, v, bt, n, 3)).lower(
            sds((slots, heads, 128), 'float32'), sds(cache.pool_shape, dtype),
            sds(cache.pool_shape, dtype),
            sds((slots, cache.max_pages), 'int32'),
            sds((slots,), 'int32')).compile()
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 1
    pool = '[%d,' % cache.pages
    assert not [ln for ln in text.splitlines() if pool in ln.split('(')[0]
                and (' copy(' in ln or 'copy-start(' in ln
                     or ' fusion(' in ln)]
    # nothing of the pool's size is made: the output is the queries' size
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_mosaic_compiles_the_kernel_over_a_head_of_64(one_v5e_chip,
                                                      monkeypatch):
    """lfm2_8b_a1b.many_streams_medium_prompts' pool: 32 query / 8 kv
    heads of 64, pages of 16, four attention layers, two kv heads to a
    row.  The pool reaches the kernel as it lies (no copy, nothing of its
    size made); the queries are widened and the result narrowed outside
    the kernel, arrays of the queries' size."""
    import jax
    from paddle_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, 'interpret', lambda: False)
    slots, heads = 96, 32
    hp, wide = paged_pool_heads(8, 64)
    cache = CacheConfig(slots=slots, layers=4, kv_heads=hp, max_len=3584,
                        head_dim=wide, dtype='bfloat16', page_len=16,
                        pages=21505)
    assert cache.pool_shape == (21505, 4, 16, 4, 128)
    assert cache.page_bytes() == 4 * 16 * 2 * 8 * 64 * 2
    assert paged_attention_eligible(cache.pool_shape, 'bfloat16')

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                    sharding=one_v5e_chip)

    compiled = jax.jit(
        lambda q, k, v, bt, n: paged_attention(q, k, v, bt, n, 3)).lower(
            sds((slots, heads, 64), 'bfloat16'),
            sds(cache.pool_shape, 'bfloat16'),
            sds(cache.pool_shape, 'bfloat16'),
            sds((slots, cache.max_pages), 'int32'),
            sds((slots,), 'int32')).compile()
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 1
    pool = '[%d,' % cache.pages
    assert not [ln for ln in text.splitlines() if pool in ln.split('(')[0]
                and (' copy(' in ln or 'copy-start(' in ln
                     or ' fusion(' in ln)]
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


def test_mosaic_compiles_the_latent_kernel_at_real_widths(one_v5e_chip,
                                                          monkeypatch):
    """`latent_attention` at axk1.shared_context_answers' extents: 64
    heads over one 576-value row a token, stored 640 wide because a DMA
    moves whole lane tiles (Mosaic refuses the 576-wide slice, and the
    eligibility rule says so first); the pool reaches the kernel as it
    lies, and nothing of its size is made."""
    import jax
    from paddle_tpu.ops import _pallas
    from paddle_tpu.ops.attention import (latent_attention,
                                          latent_attention_eligible)
    monkeypatch.setattr(_pallas, 'interpret', lambda: False)
    slots, heads, layers, pages = 64, 64, 7, 16385
    cache = CacheConfig(slots=slots, layers=layers, kv_heads=1, max_len=7184,
                        head_dim=640, dtype='bfloat16', page_len=16,
                        pages=pages, latent=512)
    assert cache.pool_shape == (pages, layers, 16, 640)
    assert latent_attention_eligible(cache.pool_shape, 'bfloat16', 512)
    assert not latent_attention_eligible((pages, layers, 16, 576),
                                         'bfloat16', 512)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                    sharding=one_v5e_chip)

    compiled = jax.jit(
        lambda ql, qr, pool, bt, n: latent_attention(
            ql, qr, pool, bt, n, 3, 0.13)).lower(
                sds((slots, heads, 512), 'float32'),
                sds((slots, heads, 128), 'float32'),
                sds(cache.pool_shape, 'bfloat16'),
                sds((slots, cache.max_pages), 'int32'),
                sds((slots,), 'int32')).compile()
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 1
    pool = '[%d,' % pages
    assert not [ln for ln in text.splitlines() if pool in ln.split('(')[0]
                and (' copy(' in ln or 'copy-start(' in ln
                     or ' fusion(' in ln)]
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20


def test_mosaic_compiles_the_delta_rule_step_at_real_widths(one_v5e_chip,
                                                            monkeypatch):
    """`kda.kda_step` at kimi_linear.many_streams_long_answers' extents:
    128 slots of six layers' [32, 128, 128] float32 matrix state, 3.2 GB,
    and their [3, 96, 128] convolution tails, 113 MB.  Both reach the
    kernel as they lie and go back aliased: nothing of their size, nor of
    one layer's slice of them, is made; and a whole `kda` layer's step
    around the kernel (`kda.step_mixer`) passes over no array of all the
    slots' tails."""
    import jax
    from paddle_tpu.ops import _pallas
    from paddle_tpu.serving.generation import kda
    monkeypatch.setattr(_pallas, 'interpret', lambda: False)
    slots, layers, H, d, taps, D, rank = 128, 6, 32, 128, 4, 2304, 128
    shape = (slots, layers, H, d, d)
    tails = (slots, layers, taps - 1, 3 * H, d)
    assert kda.kda_step_eligible(shape, tails, 'float32')

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                    sharding=one_v5e_chip)

    def kept(text):
        """Lines of a compiled module that make an array of the state's or
        the tails' extents, or of one layer's slice of them."""
        marks = ['[%s]' % ','.join(str(n) for n in s) for s in (
            shape, tails, shape[:1] + shape[2:], tails[:1] + tails[2:],
            (slots, taps - 1, 3 * H * d), (slots, taps, 3 * H * d))]
        return [ln for ln in text.splitlines()
                if any(m in ln.split('(')[0] for m in marks)
                and (' copy(' in ln or 'copy-start(' in ln
                     or ' fusion(' in ln)]

    compiled = jax.jit(
        lambda x, filt, a, beta, state, tail, active: kda.kda_step(
            x, filt, a, beta, state, tail, 3, active),
        donate_argnums=(4, 5)).lower(
            sds((slots, 3 * H * d), 'float32'),
            sds((taps, 3 * H * d), 'float32'),
            sds((slots, H, d), 'float32'), sds((slots, H), 'float32'),
            sds(shape, 'float32'), sds(tails, 'float32'),
            sds((slots,), 'bool')).compile()
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 1
    assert not kept(text)
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20
    # the layer around it, with kimi_linear's weights (bfloat16)
    kcfg = {'n_heads': H, 'head_dim': d, 'd_conv': taps, 'gate_rank': rank}
    w = {'l_' + k: sds(s, 'bfloat16')
         for k, s in kda.weight_shapes(D, kcfg).items()}
    layer = jax.jit(
        lambda w, h, state, tail, active: kda.step_mixer(
            w, 'l_', {'kda': kcfg}, h, state, 3, tail, active, True),
        donate_argnums=(2, 3)).lower(
            w, sds((slots, D), 'float32'), sds(shape, 'float32'),
            sds(tails, 'float32'), sds((slots,), 'bool')).compile()
    text = layer.as_text()
    assert text.count('tpu_custom_call') == 1
    assert not kept(text)


def test_mosaic_compiles_the_grouped_expert_products_at_real_widths(
        one_v5e_chip, monkeypatch):
    """`experts.routed` at kimi_linear.many_streams_long_answers' extents,
    a decode step of 128 slots over 64 held experts of [2304, 1024]: the
    grouped route's three products are `experts.gmm` (the label holds
    ``gmm``: what `kimi_linear.moe_share` counts), each over the bucket of
    256 sorted rows, and no route makes a copy of the 302 MB a weight
    is."""
    import jax
    from paddle_tpu.ops import _pallas
    from paddle_tpu.serving.generation import experts
    monkeypatch.setattr(_pallas, 'interpret', lambda: False)
    moe = dict(n_routed=256, top_k=8, d_expert=1024, n_shared=1,
               scale=2.446, ranks=4, rank=1)
    T, D, F, G = 128, 2304, 1024, 64
    assert experts.gmm_eligible((G, D, F))
    assert not experts.gmm_eligible((G, D, 1000))

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                    sharding=one_v5e_chip)

    compiled = jax.jit(
        lambda h, w1, w3, w2, picks, wts, valid: experts.routed(
            h, w1, w3, w2, picks, wts, valid, moe, True)).lower(
                sds((T, D), 'float32'), sds((G, D, F), 'bfloat16'),
                sds((G, D, F), 'bfloat16'), sds((G, F, D), 'bfloat16'),
                sds((T, 8), 'int32'), sds((T, 8), 'float32'),
                sds((T,), 'bool')).compile()
    text = compiled.as_text()
    calls = [ln.split(' = ')[1].split('{')[0] for ln in text.splitlines()
             if ' custom-call(' in ln and 'experts_gmm' in ln.split('(')[0]]
    assert sorted(calls) == ['f32[256,1024]', 'f32[256,1024]',
                             'f32[256,2304]']
    assert not [ln for ln in text.splitlines()
                if re.search(r'= bf16\[64,(2304,1024|1024,2304)\]\S* '
                             r'(copy|copy-start)\(', ln)]


@pytest.mark.parametrize('kernel', [True, False],
                         ids=['kernel', 'composed'])
def test_a_latent_chunk_keeps_its_scores_on_chip_at_real_widths(
        one_v5e_chip, monkeypatch, kernel):
    """One layer of `latent.prefill` at axk1.shared_context_answers'
    extents (64 heads, a chunk of 512 over a table of 7,184 rows, blocks
    of 1,024), compiled for the chip: through `latent_prefill` the
    program holds ONE Mosaic call and no array of the scores' extents in
    any dtype; the composed block loop, the route under a mesh, is what
    holds them."""
    import jax
    from paddle_tpu.ops import _pallas
    from paddle_tpu.serving.generation import latent
    monkeypatch.setattr(_pallas, 'interpret', lambda: False)
    cfg = {'n_head': 64, 'theta': 1e4, 'rms_eps': 1e-6,
           'latent': {'q_rank': 1536, 'kv_rank': 512, 'nope': 128,
                      'rope': 64, 'v': 128,
                      'yarn': {'factor': 32.0, 'beta_fast': 32.0,
                               'beta_slow': 1.0, 'original_max_len': 4096,
                               'mscale': 1.0, 'mscale_all_dim': 1.0}}}
    C, layers = 512, 7
    cache = CacheConfig(slots=64, layers=layers, kv_heads=1, max_len=7184,
                        head_dim=640, dtype='bfloat16', page_len=16,
                        pages=16385, latent=512)
    assert latent.prefill_kernel(cfg, cache, C)
    assert not latent.prefill_kernel(cfg, cache, C + 8)   # half a tile

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                    sharding=one_v5e_chip)

    public = {n: sds(shape, 'bfloat16') for n, shape in
              latent.weight_shapes(7168, 64, cfg['latent']).items()}
    parts = jax.eval_shape(
        lambda qb, kva, kvb: latent.prepare(qb, kva, kvb, 64, 128, 64, 128),
        public['att_qb_w'], public['att_kva_w'], public['att_kvb_w'])
    w = {'l_' + n: a for n, a in public.items()}
    names = [t for slot in latent.PREPARED for t in latent.PREPARED[slot]]
    w.update({'l_' + n: sds(a.shape, a.dtype)
              for n, a in zip(names, parts)})
    i32 = 'int32'
    text = jax.jit(
        lambda w, h, pos, n, pool, pg, rw, bt: latent.prefill(
            w, 'l_', cfg, h, pos, n, pool, 3, pg, rw, bt, kernel),
        donate_argnums=(4,)).lower(
            w, sds((C, 7168), 'float32'), sds((C,), i32), sds((), i32),
            sds(cache.pool_shape, 'bfloat16'), sds((C,), i32),
            sds((C,), i32), sds((cache.max_pages,), i32)).compile().as_text()
    assert text.count('tpu_custom_call') == (1 if kernel else 0)
    scores = [ln for ln in text.splitlines() if '[64,512,1024]' in ln]
    assert bool(scores) is (not kernel)


@pytest.mark.parametrize('causal,lengths', [(True, False), (False, True)],
                         ids=['self', 'cross'])
def test_short_attention_keeps_its_scores_on_chip_at_tbase_widths(
        one_v5e_chip, causal, lengths):
    """`jax.grad` through `flash_attention` at tbase.train_1chip's shape,
    as XLA:TPU compiles it: the composed route runs over tiles of the
    batch, so no score matrix of the whole batch is defined and the
    block needs next to no HBM temporaries (302.5 MB before the tiles:
    a 201 MB f32 array and its bf16 twin)."""
    import jax
    from paddle_tpu.ops.attention import _composed_tile, flash_attention
    B, H, T, D = 96, 8, 256, 64
    assert _composed_tile(B, H, T, T) < B

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                    sharding=one_v5e_chip)

    def loss(q, k, v, kl):
        return flash_attention(q, k, v, causal=causal, k_len=kl).astype(
            jnp.float32).sum()

    qkv = sds((B, H, T, D), 'bfloat16')
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv, sds((B,), 'int32') if lengths else None).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    text = compiled.as_text()
    assert ' while(' in text
    for whole in ('[%d,%d,%d,%d]' % (B, H, T, T),
                  '[%d,%d,1,%d,%d]' % (B, H, T, T)):
        assert whole not in text


# temp_size_in_bytes of the same step at the parent of PR 41, where the
# generated-kernel tier (deleted in PR 51) put 32 `row` LayerNorm kernels
# into it: 8,117,223,424; the replay read 8,173,700,096 (+0.70 %, 56 MB
# of 8.1 GB: offline compiles, PR 41).  Since PR 50 the projections
# beside the tile loops are written in the loops' layout and the step
# reads 8,312,873,472 (+139 MB; PR 51's compile reads the same to the
# byte).  The buffers, from XLA's buffer assignment of both compiles
# (PERF.md section 6, PR 50): the q, k and v products are three dots a
# self-attention where they were one, so the bf16 cast of the LayerNorm's
# output has three readers forward and three backward and XLA writes it
# out once (`add_convert_fusion bf16[96,256,512]`) and KEEPS it for the
# weights' gradients, where the parent's one product and one gradient
# each recomputed it from the f32 residual stream inside their fusion:
# 13 such buffers of 25 MB live at the peak (12 self-attentions and the
# encoder's output), less four `f32[96,256,512]` the parent still held
# there.  The price of the layout; a second pass over the stream in the
# backward would buy it back for 75 MB of traffic an attention.
# Since PR 54 each rank-2 weight's gradient is fenced from its Adam
# update (`core/executor.py` `_lower`), so a gradient the product's
# epilogue used to consume in place is written out in f32 before the
# update's loop fusion reads it: 8,327,549,952 (+14.7 MB, 0.18 %: inside
# the 1 %; offline compile, PR 54).  Since PR 55 the embedding lookups
# are XLA's own gather: the step's last two Mosaic calls (per-row DMA
# gathers, 1.42 ms a step) are gone, and with them what they forced
# around themselves, per table a reshape of the WHOLE table into
# `f32[32000,1,512]{T(1,128)}` (each row a leading-dimension slice for
# the DMA), a copy of the result `f32[24576,1,512]` back to the tiling
# its readers want, and a select in the backward for the NaN fill:
# 8,326,679,040 (-0.9 MB), re-pinned to that reading.  Since PR 58 the
# tile loops' 72 result buffers are `AllocateBuffer` custom calls where
# they were zero `broadcast`s (`ops/attention._unfilled`): the same
# buffers, but an allocation has no operand, so XLA's scheduler hoists
# it far ahead of its loop where a fill sat on the line before its
# `while`: the backward's 54 stand in two bunches of 15 and 18 (the
# decoder's, then the encoder's) and live from there:
# 8,870,397,952 (+544 MB, 6.5 %; offline compile, PR 58), re-pinned to
# that reading.  What the chip's allocator made of it: PERF.md
# section 6, PR 58.
_TBASE_STEP_TEMP_BYTES = 8870397952

# A `kind=kOutput` fusion (a convolution with an epilogue) whose result is
# three f32 arrays of one weight's extents: the weight-gradient product
# carrying Adam's update of the parameter and both moments.  49 in the
# parent of PR 54 (24 of `[512,512]`, 12 + 12 of the FFN, the output
# head's `[512,32000]`), where the products ran at 59-73 % of the MXU's
# peak against 86-91 % alone (PERF.md section 6, PR 54).
_UPDATE_IN_A_PRODUCTS_EPILOGUE = re.compile(
    r'= \(f32\[(\d+,\d+)\]\{[^}]*\}, f32\[\1\]\{[^}]*\}, '
    r'f32\[\1\]\{[^}]*\}\) fusion\(')


def test_the_one_chip_tbase_step_holds_no_kernel_of_the_tier(
        one_v5e_chip, monkeypatch):
    """tbase.train_1chip's step (96 x 256 tokens, AMP, Adam; one step of
    the K=8 scan) as XLA:TPU compiles it for one v5e chip: every fused
    group is the inline replay and the embedding lookups are XLA's own
    gather, so the step holds no Mosaic call at all and no value in the
    layouts the DMA gather asked for; no weight-gradient product carries
    its update; the step needs the scratch accounted for above, to
    within 1 %."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.core import emit, passes
    from paddle_tpu.core import executor as em
    from paddle_tpu.models import transformer as tr
    monkeypatch.setenv('PT_CACHE', '0')
    batch, seq, vocab = 96, 256, 32000
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            out = tr.build(src_vocab=vocab, trg_vocab=vocab, max_len=seq,
                           n_layer=6, n_head=8, d_model=512, d_inner=2048,
                           dropout=0.0, lr=2.0, warmup_steps=4000,
                           use_flash=True)
    main.set_amp(True)
    scope = fluid.Scope()
    fluid.Executor().run(startup, scope=scope)      # the state's shapes
    feed = tr.synthetic_batch(np.random.RandomState(0), batch, seq, vocab)

    # repo code asks the backend whether to interpret its Pallas calls
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    feed_names, fetch_names = tuple(sorted(feed)), (out['loss'].name,)
    opt, _ = passes.maybe_optimize(main, fetch_names)
    jit_fn, params_in, _ = em._lower(
        opt, feed_names, fetch_names,
        emit_engine=emit.build_engine(opt, feed_names, fetch_names))

    def sds(v):
        return jax.ShapeDtypeStruct(np.shape(v), v.dtype,
                                    sharding=one_v5e_chip)

    compiled = jit_fn.lower(
        {n: sds(scope.vars[n]) for n in params_in},
        {n: sds(feed[n]) for n in feed_names},
        jax.ShapeDtypeStruct((), jnp.uint32,
                             sharding=one_v5e_chip)).compile()
    lines = compiled.as_text().splitlines()
    mosaic = [ln for ln in lines if 'tpu_custom_call' in ln]
    assert not mosaic, [ln.split('metadata=')[-1][:120] for ln in mosaic]
    relaid = [ln.strip()[:120] for ln in lines
              if 'f32[%d,1,512]' % vocab in ln
              or 'f32[%d,1,512]' % (batch * seq) in ln]
    assert not relaid, relaid[:3]
    fused_updates = [ln.split(' fusion(')[0].strip() for ln in lines
                     if 'kind=kOutput' in ln
                     and _UPDATE_IN_A_PRODUCTS_EPILOGUE.search(ln)]
    assert not fused_updates, fused_updates[:3]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert abs(temp - _TBASE_STEP_TEMP_BYTES) \
        <= 0.01 * _TBASE_STEP_TEMP_BYTES, temp
