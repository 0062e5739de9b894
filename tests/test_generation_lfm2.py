"""A `latent_moe` model whose mixers are the gated short convolution
(shortconv.py: the whole state is the last two rows of its own input) in
the layers ``cfg['mixer']`` marks ``'conv'`` and grouped-query attention
with a norm on every query and key head (the dense block's own code over
the K and V pools) in those it marks ``'gqa'``, two dense layers and then
routed experts chosen through a bias with NO shared expert, the whole
expert layer held (experts.py, ranks = 1), a pool over the layers that
attend and convolution tails alone over the others (kv_cache.py).

Tiny sizes, float32, seeded weights.  The last logits after chunked prefill
(one shifted multiply-add from the slot's tail), a decode window (the single
step, the paged kernel in interpret mode) and one more chunk through pool and
tails are compared with the benchmark's plain reference
(benchmarks/references/lfm2_8b_a1b.py: full forward, a loop over the
experts); and this kind's two launches lower to the text pinned here, as the
other files pin the standing kinds'.
"""
import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.serving.generation import (CacheConfig, DecodeRuntime,
                                           GenerationConfig,
                                           GenerationEngine, SamplingParams,
                                           decode, experts, init_state,
                                           random_weights, shortconv,
                                           weight_names, weight_shapes)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK, WINDOW, PAGE = 8, 3, 4

CFG = {
    'block': 'latent_moe', 'vocab': 97, 'd_model': 64, 'n_layer': 8,
    'n_head': 4, 'n_kv_head': 2, 'head_dim': 16, 'd_ffn': 96, 'theta': 1e6,
    'rms_eps': 1e-5, 'max_len': 64, 'qk_norm': True,
    'mixer': ['conv', 'conv', 'gqa', 'conv', 'conv', 'conv', 'gqa', 'conv'],
    'ffn': ['dense'] * 2 + ['experts'] * 6,
    'conv': {'taps': 3},
    'moe': {'n_routed': 8, 'top_k': 2, 'd_expert': 32, 'n_shared': 0,
            'scale': 1.0, 'norm_eps': 1e-6, 'bias': True, 'ranks': 1,
            'rank': 0}}
# a head of 32 lies four kv heads to a 128-lane row of the pool
PACKED = dict(CFG, n_head=8, n_kv_head=4, head_dim=32)
CONFIGS = {'head16': CFG, 'head32_packed': PACKED}


@pytest.fixture(scope='module')
def reference():
    spec = importlib.util.spec_from_file_location(
        'lfm2_8b_a1b_reference',
        os.path.join(ROOT, 'benchmarks', 'references', 'lfm2_8b_a1b.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _weights(cfg, seed=5):
    """Seeded weights whose head norms' scales are NOT ones (a name that
    ends in `norm` is drawn as ones): a scale in the wrong order would
    show."""
    w = random_weights(cfg, seed=seed, scale=0.3)
    rng = np.random.RandomState(seed + 1)
    for n in w:
        if n.endswith(('att_q_norm', 'att_k_norm')):
            w[n] = (1.0 + 0.5 * rng.randn(*w[n].shape)).astype(np.float32)
    return w


_RUNTIMES = {}


def _runtime(name):
    """One three-slot runtime a configuration for the module (its
    executables compile once), reset before every use."""
    if name not in _RUNTIMES:
        cfg = CONFIGS[name]
        _RUNTIMES[name] = DecodeRuntime(_weights(cfg), cfg, slots=3,
                                        prefill_chunk=CHUNK, page_len=PAGE)
    _RUNTIMES[name].reset()
    return _RUNTIMES[name]


@pytest.fixture
def rt():
    return _runtime('head16')


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, CFG['vocab'], n) \
        .astype(np.int32)


def _prefill(rt, prompt):
    slot = rt.alloc_slot()
    assert rt.try_begin(slot, prompt, WINDOW) == 0
    for off in range(0, prompt.size, CHUNK):
        first, logits = rt.prefill(slot, prompt[off:off + CHUNK], off,
                                   SamplingParams())
    return slot, int(first), np.asarray(logits, np.float32)


def _window(rt, slots, steps=WINDOW):
    active = np.zeros(rt.slots, bool)
    active[list(slots)] = True
    zeros = np.zeros(rt.slots, np.int32)
    return np.asarray(rt.decode_window(
        steps, active, zeros, np.zeros(rt.slots, np.float32), zeros))


def _through_pool_and_tails(rt, prompt):
    """Chunked prefill, one decode window, one more chunk: (context, the
    logits at its last position) as the benchmark's comparison takes
    them."""
    slot, first, _ = _prefill(rt, prompt)
    toks = _window(rt, [slot])[slot]
    assert rt.ensure_capacity(slot, prompt.size + WINDOW + 1)
    _, logits = rt.prefill(slot, toks[-1:], prompt.size + WINDOW,
                           SamplingParams())
    logits = np.asarray(logits, np.float32)
    rt.free_slot(slot)
    return np.concatenate([prompt, [first], toks]).astype(np.int32), logits


def _apart(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# --------------------------------------------- the whole model, in logits

@pytest.mark.parametrize('name', sorted(CONFIGS))
@pytest.mark.parametrize('plen', [5, 19, 24])
def test_chunks_a_window_and_a_chunk_match_the_reference(reference, name,
                                                         plen):
    """Prompts of less than a chunk, of chunks that do not divide them and
    of whole chunks: tails and pages carried across chunks, steps and one
    more chunk."""
    rt = _runtime(name)
    context, got = _through_pool_and_tails(rt, _prompt(plen, seed=plen))
    want = reference.last_logits(rt.w, rt.cfg, context)
    assert _apart(got, want) < 2e-5
    # handed the program's logits, it still answers with its plain pass
    again = reference.last_logits(rt.w, rt.cfg, context, got=got)
    np.testing.assert_array_equal(again, want)


def test_the_reference_controls_are_seen(rt, reference):
    context, got = _through_pool_and_tails(rt, _prompt(19, seed=3))
    sound = _apart(got, reference.last_logits(rt.w, rt.cfg, context))
    assert reference.CONTROLS == ('tail_reset', 'no_qk_norm', 'no_bias',
                                  'no_rope', 'fp8_weights')
    assert reference.READINGS == ('bf16_stream', 'f32_operands')
    # float32 weights: no operand is rounded, with or without the reading
    np.testing.assert_array_equal(
        reference.last_logits(rt.w, rt.cfg, context, control='f32_operands'),
        reference.last_logits(rt.w, rt.cfg, context))
    for control in reference.CONTROLS + ('bf16_stream',):
        wrong = reference.last_logits(rt.w, rt.cfg, context, control=control,
                                      got=got, chunk=CHUNK)
        assert _apart(got, wrong) > 50 * sound, control
    with pytest.raises(ValueError, match='control must be one of'):
        reference.last_logits(rt.w, rt.cfg, context, control='no_such')


def test_bfloat16_operands_are_rounded_on_both_sides(reference):
    """The cell's precision at a tiny size: bfloat16 weights and cache.
    The reference rounds every product's operand and every cached row to
    the weights' dtype and is then nearer the program than the same
    reference with no operand rounded (the reading 'f32_operands')."""
    w = {n: jnp.asarray(a, jnp.bfloat16) for n, a in _weights(CFG).items()}
    rt = DecodeRuntime(w, CFG, slots=3, prefill_chunk=CHUNK, page_len=PAGE,
                       cache_dtype='bfloat16')
    context, got = _through_pool_and_tails(rt, _prompt(19, seed=3))
    rounded = reference.last_logits(rt.w, rt.cfg, context)
    unrounded = reference.last_logits(rt.w, rt.cfg, context,
                                      control='f32_operands')
    assert _apart(rounded, unrounded) > 1e-3
    assert _apart(got, rounded) < 0.5 * _apart(got, unrounded)


def test_qk_norm_off_is_another_model(reference):
    """Without ``qk_norm`` the model has no head norms: two weights fewer
    an attention layer, and other logits on the same weights."""
    bare = {k: v for k, v in CFG.items() if k != 'qk_norm'}
    assert set(weight_names(CFG)) - set(weight_names(bare)) == {
        'layer_%d_att_%s_norm' % (i, s) for i in (2, 6) for s in 'qk'}
    w = _weights(CFG)
    other = DecodeRuntime({n: w[n] for n in weight_names(bare)}, bare,
                          slots=3, prefill_chunk=CHUNK, page_len=PAGE)
    prompt = _prompt(19, seed=3)
    context, got = _through_pool_and_tails(other, prompt)
    normed = reference.last_logits(w, CFG, context)
    assert _apart(got, normed) > 0.05
    assert _apart(got, reference.last_logits(
        w, CFG, context, control='no_qk_norm')) < 2e-5


# ------------------------------------------------ the gated convolution

def _conv_weights(d=16, taps=3, seed=0):
    rng = np.random.RandomState(seed)
    return {'l_' + k: jnp.asarray(0.5 * rng.randn(*s), jnp.float32)
            for k, s in shortconv.weight_shapes(d, {'taps': taps}).items()}


def _by_definition(w, h):
    """The module's docstring, a token at a time in numpy float64."""
    T, D = h.shape
    taps = np.asarray(w['l_conv_taps'], np.float64)
    b, c, x = np.split(h.astype(np.float64)
                       @ np.asarray(w['l_conv_in_w'], np.float64), 3, axis=1)
    u = b * x
    L = taps.shape[0]
    conv = np.zeros_like(u)
    for t in range(T):
        for j in range(L):
            if t - (L - 1) + j >= 0:
                conv[t] += taps[j] * u[t - (L - 1) + j]
    return (c * conv) @ np.asarray(w['l_conv_out_w'], np.float64), u


@pytest.mark.parametrize('chunk', [1, 4, 7, 23])
def test_the_chunk_form_the_step_form_and_the_definition_agree(chunk):
    """Chunks that do not divide the prompt (the last one short, padded),
    then steps: the outputs and the tail are the definition's."""
    cfg = {'conv': {'taps': 3}}
    w, T, D = _conv_weights(), 23, 16
    h = np.random.RandomState(1).randn(T + 4, D).astype(np.float32)
    want, u = _by_definition(w, h)
    tail = jnp.zeros((2, D), jnp.float32)
    outs = []
    for off in range(0, T, chunk):
        n = min(chunk, T - off)
        padded = np.zeros((chunk, D), np.float32)
        padded[:n] = h[off:off + n]
        padded[n:] = 9.0                     # padding is not zeros
        out, tail = shortconv.prefill_mixer(w, 'l_', cfg, jnp.asarray(padded),
                                            tail, jnp.int32(n))
        outs.append(np.asarray(out)[:n])
    np.testing.assert_allclose(np.concatenate(outs), want[:T], rtol=2e-5,
                               atol=2e-5)
    # the tail is left at true_count, not at the padded end
    np.testing.assert_allclose(np.asarray(tail), u[T - 2:T], rtol=2e-5,
                               atol=2e-5)
    # steps from there on: slot 1 is live, slot 0 rides along
    tails = jnp.stack([jnp.full((2, D), 3.0), tail])
    for t in range(T, T + 4):
        out, tails = shortconv.step_mixer(
            w, 'l_', cfg, jnp.asarray(np.stack([h[0], h[t]])), tails,
            jnp.asarray([False, True]))
        np.testing.assert_allclose(np.asarray(out[1]), want[t], rtol=2e-5,
                                   atol=2e-5)
    np.testing.assert_array_equal(np.asarray(tails[0]), 3.0)
    np.testing.assert_allclose(np.asarray(tails[1]), u[T + 2:T + 4],
                               rtol=2e-5, atol=2e-5)


def test_the_mixer_keeps_a_tail_and_nothing_else():
    assert shortconv.SLOTS == ('conv_in_w', 'conv_taps', 'conv_out_w')
    assert shortconv.state_shapes(2048, {'taps': 3}) == (None, (2, 2048))
    cache = CacheConfig(slots=2, layers=1, kv_heads=2, max_len=16,
                        head_dim=8, recurrent=(None, (2, 8)),
                        recurrent_layers=3)
    assert cache.recurrent_shapes() == {'conv': (2, 3, 2, 8)}
    assert cache.recurrent_bytes() == 4 * 2 * 3 * 2 * 8
    assert cache.spec()['recurrent'] == (None, (2, 8))
    st = init_state(cache)
    assert 'ssm' not in st and st['conv'].shape == (2, 3, 2, 8)


def test_a_step_after_a_prefill_is_the_prefill_one_token_longer(rt):
    prompt = _prompt(13, seed=2)
    slot, first, _ = _prefill(rt, prompt)
    _window(rt, [slot], steps=1)
    k, v, n = rt.cache_row(slot)
    tails = np.asarray(rt.state['conv'][slot])
    rt.free_slot(slot)
    slot2, _, _ = _prefill(rt, np.append(prompt, first).astype(np.int32))
    k2, v2, n2 = rt.cache_row(slot2)
    assert n == n2 == 14
    np.testing.assert_allclose(k[:, :, :n], k2[:, :, :n], rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(v[:, :, :n], v2[:, :, :n], rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(tails, np.asarray(rt.state['conv'][slot2]),
                               rtol=2e-5, atol=2e-5)


def test_a_dead_slots_tail_is_untouched_by_a_window(rt):
    a, _, _ = _prefill(rt, _prompt(11, seed=1))
    b, _, _ = _prefill(rt, _prompt(6, seed=2))
    before = np.asarray(rt.state['conv'])
    _window(rt, [a])
    after = np.asarray(rt.state['conv'])
    np.testing.assert_array_equal(after[b], before[b])       # bit for bit
    assert np.abs(after[a] - before[a]).max() > 0


def test_a_reused_slot_starts_from_a_zero_tail(rt, reference):
    slot, _, _ = _prefill(rt, _prompt(17, seed=4))
    rt.free_slot(slot)
    assert np.abs(np.asarray(rt.state['conv'][slot])).max() > 0
    context, got = _through_pool_and_tails(rt, _prompt(9, seed=6))
    assert _apart(got, reference.last_logits(rt.w, rt.cfg, context)) < 2e-5


def test_a_stream_does_not_depend_on_its_neighbours(rt):
    alone = rt.generate(_prompt(10, seed=7), 6, steps_per_window=WINDOW)
    slot, _, _ = _prefill(rt, _prompt(14, seed=8))
    _window(rt, [slot])
    beside = rt.generate(_prompt(10, seed=7), 6, steps_per_window=WINDOW)
    assert alone == beside


def test_the_engine_batches_streams_over_the_tails(rt):
    """Continuous batching through GenerationEngine, unchanged: four
    streams over three slots give the tokens each gives alone."""
    prompts = [_prompt(n, seed=n) for n in (5, 12, 19, 9)]
    alone = [rt.generate(p, 7, steps_per_window=WINDOW) for p in prompts]
    rt.reset()
    engine = GenerationEngine(
        rt, gen_config=GenerationConfig(decode_window=WINDOW)).start()
    try:
        streams = [engine.generate(p, max_new=7) for p in prompts]
        got = [[int(t) for t in s.result(120).outputs[0]] for s in streams]
    finally:
        engine.stop()
    assert got == alone


# ---------------------------------------------------- geometry and names

def test_the_pool_holds_the_layers_that_attend_and_tails_the_others(rt):
    assert rt.recurrent and rt.latent_moe and rt.prefix is None
    assert not rt.state_kernel and not rt.prefill_kernel
    assert rt.cache.layers == 2 and rt.cache.recurrent_layers == 6
    assert rt.cache.latent is None and 'ssm' not in rt.state
    # head 16 over two kv heads has no packed layout: the dense geometry
    assert rt.cache.pool_shape == (3 * 16 + 1, 2, PAGE, 2, 16)
    assert rt.state['v'].shape == rt.state['k'].shape
    assert rt.state['conv'].shape == (3, 6, 2, 64)
    assert [lay.state if lay.pool is None else lay.pool
            for lay in decode._layers(CFG)] == [0, 1, 0, 2, 3, 4, 1, 5]
    assert rt.cache.bytes() == rt.cache.pages * rt.cache.page_bytes() \
        + 4 * rt.state['conv'].size
    packed = _runtime('head32_packed')
    # four kv heads of 32 side by side: one 128-lane row a token a layer
    assert packed.cache.pool_shape == (3 * 16 + 1, 2, PAGE, 1, 128)
    assert packed.paged
    with pytest.raises(ValueError, match='recurrent state'):
        rt._window_exec('verify', WINDOW)


@pytest.mark.parametrize('mixer,message', [
    (['latent', 'gqa'] + ['conv'] * 6, 'attends through'),
    (['gqa', 'kda'] + ['conv'] * 6, 'holds state through'),
    (['conv'] * 8, 'attend in at least one'),
    (['gqa', 'window'] + ['conv'] * 6, 'mixer must name'),
    (['gqa'] * 7, 'mixer must name'),
])
def test_one_pool_geometry_and_one_state_geometry_a_runtime(mixer, message):
    with pytest.raises(ValueError, match=message):
        decode._layers(dict(CFG, mixer=mixer))


def test_weights_follow_the_mixer_and_read_back_bit_for_bit():
    rt = _runtime('head32_packed')
    w = _weights(PACKED)
    shapes = weight_shapes(PACKED)
    assert list(shapes) == weight_names(PACKED)
    assert shapes['layer_0_conv_in_w'] == (64, 192)
    assert shapes['layer_0_conv_taps'] == (3, 64)
    assert shapes['layer_2_att_q_w'] == (64, 256)
    assert shapes['layer_2_att_k_w'] == shapes['layer_2_att_v_w'] == (64, 128)
    assert shapes['layer_2_att_q_norm'] == shapes['layer_2_att_k_norm'] \
        == (32,)
    assert 'layer_0_att_q_w' not in shapes and 'layer_2_conv_in_w' not in \
        shapes
    # no shared expert: no weights for one, not arrays of width zero
    assert not [n for n in shapes if 'shared' in n]
    assert shapes['layer_2_moe_fc1_w'] == (8, 64, 32)
    assert shapes['layer_2_moe_router_bias'] == (8,)
    assert 'layer_1_moe_router_w' not in shapes
    # q, k and v of the attention layers are held prepared, once
    assert 'layer_2_att_q_wt' in rt.params and 'layer_2_att_q_w' not in \
        rt.params
    for n in weight_names(PACKED):
        np.testing.assert_array_equal(np.asarray(rt.w[n]), w[n], err_msg=n)


def test_the_cache_row_is_the_normed_rotated_key_in_the_public_order(
        reference):
    """`cache_row` undoes both layouts: kv heads side by side in a row and
    rotated halves within a head."""
    rt = _runtime('head32_packed')
    prompt = _prompt(11, seed=9)
    slot, _, _ = _prefill(rt, prompt)
    k, v, n = rt.cache_row(slot)
    assert n == 11 and k.shape == v.shape == (2, 4, 64, 32)
    # layer 2's keys by the reference's pieces on layer 2's own input: the
    # stream after two convolution layers
    w = {name: jnp.asarray(rt.w[name]) for name in weight_names(PACKED)}
    x = w['tok_emb'][jnp.asarray(prompt)]
    for i in range(2):
        p = 'layer_%d_' % i
        lw = {s: w[p + s] for s in ('att_norm', 'conv_in_w', 'conv_taps',
                                    'conv_out_w')}
        x = reference._conv(x, lw, 11, 1e-5, None, CHUNK)
        h = reference._rms(x, w[p + 'ffn_norm'], 1e-5)
        x = x + reference._swiglu(h, w[p + 'ffn_fc1_w'], w[p + 'ffn_fc3_w'],
                                  w[p + 'ffn_fc2_w'], None)
    h = reference._rms(x, w['layer_2_att_norm'], 1e-5)
    keys = reference._rms((h @ w['layer_2_att_k_w']).reshape(11, 4, 32),
                          w['layer_2_att_k_norm'], 1e-5)
    keys = reference._rope(keys, jnp.arange(11), 1e6)
    np.testing.assert_allclose(k[0, :, :11], np.asarray(keys).transpose(
        1, 0, 2), rtol=2e-4, atol=2e-4)
    vals = (h @ w['layer_2_att_v_w']).reshape(11, 4, 32)
    np.testing.assert_allclose(v[0, :, :11], np.asarray(vals).transpose(
        1, 0, 2), rtol=2e-4, atol=2e-4)


# ------------------------------------------------------ the expert layer

def _layer_weights(moe, d=32, seed=0, scale=0.3):
    rng = np.random.RandomState(seed)
    return {k: jnp.asarray(scale * rng.randn(*s), jnp.float32)
            for k, s in experts.weight_shapes(d, moe).items()}


MOE = {'n_routed': 16, 'top_k': 4, 'd_expert': 24, 'n_shared': 0,
       'scale': 1.0, 'norm_eps': 1e-6, 'bias': True}


def _whole_layer(reference, w, h):
    """The reference's expert layer on h, without the residual."""
    whole = dict(MOE, ranks=1, rank=0)
    g, b, picks, wts, _ = reference._router(
        h, w['moe_router_w'], w['moe_router_bias'], whole, None)
    return sum(reference._expert(h, w['moe_fc1_w'][e], w['moe_fc3_w'][e],
                                 w['moe_fc2_w'][e], picks, wts, e, None)
               for e in range(16)), picks, wts


def test_the_whole_layer_without_a_shared_expert_is_the_references(
        reference):
    whole = dict(MOE, ranks=1, rank=0)
    w = _layer_weights(whole)
    assert set(w) == {'moe_router_w', 'moe_router_bias', 'moe_fc1_w',
                      'moe_fc3_w', 'moe_fc2_w'}
    h = jnp.asarray(np.random.RandomState(1).randn(23, 32), jnp.float32)
    got, stats = experts.expert_layer(
        {'l_' + k: v for k, v in w.items()}, 'l_', {'moe': whole}, h,
        jnp.ones(23, bool))
    want, picks, wts = _whole_layer(reference, w, h)
    assert int(stats[0]) == int(stats[1]) * 4 == 23 * 4    # every pair, here
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    # the source's + 1e-6 under the weights, and the bias in none of them
    mine = experts.route(h, w['moe_router_w'], whole, w['moe_router_bias'])
    np.testing.assert_array_equal(np.sort(np.asarray(mine[0]), 1),
                                  np.sort(np.asarray(picks), 1))
    g = jax.nn.sigmoid(h @ w['moe_router_w'])
    gp = jnp.take_along_axis(g, mine[0], 1)
    np.testing.assert_allclose(
        np.asarray(mine[1]), np.asarray(gp / (gp.sum(1, keepdims=True)
                                              + 1e-6)), rtol=1e-6)
    assert float(jnp.abs(mine[1].sum(1) - 1.0).max()) < 1e-5
    plain = experts.route(h, w['moe_router_w'], dict(whole, norm_eps=0),
                          w['moe_router_bias'])
    assert float(jnp.abs(plain[1] - mine[1]).max()) > 0


@pytest.mark.parametrize('ranks', [2, 4])
def test_the_shares_add_up_to_the_whole_layer(reference, ranks):
    """Every rank routes over all experts (through the choice bias) and
    adds its own experts' part; with no shared expert the shares alone are
    the whole layer."""
    w = _layer_weights(dict(MOE, ranks=1, rank=0))
    h = jnp.asarray(np.random.RandomState(1).randn(23, 32), jnp.float32)
    valid = jnp.ones(23, bool)
    want, _, _ = _whole_layer(reference, w, h)
    total, assignments = 0.0, 0
    for r in range(ranks):
        part = dict(MOE, ranks=ranks, rank=r)
        first, n = experts.held(part)
        assert (first, n) == (16 // ranks * r, 16 // ranks)
        held = {'l_' + k: (v[first:first + n] if k.startswith('moe_fc')
                           else v) for k, v in w.items()}
        assert {k[2:]: v.shape for k, v in held.items()} \
            == experts.weight_shapes(32, part)
        y, st = experts.expert_layer(held, 'l_', {'moe': part}, h, valid)
        total = total + y
        assignments += int(st[0])
    assert assignments == 23 * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------- counters, scopes, text

def _lower(rt, kind):
    S, sds = rt.slots, rt._sds
    i32, f32 = sds((), jnp.int32), sds((), jnp.float32)
    if kind == 'prefill':
        fn = decode._prefill_fn(rt.cfg, rt.cache, CHUNK, kernels=rt.kernels)
        args = [rt._param_structs(), rt._state_structs(),
                sds((rt.cache.max_pages,), jnp.int32),
                sds((CHUNK,), jnp.int32), i32, i32, i32, i32, f32, i32]
    else:
        fn = decode._decode_fn(rt.cfg, rt.cache, WINDOW, rt.kernels)
        args = [rt._param_structs(), rt._state_structs(), rt._bt_struct(S),
                sds((S,), jnp.bool_), sds((S,), jnp.int32),
                sds((S,), jnp.float32), sds((S,), jnp.int32)]
    return jax.jit(fn, donate_argnums=(1,)).lower(*args)


def test_the_launches_carry_the_scopes(rt):
    window = _lower(rt, 'decode').as_text(debug_info=True)
    chunk = _lower(rt, 'prefill').as_text(debug_info=True)
    for scope in ('shortconv.project', 'shortconv.taps', 'shortconv.out',
                  'attention.qk_norm', 'attn.qkv', 'kv.write', 'attn.scores',
                  'moe.route', 'moe.experts'):
        assert scope in window and scope in chunk, scope
    assert 'moe.shared' not in window and 'moe.shared' not in chunk
    # the step attends in place, the chunk over gathered rows
    assert 'paged_attention' in window and 'kv.gather' not in window
    assert 'kv.gather' in chunk and 'paged_attention' not in chunk


def test_the_counters_follow_the_tails_and_the_live_rows():
    cfg = CFG
    before = obs.counters()
    rt = DecodeRuntime(_weights(cfg), cfg, slots=3, prefill_chunk=CHUNK,
                       page_len=PAGE, prefix_cache=True)
    assert obs.counters()['generation.recurrent_state_bytes'] \
        == 4 * 3 * 6 * 2 * 64
    a, _, _ = _prefill(rt, _prompt(11, seed=1))
    b, _, _ = _prefill(rt, _prompt(6, seed=2))
    _window(rt, [a, b])
    _window(rt, [a])
    int(_window(rt, [a])[a, -1])                  # a read moves the stats
    now = obs.counters()

    def moved(name):
        return now.get(name, 0) - before.get(name, 0)

    assert moved('generation.compiles') == 2
    assert moved('generation.state_resets') == 2
    assert moved('generation.prefix_refused_recurrent') == 2
    # no in-place kernel over the tails: every slot's are read and written
    assert moved('generation.state_slot_steps') == 3 * 3 * WINDOW
    assert moved('generation.state_live_slot_steps') == 4 * WINDOW
    # the kernel reads whole pages of the live streams' rows, per layer
    lens = [[11, 6], [14], [17]]
    live = sum(n + j for row in lens for n in row
               for j in range(1, WINDOW + 1))
    rows = sum(-(-(n + j) // PAGE) * PAGE for row in lens for n in row
               for j in range(1, WINDOW + 1))
    assert moved('generation.kv_tokens_live') == live
    assert moved('generation.kv_rows_read') == rows < 3 * 3 * WINDOW * 64
    # ranks = 1: every pair of every routed token, in six expert layers
    assert moved('generation.window_moe_tokens') == 6 * 4 * WINDOW
    assert moved('generation.window_moe_assignments') == 2 * 6 * 4 * WINDOW
    assert 0 < moved('generation.window_moe_experts_touched') \
        <= 8 * 6 * 3 * WINDOW
    assert moved('generation.window_moe_touched_only_calls') \
        == 6 * 3 * WINDOW
    assert moved('generation.latent_rows_read') == 0
    assert 'generation.kda_state_bytes' not in now \
        or moved('generation.kda_state_bytes') == 0


# sha256 of this kind's two launches' lowered StableHLO as PR 63 left them
# (taken with `_lower` above at CHUNK 8, WINDOW 3, three slots, pages of 4):
# a PR that changes one on purpose re-pins it and says why.
PINNED_SHA256 = {
    ('head16', 'prefill'): 
        '08eae818a619522ad4e041c40e7bcd9e966dd782986961238ad71ebfc40c26db',
    ('head16', 'decode'): 
        'f1abd177fc2a3fc26ea353791f35f532f87e78441f51e2ade5420e50d2026a36',
    ('head32_packed', 'prefill'): 
        'c92f775649800b8ef30c8e27cc523bcaf47a5df3d05bc448da4ce9d38015ee2c',
    ('head32_packed', 'decode'): 
        '8e063b42bf01f08aaf7a3ce91f3586b32dc876ecfa10c3fa1ada6d7b680d66f3',
}


@pytest.mark.parametrize('which', sorted(PINNED_SHA256), ids='-'.join)
def test_the_two_launches_lower_to_the_pinned_text(which):
    text = _lower(_runtime(which[0]), which[1]).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED_SHA256[which]
