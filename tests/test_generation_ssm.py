"""The Falcon-H1 block (`block: 'falcon_h1'`) on the serving path:
attention and a Mamba-2 mixer side by side in every layer, recurrent
state per slot beside the paged pool.

Tiny sizes, float32, seeded weights with SLOW decay (dt * A about -0.01,
where the benchmark's draw forgets within a few tokens): a state lost or
mangled at ANY hand-off (chunk -> chunk, chunk -> window, window -> chunk,
slot -> next owner) moves the last logits, which are compared with the
benchmark's plain reference (benchmarks/references/falconh1_34b.py: full
forward, sequential recurrence).
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.ops import sampling as ops_sampling
from paddle_tpu.serving.generation import (DecodeRuntime, GenerationConfig,
                                           GenerationEngine, SamplingParams,
                                           init_state, random_weights, ssm,
                                           weight_names)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK, WINDOW = 8, 3

CFG = {
    'block': 'falcon_h1', 'vocab': 97, 'd_model': 32, 'n_layer': 2,
    'n_head': 4, 'n_kv_head': 2, 'head_dim': 16, 'd_ffn': 64,
    'theta': 1e4, 'rms_eps': 1e-5, 'max_len': 64,
    # blocks of 4 inside a chunk of 8: the scan carries its state across
    # a block boundary inside one launch too
    'ssm': {'d_ssm': 48, 'n_heads': 6, 'n_groups': 2, 'd_state': 8,
            'd_conv': 4, 'chunk': 4},
    'multipliers': {'embedding': 2.0, 'lm_head': 0.5, 'attention_in': 1.0,
                    'attention_out': 0.5, 'key': 0.5, 'ssm_in': 0.5,
                    'ssm_out': 0.7, 'ssm': [0.5, 0.6, 0.7, 0.8, 0.9],
                    'mlp_gate': 0.8, 'mlp_down': 0.6}}
DENSE = {k: v for k, v in CFG.items()
         if k not in ('block', 'ssm', 'multipliers', 'head_dim', 'rms_eps')}


@pytest.fixture(scope='module')
def reference():
    spec = importlib.util.spec_from_file_location(
        'falconh1_reference',
        os.path.join(ROOT, 'benchmarks', 'references', 'falconh1_34b.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope='module')
def weights():
    w = random_weights(CFG, seed=3, scale=0.2)
    rng = np.random.RandomState(4)
    for i in range(CFG['n_layer']):
        # A about -0.02, dt about 0.5: a position keeps 99 % of the state
        w['layer_%d_ssm_A_log' % i] = (np.log(0.02) + 0.1 * rng.randn(
            CFG['ssm']['n_heads'])).astype(np.float32)
        w['layer_%d_ssm_dt_bias' % i] = (-0.5 + 0.1 * rng.randn(
            CFG['ssm']['n_heads'])).astype(np.float32)
    return w


def _runtime(weights, slots=3, **kw):
    return DecodeRuntime(weights, CFG, slots=slots, prefill_chunk=CHUNK,
                         page_len=4, **kw)


@pytest.fixture
def rt(weights, _shared=[]):
    """One three-slot runtime for the module (its executables compile
    once), reset before every test; asked for the prefix cache, which it
    must forgo."""
    if not _shared:
        _shared.append(_runtime(weights, prefix_cache=True))
    _shared[0].reset()
    return _shared[0]


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, CFG['vocab'], n) \
        .astype(np.int32)


def _serve(rt, prompt, slot=None, before_last_chunk=None, others=()):
    """What the benchmark's comparison does: chunked prefill, one decode
    window, one more one-token chunk.  Returns (context, last logits)."""
    slot = rt.alloc_slot() if slot is None else slot
    assert rt.try_begin(slot, prompt, WINDOW) == 0
    for off in range(0, prompt.size, CHUNK):
        first, _ = rt.prefill(slot, prompt[off:off + CHUNK], off,
                              SamplingParams())
    active = np.zeros(rt.slots, bool)
    active[[slot] + list(others)] = True
    zeros = np.zeros(rt.slots, np.int32)
    toks = rt.decode_window(WINDOW, active, zeros,
                            np.zeros(rt.slots, np.float32), zeros)[slot]
    assert rt.ensure_capacity(slot, prompt.size + WINDOW + 1)
    if before_last_chunk:
        before_last_chunk(rt, slot)
    _, logits = rt.prefill(slot, toks[-1:], prompt.size + WINDOW,
                           SamplingParams())
    context = np.concatenate([prompt, [first], toks]).astype(np.int32)
    return slot, context, np.asarray(logits, np.float32)


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ------------------------------------------------------------------ (a)

@pytest.mark.parametrize('plen', [5, 8, 13, 19])
def test_chunks_a_window_and_a_chunk_match_the_reference(rt, reference, plen):
    """1, 2 and 3 chunks, ragged and full last chunks."""
    _, context, got = _serve(rt, _prompt(plen, seed=plen))
    want = reference.last_logits(rt.w, CFG, context)
    assert _rel(got, want) < 2e-4


@pytest.mark.parametrize('lost', ['ssm', 'conv'])
def test_a_lost_state_moves_the_logits(rt, reference, lost):
    """The comparison's power: zero one kind of state before the last
    chunk and the logits leave the reference by far more than rounding."""
    def lose(rt, slot):
        rt.state = dict(rt.state,
                        **{lost: rt.state[lost].at[slot].set(0.0)})
    _, context, got = _serve(rt, _prompt(13, seed=13), before_last_chunk=lose)
    assert _rel(got, reference.last_logits(rt.w, CFG, context)) > 5e-3


# ------------------------------------------------------------------ (b)

@pytest.mark.parametrize('block,pad', [(4, 0), (4, 3), (12, 5), (2, 0)])
def test_chunk_scan_matches_the_sequential_recurrence(block, pad):
    rng = np.random.RandomState(block + pad)
    T, H, P, G, N = 12, 6, 5, 2, 7
    x = rng.randn(T, H, P).astype(np.float32)
    dt = np.log1p(np.exp(rng.randn(T, H))).astype(np.float32)
    dt[T - pad:] = 0.0              # padding: neither decays nor feeds
    A = -np.exp(rng.randn(H)).astype(np.float32)
    B = rng.randn(T, G, N).astype(np.float32)
    C = rng.randn(T, G, N).astype(np.float32)
    D = rng.randn(H).astype(np.float32)
    S0 = rng.randn(H, P, N).astype(np.float32)
    y, S = ssm.scan_chunk(x, dt, A, B, C, D, S0, block)
    want_S, want_y = jnp.asarray(S0)[None], []
    for t in range(T):
        yt, want_S = ssm.scan_step(x[t][None], dt[t][None], A, B[t][None],
                                   C[t][None], D, want_S)
        if t == T - pad - 1:
            at_true_count = want_S
        want_y.append(yt[0])
    np.testing.assert_allclose(y, np.stack(want_y), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(S, want_S[0], rtol=2e-4, atol=2e-4)
    # the state after the padding is the state at the last real position
    np.testing.assert_array_equal(np.asarray(want_S),
                                  np.asarray(at_true_count))


# ------------------------------------------------------------------ (c)

def test_a_reused_slot_starts_from_zeros(rt):
    slot, _, fresh = _serve(rt, _prompt(13, seed=2))
    rt.reset()
    assert _serve(rt, _prompt(19, seed=1))[0] == slot
    assert float(jnp.abs(rt.state['ssm'][slot]).max()) > 0
    rt.free_slot(slot)
    before = obs.counters().get('generation.state_resets', 0)
    again, _, got = _serve(rt, _prompt(13, seed=2))
    assert again == slot
    assert obs.counters()['generation.state_resets'] == before + 1
    np.testing.assert_array_equal(got, fresh)
    rt.reset()
    assert float(jnp.abs(rt.state['ssm']).max()) == 0
    assert float(jnp.abs(rt.state['conv']).max()) == 0


# ------------------------------------------------------------------ (d)

def test_a_stream_does_not_depend_on_its_neighbours(rt):
    alone = _serve(rt, _prompt(13, seed=5))[2]
    rt.reset()
    other, _, _ = _serve(rt, _prompt(19, seed=6))      # slot 0 stays live
    slot, _, got = _serve(rt, _prompt(13, seed=5), others=[other])
    assert slot != other
    np.testing.assert_allclose(got, alone, rtol=1e-5, atol=1e-6)


def test_an_inactive_slots_state_is_bit_identical_after_a_window(rt):
    idle, _, _ = _serve(rt, _prompt(13, seed=7))
    live, _, _ = _serve(rt, _prompt(8, seed=8))
    kept = {k: np.asarray(rt.state[k][idle]) for k in ('ssm', 'conv')}
    moved = np.asarray(rt.state['ssm'][live])
    before = obs.counters()
    active = np.zeros(rt.slots, bool)
    active[live] = True
    zeros = np.zeros(rt.slots, np.int32)
    rt.decode_window(WINDOW, active, zeros, np.zeros(rt.slots, np.float32),
                     zeros)
    for k, was in kept.items():
        np.testing.assert_array_equal(np.asarray(rt.state[k][idle]), was)
    assert np.abs(np.asarray(rt.state['ssm'][live]) - moved).max() > 0
    # the kernel touched the live slot's state alone
    assert rt.state_kernel
    after = obs.counters()
    assert after['generation.state_slot_steps'] \
        - before.get('generation.state_slot_steps', 0) == WINDOW
    assert after['generation.state_live_slot_steps'] \
        - before.get('generation.state_live_slot_steps', 0) == WINDOW


def test_the_window_lowers_the_kernel_route_and_not_the_composed_one(weights):
    before = obs.counters()
    fresh = _runtime(weights, slots=2)
    assert fresh.state_kernel
    fresh.warmup(steps=WINDOW)
    after = obs.counters()
    assert after['ssm.step_kernel'] > before.get('ssm.step_kernel', 0)
    assert after.get('ssm.step_composed', 0) \
        == before.get('ssm.step_composed', 0)


def test_the_engine_batches_streams_over_recurrent_state(rt):
    """Continuous batching through GenerationEngine: four streams over
    three slots, admitted together, give the tokens each gives alone."""
    prompts = [_prompt(n, seed=n) for n in (5, 13, 19, 9)]
    alone = [rt.generate(p, 7, steps_per_window=WINDOW) for p in prompts]
    rt.reset()
    engine = GenerationEngine(rt, gen_config=GenerationConfig(
        decode_window=WINDOW)).start()
    try:
        streams = [engine.generate(p, max_new=7) for p in prompts]
        got = [[int(t) for t in s.result(60).outputs[0]] for s in streams]
    finally:
        engine.stop()
    assert got == alone


# ------------------------------------------------------------------ (e)

def test_a_prompt_that_would_hit_the_prefix_cache_is_prefilled_whole(
        rt, weights, reference):
    assert rt.prefix is None
    again = _runtime(weights, slots=1, prefix_cache=False)   # sets the gauge
    assert obs.metrics.gauge('generation.recurrent_state_bytes').snapshot() \
        == again.cache.recurrent_bytes() > 0
    assert rt.pool_snapshot()['recurrent_state_bytes'] \
        == 4 * (rt.state['ssm'].size + rt.state['conv'].size)
    prompt = _prompt(19, seed=9)
    slot, _, _ = _serve(rt, prompt)
    assert rt.promote_prefix(slot, prompt) == 0
    rt.free_slot(slot)
    before = obs.counters()['generation.prefix_refused_recurrent']
    # the same prompt again: a dense runtime would skip its full pages
    _, context, got = _serve(rt, prompt)       # asserts try_begin gave 0
    assert obs.counters()['generation.prefix_refused_recurrent'] \
        == before + 1
    assert _rel(got, reference.last_logits(rt.w, CFG, context)) < 2e-4
    dense = DecodeRuntime(random_weights(DENSE, seed=3), DENSE, slots=2,
                          prefill_chunk=CHUNK, page_len=4)
    assert dense.prefix is not None and not dense.recurrent
    assert dense.cache.recurrent_bytes() == 0


# ------------------------------------------------------------------ (f)

def test_speculative_decode_is_refused(rt):
    with pytest.raises(ValueError, match='cannot be rolled back'):
        GenerationEngine(rt, gen_config=GenerationConfig(speculative=True))
    with pytest.raises(ValueError, match='cannot be rolled back'):
        rt.warmup(steps=WINDOW, speculative=True)
    with pytest.raises(ValueError, match='cannot be rolled back'):
        rt.verify_window(WINDOW, np.zeros((rt.slots, WINDOW), np.int32),
                         np.ones(rt.slots, bool), np.zeros(rt.slots),
                         np.zeros(rt.slots), np.zeros(rt.slots))
    with pytest.raises(ValueError, match='ring prefill'):
        rt.prefill_ring(0, _prompt(16), SamplingParams())


# ------------------------------------------------------------------ (g)

def _sample_as_before(logits, seeds, positions, temps, top_ks):
    """`sample_tokens_at` as it was before the sort went under a cond."""
    def row(lg, key, temperature, top_k):
        lg = lg.astype(jnp.float32)
        v = lg.shape[-1]
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        k = jnp.clip(jnp.asarray(top_k, jnp.int32), 0, v)
        thresh = (-jnp.sort(-lg, axis=-1))[jnp.clip(k - 1, 0, v - 1)]
        allowed = jnp.where(k > 0, lg >= thresh, True)
        temp = jnp.asarray(temperature, jnp.float32)
        scaled = jnp.where(allowed, lg, -1e30) / jnp.where(temp > 0, temp, 1.)
        drawn = jax.random.categorical(key, scaled).astype(jnp.int32)
        return jnp.where(temp > 0, drawn, greedy)
    keys = jax.vmap(ops_sampling.token_key)(seeds, positions)
    return jax.vmap(row)(logits, keys, temps, top_ks)


@pytest.mark.parametrize('temps,top_ks', [
    ([0, 0, 0, 0], [0, 5, 0, 3]),            # all greedy: no sort, no draw
    ([.7, 0, 1.3, 0], [0, 4, 0, 0]),         # draws, nobody restricts: no sort
    ([.7, 0, 1.3, .2], [3, 4, 0, 1]),        # some row sorts: all as before
    ([.9, .9, .9, .9], [2, 2, 2, 2])])
def test_sampling_gives_the_tokens_it_gave_with_the_sort_skipped_or_not(
        temps, top_ks):
    rng = np.random.RandomState(11)
    logits = jnp.asarray(rng.randn(4, 211) * 3, jnp.float32)
    seeds = jnp.asarray([3, 1, 4, 1], jnp.int32)
    positions = jnp.asarray([15, 9, 2, 6], jnp.int32)
    temps = jnp.asarray(temps, jnp.float32)
    top_ks = jnp.asarray(top_ks, jnp.int32)
    want = _sample_as_before(logits, seeds, positions, temps, top_ks)
    got = jax.jit(ops_sampling.sample_tokens_at)(logits, seeds, positions,
                                                 temps, top_ks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for i in range(4):       # and the one-row form prefill samples with
        one = ops_sampling.sample_logits(
            logits[i], ops_sampling.token_key(seeds[i], positions[i]),
            temps[i], top_ks[i])
        assert int(one) == int(want[i])


def test_the_sort_sits_under_a_cond():
    """No sort outside a conditional in the batch sampler's program."""
    args = (jnp.zeros((4, 64)), jnp.zeros(4, jnp.int32),
            jnp.zeros(4, jnp.int32), jnp.zeros(4), jnp.zeros(4, jnp.int32))
    jaxpr = jax.make_jaxpr(ops_sampling.sample_tokens_at)(*args)
    top = [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert 'cond' in top and 'sort' not in top
    assert 'sort' in str(jaxpr)


# ------------------------------------------------------------------ (h)

def test_the_dense_decoder_is_what_it_was():
    per_layer = ['att_q_w', 'att_k_w', 'att_v_w', 'att_o_w', 'att_norm',
                 'ffn_norm', 'ffn_fc1_w', 'ffn_fc2_w', 'ffn_fc3_w']
    assert weight_names(DENSE) == ['tok_emb', 'final_norm', 'lm_proj_w'] + [
        'layer_%d_%s' % (i, s) for i in range(2) for s in per_layer]
    w = random_weights(DENSE, seed=0)
    assert sorted(w) == sorted(weight_names(DENSE))
    rt = DecodeRuntime(w, DENSE, slots=2, prefill_chunk=CHUNK, page_len=4)
    assert sorted(rt.state) == ['k', 'lengths', 'tok', 'v']
    assert rt.cache.head_dim == DENSE['d_model'] // DENSE['n_head']
    assert 'recurrent' not in rt.cache.spec()
    assert sorted(init_state(rt.cache)) == ['k', 'lengths', 'tok', 'v']
    # the block kinds differ by the mixer's names alone
    extra = set(weight_names(CFG)) - set(weight_names(DENSE))
    assert extra == {'layer_%d_%s' % (i, s) for i in range(2)
                     for s in ssm.SLOTS}
    with pytest.raises(ValueError, match='block must be'):
        weight_names(dict(DENSE, block='mamba'))


def test_head_dim_and_attention_output_shape_follow_the_model_dict():
    """`att_o_w` is [heads * head_dim, d_model], as the benchmark's runner
    shapes it, also where heads * head_dim is not d_model."""
    wide = dict(DENSE, head_dim=16)                   # 4 * 16 = 64 != 32
    w = random_weights(wide, seed=0)
    assert w['layer_0_att_o_w'].shape == (64, 32)
    assert w['layer_0_att_q_w'].shape == (32, 64)
    rt = DecodeRuntime(w, wide, slots=2, prefill_chunk=CHUNK, page_len=4)
    assert rt.cache.head_dim == 16
    assert len(rt.generate(_prompt(11), 5, steps_per_window=2)) == 5
