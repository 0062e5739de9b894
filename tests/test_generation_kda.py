"""A `latent_moe` model with a MIXER PER LAYER on the serving path: Kimi
Delta Attention (kda.py: a gated delta rule over a float32 matrix state a
head with a decay a channel) in the layers ``cfg['mixer']`` marks ``'kda'``,
latent attention without a low-rank query step and without positions
(latent.py) in the others, routed experts chosen through a choice bias
(experts.py), a pool over the layers that attend and recurrent state over
those that hold one (kv_cache.py).

Tiny sizes, float32, seeded weights.  The last logits after chunked prefill
(the chunk form), a decode window (the single step) and one more chunk
through pool and state are compared with the benchmark's plain reference
(benchmarks/references/kimi_linear.py: full forward, the delta rule a token at
a time, a loop over the experts held); and the three block kinds that stood
before lower to the text they lowered to.
"""
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.serving.generation import (CacheConfig, DecodeRuntime,
                                           GenerationConfig,
                                           GenerationEngine, SamplingParams,
                                           decode, experts, init_state, kda,
                                           latent, random_weights,
                                           weight_names, weight_shapes)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK, WINDOW, PAGE = 8, 3, 4

CFG = {
    'block': 'latent_moe', 'vocab': 97, 'd_model': 32, 'n_layer': 4,
    'n_head': 4, 'd_ffn': 48, 'theta': 1e4, 'rms_eps': 1e-5, 'max_len': 64,
    'ffn': ['dense', 'experts', 'experts', 'experts'],
    'mixer': ['kda', 'kda', 'latent', 'kda'],
    'latent': {'q_rank': None, 'kv_rank': 16, 'nope': 8, 'rope': 4, 'v': 8,
               'rotate': False},
    'kda': {'n_heads': 3, 'head_dim': 8, 'd_conv': 4, 'gate_rank': 6,
            'dt_shift': -2.0},
    'moe': {'n_routed': 16, 'top_k': 4, 'd_expert': 24, 'n_shared': 1,
            'scale': 2.446, 'ranks': 4, 'rank': 1, 'bias': True}}


@pytest.fixture(scope='module')
def reference():
    spec = importlib.util.spec_from_file_location(
        'kimi_linear_reference',
        os.path.join(ROOT, 'benchmarks', 'references', 'kimi_linear.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope='module')
def weights():
    return random_weights(CFG, seed=5, scale=0.3)


@pytest.fixture
def rt(weights, _shared=[]):
    """One three-slot runtime for the module (its executables compile
    once), reset before every test."""
    if not _shared:
        _shared.append(DecodeRuntime(weights, CFG, slots=3,
                                     prefill_chunk=CHUNK, page_len=PAGE))
    _shared[0].reset()
    return _shared[0]


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, CFG['vocab'], n) \
        .astype(np.int32)


def _own_axes(cfg):
    """Each layer's index on the axis of what it stores: the pool's
    layer axis where it attends, the recurrent arrays' where it holds
    state."""
    return [lay.state if lay.pool is None else lay.pool
            for lay in decode._layers(cfg)]


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


def _through_pool_and_state(rt, prompt):
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


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ------------------------------------------------ against the reference

@pytest.mark.parametrize('plen', [5, 8, 19, 30])
def test_chunks_a_window_and_a_chunk_match_the_reference(rt, reference,
                                                         plen):
    """One chunk, exactly one, three with the last ending mid-chunk, four:
    the state is carried from chunk to chunk, into the window and out of
    it."""
    context, got = _through_pool_and_state(rt, _prompt(plen, plen))
    want = reference.last_logits(rt.w, CFG, context)
    assert _rel(got, want) < 2e-4


@pytest.mark.parametrize('control', ['no_delta', 'mean_decay', 'bf16_state',
                                     'no_pe', 'chunk_reset', 'fp8_weights'])
def test_the_reference_controls_are_seen(rt, reference, control):
    """Each way of making the reference wrong moves the logits by far more
    than the sound reference differs from the program."""
    context, got = _through_pool_and_state(rt, _prompt(19, 3))
    sound = _rel(got, reference.last_logits(rt.w, CFG, context))
    wrong = _rel(got, reference.last_logits(rt.w, CFG, context,
                                            control=control, chunk=CHUNK))
    assert sound < 2e-4 and wrong > 20 * sound and wrong > 2e-3, \
        (control, sound, wrong)


def _routing_line(capsys):
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('routing: ')]
    return json.loads(lines[-1][len('routing: '):])


def test_the_reference_resolves_near_ties_in_two_layers_at_once(
        rt, reference, capsys, monkeypatch):
    """In float32 the program's picks ARE the reference's: the plain
    selection lies within the limit and nothing is searched.  A program whose
    LAST chunk is made to pick, in TWO expert layers, another held expert
    than the plain choice gives logits the reference reaches where those
    selections lie within NEAR_TIE: layer by layer, the second layer's
    options taken anew on the stream the first flip moved; and does NOT reach
    where they lie outside the band."""
    prompt = _prompt(21, 13)
    recorded = []

    def served(forced=None):
        rt.reset()
        slot, first, _ = _prefill(rt, prompt)
        toks = _window(rt, [slot])[slot]
        assert rt.ensure_capacity(slot, prompt.size + WINDOW + 1)
        plain_select, calls = experts.select, []

        def select(scores, moe, bias=None):
            picks = plain_select(scores, moe, bias)
            calls.append(None)
            chosen = (forced or {}).get(len(calls) - 1)
            if chosen is not None:               # the compared token is row 0
                picks = picks.at[0].set(jnp.asarray(chosen, picks.dtype))
            return picks

        if forced:
            monkeypatch.setattr(experts, 'select', select)
            plain_exec = rt._execs.pop(('prefill', CHUNK))
        try:
            _, logits = rt.prefill(slot, toks[-1:], prompt.size + WINDOW,
                                   SamplingParams())
        finally:
            if forced:
                monkeypatch.setattr(experts, 'select', plain_select)
                rt._execs[('prefill', CHUNK)] = plain_exec
        rt.free_slot(slot)
        return np.concatenate([prompt, [first], toks]).astype(np.int32), \
            np.asarray(logits, np.float32)

    context, got = served()
    want = reference.last_logits(rt.w, CFG, context, got=got,
                                 picks_out=recorded)
    said = _routing_line(capsys)
    assert _rel(got, want) < 2e-4 and said['taken'] == []
    assert said['compared_with_logits'] and len(recorded) == 3
    assert said['weighed'] == []        # within the limit: nothing weighed
    # a band that admits every set, against logits that are no selection's:
    # every expert layer's selections are weighed, each once, and none that
    # is taken lies farther from those logits than the unmoved stream did
    held = set(range(4, 8))
    monkeypatch.setattr(reference, 'NEAR_TIE', 50.0)
    off = got + 0.2 * np.linalg.norm(got) / np.sqrt(got.size) \
        * np.random.RandomState(0).randn(got.size).astype(np.float32)
    out = reference.last_logits(rt.w, CFG, context, got=off)
    wide = _routing_line(capsys)
    assert {w['layer'] for w in wide['weighed']} == {1, 2, 3}
    assert len(wide['weighed']) == 3 * (2 ** len(held) - 1)
    assert _rel(off, out) <= _rel(off, want)

    def single(layer):
        """The compared position's plain set in that expert layer with a
        held expert in place of the last-ranked pick held elsewhere."""
        ranked = recorded[layer - 1][0][-1].tolist()
        add = next(e for e in sorted(held) if e not in ranked)
        drop = [e for e in ranked if e not in held][-1]
        return sorted(set(ranked) - {drop} | {add})

    # the program flips in expert layers 1 and 3 (the first and the third
    # `select` of the chunk)
    flips = {1: single(1), 3: single(3)}
    context2, got2 = served(forced={0: flips[1], 2: flips[3]})
    assert np.array_equal(context2, context) and _rel(got2, got) > 1e-3
    monkeypatch.setattr(reference, 'LOGIT_RTOL', 2e-4)
    out = reference.last_logits(rt.w, CFG, context, got=got2)
    said = _routing_line(capsys)
    # (behind the first flip the stream is another one: which experts held
    # ELSEWHERE fill a set there is that stream's ranking, which moves the
    # weights' sum and nothing else)
    assert _rel(got2, out) < 0.25 * _rel(got2, want)
    taken = {t['layer']: set(t['experts']) & held for t in said['taken']}
    assert all(taken[j] == set(flips[j]) & held for j in (1, 3))
    # no selection of the first layer reaches it alone: the second was
    # taken behind the first
    assert min(w['from_compared'] for w in said['weighed']
               if w['layer'] == 1) > 100 * _rel(got2, out)
    # the same program against a band that does not admit its selections
    monkeypatch.setattr(reference, 'NEAR_TIE', 1e-6)
    out = reference.last_logits(rt.w, CFG, context, got=got2)
    said = _routing_line(capsys)
    assert said['taken'] == [] and said['weighed'] == []
    assert np.array_equal(out, want) and _rel(got2, out) > 1e-3
    # a control is held to the same rule: its selections are weighed too,
    # and it stays far from the program whatever it takes
    monkeypatch.setattr(reference, 'NEAR_TIE', 50.0)
    a = reference.last_logits(rt.w, CFG, context, control='no_delta',
                              got=got2, chunk=CHUNK)
    assert _routing_line(capsys)['weighed']
    b = reference.last_logits(rt.w, CFG, context, control='no_delta',
                              chunk=CHUNK)
    assert _routing_line(capsys)['compared_with_logits'] is False
    assert _rel(got2, a) <= _rel(got2, b) and _rel(got2, a) > 5e-3


# -------------------------------------------- the chunk form of the scan

def _scan_inputs(T, H, d, seed, g_scale):
    rng = np.random.RandomState(seed)

    def unit(a):
        return (a / np.linalg.norm(a, axis=-1, keepdims=True)) \
            .astype(np.float32)

    return (unit(rng.randn(T, H, d)) * d ** -0.5, unit(rng.randn(T, H, d)),
            rng.randn(T, H, d).astype(np.float32),
            (-g_scale * np.abs(rng.randn(T, H, d))).astype(np.float32),
            rng.rand(T, H).astype(np.float32),
            rng.randn(H, d, d).astype(np.float32))


@pytest.mark.parametrize('sub,block', [(8, 8), (16, 4), (32, 4), (32, 8)])
@pytest.mark.parametrize('g_scale', [0.01, 1.0, 40.0],
                         ids=['slow', 'fast', 'strongly_negative'])
def test_the_chunk_form_is_the_token_form(sub, block, g_scale):
    """From a non-zero start state, with one block a sub-chunk and with
    several (the pairs of two blocks are products of two factors, each at
    most one), and with a decay so strong that exp(-G) would overflow."""
    q, k, v, g, beta, S0 = _scan_inputs(32, 3, 8, 0, g_scale)
    want_o, want_S = kda.token_scan(q, k, v, g, beta, S0)
    o, S = kda.chunk_scan(q, k, v, g, beta, S0, sub, block)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(S, want_S, rtol=2e-5, atol=2e-6)


def test_padding_neither_decays_the_state_nor_writes_it():
    """g = 0 and beta = 0 past the last real position: the state after the
    padding is the state at the last real position."""
    q, k, v, g, beta, S0 = _scan_inputs(16, 3, 8, 1, 1.0)
    real = np.arange(16) < 11
    g = np.where(real[:, None, None], g, 0.0)
    beta = np.where(real[:, None], beta, 0.0)
    _, S = kda.chunk_scan(q, k, v, g, beta, S0, 8, 4)
    _, at_11 = kda.token_scan(q[:11], k[:11], v[:11], g[:11], beta[:11], S0)
    np.testing.assert_allclose(S, at_11, rtol=2e-5, atol=2e-6)


def test_a_chunk_of_the_cells_size_takes_the_modules_blocks():
    assert kda._sizes(512) == (64, 16)
    assert kda._sizes(CHUNK) == (8, 8)
    assert kda._sizes(24) == (8, 8)


@pytest.mark.parametrize('path', ['step', 'window', 'window_chunk_window'])
def test_a_step_after_a_prefill_is_the_prefill_one_token_longer(rt, path):
    """The single step and the chunk form agree on the same state and the
    same tail: the token after the context, by a window's last step fed the
    sampled tokens and by prefilling the longer prompt.  One step; a window
    of several; and a window, a chunk of one token and one more step on the
    same slot: the tail a chunk writes is the tail the kernel reads, and
    the other way round."""
    prompt = _prompt(13, 4)
    slot, first, _ = _prefill(rt, prompt)
    toks = list(_window(rt, [slot], steps=1 if path == 'step'
                        else WINDOW)[slot])
    if path == 'window_chunk_window':
        assert rt.ensure_capacity(slot, prompt.size + WINDOW + 2)
        fed, _ = rt.prefill(slot, np.asarray(toks[-1:], np.int32),
                            prompt.size + WINDOW, SamplingParams())
        toks += [int(fed)]
        toks += list(_window(rt, [slot], steps=1)[slot])
    state = {k: np.asarray(rt.state[k][slot]) for k in ('ssm', 'conv')}
    assert np.abs(state['conv']).min(axis=(1, 2)).max() > 0   # every row
    rt.reset()
    longer = np.concatenate([prompt, [first], toks[:-1]]).astype(np.int32)
    again, nxt, _ = _prefill(rt, longer)
    assert again == slot and nxt == toks[-1]
    for name, was in state.items():
        np.testing.assert_allclose(np.asarray(rt.state[name][slot]), was,
                                   rtol=1e-4, atol=1e-5)


# ------------------------------------------------- slots and their state

@pytest.mark.parametrize('plen', [13, 2])
def test_a_reused_slot_starts_from_a_zero_state(rt, plen):
    """Also a prompt shorter than the convolutions' reach: the tail rows
    from before its first token are zeros, not the last occupant's, and the
    window behind it gives the tokens a fresh runtime gives."""
    slot, _, fresh = _prefill(rt, _prompt(plen, 2))
    fresh_toks = _window(rt, [slot])[slot]
    rt.reset()
    assert _prefill(rt, _prompt(19, 1))[0] == slot
    _window(rt, [slot])
    assert float(jnp.abs(rt.state['ssm'][slot]).max()) > 0
    assert float(jnp.abs(rt.state['conv'][slot]).min()) > 0
    rt.free_slot(slot)
    before = obs.counters().get('generation.state_resets', 0)
    again, _, got = _prefill(rt, _prompt(plen, 2))
    assert again == slot
    assert obs.counters()['generation.state_resets'] == before + 1
    np.testing.assert_array_equal(got, fresh)
    tails = np.asarray(rt.state['conv'][slot])           # [L, K-1, 3 H, d]
    kept = min(plen, tails.shape[1])
    assert np.abs(tails[:, :tails.shape[1] - kept]).max(initial=0) == 0
    assert np.abs(tails[:, tails.shape[1] - kept:]).min() > 0
    np.testing.assert_array_equal(_window(rt, [slot])[slot], fresh_toks)
    rt.reset()
    assert float(jnp.abs(rt.state['ssm']).max()) == 0
    assert float(jnp.abs(rt.state['conv']).max()) == 0


def test_a_dead_slots_state_is_untouched_by_a_window(rt):
    idle, _, _ = _prefill(rt, _prompt(13, 7))
    live, _, _ = _prefill(rt, _prompt(8, 8))
    kept = {k: np.asarray(rt.state[k][idle]) for k in ('ssm', 'conv')}
    moved = np.asarray(rt.state['ssm'][live])
    before = dict(obs.counters())
    _window(rt, [live])
    for name, was in kept.items():
        np.testing.assert_array_equal(np.asarray(rt.state[name][idle]), was)
    assert np.abs(np.asarray(rt.state['ssm'][live]) - moved).max() > 0
    c = {k: v - before.get(k, 0) for k, v in obs.counters().items()}
    # the kernel reads and writes the ONE live slot's state in three layers
    assert rt.state_kernel
    assert c['generation.state_slot_steps'] == WINDOW
    assert c['generation.state_live_slot_steps'] == WINDOW
    assert c['generation.kda_state_bytes'] \
        == c['generation.window_kda_state_bytes'] \
        == WINDOW * 3 * 2 * kda.state_bytes(CFG['kda'])
    # and the one live slot's tails: three rows of 3 H d
    assert kda.tail_bytes(CFG['kda']) == 4 * 3 * 3 * 3 * 8 \
        == 4 * rt.state['conv'][0, 0].size
    assert c['generation.kda_tail_bytes'] \
        == c['generation.window_kda_tail_bytes'] \
        == WINDOW * 3 * 2 * kda.tail_bytes(CFG['kda'])
    assert c.get('generation.kda_chunk_tokens', 0) == 0


def test_the_composed_step_gives_the_kernels_tokens_and_state(weights, rt):
    """The route under a mesh: every slot steps and a dead one's state is
    kept by a select; it moves every slot's state and says so."""
    prompts = [_prompt(13, 5), _prompt(8, 6)]

    def run(runtime):
        slots = [_prefill(runtime, p)[0] for p in prompts]
        idle = _prefill(runtime, _prompt(5, 9))[0]
        kept = {k: np.asarray(runtime.state[k][idle])
                for k in ('ssm', 'conv')}
        toks = _window(runtime, slots)[slots]
        for name, was in kept.items():
            np.testing.assert_array_equal(
                np.asarray(runtime.state[name][idle]), was)
        return toks, np.asarray(runtime.state['ssm']), \
            np.asarray(runtime.state['conv'])

    want_toks, want_state, want_tails = run(rt)
    composed = DecodeRuntime(weights, CFG, slots=3, prefill_chunk=CHUNK,
                             page_len=PAGE)
    composed.kernels = composed.kernels._replace(state=False)
    before = dict(obs.counters())
    toks, state, tails = run(composed)
    c = {k: v - before.get(k, 0) for k, v in obs.counters().items()}
    assert c['kda.step_composed'] > 0 and c.get('kda.step_kernel', 0) == 0
    assert c['generation.state_slot_steps'] == 3 * WINDOW
    assert c['generation.kda_state_bytes'] \
        == WINDOW * 3 * 3 * 2 * kda.state_bytes(CFG['kda'])
    # every slot's tails in every layer, the live ones' or not
    assert c['generation.window_kda_tail_bytes'] \
        == WINDOW * 3 * 3 * 2 * kda.tail_bytes(CFG['kda'])
    # (the chunks count none: three prompts' went through them)
    assert c['generation.kda_tail_bytes'] \
        == c['generation.window_kda_tail_bytes']
    np.testing.assert_array_equal(toks, want_toks)
    np.testing.assert_allclose(state, want_state, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(tails, want_tails, rtol=1e-5, atol=1e-5)


_LIVE = pytest.mark.parametrize(
    'live', [[1, 0, 1, 1, 0], [0, 0, 0, 0, 0], [1, 1, 1, 1, 1],
             [0, 0, 0, 0, 1]], ids=['some', 'none', 'all', 'last'])


def _step_inputs(S, L, H, d, taps, seed=0):
    """(x [S, 3 H d], taps [K, 3 H d], a [S, H, d], beta [S, H], state [S,
    L, H, d, d], tails [S, L, K-1, 3 H, d]) float32."""
    rng = np.random.RandomState(seed)
    f32 = jnp.float32
    return (jnp.asarray(rng.randn(S, 3 * H * d), f32),
            jnp.asarray(rng.randn(taps, 3 * H * d), f32),
            jnp.asarray(np.exp(-np.abs(rng.randn(S, H, d))), f32),
            jnp.asarray(rng.rand(S, H), f32),
            jnp.asarray(rng.randn(S, L, H, d, d), f32),
            jnp.asarray(rng.randn(S, L, taps - 1, 3 * H, d), f32))


@pytest.mark.parametrize('taps', [4, 2])
@_LIVE
def test_the_kernel_steps_the_live_slots_of_one_layer_in_place(live, taps):
    """`kda_step` from the projections on, against the convolution, silu
    and the unit norms written out here and `token_scan` behind them: four
    taps (the tail moves up a row) and two (the tail IS the last row)."""
    S, L, H, d = 5, 3, 3, 8
    x, filt, a, beta, state, tails = _step_inputs(S, L, H, d, taps)
    active = jnp.asarray(live, bool)
    o, new, new_tails = jax.jit(kda.kda_step)(
        x, filt, a, beta, state, tails, jnp.int32(1), active)
    want_o, want_S, want_tail = [], [], []
    for s in range(S):
        full = jnp.concatenate([tails[s, 1].reshape(taps - 1, -1), x[s:s + 1]])
        conv = jnp.sum(full * filt, axis=0)
        y = (conv / (1 + jnp.exp(-conv))).reshape(3, H, d)
        q, k = (r / jnp.sqrt(jnp.sum(r * r, -1, keepdims=True) + 1e-6)
                for r in y[:2])
        step_o, step_S = kda.token_scan(
            (q * d ** -0.5)[None], k[None], y[2][None], jnp.log(a[s:s + 1]),
            beta[s:s + 1], state[s, 1])
        want_o.append(step_o[0])
        want_S.append(step_S)
        want_tail.append(full[1:].reshape(taps - 1, 3 * H, d))
    mask = np.asarray(live, bool)
    np.testing.assert_allclose(
        o, np.where(mask[:, None, None], np.stack(want_o), 0.0),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new[:, 1])[mask],
                               np.stack(want_S)[mask], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_tails[:, 1])[mask],
                               np.stack(want_tail)[mask], rtol=1e-5,
                               atol=1e-5)
    # a dead slot's state and tail, and every other layer's, bit for bit
    for got, was in ((new, state), (new_tails, tails)):
        np.testing.assert_array_equal(np.asarray(got[:, 1])[~mask],
                                      np.asarray(was[:, 1])[~mask])
        np.testing.assert_array_equal(np.asarray(got[:, [0, 2]]),
                                      np.asarray(was[:, [0, 2]]))


@_LIVE
def test_the_composed_layer_is_the_kernels(weights, live):
    """`step_mixer` by either route on one layer of the same state: the
    layer's output for the live slots (a dead one's is masked by whoever
    calls), the state and the tails of every slot."""
    S, kd = 5, CFG['kda']
    _x, _f, _a, _b, state, tails = _step_inputs(
        S, 3, kd['n_heads'], kd['head_dim'], kd['d_conv'], seed=1)
    w = {k: jnp.asarray(v) for k, v in weights.items()
         if k.startswith('layer_1_kda_')}
    h = jnp.asarray(np.random.RandomState(2).randn(S, CFG['d_model']),
                    jnp.float32)
    active = jnp.asarray(live, bool)

    def layer(kernel):
        return jax.jit(lambda h, state, tails: kda.step_mixer(
            w, 'layer_1_', CFG, h, state, 1, tails, active, kernel))(
                h, state, tails)

    mask = np.asarray(live, bool)
    for got, want in zip(layer(True), layer(False)):
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_allclose(got[mask], want[mask], rtol=1e-5,
                                   atol=1e-5)
        if got.ndim > 2:                # state and tails: the dead slots'
            np.testing.assert_array_equal(got[~mask], want[~mask])


@pytest.mark.parametrize('shape,taps,dtype,devices,takes', [
    ((128, 6, 32, 128, 128), 4, 'float32', 1, True),    # the cell's
    ((128, 6, 32, 128, 128), 4, 'bfloat16', 1, False),
    ((128, 6, 32, 128, 128), 4, 'float32', 2, False),   # under a mesh
    ((128, 6, 32, 64, 64), 4, 'float32', 1, False),     # half a tile of lanes
    ((128, 6, 12, 128, 128), 4, 'float32', 1, False),   # [H, d] splits a tile
    ((128, 6, 64, 128, 128), 4, 'float32', 1, False),   # four tiles too many
    ((8, 2, 8, 128, 128), 2, 'float32', 1, True),
], ids=['cell', 'bf16', 'mesh', 'narrow_head', 'ragged_heads', 'too_large',
        'small'])
def test_the_kernel_is_chosen_by_what_the_state_is(monkeypatch, shape, taps,
                                                   dtype, devices, takes):
    """The rule is static and reads the arrays' extents, the dtype and the
    mesh; the interpreter takes anything of one device's float32."""
    from paddle_tpu.ops import _pallas
    S, L, H, d, _ = shape
    tails = (S, L, taps - 1, 3 * H, d)
    mesh = None if devices == 1 else jax.make_mesh(
        (devices,), ('x',), devices=jax.devices()[:devices])
    assert kda.kda_step_eligible(shape, tails, dtype, mesh) \
        == (dtype == 'float32' and devices == 1)
    monkeypatch.setattr(_pallas, 'interpret', lambda: False)
    assert kda.kda_step_eligible(shape, tails, dtype, mesh) == takes


def test_a_stream_does_not_depend_on_its_neighbours(rt):
    alone = rt.generate(_prompt(13, 5), 7, steps_per_window=WINDOW)
    rt.reset()
    other, _, _ = _prefill(rt, _prompt(19, 6))          # slot 0 stays live
    assert rt.generate(_prompt(13, 5), 7, steps_per_window=WINDOW) == alone


def test_the_engine_batches_streams_over_the_matrix_state(rt):
    """Continuous batching through GenerationEngine, unchanged: four
    streams over three slots give the tokens each gives alone."""
    prompts = [_prompt(n, seed=n) for n in (5, 13, 19, 9)]
    alone = [rt.generate(p, 7, steps_per_window=WINDOW) for p in prompts]
    rt.reset()
    before = dict(obs.counters())
    engine = GenerationEngine(rt, gen_config=GenerationConfig(
        decode_window=WINDOW)).start()
    try:
        streams = [engine.generate(p, max_new=7) for p in prompts]
        got = [[int(t) for t in s.result(60).outputs[0]] for s in streams]
    finally:
        engine.stop()
    assert got == alone
    c = {k: v - before.get(k, 0) for k, v in obs.counters().items()}
    assert c['generation.kda_chunk_tokens'] == 5 + 13 + 19 + 9
    assert c['generation.prefix_refused_recurrent'] == 4


# -------------------------------------------- the pool and what is refused

def test_the_pool_holds_the_layers_that_attend_and_the_state_the_others(
        rt, weights):
    assert rt.recurrent and rt.latent_moe and rt.state_kernel
    assert rt.prefix is None
    # one of four layers attends: a page is that layer's rows alone
    assert rt.cache.layers == 1 and rt.cache.recurrent_layers == 3
    assert rt.cache.pool_shape == (3 * 16 + 1, 1, PAGE, 128)
    assert rt.cache.page_bytes() == 4 * 1 * PAGE * 128
    assert rt.state['ssm'].shape == (3, 3, 3, 8, 8)
    assert rt.state['conv'].shape == (3, 3, 3, 3 * 3, 8)
    assert rt.cache.recurrent_bytes() \
        == 4 * (rt.state['ssm'].size + rt.state['conv'].size)
    assert rt.cache.bytes() == rt.cache.pages * rt.cache.page_bytes() \
        + rt.cache.recurrent_bytes()
    assert rt.cache.spec()['recurrent_layers'] == 3
    assert _own_axes(CFG) == [0, 1, 0, 2]
    # a model that attends in every layer says nothing of it
    falcon = CacheConfig(slots=2, layers=3, kv_heads=2, max_len=16,
                         head_dim=8, recurrent=((2, 4, 4), (3, 8)))
    assert falcon.recurrent_layers == 3
    assert 'recurrent_layers' not in falcon.spec()
    assert init_state(falcon)['ssm'].shape == (2, 3, 2, 4, 4)
    with pytest.raises(ValueError, match='recurrent state'):
        rt._window_exec('verify', WINDOW)
    with pytest.raises(ValueError, match='mixer must name'):
        DecodeRuntime(weights, dict(CFG, mixer=['kda'] * 4), slots=2,
                      prefill_chunk=CHUNK, page_len=PAGE)
    with pytest.raises(ValueError, match='mixer must name'):
        weight_names(dict(CFG, mixer=['kda', 'latent']))


def test_weights_follow_the_mixer_and_read_back_bit_for_bit(rt, weights):
    names = weight_names(CFG)
    assert 'layer_0_kda_q_w' in names and 'layer_0_att_q_w' not in names
    assert 'layer_2_att_q_w' in names and 'layer_2_att_qa_w' not in names
    assert 'layer_2_kda_q_w' not in names
    assert 'layer_1_moe_router_bias' in names
    assert 'layer_0_moe_router_bias' not in names         # the dense layer
    shapes = weight_shapes(CFG)
    assert shapes['layer_2_att_q_w'] == (32, 4 * (8 + 4))
    assert shapes['layer_0_kda_fb_w'] == (6, 24)
    assert shapes['layer_3_kda_A_log'] == (3,)
    for name in names:
        assert np.array_equal(np.asarray(rt.w[name]), weights[name]), name
    assert latent.slots(CFG['latent'])[0] == 'att_q_w'
    assert 'att_q_w' in latent.prepared(CFG['latent'])


def test_the_launches_carry_the_scopes(rt):
    S, sds = rt.slots, rt._sds
    fn = decode._decode_fn(rt.cfg, rt.cache, WINDOW,
                           rt.kernels._replace(experts=False))
    window = jax.jit(fn).lower(
        rt._param_structs(), rt._state_structs(), rt._bt_struct(S),
        sds((S,), jnp.bool_), sds((S,), jnp.int32), sds((S,), jnp.float32),
        sds((S,), jnp.int32)).as_text(debug_info=True)
    i32, f32 = sds((), jnp.int32), sds((), jnp.float32)
    chunk = jax.jit(decode._prefill_fn(
        rt.cfg, rt.cache, CHUNK,
        kernels=rt.kernels._replace(experts=False))).lower(
        rt._param_structs(), rt._state_structs(),
        sds((rt.cache.max_pages,), jnp.int32), sds((CHUNK,), jnp.int32),
        i32, i32, i32, i32, f32, i32).as_text(debug_info=True)
    for scope in ('kda.proj', 'kda.gate', 'kda.out',
                  'attn.latent.q', 'attn.latent.scores', 'moe.route'):
        assert scope in window and scope in chunk, scope
    # the kernel holds the step's convolutions: the window has no scope of
    # theirs
    assert 'kda.conv' in chunk and 'kda.conv' not in window
    assert 'kda.step' in window and 'kda.scan' not in window
    assert 'kda.scan' in chunk and 'kda.step' not in chunk
    assert 'latent_attention' in window and 'kda_step' in window


# ------------------------------------------------------ the expert layer

def _layer_weights(moe, d=32, seed=0, scale=0.3):
    rng = np.random.RandomState(seed)
    return {k: jnp.asarray(scale * rng.randn(*s), jnp.float32)
            for k, s in experts.weight_shapes(d, moe).items()}


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Every rank routes over all experts (through the choice bias) and
    adds its own experts' part; the shares, with the shared expert counted
    once, are the whole layer."""
    moe = {'n_routed': 32, 'top_k': 4, 'd_expert': 24, 'n_shared': 1,
           'scale': 2.446, 'bias': True}
    whole = dict(moe, ranks=1, rank=0)
    w = _layer_weights(whole)
    h = jnp.asarray(np.random.RandomState(1).randn(23, 32), jnp.float32)
    valid = jnp.ones(23, bool)
    full, stats = experts.expert_layer(
        {'l_' + k: v for k, v in w.items()}, 'l_', {'moe': whole}, h, valid)
    assert int(stats[0]) == 23 * 4
    total, assignments = experts.swiglu(
        h, w['moe_shared_fc1_w'], w['moe_shared_fc3_w'],
        w['moe_shared_fc2_w']), 0
    for r in range(4):
        part = dict(moe, ranks=4, rank=r)
        first, n = experts.held(part)
        assert (first, n) == (8 * r, 8)
        picks, wts = experts.route(h, w['moe_router_w'], part,
                                   w['moe_router_bias'])
        y, st = experts.routed(
            h, w['moe_fc1_w'][first:first + n],
            w['moe_fc3_w'][first:first + n],
            w['moe_fc2_w'][first:first + n], picks, wts, valid, part)
        total = total + y
        assignments += int(st[0])
    assert assignments == 23 * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_the_bias_moves_the_choice_and_never_a_weight(reference):
    moe = {'n_routed': 32, 'top_k': 4, 'scale': 2.446}
    rng = np.random.RandomState(3)
    h = jnp.asarray(rng.randn(40, 32), jnp.float32)
    router = jnp.asarray(0.3 * rng.randn(32, 32), jnp.float32)
    bias = jnp.asarray(0.3 * rng.randn(32), jnp.float32)
    g = np.asarray(jax.nn.sigmoid(h @ router))
    picks, wts = (np.asarray(a) for a in experts.route(h, router, moe, bias))
    plain, _ = (np.asarray(a) for a in experts.route(h, router, moe))
    # the choice is top-k of g + b, and differs from the plain one
    want = np.argsort(-(g + np.asarray(bias)), axis=1)[:, :4]
    assert (np.sort(picks, 1) == np.sort(want, 1)).all()
    assert (np.sort(picks, 1) != np.sort(plain, 1)).any()
    # the weights are the picks' UNBIASED scores, renormalised and scaled
    gp = np.take_along_axis(g, picks, axis=1)
    np.testing.assert_allclose(
        wts, 2.446 * gp / gp.sum(1, keepdims=True), rtol=1e-5)
    # and the reference's one function picks the same sets
    ref_picks, margin = reference.select(jnp.asarray(g), bias, 4)
    assert (np.sort(np.asarray(ref_picks), 1) == np.sort(picks, 1)).all()
    assert (np.asarray(margin) >= 0).all()


def test_selections_are_the_tied_sets_that_change_what_is_held(reference):
    g = np.full(16, 0.2)
    g[[0, 1, 2]] = 0.9                 # sure
    g[[5, 9]] = [0.5001, 0.5]          # the 4th and 5th: a tie
    none = np.zeros(16)
    # rank 1 of 4 holds experts 4..7: 5 is held, 9 is not
    assert reference.selections(g, none, 4, 0.05, 4, 4) == [[0, 1, 2, 9]]
    assert reference.selections(g, none, 4, 0.05, 12, 4) == []   # neither
    # the band is in ROUTER LOGITS, whatever the bias: 5 leads by 0.02 of
    # score = 0.08 logits at g = 1/2, and a bias that lifts both alike
    # changes neither the distance nor the tie
    g[5] = 0.52
    lifted = np.where(np.isin(np.arange(16), [5, 9]), 0.3, 0.0)
    for b in (none, lifted):
        assert reference.selections(g, b, 4, 0.05, 4, 4) == [[0, 1, 2, 9]]
        assert reference.selections(g, b, 4, 0.03, 4, 4) == []
        _, margin = reference.select(jnp.asarray(g[None]), jnp.asarray(b), 4)
        assert float(margin[0]) == pytest.approx(0.08, rel=0.02)
    g[9] = 0.3                                                # no tie
    assert reference.selections(g, none, 4, 0.05, 4, 4) == []


# -------------------------------------- what stood lowers to what it did

YARN = {'factor': 32.0, 'beta_fast': 32.0, 'beta_slow': 1.0,
        'original_max_len': 4096, 'mscale': 1.0, 'mscale_all_dim': 1.0}
STANDING = {
    'dense': dict(vocab=64, d_model=32, n_layer=2, n_head=4, n_kv_head=2,
                  d_ffn=64, theta=1e4, max_len=64),
    'falcon_h1': {
        'block': 'falcon_h1', 'vocab': 97, 'd_model': 32, 'n_layer': 2,
        'n_head': 4, 'n_kv_head': 2, 'head_dim': 16, 'd_ffn': 64,
        'theta': 1e4, 'rms_eps': 1e-5, 'max_len': 64,
        'ssm': {'d_ssm': 48, 'n_heads': 6, 'n_groups': 2, 'd_state': 8,
                'd_conv': 4, 'chunk': 4},
        'multipliers': {'embedding': 2.0, 'lm_head': 0.5,
                        'attention_in': 1.0, 'attention_out': 0.5,
                        'key': 0.5, 'ssm_in': 0.5, 'ssm_out': 0.7,
                        'ssm': [0.5, 0.6, 0.7, 0.8, 0.9], 'mlp_gate': 0.8,
                        'mlp_down': 0.6}},
    # the axk1-shaped model of tests/test_generation_latent_moe.py
    'latent_moe': {
        'block': 'latent_moe', 'vocab': 97, 'd_model': 32, 'n_layer': 3,
        'n_head': 4, 'd_ffn': 48, 'theta': 1e4, 'rms_eps': 1e-6,
        'max_len': 64, 'ffn': ['dense', 'experts', 'experts'],
        'latent': {'q_rank': 24, 'kv_rank': 16, 'nope': 8, 'rope': 4,
                   'v': 8, 'yarn': YARN},
        'moe': {'n_routed': 16, 'top_k': 4, 'd_expert': 24, 'n_shared': 1,
                'scale': 2.5, 'ranks': 4, 'rank': 1}},
    # this file's own kimi-shaped model: `kda` layers beside a latent one
    'kimi': CFG}
# sha256 of the lowered StableHLO at the PARENT of PR 61 (commit 5c0fdb1):
# the dense and falcon_h1 ones are tests/test_generation_pipeline.py's own
# pins at its sizes (PR 38), the latent_moe ones were taken on the parent
# with `_lowered` below before this PR touched a file.  PR 62 re-took the
# three latent_moe ones for ONE reason: `experts.STATS` has a fifth entry
# (`moe_touched_only_calls`), so every launch's stats array is one longer.
# With that entry taken out of PR 62's tree all seven held as PR 61 left
# them (1f4e15cf..., fae3d725..., 5d089b5a... for prefill, decode, verify):
# nothing else of the lowering of a call of at most 64 tokens moved.
PARENT_SHA256 = {
    ('dense', 'prefill'):
        '6bac5846a0f42a6c46eb77149a6cb5c0920825f818098f7a273ca57b767c799e',
    ('dense', 'decode'):
        'a3372b00ffb2d2e3ffde3adf3fe84600ebf899ee92fc6fade56a7da60170f33b',
    ('falcon_h1', 'prefill'):
        '2b524a69c3c033414b22f1a7c302ba27bc6dee214e2461d857d988c9f7d6f02a',
    ('falcon_h1', 'decode'):
        'ab9caec757f8bdabc90cb080be5800350d505e496b1358c49266c6a163bcd64e',
    ('latent_moe', 'prefill'):
        '44bb9a18b74e6a6aa054e99af08408f921382040fe44c7bc63879e5e45d66a4b',
    ('latent_moe', 'decode'):
        '9075ee4dd4075621c0941d058dd557f32591760863d1a0e610f37e60a52cdf7a',
    ('latent_moe', 'verify'):
        'e71f40bc981998fb09f30b406075414b60874b5ea4bc3d47db45d49ba9177bb7',
    # the kimi-shaped model (`CFG`), which PR 65's step last changed: taken
    # on PR 66's parent (the code of a37207e) in that PR's first commit,
    # before it touched a file of the package; a verify window is refused
    # for a recurrent model
    ('kimi', 'prefill'):
        'c121ebca883f2138863d7a281666a18249400b87772ef90b05e581efca3e6834',
    ('kimi', 'decode'):
        '7b5cd06b1de95353a6ea6d61d7d6f53072101237871fa33615eb213224098331',
}
# (chunk, window, slots, page, weights' seed) each fixture was pinned at
_SIZES = {'dense': (4, 3, 3, 4, 1), 'falcon_h1': (4, 3, 3, 4, 1),
          'latent_moe': (8, 3, 3, 4, 5), 'kimi': (CHUNK, WINDOW, 3, PAGE, 5)}


def _lowered(block, kind):
    chunk, window, slots, page, seed = _SIZES[block]
    cfg = STANDING[block]
    rt = DecodeRuntime(random_weights(cfg, seed=seed, scale=0.3), cfg,
                       slots=slots, prefill_chunk=chunk, page_len=page)
    sds = rt._sds
    i32, f32 = sds((), jnp.int32), sds((), jnp.float32)
    S = rt.slots
    # the pins were taken without the grouped expert kernel
    kernels = rt.kernels._replace(experts=False)
    if kind == 'prefill':
        fn = decode._prefill_fn(rt.cfg, rt.cache, chunk, kernels=kernels)
        args = [rt._param_structs(), rt._state_structs(),
                sds((rt.cache.max_pages,), jnp.int32),
                sds((chunk,), jnp.int32), i32, i32, i32, i32, f32, i32]
    else:
        make = decode._verify_fn if kind == 'verify' else decode._decode_fn
        fn = make(rt.cfg, rt.cache, window, kernels)
        args = [rt._param_structs(), rt._state_structs(), rt._bt_struct(S)]
        if kind == 'verify':
            args.append(sds((window, S), jnp.int32))
        args += [sds((S,), jnp.bool_), sds((S,), jnp.int32),
                 sds((S,), jnp.float32), sds((S,), jnp.int32)]
    text = jax.jit(fn, donate_argnums=(1,)).lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize('which', sorted(PARENT_SHA256), ids='-'.join)
def test_what_stood_lowers_to_the_parents_text(which):
    assert _lowered(*which) == PARENT_SHA256[which]
