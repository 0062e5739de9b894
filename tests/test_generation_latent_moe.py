"""The `latent_moe` block on the serving path: latent attention over a
one-row-a-token pool (latent.py, ops.attention.latent_attention) and, per
layer, a dense SwiGLU or routed experts beside a shared one as ONE
expert-parallel rank holds them (experts.py).

Tiny sizes, float32, seeded weights.  The last logits after chunked
prefill (expanded), a decode window (absorbed, the kernel in interpret
mode) and one more chunk through the pool are compared with the
benchmark's plain reference (benchmarks/references/axk1.py: full forward,
every head's keys and values expanded, a loop over the experts held).
"""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.ops import attention as ops_attention
from paddle_tpu.serving.generation import (CacheConfig, DecodeRuntime,
                                           GenerationConfig,
                                           GenerationEngine, SamplingParams,
                                           experts, init_state, latent,
                                           random_weights, weight_names)
from paddle_tpu.serving.generation import decode

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK, WINDOW, PAGE = 8, 3, 4

YARN = {'factor': 32.0, 'beta_fast': 32.0, 'beta_slow': 1.0,
        'original_max_len': 4096, 'mscale': 1.0, 'mscale_all_dim': 1.0}
CFG = {
    'block': 'latent_moe', 'vocab': 97, 'd_model': 32, 'n_layer': 3,
    'n_head': 4, 'd_ffn': 48, 'theta': 1e4, 'rms_eps': 1e-6, 'max_len': 64,
    'ffn': ['dense', 'experts', 'experts'],
    'latent': {'q_rank': 24, 'kv_rank': 16, 'nope': 8, 'rope': 4, 'v': 8,
               'yarn': YARN},
    'moe': {'n_routed': 16, 'top_k': 4, 'd_expert': 24, 'n_shared': 1,
            'scale': 2.5, 'ranks': 4, 'rank': 1}}


@pytest.fixture(scope='module')
def reference():
    spec = importlib.util.spec_from_file_location(
        'axk1_reference',
        os.path.join(ROOT, 'benchmarks', 'references', 'axk1.py'))
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


def _through_the_pool(rt, prompt, start=None):
    """Chunked prefill, one decode window, one more chunk: (context, the
    logits at its last position) as the benchmark's comparison takes them.
    ``start`` asserts the offset the prefix cache granted."""
    slot = rt.alloc_slot()
    begin = rt.try_begin(slot, prompt, WINDOW)
    if start is not None:
        assert begin == start
    for off in range(begin, prompt.size, CHUNK):
        first, _ = rt.prefill(slot, prompt[off:off + CHUNK], off,
                              SamplingParams())
    rt.promote_prefix(slot, prompt)
    active = np.zeros(rt.slots, bool)
    active[slot] = True
    zeros = np.zeros(rt.slots, np.int32)
    toks = rt.decode_window(WINDOW, active, zeros,
                            np.zeros(rt.slots, np.float32), zeros)[slot]
    assert rt.ensure_capacity(slot, prompt.size + WINDOW + 1)
    _, logits = rt.prefill(slot, toks[-1:], prompt.size + WINDOW,
                           SamplingParams())
    logits = np.asarray(logits, np.float32)
    rt.free_slot(slot)
    return np.concatenate([prompt, [int(first)], toks]).astype(np.int32), \
        logits


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ------------------------------------------------ against the reference

@pytest.mark.parametrize('plen', [5, 8, 19, 30])
def test_chunks_a_window_and_a_chunk_match_the_reference(rt, reference,
                                                         plen):
    context, got = _through_the_pool(rt, _prompt(plen, plen))
    want = reference.last_logits(rt.w, CFG, context)
    assert _rel(got, want) < 2e-4


@pytest.mark.parametrize('kernel', [True, False],
                         ids=['kernel', 'composed'])
def test_a_chunk_visits_its_context_block_by_block(weights, reference,
                                                   monkeypatch, kernel):
    """Blocks of 16 cached positions where the module's fixture has one
    of 64: the online softmax over several blocks, a context that ends
    mid-block, and blocks past it not visited (their rows are not
    counted), on either route of the chunk."""
    monkeypatch.setattr(latent, '_PREFILL_KEY_BLOCK', 16)
    small = DecodeRuntime(weights, CFG, slots=2, prefill_chunk=CHUNK,
                          page_len=PAGE)
    assert small.prefill_kernel
    small.kernels = small.kernels._replace(prefill=kernel)
    before = dict(obs.counters())
    context, got = _through_the_pool(small, _prompt(37, 12))
    assert _rel(got, reference.last_logits(small.w, CFG, context)) < 2e-4
    c = {k: v - before.get(k, 0) for k, v in obs.counters().items()}
    # chunks end at 8, 16, 24, 32, 37 and 41 positions: 16, 16, 32, 32,
    # 48 and 48 rows a layer
    assert c['generation.latent_rows_read'] \
        - c['generation.window_latent_rows_read'] == 3 * 192
    # one lowering of the chunk, one count a layer, of the route taken
    assert c.get('latent.prefill_kernel', 0) == (3 if kernel else 0)
    assert c.get('latent.prefill_composed', 0) == (0 if kernel else 3)


def test_the_reference_controls_are_seen(rt, reference):
    """What the chip run's controls rest on: each fault moves the logits
    far past float32 rounding."""
    context, got = _through_the_pool(rt, _prompt(30, 1))
    for control in reference.CONTROLS:
        wrong = reference.last_logits(rt.w, CFG, context, control=control)
        assert _rel(got, wrong) > 5e-3, control


def _routing_line(capsys):
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('routing: ')]
    return json.loads(lines[-1][len('routing: '):])


def test_the_reference_resolves_a_near_tie_at_the_compared_position_itself(
        rt, reference, capsys, monkeypatch):
    """In float32 the program's picks ARE the reference's, so nothing is
    taken whatever the band.  A program whose LAST chunk is made to pick,
    in one expert layer, another held expert than the plain top-k (the
    chunk's executable rebuilt with `experts.select` forced; the earlier
    chunks and the window ran the plain one) gives logits the reference
    reaches through its own alternatives where that selection lies within
    NEAR_TIE of its router logits, and does NOT reach where it lies
    outside: a wrong selection shows in the logits."""
    prompt = _prompt(21, 13)
    recorded = []

    def served(forced=None):
        """`_through_the_pool`, the last chunk's picks in expert layer
        ``forced[0]`` replaced by ``forced[1]``."""
        rt.reset()
        slot = rt.alloc_slot()
        assert rt.try_begin(slot, prompt, WINDOW) == 0
        for off in range(0, prompt.size, CHUNK):
            first, _ = rt.prefill(slot, prompt[off:off + CHUNK], off,
                                  SamplingParams())
        active = np.zeros(rt.slots, bool)
        active[slot] = True
        zeros = np.zeros(rt.slots, np.int32)
        toks = rt.decode_window(WINDOW, active, zeros,
                                np.zeros(rt.slots, np.float32), zeros)[slot]
        assert rt.ensure_capacity(slot, prompt.size + WINDOW + 1)
        plain_select, calls = experts.select, []

        def select(scores, moe, bias=None):
            picks = plain_select(scores, moe, bias)
            calls.append(None)
            if len(calls) - 1 == forced[0]:      # the compared token is row 0
                picks = picks.at[0].set(jnp.asarray(forced[1], picks.dtype))
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
        return np.concatenate([prompt, [int(first)], toks]) \
            .astype(np.int32), np.asarray(logits, np.float32)

    context, got = served()
    want = reference.last_logits(rt.w, CFG, context, got=got,
                                 picks_out=recorded)
    said = _routing_line(capsys)
    assert _rel(got, want) < 2e-4 and said['taken'] == []
    assert said['compared_with_logits'] and len(recorded) == 2
    # a band that admits every set: the alternatives of the first expert
    # layer (layer 1; experts 4..7 are held), one of them made by the program
    monkeypatch.setattr(reference, 'NEAR_TIE', 50.0)
    reference.last_logits(rt.w, CFG, context, got=got)
    wide = _routing_line(capsys)
    assert wide['taken'] == [] and wide['alternatives']
    assert all(a['from_plain'] > 1e-3 for a in wide['alternatives'])
    # the position alone over the plain pass's stream is the plain pass
    assert wide['position_alone_from_plain'][0] < 1e-5
    held = set(range(4, 8))
    chosen = next(a['selections'][0]['experts'] for a in wide['alternatives']
                  if [t['layer'] for t in a['selections']] == [1])
    assert set(chosen) & held != set(int(e) for e in recorded[0][0][-1]) & held
    context2, got2 = served(forced=(0, chosen))
    assert np.array_equal(context2, context) and _rel(got2, got) > 1e-3
    out = reference.last_logits(rt.w, CFG, context, got=got2)
    said = _routing_line(capsys)
    assert _rel(got2, out) < 2e-4
    assert said['taken'][0] == {'layer': 1, 'experts': sorted(chosen)}
    # the same program against a band that does not admit its selection
    monkeypatch.setattr(reference, 'NEAR_TIE', 1e-6)
    out = reference.last_logits(rt.w, CFG, context, got=got2)
    said = _routing_line(capsys)
    assert said['taken'] == [] and said['alternatives'] == []
    assert np.array_equal(out, want) and _rel(got2, out) > 1e-3
    # a control is held to the same rule: it takes its nearest alternative
    monkeypatch.setattr(reference, 'NEAR_TIE', 50.0)
    a = reference.last_logits(rt.w, CFG, context, control='unnormalised',
                              got=got2)
    assert _routing_line(capsys)['taken']
    b = reference.last_logits(rt.w, CFG, context, control='unnormalised')
    assert _routing_line(capsys)['compared_with_logits'] is False
    assert _rel(got2, a) < _rel(got2, b) and _rel(got2, a) > 5e-3


def test_selections_are_the_correct_top_k_sets_that_change_what_is_held(
        reference):
    g = np.asarray([.9, .8, .7, .6, .1])
    logit = np.log(g) - np.log1p(-g)
    tie = float(logit[1] - logit[2]) * 1.01        # 2nd and 3rd tie, no more
    # top-2 of five, experts 2..3 held: plain {0, 1}; {0, 2} is correct too
    assert reference.selections(g, 2, tie, 2, 2) == [[0, 2]]
    # nothing held is in the tie: no alternative changes what is held
    assert reference.selections(g, 2, tie, 3, 2) == []
    assert reference.selections(g, 2, tie * 0.9, 2, 2) == []
    # a wider band admits the 4th: it may stand in for the 2nd or the 3rd
    wide = float(logit[1] - logit[3]) * 1.01
    assert sorted(reference.selections(g, 2, wide, 2, 2)) \
        == [[0, 2], [0, 3]]
    # both held experts in for two tied ones held elsewhere
    assert [2, 3] in reference.selections(g, 2, 10.0, 2, 2)


def test_the_window_lowers_the_kernel_and_the_absorbed_form(rt):
    """The decode window holds the latent kernel and no expansion of the
    cached rows to per-head keys; the prefill chunk holds no kernel."""
    assert rt.paged and rt.cache.latent == 16
    assert rt.cache.pool_shape == (3 * 16 + 1, 3, PAGE, 128)
    assert 'v' not in rt.state
    S = rt.slots
    sds = rt._sds
    fn = decode._decode_fn(rt.cfg, rt.cache, WINDOW,
                           decode.Kernels(paged=True))
    text = jax.jit(fn).lower(
        rt._param_structs(), rt._state_structs(), rt._bt_struct(S),
        sds((S,), jnp.bool_), sds((S,), jnp.int32), sds((S,), jnp.float32),
        sds((S,), jnp.int32)).as_text(debug_info=True)
    for scope in ('attn.latent.q', 'attn.latent.kv', 'attn.latent.scores',
                  'moe.route', 'moe.experts', 'moe.shared'):
        assert scope in text, scope
    assert 'latent_attention' in text and 'ragged_dot' in text


def test_weights_read_back_bit_for_bit_and_rows_in_the_public_order(
        rt, weights, reference):
    for name in weight_names(CFG):
        assert np.array_equal(np.asarray(rt.w[name]), weights[name]), name
    prompt = _prompt(11, 2)
    slot = rt.alloc_slot()
    rt.try_begin(slot, prompt, WINDOW)
    for off in range(0, prompt.size, CHUNK):
        rt.prefill(slot, prompt[off:off + CHUNK], off, SamplingParams())
    rows, v, n = rt.cache_row(slot)
    assert v is None and n == 11
    assert rows.shape == (3, 1, CFG['max_len'], 20)
    # layer 0's rows from the embedding alone, in the reference's terms
    lat = CFG['latent']
    x = weights['tok_emb'][prompt]
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) \
        * weights['layer_0_att_norm']
    ckv_kr = h @ weights['layer_0_att_kva_w']
    c = ckv_kr[:, :16]
    c = c / np.sqrt((c * c).mean(-1, keepdims=True) + 1e-6)
    k_r = np.asarray(reference._rope(
        jnp.asarray(ckv_kr[:, 16:]),
        reference.yarn_inv_freq(lat, CFG['theta']), 0))
    np.testing.assert_allclose(rows[0, 0, :11, :16], c, atol=2e-5)
    np.testing.assert_allclose(rows[0, 0, :11, 16:], k_r, atol=2e-5)
    np.testing.assert_array_equal(
        latent.yarn_inv_freq(lat, CFG['theta']),
        reference.yarn_inv_freq(lat, CFG['theta']))
    assert latent.score_scale(lat) == pytest.approx(
        reference.score_scale(lat))


def test_the_absorbed_step_matches_the_expanded_chunk_on_the_same_cache(rt):
    """One more token by a decode step (absorbed) and by a one-token
    prefill chunk (expanded) over the same cached rows: the same logits."""
    prompt = _prompt(13, 3)
    got = []
    for stepwise in (True, False):
        rt.reset()
        slot = rt.alloc_slot()
        rt.try_begin(slot, prompt, 4)
        for off in range(0, prompt.size, CHUNK):
            first, _ = rt.prefill(slot, prompt[off:off + CHUNK], off,
                                  SamplingParams())
        first = int(first)
        if stepwise:
            active = np.zeros(rt.slots, bool)
            active[slot] = True
            zeros = np.zeros(rt.slots, np.int32)
            tok = rt.decode_window(1, active, zeros,
                                   np.zeros(rt.slots, np.float32),
                                   zeros)[slot][0]
        else:
            tok, _ = rt.prefill(slot, np.asarray([first], np.int32),
                                prompt.size, SamplingParams())
        _, logits = rt.prefill(slot, np.asarray([int(tok)], np.int32),
                               prompt.size + 1, SamplingParams())
        got.append((int(tok), np.asarray(logits, np.float32)))
        rt.free_slot(slot)
    assert got[0][0] == got[1][0]
    assert _rel(got[0][1], got[1][1]) < 1e-5


def test_a_prefix_hit_over_latent_pages_gives_the_cold_logits(rt):
    shared = _prompt(16, 4)
    a = np.concatenate([shared, _prompt(7, 5)])
    b = np.concatenate([shared, _prompt(9, 6)])
    before = dict(obs.counters())
    _through_the_pool(rt, a, start=0)
    _, hit = _through_the_pool(rt, b, start=16)     # four shared pages
    assert obs.counters()['generation.prefix_hits'] \
        - before.get('generation.prefix_hits', 0) == 1
    rt.reset()
    _, cold = _through_the_pool(rt, b, start=0)
    np.testing.assert_array_equal(hit, cold)


def test_a_stream_does_not_depend_on_its_neighbours(rt):
    alone = rt.generate(_prompt(9, 7), 7, steps_per_window=WINDOW)
    rt.reset()
    gen = GenerationEngine(rt, gen_config=GenerationConfig(
        decode_window=WINDOW)).start()
    try:
        streams = [gen.generate(_prompt(9, 7), max_new=7),
                   gen.generate(_prompt(21, 8), max_new=5),
                   gen.generate(_prompt(4, 9), max_new=9)]
        got = [s.result(60) for s in streams]
    finally:
        gen.stop()
    assert all(r.ok for r in got)
    assert list(streams[0].tokens_so_far()) == alone


def test_a_verify_window_is_the_sequential_stream(rt):
    """Speculative windows over the latent pool: rejected rows stay
    behind the committed length and the stream is the plain one."""
    prompt = np.tile(_prompt(6, 14), 4)            # repeats: drafts accept
    want = rt.generate(prompt, 9, steps_per_window=WINDOW)
    rt.reset()
    assert rt.generate(prompt, 9, steps_per_window=WINDOW,
                       speculative=True) == want


def test_the_launches_count_routing_and_rows(rt):
    before = dict(obs.counters())
    prompt = _prompt(13, 10)
    rt.generate(prompt, 1 + 2 * WINDOW, steps_per_window=WINDOW)
    c = {k: v - before.get(k, 0) for k, v in obs.counters().items()}
    # two expert layers; 13 prompt tokens in two chunks, six decode steps
    assert c['generation.moe_tokens'] == 2 * (13 + 2 * WINDOW)
    assert c['generation.window_moe_tokens'] == 2 * 2 * WINDOW
    assert 0 < c['generation.moe_assignments'] \
        <= CFG['moe']['top_k'] * c['generation.moe_tokens']
    assert 0 < c['generation.moe_experts_touched'] <= 4 * 2 * (2 + 2 * WINDOW)
    assert c['generation.moe_busiest_expert_tokens'] \
        <= c['generation.moe_assignments']
    # the kernel's rows, three layers: the whole pages that positions
    # 14..19 cover, what kv_rows_read counts per layer
    assert c['generation.window_latent_rows_read'] \
        == 3 * c['generation.kv_rows_read'] == 3 * (16 + 16 + 16 + 20 * 3)
    assert c['generation.latent_rows_read'] \
        == c['generation.window_latent_rows_read'] + 2 * 3 * CFG['max_len']
    assert c['generation.kv_tokens_live'] == sum(range(14, 20))


def test_what_the_block_cannot_do_is_refused(weights):
    with pytest.raises(ValueError, match='int8'):
        DecodeRuntime(weights, CFG, slots=2, prefill_chunk=CHUNK,
                      page_len=PAGE, kv_quant='int8')
    with pytest.raises(ValueError, match='ffn must name'):
        DecodeRuntime(weights, dict(CFG, ffn=['dense', 'experts']), slots=2,
                      prefill_chunk=CHUNK, page_len=PAGE)
    with pytest.raises(ValueError, match='block must be one of'):
        weight_names(dict(CFG, block='mla'))
    with pytest.raises(ValueError, match='kv_heads=1'):
        CacheConfig(slots=2, layers=1, kv_heads=2, max_len=8, head_dim=128,
                    latent=16)
    cache = CacheConfig(slots=2, layers=3, kv_heads=1, max_len=16,
                        head_dim=128, page_len=4, latent=16, dtype='bfloat16')
    assert cache.page_bytes() == 2 * 3 * 4 * 128      # one pool, no V
    assert cache.spec()['latent'] == 16
    assert set(init_state(cache)) == {'k', 'lengths', 'tok'}


# ------------------------------------------------------ the expert layer

def _layer_weights(moe, d=32, seed=0, scale=0.3):
    rng = np.random.RandomState(seed)
    return {k: jnp.asarray(scale * rng.randn(*s), jnp.float32)
            for k, s in experts.weight_shapes(d, moe).items()}


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Every rank routes over all experts and adds its own experts' part;
    the shares, with the shared expert counted once, are the whole layer
    (ranks 1: one rank holds every expert)."""
    moe = {'n_routed': 32, 'top_k': 4, 'd_expert': 24, 'n_shared': 1,
           'scale': 2.5}
    whole = dict(moe, ranks=1, rank=0)
    w = _layer_weights(whole)
    h = jnp.asarray(np.random.RandomState(1).randn(23, 32), jnp.float32)
    valid = jnp.ones(23, bool)
    cfg = {'moe': whole}
    full, stats = experts.expert_layer(
        {'l_' + k: v for k, v in w.items()}, 'l_', cfg, h, valid)
    assert int(stats[0]) == 23 * 4 and int(stats[1]) == 23
    shared = experts.swiglu(h, w['moe_shared_fc1_w'], w['moe_shared_fc3_w'],
                            w['moe_shared_fc2_w'])
    total, assignments = shared, 0
    for r in range(16):
        part = dict(moe, ranks=16, rank=r)
        first, n = experts.held(part)
        assert (first, n) == (2 * r, 2)
        picks, wts = experts.route(h, w['moe_router_w'], part)
        y, st = experts.routed(
            h, w['moe_fc1_w'][first:first + n],
            w['moe_fc3_w'][first:first + n],
            w['moe_fc2_w'][first:first + n], picks, wts, valid, part)
        total = total + y
        assignments += int(st[0])
    assert assignments == 23 * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               rtol=2e-5, atol=2e-5)
    # and the whole layer is the plain sum over every token's picks
    picks, wts = experts.route(h, w['moe_router_w'], whole)
    plain = np.asarray(shared).copy()
    for t in range(23):
        for e, we in zip(np.asarray(picks[t]), np.asarray(wts[t])):
            plain[t] += we * np.asarray(experts.swiglu(
                h[t:t + 1], w['moe_fc1_w'][e], w['moe_fc3_w'][e],
                w['moe_fc2_w'][e]))[0]
    np.testing.assert_allclose(np.asarray(full), plain, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(wts).sum(1), 2.5, rtol=1e-6)


def _plain_routed(h, w, picks, wts, valid, moe):
    """Every valid token's held picks, one product at a time."""
    first, n = experts.held(moe)
    want = np.zeros(h.shape, np.float32)
    for t in np.flatnonzero(valid):
        for e, we in zip(np.asarray(picks[t]), np.asarray(wts[t])):
            if first <= e < first + n:
                want[t] += we * np.asarray(experts.swiglu(
                    h[t:t + 1], w['moe_fc1_w'][e - first],
                    w['moe_fc3_w'][e - first], w['moe_fc2_w'][e - first]))[0]
    return want


# which route of `experts.routed` each case below takes at 90 tokens, four
# held experts (a bucket of 16 sorted rows): at 19 tokens every one takes
# `unbatched`, whatever the routing
_ROUTE_AT_90 = {'one_expert': 'unbatched', 'all_held': 'unbatched',
                'none_held': 'grouped', 'as_routed': 'batched',
                'few_held': 'grouped', 'bucket_full': 'grouped',
                'bucket_and_one': 'batched'}
_HELD_PAIRS = {'few_held': 11, 'bucket_full': 16, 'bucket_and_one': 17}


@pytest.mark.parametrize('T', [19, 90], ids=['step', 'chunk'])
@pytest.mark.parametrize('uneven', sorted(_ROUTE_AT_90))
def test_no_token_is_dropped_however_uneven_the_routing(uneven, T):
    """Every token to ONE held expert (at 90 tokens its group passes the
    batched route's 64 rows: `ragged_dot` over T rows); every pick of
    every token held (T * top_k rows); nothing held at all (at 90 tokens
    the grouped route over its bucket of 16 rows, every one of them
    padding); the router's own picks (at 90 tokens the batched route:
    many pairs, every group within its 64 rows); and 11, 16 and 17 held
    pairs in all: under the bucket, exactly it, and one over, which at 90
    tokens is the batched route's.  `moe_touched_only_calls` says which
    read the experts with rows alone."""
    moe = {'n_routed': 16, 'top_k': 4, 'd_expert': 24, 'n_shared': 1,
           'scale': 2.5, 'ranks': 4, 'rank': 1}
    assert experts._GROUPED_ROWS * experts.held(moe)[1] == 16
    w = _layer_weights(moe, seed=2)
    h = jnp.asarray(np.random.RandomState(3).randn(T, 32), jnp.float32)
    wts = jnp.asarray(np.random.RandomState(4).rand(T, 4), jnp.float32)
    if uneven == 'as_routed':
        picks, wts = experts.route(h, w['moe_router_w'], moe)
        picks = np.asarray(picks)
    elif uneven in _HELD_PAIRS:                        # experts 4..7 are held
        picks = np.tile(np.asarray([0, 1, 2, 3], np.int32), (T, 1))
        n = _HELD_PAIRS[uneven]
        picks[:n, 1] = 4 + np.arange(n) % 3            # one of them untouched
    else:
        picks = np.tile(np.asarray(
            {'one_expert': [5, 0, 1, 2], 'all_held': [4, 5, 6, 7],
             'none_held': [0, 1, 2, 3]}[uneven], np.int32), (T, 1))
    valid = np.ones(T, bool)
    valid[-2:] = False                                 # padding routes nowhere
    y, stats = experts.routed(h, w['moe_fc1_w'], w['moe_fc3_w'],
                              w['moe_fc2_w'], jnp.asarray(picks), wts,
                              jnp.asarray(valid), moe)
    np.testing.assert_allclose(
        np.asarray(y), _plain_routed(h, w, picks, wts, valid, moe),
        rtol=2e-5, atol=2e-5)
    in_held = (picks[:T - 2] >= 4) & (picks[:T - 2] < 8)
    sizes = [int((picks[:T - 2] == e).sum()) for e in range(4, 8)]
    route = _ROUTE_AT_90[uneven] if T > experts._GROUP_ROWS else 'unbatched'
    assert [int(s) for s in stats] == [
        int(in_held.sum()), T - 2, sum(n > 0 for n in sizes), max(sizes),
        route != 'batched']
    if uneven in _HELD_PAIRS:
        assert int(stats[0]) == _HELD_PAIRS[uneven]
    if route == 'batched':
        assert 16 < int(stats[0]) and max(sizes) <= experts._GROUP_ROWS < T
    if route == 'grouped':
        assert int(stats[0]) <= 16


def test_a_step_of_many_slots_reads_the_touched_experts_alone(weights, rt):
    """More than `_GROUP_ROWS` slots, three of them live: every expert
    layer of every step of a window takes a route that reads the experts
    with rows alone (a dozen held pairs: the grouped route), and each
    stream is the one it is alone in the three-slot runtime, whose steps
    take `unbatched`."""
    prompts = [(_prompt(9, 7), 7), (_prompt(21, 8), 5), (_prompt(4, 9), 9)]
    alone = []
    for prompt, new in prompts:
        alone.append(rt.generate(prompt, new, steps_per_window=WINDOW))
        rt.reset()
    slots = experts._GROUP_ROWS + 2
    many = DecodeRuntime(weights, CFG, slots=slots, prefill_chunk=CHUNK,
                         page_len=PAGE)
    before = dict(obs.counters())
    gen = GenerationEngine(many, gen_config=GenerationConfig(
        decode_window=WINDOW)).start()
    try:
        streams = [gen.generate(prompt, max_new=new)
                   for prompt, new in prompts]
        got = [s.result(120) for s in streams]
    finally:
        gen.stop()
    assert all(r.ok for r in got)
    assert [list(s.tokens_so_far()) for s in streams] == alone
    c = {k: v - before.get(k, 0) for k, v in obs.counters().items()}
    steps = c['generation.decode_slot_steps'] // slots
    assert steps >= 8 and c['generation.decode_slot_steps'] == steps * slots
    assert c['generation.window_moe_touched_only_calls'] == 2 * steps
    assert 0 < c['generation.window_moe_assignments'] \
        <= experts._GROUPED_ROWS * 4 * 2 * steps


@pytest.mark.parametrize('rows,sizes', [
    (64, [0, 3, 0, 40, 1, 0, 5, 0]),      # empty groups between full tiles
    (64, [0, 0, 0, 0, 0, 0, 0, 0]),       # no row at all: zeros
    (64, [0, 0, 64, 0, 0, 0, 0, 0]),      # one group with every row
    (70, [9, 0, 0, 33, 0, 0, 22, 6]),     # rows no multiple of the tile
    (24, [1, 1, 1, 1, 1, 1, 1, 1]),       # under one tile, padded rows behind
], ids=['empty_between', 'no_row', 'one_group', 'ragged_tail', 'one_each'])
def test_the_grouped_product_is_ragged_dot_over_the_groups_with_rows(
        rows, sizes):
    """`experts.gmm` in interpret mode against `jax.lax.ragged_dot`, and
    the grid it walks: a group without rows is no step."""
    rng = np.random.RandomState(rows)
    x = jnp.asarray(rng.randn(rows, 40), jnp.float32)
    w = jnp.asarray(rng.randn(len(sizes), 40, 48), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(experts.gmm)(x, w, sizes)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jax.lax.ragged_dot(x, w, sizes)),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got)[int(sizes.sum()):], 0.0)
    tm = experts._GMM_ROWS
    padded = -(-rows // tm) * tm
    offsets, group, tile, count = (np.asarray(a) for a in
                                   experts._gmm_visits(sizes, padded, tm))
    want = [(g, t) for g, n in enumerate(np.asarray(sizes)) if n
            for t in range(offsets[g] // tm, (offsets[g + 1] - 1) // tm + 1)]
    assert list(zip(group[:count], tile[:count])) == (want or [(7, 0)])
    assert len(group) == padded // tm + len(sizes) - 1 >= count


# ------------------------------------------------------------ the kernel

@pytest.mark.parametrize('pages_per_block', [1, 2, None])
def test_the_kernel_is_the_composed_form_over_the_live_slots(
        pages_per_block):
    """Lengths that end mid-page and mid-block, a slot of one position, a
    full slot and a dead one (it reads nothing and gets zeros)."""
    rng = np.random.RandomState(0)
    S, H, v_dim, W, PL, M = 5, 4, 128, 256, 4, 6
    pool = jnp.asarray(rng.randn(40, 2, PL, W), jnp.float32)
    pool = pool.at[..., 200:].set(0.0)                 # the pad columns
    bt = jnp.asarray(rng.permutation(np.arange(1, 40))[:S * M]
                     .reshape(S, M), jnp.int32)
    n = jnp.asarray([7, 1, 0, 24, 13], jnp.int32)
    q_lat = jnp.asarray(rng.randn(S, H, v_dim), jnp.float32)
    q_r = jnp.asarray(rng.randn(S, H, W - v_dim), jnp.float32)
    got = ops_attention.latent_attention(
        q_lat, q_r, pool, bt, n, 1, 0.2, pages_per_block=pages_per_block)
    rows = pool[bt, 1].reshape(S, M * PL, W)
    want = ops_attention.latent_attention_composed(
        q_lat[:, :, None], q_r[:, :, None], rows, (n - 1)[:, None],
        0.2)[:, :, 0]
    live = np.asarray(n) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(got)[~live].any()
    assert ops_attention.paged_attention_rows(np.asarray(n), PL) \
        == 8 + 4 + 0 + 24 + 16


@pytest.mark.parametrize('shape,dtype,v_dim,devices,want', [
    ((9, 2, 16, 640), 'bfloat16', 512, 1, True),
    ((9, 2, 16, 576), 'bfloat16', 512, 1, False),      # not whole lane tiles
    ((9, 2, 8, 640), 'bfloat16', 512, 1, False),       # half a sublane tile
    ((9, 2, 8, 640), 'float32', 512, 1, True),
    ((9, 2, 16, 640), 'int8', 512, 1, False),
    ((9, 2, 16, 640), 'bfloat16', 512, 4, False)])
def test_on_an_accelerator_the_rule_asks_for_whole_tiles(
        monkeypatch, shape, dtype, v_dim, devices, want):
    from paddle_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, 'interpret', lambda: False)

    class Mesh(object):
        size = devices
    assert ops_attention.latent_attention_eligible(
        shape, dtype, v_dim, None if devices == 1 else Mesh()) is want


def test_the_composed_route_gives_the_same_tokens(weights, rt):
    """Under a mesh the step gathers the rows (`rt.paged` False): the same
    stream, and `kv_rows_read` counts every slot's max_len."""
    want = rt.generate(_prompt(10, 11), 6, steps_per_window=WINDOW)
    plain = DecodeRuntime(weights, CFG, slots=3, prefill_chunk=CHUNK,
                          page_len=PAGE)
    plain.kernels = plain.kernels._replace(paged=False)
    plain._gathered = 3 * CFG['max_len']
    before = dict(obs.counters())
    assert plain.generate(_prompt(10, 11), 6, steps_per_window=WINDOW) == want
    c = {k: v - before.get(k, 0) for k, v in obs.counters().items()}
    assert c['generation.kv_rows_read'] == 2 * WINDOW * 3 * CFG['max_len']
    assert c['generation.window_latent_rows_read'] \
        == 3 * c['generation.kv_rows_read']


# ---------------------------------------------- the chunk's two routes

def _one_chunk(rt, kernel, offset, count, poison, layer=1):
    """Layer ``layer``'s attention of one chunk at ``offset`` with
    ``count`` real tokens (the rest is the padded tail), over a slot
    whose cached rows are random; positions from ``poison`` on hold NaN
    before the chunk's own rows are written."""
    rng = np.random.RandomState(3)
    M, PL = rt.cache.max_pages, rt.cache.page_len
    lat = CFG['latent']
    bt_row = jnp.asarray(rng.permutation(np.arange(1, rt.cache.pages))[:M],
                         jnp.int32)
    pool = rng.randn(*rt.cache.pool_shape).astype(np.float32)
    pool[..., latent.row_width(lat):] = 0.0            # the pad columns
    flat = pool[np.asarray(bt_row), layer].reshape(M * PL, -1)
    flat[poison:] = np.nan
    pool[np.asarray(bt_row), layer] = flat.reshape(M, PL, -1)
    h = jnp.asarray(rng.randn(CHUNK, CFG['d_model']), jnp.float32)
    pos = offset + jnp.arange(CHUNK)
    pg = jnp.where(jnp.arange(CHUNK) < count, bt_row[pos // PL], 0)
    att, _ = jax.jit(
        lambda pool: latent.prefill(
            rt.params, 'layer_%d_' % layer, CFG, h, pos, offset + count,
            pool, layer, pg, pos % PL, bt_row, kernel))(jnp.asarray(pool))
    return np.asarray(att)


@pytest.mark.parametrize('offset,count', [
    (0, CHUNK),         # offset 0: one block, the chunk its own context
    (24, CHUNK),        # mid-context, ends on a block's last row
    (32, CHUNK),        # ends inside the third block
    (40, 3),            # a short final chunk: five rows of padded tail
], ids=['offset_0', 'mid_context', 'ends_mid_block', 'padded_tail'])
def test_the_kernel_is_the_block_loop_on_the_same_cache(rt, monkeypatch,
                                                        offset, count):
    """`latent_prefill` (interpret mode) against the composed block loop
    it replaces, blocks of 16: the same rows written, the same rows
    attended.  Every block past the context holds NaN and neither route
    visits it; the padded tail's queries see the same rows on both."""
    monkeypatch.setattr(latent, '_PREFILL_KEY_BLOCK', 16)
    visited = latent.prefill_rows(offset + count, CFG['max_len'])
    assert visited == -(-(offset + count) // 16) * 16
    got = _one_chunk(rt, True, offset, count, poison=visited)
    want = _one_chunk(rt, False, offset, count, poison=visited)
    assert np.isfinite(got).all() and np.isfinite(want).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    if visited > offset + CHUNK:
        # one block sooner and the chunk would have met the NaN
        assert not np.isfinite(_one_chunk(rt, True, offset, count,
                                          poison=visited - 16)).all()


@pytest.mark.parametrize('kernel', [True, False],
                         ids=['kernel', 'composed'])
def test_the_chunk_lowers_the_route_the_rule_chose(rt, kernel):
    """The prefill program holds the kernel where the rule allows and
    the block loop where it does not; either lowering counts once a
    layer."""
    sds = rt._sds
    i32, f32 = sds((), jnp.int32), sds((), jnp.float32)
    before = dict(obs.counters())
    text = jax.jit(
        decode._prefill_fn(rt.cfg, rt.cache, CHUNK,
                           kernels=decode.Kernels(prefill=kernel)),
        donate_argnums=(1,)).lower(
            rt._param_structs(), rt._state_structs(),
            sds((rt.cache.max_pages,), jnp.int32), sds((CHUNK,), jnp.int32),
            i32, i32, i32, i32, f32, i32).as_text(debug_info=True)
    c = {k: v - before.get(k, 0) for k, v in obs.counters().items()}
    L = CFG['n_layer']
    assert c.get('latent.prefill_kernel', 0) == (L if kernel else 0)
    assert c.get('latent.prefill_composed', 0) == (0 if kernel else L)
    assert 'attn.latent.scores' in text
    # the block loop's scores are an array of every head's; the kernel
    # (interpret mode here: plain operations, a head and a block at a
    # time) makes none of that extent
    scores = 'tensor<%dx%dx%dxf32>' % (CFG['n_head'], CHUNK, CFG['max_len'])
    assert (scores in text) is (not kernel)


def test_the_composed_chunk_gives_the_same_tokens(weights, rt):
    """Under a mesh the chunk's scores go through the block loop
    (`rt.prefill_kernel` False): the same stream."""
    assert rt.prefill_kernel
    want = rt.generate(_prompt(21, 4), 6, steps_per_window=WINDOW)
    plain = DecodeRuntime(weights, CFG, slots=3, prefill_chunk=CHUNK,
                          page_len=PAGE)
    plain.kernels = plain.kernels._replace(prefill=False)
    before = dict(obs.counters())
    assert plain.generate(_prompt(21, 4), 6, steps_per_window=WINDOW) == want
    c = {k: v - before.get(k, 0) for k, v in obs.counters().items()}
    assert c['latent.prefill_composed'] == CFG['n_layer']
    assert 'latent.prefill_kernel' not in c or not c['latent.prefill_kernel']


@pytest.mark.parametrize('shape,dtype,chunk,block,dims,devices,want', [
    ((9, 7, 16, 640), 'bfloat16', 512, 1024, (512, 128, 128), 1, True),
    ((9, 7, 16, 640), 'float32', 512, 1024, (512, 128, 128), 1, True),
    ((9, 7, 16, 576), 'bfloat16', 512, 1024, (512, 128, 128), 1, False),
    ((9, 7, 16, 640), 'bfloat16', 512, 1024, (512, 96, 128), 1, False),
    ((9, 7, 16, 640), 'bfloat16', 512, 1024, (512, 128, 64), 1, False),
    ((9, 7, 16, 640), 'bfloat16', 512, 1024, (448, 128, 128), 1, False),
    ((9, 7, 16, 640), 'bfloat16', 512, 720, (512, 128, 128), 1, False),
    ((9, 7, 16, 640), 'bfloat16', 8, 1024, (512, 128, 128), 1, False),
    ((9, 7, 16, 640), 'float32', 8, 1024, (512, 128, 128), 1, True),
    ((9, 7, 16, 640), 'int8', 512, 1024, (512, 128, 128), 1, False),
    ((9, 7, 16, 640), 'bfloat16', 512, 1024, (512, 128, 128), 4, False)],
    ids=['axk1', 'axk1_f32', 'row_not_lane_tiles', 'nope_96', 'v_64',
         'rank_448', 'key_block_720', 'chunk_half_a_tile', 'chunk_8_f32',
         'int8', 'mesh_of_4'])
def test_on_an_accelerator_the_chunk_rule_asks_for_whole_tiles(
        monkeypatch, shape, dtype, chunk, block, dims, devices, want):
    from paddle_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, 'interpret', lambda: False)

    class Mesh(object):
        size = devices
    assert ops_attention.latent_prefill_eligible(
        shape, dtype, chunk, block, *dims,
        mesh=None if devices == 1 else Mesh()) is want
