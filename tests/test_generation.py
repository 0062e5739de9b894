"""Streaming generative decode (paddle_tpu/serving/generation/): slotted
KV cache, chunked/ring prefill parity against a dense reference, bitwise
fused-vs-sequential decode parity (fresh AND restored from the AOT disk
cache), position-keyed sampling determinism, and the GenerationEngine's
token streaming, SLOs, termination, and fault behavior."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.observability as obs
from paddle_tpu.observability import tracing
from paddle_tpu.serving.engine import ServingConfig
from paddle_tpu.serving.generation import (CacheConfig, DecodeRuntime,
                                           GenerationConfig,
                                           GenerationEngine, SamplingParams,
                                           SlotAllocator, dense_reference)
from paddle_tpu.serving.generation.decode import random_weights
from paddle_tpu.ops.sampling import sample_logits, token_key
from paddle_tpu.testing import faults

CFG = dict(vocab=64, d_model=32, n_layer=2, n_head=4, n_kv_head=2,
           d_ffn=64, theta=10000.0, max_len=32)
PROMPT = [1, 5, 9, 2, 7, 3]


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()
    # drop this test's serving spans/flows from the global trace ring so
    # later trace-export tests see only their own events
    tracing.reset()


def _runtime(slots=3, chunk=4, mesh=None, seed=0):
    return DecodeRuntime(random_weights(CFG, seed=seed), CFG, slots=slots,
                         prefill_chunk=chunk, mesh=mesh)


def _engine(rt=None, window=4, **gen_kw):
    rt = rt or _runtime()
    return GenerationEngine(rt, config=ServingConfig(),
                            gen_config=GenerationConfig(
                                decode_window=window, **gen_kw)).start()


def _cnt(name):
    return obs.counters().get(name) or 0


# ------------------------------------------------------------- allocator

def test_slot_allocator_lowest_first_and_exhaustion():
    a = SlotAllocator(3)
    assert [a.alloc(), a.alloc(), a.alloc()] == [0, 1, 2]
    assert a.alloc() is None
    assert a.in_use() == 3
    a.free(1)
    assert a.alloc() == 1          # reuses the lowest free slot
    a.free(0)
    a.free(1)
    a.free(2)
    assert a.free_count() == 3


def test_slot_allocator_rejects_bad_frees():
    a = SlotAllocator(2)
    a.alloc()
    with pytest.raises(ValueError, match='out of range'):
        a.free(5)
    a.free(0)
    with pytest.raises(ValueError, match='double free'):
        a.free(0)


def test_cache_config_geometry():
    c = CacheConfig(slots=4, layers=2, kv_heads=2, max_len=32, head_dim=8)
    assert c.page_len == 8 and c.max_pages == 4
    assert c.pages == 4 * 4 + 1              # dense-equivalent + garbage
    assert c.pool_shape == (c.pages, 2, 8, 2, 8)   # token-major pages
    assert c.bytes() == c.pages * c.page_bytes()
    assert c.page_bytes() == 2 * 4 * 2 * 2 * 8 * 8
    assert (c.pages_for(0), c.pages_for(1), c.pages_for(8),
            c.pages_for(9)) == (0, 1, 1, 2)
    assert c.dense_slot_bytes() == 2 * 4 * 2 * 2 * 32 * 8
    q = CacheConfig(slots=4, layers=2, kv_heads=2, max_len=32, head_dim=8,
                    page_len=4, quant='int8')
    assert q.store_dtype == 'int8'
    # int8 K+V page + f32 per-row scales
    assert q.page_bytes() == 2 * (2 * 2 * 4 * 8) + 2 * 4 * (2 * 2 * 4)
    with pytest.raises(ValueError):
        CacheConfig(slots=0, layers=1, kv_heads=1, max_len=8, head_dim=4)
    with pytest.raises(ValueError):
        CacheConfig(slots=1, layers=1, kv_heads=1, max_len=8, head_dim=4,
                    page_len=3)              # must divide max_len
    with pytest.raises(ValueError):
        CacheConfig(slots=1, layers=1, kv_heads=1, max_len=8, head_dim=4,
                    quant='int4')


# -------------------------------------------------------------- sampling

def test_sample_logits_greedy_and_topk1_are_argmax():
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(16).astype('float32'))
    am = int(jnp.argmax(logits))
    key = token_key(7, 3)
    assert int(sample_logits(logits, key)) == am
    # top_k=1 with any temperature can only pick the argmax
    for seed in range(5):
        got = int(sample_logits(logits, token_key(seed, 0),
                                temperature=2.0, top_k=1))
        assert got == am


def test_sample_logits_topk_respects_support():
    rng = np.random.RandomState(1)
    logits = jnp.asarray(rng.randn(32).astype('float32'))
    top5 = set(np.argsort(np.asarray(logits))[-5:].tolist())
    for seed in range(20):
        got = int(sample_logits(logits, token_key(seed, 0),
                                temperature=1.5, top_k=5))
        assert got in top5


def test_sampling_is_position_and_seed_keyed():
    rng = np.random.RandomState(2)
    logits = jnp.asarray(rng.randn(64).astype('float32'))

    def draw(seed, pos):
        return int(sample_logits(logits, token_key(seed, pos),
                                 temperature=1.0))

    assert draw(3, 11) == draw(3, 11)           # deterministic
    draws = {draw(3, p) for p in range(40)}
    assert len(draws) > 1                        # position moves the draw
    draws_b = [draw(4, p) for p in range(40)]
    assert [draw(3, p) for p in range(40)] != draws_b  # seed moves it


def test_sample_tokens_op_matches_across_optimizer(monkeypatch):
    import paddle_tpu as fluid
    from paddle_tpu import layers

    def run(opt):
        monkeypatch.setenv('PT_OPT', opt)
        main, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, start):
            x = layers.data('x', shape=[8], dtype='float32')
            greedy = layers.sample_tokens(x)
            drawn = layers.sample_tokens(x, temperature=0.8, top_k=3,
                                         seed=7)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(start)
        feed = {'x': np.random.RandomState(0).randn(4, 8).astype('float32')}
        return exe.run(main, feed=feed, fetch_list=[greedy, drawn])

    g0, d0 = run('0')
    g1, d1 = run('1')
    x = np.random.RandomState(0).randn(4, 8).astype('float32')
    assert np.array_equal(np.asarray(g0).ravel(), np.argmax(x, -1))
    assert np.array_equal(g0, g1) and np.array_equal(d0, d1)


# ------------------------------------------------------- prefill parity

def test_chunked_prefill_matches_dense_reference():
    rt = _runtime(chunk=4)
    prompt = np.asarray(PROMPT, np.int32)
    slot = rt.alloc_slot()
    assert rt.ensure_capacity(slot, prompt.size)   # map pages for the slot
    p = SamplingParams()
    logits = None
    for off in range(0, prompt.size, rt.prefill_chunk):
        first, logits = rt.prefill(slot, prompt[off:off + rt.prefill_chunk],
                                   off, p)
    kref, vref, lref = dense_reference(rt.w, CFG, prompt)
    krow, vrow, length = rt.cache_row(slot)
    assert length == prompt.size
    np.testing.assert_allclose(krow[:, :, :prompt.size], kref, atol=1e-5)
    np.testing.assert_allclose(vrow[:, :, :prompt.size], vref, atol=1e-5)
    np.testing.assert_allclose(logits, lref, atol=1e-5)
    assert first == int(np.argmax(lref))


@pytest.mark.skipif(len(jax.devices()) < 4, reason='needs 4 devices')
def test_ring_prefill_matches_dense_reference():
    from paddle_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(data=1, seq=4, model=1, pipe=1,
                     devices=jax.devices()[:4])
    rt = _runtime(slots=2, chunk=4, mesh=mesh)
    prompt = (np.arange(1, 11) % 63).astype(np.int32)   # pads 10 -> 12
    slot = rt.alloc_slot()
    assert rt.ensure_capacity(slot, prompt.size)   # map pages for the slot
    first, logits = rt.prefill_ring(slot, prompt, SamplingParams())
    kref, vref, lref = dense_reference(rt.w, CFG, prompt)
    krow, vrow, length = rt.cache_row(slot)
    assert length == prompt.size
    np.testing.assert_allclose(krow[:, :, :prompt.size], kref, atol=1e-5)
    np.testing.assert_allclose(vrow[:, :, :prompt.size], vref, atol=1e-5)
    np.testing.assert_allclose(logits, lref, atol=1e-5)
    rt.free_slot(slot)
    # the two prefill strategies feed bitwise-identical decode streams
    out_ring = rt.generate(prompt, 6, use_ring=True)
    rt.reset()
    out_chunk = rt.generate(prompt, 6, use_ring=False)
    assert out_ring == out_chunk


# ------------------------------------------------- fused decode parity

@pytest.mark.parametrize('params', [SamplingParams(),
                                    SamplingParams(0.9, 5, 11)],
                         ids=['greedy', 'topk'])
def test_fused_window_bitwise_equals_sequential(params):
    rt = _runtime()
    seq = rt.generate(PROMPT, 8, params, steps_per_window=1)
    rt.reset()
    fused = rt.generate(PROMPT, 8, params, steps_per_window=4)
    assert fused == seq            # bitwise: same ints, any K


def test_decode_parity_through_restored_aot_cache(tmp_path, monkeypatch):
    monkeypatch.setenv('PT_CACHE', '1')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    params = SamplingParams(0.7, 5, 9)
    w = random_weights(CFG)
    rt1 = DecodeRuntime(w, CFG, slots=2, prefill_chunk=4)
    out1 = rt1.generate(PROMPT, 8, params, steps_per_window=4)
    hits0 = _cnt('compile_cache.disk_hits')
    # a fresh runtime (fresh process stand-in) loads the SAME executables
    # from disk and produces the SAME tokens
    rt2 = DecodeRuntime(w, CFG, slots=2, prefill_chunk=4)
    out2 = rt2.generate(PROMPT, 8, params, steps_per_window=4)
    assert out2 == out1
    assert _cnt('compile_cache.disk_hits') >= hits0 + 2


def test_no_retrace_across_batch_compositions():
    rt = _runtime(slots=3)
    compiles0 = _cnt('generation.compiles')
    rt.generate(PROMPT, 4, steps_per_window=2)
    rt.generate([4, 4], 4, SamplingParams(1.0, 3, 5), steps_per_window=2)
    rt.generate([9] * 7, 4, steps_per_window=2)
    # one prefill executable + one decode executable, total — sampling
    # params and prompt lengths are data, not signatures
    assert _cnt('generation.compiles') - compiles0 == 2


def test_runtime_generate_refuses_overlong():
    rt = _runtime()
    with pytest.raises(ValueError, match='never truncated'):
        rt.generate(list(range(30)), 8)


# ------------------------------------------------------------- engine

def test_engine_streams_and_resolves_max_tokens():
    eng = _engine()
    try:
        s = eng.generate(PROMPT, max_new=8)
        toks = list(s.tokens(timeout=30))
        r = s.result(5)
        assert r.ok and r.reason == 'max_tokens'
        assert toks == list(r.outputs[0]) and len(toks) == 8
        assert s.tokens_so_far() == toks
        # engine stream == direct sequential runtime stream
        ref = _runtime().generate(PROMPT, 8, steps_per_window=1)
        assert toks == ref
    finally:
        eng.stop()
    assert _cnt('serving.deadlocks') == 0


def test_engine_eos_terminates():
    rt = _runtime()
    eos = rt.generate(PROMPT, 1)[0]          # learn the first greedy token
    rt.reset()
    eng = _engine(rt, eos_id=eos)
    try:
        r = eng.generate(PROMPT, max_new=8).result(30)
        assert r.ok and r.reason == 'eos'
        assert len(r.outputs[0]) == 1 and int(r.outputs[0][0]) == eos
    finally:
        eng.stop()


def test_engine_rejects_overlong_prompt_never_truncates():
    eng = _engine()
    try:
        r = eng.generate(list(range(30)), max_new=8).result(1)
        assert r.status == 'rejected' and r.reason == 'too_long'
        assert 'truncated' in r.error and 'max_len=32' in r.error
        assert _cnt('serving.rejected.too_long') >= 1
        # boundary: exactly max_len fits
        ok = eng.generate(list(range(1, 29)), max_new=4).result(30)
        assert ok.ok and len(ok.outputs[0]) == 4
    finally:
        eng.stop()


def test_engine_rejects_empty_prompt_and_bad_max_new():
    eng = _engine()
    try:
        assert eng.generate([], max_new=4).result(1).reason == 'bad_request'
        assert eng.generate([1], max_new=0).result(1).reason == 'bad_request'
    finally:
        eng.stop()


def test_engine_submit_is_closed_off():
    eng = _engine()
    try:
        with pytest.raises(TypeError, match='generate'):
            eng.submit({'x': np.ones((1, 2))})
    finally:
        eng.stop()


def test_engine_seeded_topk_deterministic_across_restarts():
    outs = []
    for _ in range(2):
        eng = _engine(window=3)
        try:
            r = eng.generate(PROMPT, max_new=6, temperature=0.8, top_k=5,
                             seed=42).result(30)
            assert r.ok
            outs.append(list(r.outputs[0]))
        finally:
            eng.stop()
    assert outs[0] == outs[1]


def test_engine_cancel_mid_stream_sheds():
    eng = _engine()
    try:
        s = eng.generate(PROMPT, max_new=24, temperature=0.5, seed=1)
        it = s.tokens(timeout=30)
        next(it)                       # wait for the stream to be live
        s.cancel()
        r = s.result(10)
        assert r.status == 'shed' and r.reason == 'cancelled'
        assert _cnt('generation.cancelled') >= 1
    finally:
        eng.stop()
    assert _cnt('serving.deadlocks') == 0


def test_engine_concurrent_mixed_prefill_decode():
    eng = _engine(_runtime(slots=3))
    mixed0 = _cnt('generation.mixed_dispatches')
    try:
        streams = [eng.generate([i + 1] * (3 + i), max_new=5, seed=i)
                   for i in range(6)]          # 6 requests, 3 slots
        results = [s.result(60) for s in streams]
        assert all(r.ok and len(r.outputs[0]) == 5 for r in results)
        assert _cnt('generation.mixed_dispatches') > mixed0
    finally:
        eng.stop()
    assert _cnt('serving.deadlocks') == 0


def test_engine_ttft_itl_histograms_and_schema():
    eng = _engine()
    try:
        r = eng.generate(PROMPT, max_new=6).result(30)
        assert r.ok
    finally:
        eng.stop()
    assert obs.histogram('serving.ttft_ms').quantile(0.5) is not None
    assert obs.histogram('serving.itl_ms').quantile(0.5) is not None
    tel = obs.telemetry_snapshot('serving')
    for k in ('ttft_p50_ms', 'ttft_p99_ms', 'itl_p50_ms', 'itl_p99_ms',
              'kv_slots_in_use'):
        assert k in tel
    assert tel['kv_slots_in_use'] == 0
    assert any(k.startswith('generation.') for k in tel['counters'])


def test_engine_overall_deadline_mid_stream():
    eng = _engine()
    try:
        s = eng.generate(PROMPT, max_new=26, timeout_s=0.01)
        r = s.result(10)
        assert r.status == 'deadline_exceeded'
    finally:
        eng.stop()
    assert _cnt('serving.deadlocks') == 0


def test_engine_drain_sheds_active_streams():
    eng = _engine(window=1)
    s = eng.generate([1, 2], max_new=26)
    it = s.tokens(timeout=30)
    next(it)                            # actively decoding now
    eng.stop()
    r = s.result(5)
    # either it finished in time or it was shed at shutdown — never silent
    assert r.status in ('ok', 'shed')
    assert _cnt('serving.deadlocks') == 0


def test_engine_decode_step_fault_gives_error_replies_and_frees_slots():
    faults.configure('decode_step:at=1')
    rt = _runtime(slots=2)
    eng = _engine(rt)
    try:
        streams = [eng.generate(PROMPT, max_new=6, seed=i)
                   for i in range(2)]
        results = [s.result(30) for s in streams]
        # the faulted window errors every decoding request; requests that
        # were still prefilling at fire time finish OK afterwards
        assert any(r.status == 'error' and r.reason == 'decode_step'
                   for r in results)
        assert all(r.status in ('ok', 'error') for r in results)
        assert _cnt('faults.injected.decode_step') == 1
        assert rt.free_slots() == rt.slots     # no leaked slots
        # the engine keeps serving after the fault
        r2 = eng.generate(PROMPT, max_new=3).result(30)
        assert r2.ok
    finally:
        eng.stop()
    assert _cnt('serving.deadlocks') == 0


def test_llama_make_streaming_runtime_end_to_end():
    import paddle_tpu as fluid
    from paddle_tpu.models import llama

    main, start = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, start):
        llama.build('tiny', is_train=False)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(start)
    scope = fluid.global_scope()
    rt = llama.make_streaming_runtime(scope, 'tiny', slots=2,
                                      prefill_chunk=8)
    eng = GenerationEngine(rt, gen_config=GenerationConfig(
        decode_window=2)).start()
    try:
        r = eng.generate([1, 2, 3, 4], max_new=4).result(60)
        assert r.ok and len(r.outputs[0]) == 4
        assert all(0 <= t < llama.CONFIGS['tiny']['vocab']
                   for t in r.outputs[0])
    finally:
        eng.stop()
    assert _cnt('serving.deadlocks') == 0
