"""Paged + quantized KV cache, shared-prefix caching, speculative
decode (paddle_tpu/serving/generation/): PagePool refcounting and
eviction, PrefixCache chain keys, paged multi-page parity against the
dense reference, int8 parity budget with greedy stream equality,
prefix-hit and speculative streams pinned BITWISE against cold/plain
decode, and the two kv_oom surfaces (admission backpressure stays
queued; mid-stream exhaustion is a terminal error, never truncation)."""
import json
import os

import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.observability import tracing
from paddle_tpu.serving.engine import ServingConfig
from paddle_tpu.serving.generation import (CacheConfig, DecodeRuntime,
                                           GenerationConfig,
                                           GenerationEngine, PagePool,
                                           PrefixCache, SamplingParams,
                                           default_page_len,
                                           dense_reference)
from paddle_tpu.serving.generation.decode import random_weights
from paddle_tpu.serving.generation.sampling import draft_ngram
from paddle_tpu.testing import faults

CFG = dict(vocab=64, d_model=32, n_layer=2, n_head=4, n_kv_head=2,
           d_ffn=64, theta=10000.0, max_len=32)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()
    tracing.reset()


def _cfg(slots=2, page_len=4, pages=None, quant='none'):
    return CacheConfig(slots=slots, layers=2, kv_heads=2, max_len=32,
                       head_dim=8, page_len=page_len, pages=pages,
                       quant=quant)


def _rt(slots=2, page_len=4, **kw):
    kw.setdefault('prefill_chunk', 4)
    return DecodeRuntime(random_weights(CFG, seed=0), CFG, slots=slots,
                         page_len=page_len, **kw)


def _cnt(name):
    return int(obs.counters().get(name) or 0)


# ----------------------------------------------------------- page pool

def test_default_page_len_largest_divisor_up_to_8():
    assert default_page_len(32) == 8
    assert default_page_len(24) == 8
    assert default_page_len(20) == 5
    assert default_page_len(7) == 7


def test_page_pool_alloc_lowest_first_all_or_nothing():
    pool = PagePool(_cfg(pages=6))        # pages 1..5 allocatable
    assert pool.capacity == 5
    a = pool.alloc(3)
    assert a == [1, 2, 3]                 # page 0 reserved, lowest first
    assert pool.alloc(3) is None          # 2 free < 3: all-or-nothing
    assert pool.in_use() == 3             # the failed alloc leaked nothing
    b = pool.alloc(2)
    assert b == [4, 5]
    pool.release(a)
    pool.release(b)
    assert pool.free_count() == 5
    assert pool.alloc(0) == []


def test_page_pool_refcounts_shared_pages():
    pool = PagePool(_cfg(pages=4))
    pages = pool.alloc(2)
    pool.retain(pages)                    # second holder (prefix share)
    pool.release(pages)
    assert pool.in_use() == 2             # survives the first release
    assert pool.refcount(pages[0]) == 1
    pool.release(pages)
    assert pool.in_use() == 0
    with pytest.raises(ValueError, match='release of free'):
        pool.release(pages)
    with pytest.raises(ValueError, match='retain of unallocated'):
        pool.retain([3])


def test_page_pool_evict_callback_frees_under_pressure():
    pool = PagePool(_cfg(pages=4))        # 3 allocatable
    held = [pool.alloc(1), pool.alloc(1), pool.alloc(1)]

    def evict():
        if held:
            pool.release(held.pop(0))
            return True
        return False

    assert pool.alloc(2) is None          # no evictor: exhausted
    got = pool.alloc(2, evict=evict)      # evictor drains oldest holds
    assert got is not None and len(got) == 2
    assert len(held) == 1                 # exactly as many evictions as needed


def test_page_pool_kv_oom_fault_site_forces_exhaustion():
    assert 'kv_oom' in faults.SITES
    pool = PagePool(_cfg(pages=6))
    faults.configure('kv_oom:at=1:times=1')
    assert pool.alloc(1) is None          # injected exhaustion
    got = pool.alloc(1)                   # budget spent: pool recovers
    assert got == [1]


# --------------------------------------------------------- prefix cache

def test_prefix_cache_chain_match_insert_evict():
    pool = PagePool(_cfg(pages=8))
    pc = PrefixCache(pool, page_len=4)
    prompt = np.arange(1, 13, dtype=np.int32)       # 12 tokens = 3 pages
    pages = pool.alloc(3)
    h0 = _cnt('generation.prefix_inserts')
    assert pc.insert(prompt, pages) == 3            # depths 1, 2, 3
    assert len(pc) == 3
    assert _cnt('generation.prefix_inserts') == h0 + 3
    # a prompt sharing 2 pages + fresh tail hits depth 2, retained for us
    other = np.concatenate([prompt[:8], [60, 61, 62]]).astype(np.int32)
    hits0 = _cnt('generation.prefix_hits')
    got = pc.match(other)
    assert got == pages[:2]
    assert _cnt('generation.prefix_hits') == hits0 + 1
    # holders of page 1: the original alloc, one per chain entry that
    # includes it (depths 1..3), and the match we just took
    assert pool.refcount(pages[0]) == 5
    pool.release(got)
    # a diverging prompt misses entirely
    assert pc.match(np.asarray([9, 9, 9, 9, 9, 9], np.int32)) == []
    # matching never covers the whole prompt: one suffix token must
    # prefill to produce the first-token logits
    assert pc.match(prompt[:4]) == []
    one = pc.match(prompt[:5])
    assert one == pages[:1]
    pool.release(one)
    # FIFO eviction drops the oldest entry; reset drains the rest
    ev0 = _cnt('generation.prefix_evictions')
    assert pc.evict_one()
    assert len(pc) == 2
    assert _cnt('generation.prefix_evictions') == ev0 + 1
    pc.reset()
    assert len(pc) == 0
    pool.release(pages)                   # the original stream's hold
    assert pool.in_use() == 0


# ------------------------------------------------- paged decode parity

def test_multipage_prefill_matches_dense_reference():
    # 10 tokens over page_len=4 spans 3 pages — the gather/scatter must
    # follow the block table, not page 0
    rt = _rt(page_len=4)
    prompt = (np.arange(1, 11) * 3 % 63 + 1).astype(np.int32)
    slot = rt.alloc_slot()
    assert rt.ensure_capacity(slot, prompt.size)
    logits = None
    for off in range(0, prompt.size, rt.prefill_chunk):
        _, logits = rt.prefill(slot, prompt[off:off + rt.prefill_chunk],
                               off, SamplingParams())
    kref, vref, lref = dense_reference(rt.w, CFG, prompt)
    krow, vrow, length = rt.cache_row(slot)
    assert length == prompt.size
    # the slot's pages are non-contiguous in the pool by construction
    assert len(rt.owned[slot]) == 3
    np.testing.assert_allclose(krow[:, :, :prompt.size], kref, atol=1e-5)
    np.testing.assert_allclose(vrow[:, :, :prompt.size], vref, atol=1e-5)
    np.testing.assert_allclose(logits, lref, atol=1e-5)
    rt.free_slot(slot)
    assert rt.pool.in_use() == 0


def test_int8_quant_greedy_stream_equal_and_logit_budget():
    prompt = [1, 5, 9, 2, 7, 3, 11, 4, 8, 2]
    rt32 = _rt(page_len=4, prefix_cache=False)
    rt8 = DecodeRuntime(rt32.w, CFG, slots=2, prefill_chunk=4, page_len=4,
                        kv_quant='int8', prefix_cache=False)
    assert rt8.cache.store_dtype == 'int8'
    assert rt8.cache.page_bytes() < rt32.cache.page_bytes()
    # documented parity budget: final-chunk logits within 2e-2 absolute
    s32, s8 = rt32.alloc_slot(), rt8.alloc_slot()
    assert rt32.ensure_capacity(s32, len(prompt))
    assert rt8.ensure_capacity(s8, len(prompt))
    l32 = l8 = None
    for off in range(0, len(prompt), 4):
        _, l32 = rt32.prefill(s32, prompt[off:off + 4], off,
                              SamplingParams())
        _, l8 = rt8.prefill(s8, prompt[off:off + 4], off, SamplingParams())
    assert float(np.max(np.abs(l32 - l8))) <= 2e-2
    rt32.free_slot(s32)
    rt8.free_slot(s8)
    # and the budget is small enough that GREEDY streams are identical
    assert rt8.generate(prompt, 10) == rt32.generate(prompt, 10)


def test_prefix_hit_stream_bitwise_equals_cold():
    rt = _rt(page_len=4)                  # prefix cache on by default
    assert rt.prefix is not None
    prompt = [7, 3, 11, 2, 9, 1, 4, 6, 13, 5]      # 2 full pages + tail
    cold = rt.generate(prompt, 8)
    inserted = _cnt('generation.prefix_inserts')
    assert inserted >= 2                  # both full pages published
    hits0 = _cnt('generation.prefix_hits')
    warm = rt.generate(prompt, 8)
    assert _cnt('generation.prefix_hits') == hits0 + 1
    assert warm == cold                   # bitwise: a hit never shifts tokens
    # seeded top-k must be equally invisible
    p = SamplingParams(temperature=0.9, top_k=5, seed=11)
    cold_tk = rt.generate(prompt, 8, p)
    warm_tk = rt.generate(prompt, 8, p)
    assert warm_tk == cold_tk
    # cached chains hold pages after every stream retired — that is the
    # cache working, not a leak; reset releases them all
    assert rt.pool.in_use() > 0
    assert rt.allocator.in_use() == 0
    rt.prefix.reset()
    assert rt.pool.in_use() == 0


def test_speculative_stream_bitwise_equals_plain():
    rt = _rt(page_len=4, prefix_cache=False)
    prompt = [1, 5, 9, 2, 7, 3]
    plain = rt.generate(prompt, 14)
    prop0, acc0 = _cnt('generation.spec_proposed'), \
        _cnt('generation.spec_accepted')
    compiles0 = _cnt('generation.compiles')
    spec = rt.generate(prompt, 14, speculative=True)
    assert spec == plain                  # speculation never changes tokens
    assert _cnt('generation.spec_proposed') > prop0
    assert _cnt('generation.spec_accepted') >= acc0
    # seeded top-k sampling replays identically through accept/verify
    p = SamplingParams(temperature=0.9, top_k=5, seed=11)
    assert rt.generate(prompt, 10, p, speculative=True) == \
        rt.generate(prompt, 10, p)
    # the verify executable was the only extra compile
    rt.warmup(steps=4, speculative=True)
    c0 = _cnt('generation.compiles')
    rt.generate(prompt, 8, speculative=True)
    assert _cnt('generation.compiles') == c0


def test_draft_ngram_prompt_lookup():
    # last token 5 occurred before at index 1; propose its continuation
    ctx = np.asarray([3, 5, 8, 13, 5], np.int32)
    np.testing.assert_array_equal(draft_ngram(ctx, 3), [8, 13, 5])
    # no prior occurrence: pad with the last token
    np.testing.assert_array_equal(draft_ngram(np.asarray([1, 2, 3]), 2),
                                  [3, 3])


# ----------------------------------------------------- kv_oom surfaces

def test_admission_never_fits_rejected_and_backpressure_queues():
    # 2 allocatable pages of 4 tokens: one stream fills the pool
    rt = _rt(slots=2, page_len=4, pages=3, prefix_cache=False)
    eng = GenerationEngine(rt, config=ServingConfig(max_queue=16),
                           gen_config=GenerationConfig(
                               decode_window=4)).start()
    try:
        # could never fit even on an idle pool -> terminal kv_oom reject
        res = eng.generate(list(range(1, 10)), max_new=9).result(30)
        assert res.status == 'rejected' and res.reason == 'kv_oom'
        # oversubscribe: page-short streams stay QUEUED and complete
        # once the pool frees — backpressure, not failure
        # prompt + max_new exactly fills the 2-page pool, so each
        # stream FITS alone but two can never run together
        bp0 = _cnt('generation.kv_backpressure')
        streams = [eng.generate([1 + i, 5, 9, 2], max_new=4,
                                timeout_s=60.0) for i in range(4)]
        results = [s.result(60) for s in streams]
        assert all(r.ok for r in results)
        assert _cnt('generation.kv_backpressure') > bp0
    finally:
        eng.stop()
    assert rt.pool.in_use() == 0
    assert rt.free_slots() == rt.slots


def test_midstream_kv_oom_terminal_error_with_flight_dump(tmp_path,
                                                          monkeypatch):
    monkeypatch.setenv('PT_FLIGHT_DIR', str(tmp_path))
    rt = _rt(slots=1, page_len=4, prefix_cache=False)
    eng = GenerationEngine(rt, config=ServingConfig(),
                           gen_config=GenerationConfig(
                               decode_window=4)).start()
    try:
        oom0 = _cnt('generation.kv_oom')
        # alloc #1 claims the admission span; alloc #2 is the
        # mid-stream growth for the second window, tried while the first
        # still runs, and alloc #3 the same growth tried again at the
        # boundary, where the verdict falls (a stream leaving with the
        # first window would have given its pages back by then) —
        # inject at both
        faults.configure('kv_oom:at=2:times=2')
        s = eng.generate([2, 7], max_new=8, timeout_s=60.0)
        res = s.result(60)
        assert res.status == 'error' and res.reason == 'kv_oom'
        assert len(s.tokens_so_far()) >= 1      # streamed work stays readable
        assert _cnt('generation.kv_oom') == oom0 + 1
    finally:
        eng.stop()
    assert rt.free_slots() == rt.slots and rt.pool.in_use() == 0
    dumps = [fn for fn in os.listdir(str(tmp_path)) if 'kv_oom' in fn]
    assert dumps, 'mid-stream kv_oom left no flight dump'
    art = json.load(open(os.path.join(str(tmp_path), dumps[0])))
    assert 'kv_pool' in art['extra']
    assert art['extra']['kv_pool']['pages_capacity'] == rt.pool.capacity
