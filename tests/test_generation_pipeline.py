"""The reordered serving round (scheduler.py `_work`): the next chunk and
window are prepared and uploaded while the chip runs the current window,
and launched the moment its tokens land.

Every scenario is driven BY HAND, round by round on the test's own
thread, on the dense block and on `falcon_h1`, through two engines over
one runtime: the engine as it is, and `SerialEngine`, the round as it was
before the reordering (admit -> chunk, read -> window, read -> emit), kept
here as the oracle.  A stream's tokens depend on its prompt, seed and
position only, so they must agree token for token whatever rode with them.
"""
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.serving.engine import ERROR, READY, ServingConfig
from paddle_tpu.serving.generation import (DecodeRuntime, GenerationConfig,
                                           GenerationEngine, SamplingParams,
                                           decode, random_weights)

CHUNK, WINDOW, SLOTS, PAGE = 4, 3, 3, 4
CFGS = {
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
                        'mlp_down': 0.6}}}
_RUNTIMES = {}


def _runtime(block, pages=None):
    """One runtime per (block, pool depth) for the module: its
    executables compile once; reset before every use."""
    key = (block, pages)
    if key not in _RUNTIMES:
        cfg = CFGS[block]
        _RUNTIMES[key] = DecodeRuntime(
            random_weights(cfg, seed=1, scale=0.3), cfg, slots=SLOTS,
            prefill_chunk=CHUNK, page_len=PAGE, pages=pages)
    rt = _RUNTIMES[key]
    _unwrapped(rt)
    return rt


def _unwrapped(rt):
    rt.__dict__.pop('prefill', None)        # a test's wrappers, if left
    rt.__dict__.pop('decode_window', None)
    rt.reset()


@pytest.fixture(params=sorted(CFGS))
def block(request):
    return request.param


class SerialEngine(GenerationEngine):
    """The round before the reordering, as the oracle: every launch is
    read at once, and nothing is ever queued behind a running one."""

    def _work(self):
        if not self._admit_and_sweep():
            return False
        rt, K = self.runtime, self._gen.decode_window
        pre = [r for r in self._active if r.offset < r.prompt.size]
        if pre:
            r = min(pre, key=lambda x: x.t_submit)
            chunk = r.prompt[r.offset:r.offset + rt.prefill_chunk]
            first, _ = rt.prefill(r.slot, chunk, r.offset, r.params)
            r.offset += int(chunk.size)
            if r.offset >= r.prompt.size:
                rt.promote_prefix(r.slot, r.prompt)
                self._emit_tokens(r, [int(first)])
        dec = [r for r in self._active if r.offset >= r.prompt.size]
        for r in list(dec):
            if not rt.ensure_capacity(r.slot, int(rt.host_len[r.slot]) + K):
                dec.remove(r)
                obs.metrics.counter('generation.kv_oom').inc()
                self._retire(r, ERROR, reason='kv_oom', error='pool')
        if dec:
            active = np.zeros(rt.slots, bool)
            seeds = np.zeros(rt.slots, np.int32)
            temps = np.zeros(rt.slots, np.float32)
            topks = np.zeros(rt.slots, np.int32)
            for r in dec:
                active[r.slot] = True
                seeds[r.slot] = r.params.seed
                temps[r.slot] = r.params.temperature
                topks[r.slot] = r.params.top_k
            toks = np.asarray(rt.decode_window(K, active, seeds, temps,
                                               topks))
            for r in list(dec):
                self._emit_tokens(r, [int(t) for t in toks[r.slot]])
        return True


def _drive(cls, rt, script, eos_id=None, clock=time.monotonic, rounds=None):
    """Run ``script`` ({round: [action(engine, streams)]}) round by round
    on this thread.  Returns ({name: (status, reason, tokens)}, engine)."""
    eng = cls(rt, config=ServingConfig(),
              gen_config=GenerationConfig(decode_window=WINDOW,
                                          eos_id=eos_id), clock=clock)
    eng._set_state(READY)
    streams, n = {}, 0
    while n <= max(script) or eng._queue or eng._active:
        for action in script.get(n, ()):
            action(eng, streams)
        if eng._queue or eng._active:
            assert eng._round()
            if rounds is not None:
                rounds.append(n)
        n += 1
        assert n < 300, 'the engine does not finish'
    out = {}
    for name, s in streams.items():
        assert s.done(), name
        res = s.result(0)
        out[name] = (res.status, res.reason, s.tokens_so_far())
    assert rt.free_slots() == rt.slots
    assert rt.prefix is not None or rt.pool.in_use() == 0
    return out, eng


def _submit(name, prompt, max_new, **kw):
    def action(eng, streams):
        streams[name] = eng.generate(prompt, max_new=max_new, **kw)
    return action


def _cancel(name):
    return lambda eng, streams: streams[name].cancel()


def _oracle(rt, prompt, max_new, **kw):
    """The stream alone, through `DecodeRuntime.generate`."""
    _unwrapped(rt)
    out = rt.generate(prompt, max_new, SamplingParams(**kw),
                      steps_per_window=WINDOW)
    rt.reset()
    return out


def _prompt(seed, n, block):
    return np.random.RandomState(seed).randint(
        1, CFGS[block]['vocab'], n).tolist()


def _delta(before):
    now = obs.counters()
    return {k: now.get(k, 0) - before.get(k, 0) for k in now}


# ------------------------------------------------ (a) the serial order's

def _mixed(block):
    """Arrivals at several rounds: one chunk and many, greedy and sampled,
    a stream of one token, streams that outlive others."""
    return {0: [_submit('a', _prompt(1, 6, block), 11, seed=3,
                        temperature=1.0),
                _submit('b', _prompt(2, 3, block), 1)],
            1: [_submit('c', _prompt(3, 11, block), 5, seed=9,
                        temperature=0.7, top_k=8)],
            4: [_submit('d', _prompt(4, 4, block), 8)],
            9: [_submit('e', _prompt(5, 9, block), 7, seed=1,
                        temperature=1.0),
                _submit('f', _prompt(6, 2, block), 2)]}


def test_mixed_arrivals_give_the_serial_orders_tokens(block):
    rt = _runtime(block)
    want, _ = _drive(SerialEngine, rt, _mixed(block))
    rt.reset()
    got, _ = _drive(GenerationEngine, rt, _mixed(block))
    assert got == want
    assert all(v[:2] == ('ok', 'max_tokens') for v in got.values())
    assert [len(got[k][2]) for k in 'abcdef'] == [11, 1, 5, 8, 7, 2]
    # ... and what each stream gives alone
    assert got['a'][2] == _oracle(rt, _prompt(1, 6, block), 11, seed=3,
                                  temperature=1.0)
    assert got['d'][2] == _oracle(rt, _prompt(4, 4, block), 8)


def _fresh_token(stream, lo):
    """Index >= lo of a token the stream has not shown before it."""
    return next(j for j in range(lo, len(stream))
                if stream[j] not in stream[:j])


@pytest.mark.parametrize('where', ['inside_a_window', 'first_token'])
def test_eos_ends_a_stream_where_the_serial_order_ends_it(block, where):
    """EOS inside a window: the host could not foresee it, the stream was
    staged for the next window and is taken out at the boundary
    (`generation.restaged`).  EOS as the FIRST token: the window behind
    the chunk is already launched, so its slot runs K steps for nobody
    (`generation.overrun_slot_steps`), and nobody sees them."""
    rt = _runtime(block)
    pa, pb = _prompt(7, 5, block), _prompt(8, 6, block)
    full = _oracle(rt, pa, 14, seed=5, temperature=1.0)
    # index 2 is the second token of the first window: inside it
    j = 0 if where == 'first_token' else _fresh_token(full, 2)
    assert where == 'first_token' or j % WINDOW != 0 or j + 1 < len(full)
    eos = full[j]
    other = _oracle(rt, pb, 9, seed=6, temperature=1.0)
    cut = other.index(eos) + 1 if eos in other else len(other)
    script = {0: [_submit('a', pa, 14, seed=5, temperature=1.0)],
              2: [_submit('b', pb, 9, seed=6, temperature=1.0)]}
    want, _ = _drive(SerialEngine, rt, script, eos_id=eos)
    rt.reset()
    before = dict(obs.counters())
    got, _ = _drive(GenerationEngine, rt, script, eos_id=eos)
    c = _delta(before)
    assert got == want
    assert got['a'] == ('ok', 'eos', full[:j + 1])
    assert got['b'][2] == other[:cut]
    if where == 'first_token':
        assert c['generation.overrun_slot_steps'] >= WINDOW
    elif (j - 1) // WINDOW < (14 - 2) // WINDOW:
        # a window was staged behind the one that held the EOS
        assert c['generation.restaged'] >= 1


def test_max_new_one_rides_no_window(block):
    rt = _runtime(block)
    p = _prompt(9, 6, block)
    before = dict(obs.counters())
    got, _ = _drive(GenerationEngine, rt, {0: [_submit('a', p, 1)]})
    c = _delta(before)
    assert got['a'] == ('ok', 'max_tokens', _oracle(rt, p, 1))
    assert c.get('generation.decode_windows', 0) == 0
    assert c.get('generation.overrun_slot_steps', 0) == 0


@pytest.mark.parametrize('how', ['cancel', 'deadline'])
def test_an_end_from_outside_mid_stream(block, how):
    """A cancel and a deadline are swept while the window runs and again
    at its boundary: the stream keeps what it was sent (a prefix of its
    serial tokens), its neighbour is untouched, and the slot and pages
    come back though a window over them may still be running."""
    rt = _runtime(block)
    pa, pb = _prompt(10, 5, block), _prompt(11, 7, block)
    now = [100.0]
    script = {0: [_submit('a', pa, 30, seed=2, temperature=1.0,
                          timeout_s=50.0),
                  _submit('b', pb, 12, seed=4, temperature=1.0)]}
    if how == 'cancel':
        script[5] = [_cancel('a')]
    else:
        script[5] = [lambda eng, streams: now.__setitem__(0, 200.0)]
    got, _ = _drive(GenerationEngine, rt, script, clock=lambda: now[0])
    status, reason, toks = got['a']
    assert (status, reason) == (('shed', 'cancelled') if how == 'cancel'
                                else ('deadline_exceeded', 'deadline'))
    full = _oracle(rt, pa, 30, seed=2, temperature=1.0)
    assert 0 < len(toks) < 30 and toks == full[:len(toks)]
    assert got['b'] == ('ok', 'max_tokens',
                        _oracle(rt, pb, 12, seed=4, temperature=1.0))


def test_a_pool_too_small_to_grow_both_ends_one_as_the_serial_order(block):
    """Capacity 7 pages of 4: two prompts of 6 begin with 3 pages each,
    and only one can take a fourth.  The verdict falls at the boundary,
    after the landed window's tokens are out, so the loser has exactly
    the tokens the serial order gave it."""
    rt = _runtime(block, pages=8)
    script = {0: [_submit('a', _prompt(12, 6, block), 12, seed=1,
                          temperature=1.0),
                  _submit('b', _prompt(13, 6, block), 12, seed=2,
                          temperature=1.0)]}
    want, _ = _drive(SerialEngine, rt, script)
    rt.reset()
    got, _ = _drive(GenerationEngine, rt, script)
    assert got == want
    assert sorted(v[:2] for v in got.values()) == [
        ('error', 'kv_oom'), ('ok', 'max_tokens')]
    lost = next(v for v in got.values() if v[0] == 'error')
    assert 0 < len(lost[2]) < 12


def test_backpressure_admits_the_third_when_a_stream_leaves(block):
    """Capacity 8 pages: two streams of 4 pages fill it, the third stays
    QUEUED (never truncated) and begins at the boundary where the first
    leaver's window lands: that boundary keeps the serial order."""
    rt = _runtime(block, pages=9)
    script = {0: [_submit(n, _prompt(20 + i, 6, block), 7, seed=i,
                          temperature=1.0) for i, n in enumerate('abc')]}
    before = dict(obs.counters())
    want, _ = _drive(SerialEngine, rt, script)
    rt.reset()
    serial_rounds, rounds = [], []
    _drive(SerialEngine, rt, script, rounds=serial_rounds)
    rt.reset()
    got, _ = _drive(GenerationEngine, rt, script, rounds=rounds)
    c = _delta(before)
    assert got == want
    assert all(v[:2] == ('ok', 'max_tokens') and len(v[2]) == 7
               for v in got.values())
    assert c['generation.kv_backpressure'] > 0
    # the queued request waits no round longer than it did
    assert len(rounds) <= len(serial_rounds) + 1


def test_prefix_hits_skip_ahead_under_a_running_window():
    rt = _runtime('dense')
    shared = _prompt(30, 8, 'dense')           # two full pages
    script = {0: [_submit('a', shared + [5, 6, 7], 9)],
              4: [_submit('b', shared + [9, 10], 6, seed=3,
                          temperature=1.0)],
              5: [_submit('c', shared + [11], 4)]}
    before = dict(obs.counters())
    want, _ = _drive(SerialEngine, rt, script)
    rt.prefix.reset()
    rt.reset()
    got, _ = _drive(GenerationEngine, rt, script)
    c = _delta(before)
    assert got == want
    # a alone computes its whole prompt; b and c skip the shared pages,
    # in both engines
    assert c['generation.prefill_tokens'] == 2 * (11 + 2 + 1)


# --------------------------- (b) the launches, as the benchmark sees them

def _wrapped(rt, log):
    """Wrap the INSTANCE's two launch calls the way
    benchmarks/runners/serve.py does: exact positional signatures, and
    `host_len` read on entry."""
    prefill, decode_window = rt.prefill, rt.decode_window

    def spanned_prefill(slot, tokens, offset, params):
        log.append(('prefill', int(slot), int(offset), len(tokens)))
        return prefill(slot, tokens, offset, params)

    def spanned_window(steps, active, seeds, temps, topks):
        live = np.asarray(active, bool)
        log.append(('window', live.copy(), rt.host_len.copy()))
        return decode_window(steps, active, seeds, temps, topks)

    rt.prefill, rt.decode_window = spanned_prefill, spanned_window


def test_launches_go_through_the_instances_calls_once_each(block):
    rt = _runtime(block)
    log = []
    _wrapped(rt, log)
    before = dict(obs.counters())
    got, _ = _drive(GenerationEngine, rt, _mixed(block))
    c = _delta(before)
    assert all(v[0] == 'ok' for v in got.values())
    chunks = [e for e in log if e[0] == 'prefill']
    windows = [e for e in log if e[0] == 'window']
    assert len(chunks) == c['generation.prefill_chunks']
    assert len(windows) == c['generation.decode_windows']
    assert len(log) == c['generation.launches']
    # `host_len` on entry is every live slot's length at the window's
    # START: what its chunks and earlier windows left, nothing of its own
    length = np.zeros(rt.slots, np.int64)
    for e in log:
        if e[0] == 'prefill':
            length[e[1]] = e[2] + e[3]
        else:
            live, seen = e[1], e[2]
            assert live.any()
            np.testing.assert_array_equal(seen[live], length[live])
            length[live] += WINDOW
    # the counters taken at the launch agree with the wrapper's view
    assert c['generation.decode_live_slot_steps'] == WINDOW * sum(
        int(e[1].sum()) for e in windows)


def test_a_request_arriving_during_the_fetch_is_launched_behind_it(block):
    """The host is blocked reading window N when a request arrives: it
    was not there at the staging, and its first chunk still goes directly
    behind N, with the window after it, in the same round."""
    rt = _runtime(block)
    log, late = [], {}
    _wrapped(rt, log)
    spanned = rt.decode_window
    pl = _prompt(41, 3, block)

    class Arrives(object):
        """A window's tokens; reading them is when the request comes."""

        def __init__(self, toks):
            self.toks = toks

        def __array__(self, dtype=None, copy=None):
            if len([e for e in log if e[0] == 'window']) == 2 \
                    and 'at' not in late:
                late['at'] = len(log)
                late['stream'] = late['eng'].generate(pl, max_new=5)
            return np.asarray(self.toks)

    rt.decode_window = lambda *a: Arrives(spanned(*a))
    pa = _prompt(40, 4, block)
    eng = GenerationEngine(rt, config=ServingConfig(),
                           gen_config=GenerationConfig(decode_window=WINDOW))
    eng._set_state(READY)
    late['eng'] = eng
    a = eng.generate(pa, max_new=20, seed=1, temperature=1.0)
    before = dict(obs.counters())
    while eng._queue or eng._active:
        assert eng._round()
    c = _delta(before)
    after = log[late['at']:]
    assert after[0][0] == 'prefill' and after[0][3] == len(pl)
    assert after[1][0] == 'window' and int(after[1][1].sum()) == 2
    assert c['generation.rounds_to_first_token'] == 2    # one each
    assert a.tokens_so_far() == _oracle(rt, pa, 20, seed=1, temperature=1.0)
    assert late['stream'].tokens_so_far() == _oracle(rt, pl, 5)


# --------------------------------------------- (c) staging, in the runtime

def _one_stream(rt, prompt):
    slot = rt.alloc_slot()
    assert rt.try_begin(slot, np.asarray(prompt, np.int32), WINDOW) == 0
    for off in range(0, len(prompt), CHUNK):
        rt.prefill(slot, prompt[off:off + CHUNK], off, SamplingParams())
    active = np.zeros(rt.slots, bool)
    active[slot] = True
    zeros = np.zeros(rt.slots, np.int32)
    return slot, (active, zeros, np.zeros(rt.slots, np.float32), zeros)


def test_what_is_staged_owns_its_bytes_and_is_found_by_value(block):
    rt = _runtime(block)
    prompt = _prompt(50, 6, block)
    slot, vectors = _one_stream(rt, prompt)
    want = np.asarray(rt.decode_window(WINDOW, *vectors))[slot]

    rt.reset()
    slot, vectors = _one_stream(rt, prompt)
    table = rt.block_tables.copy()
    before = dict(obs.counters())
    rt.stage_window(*vectors)
    staged = [dev for _, dev in rt._staged['window']]
    toks = rt.decode_window(WINDOW, *vectors)
    # the table changes while the launch is in flight (a neighbour is
    # admitted, a stream retires): neither the launch nor the copies see it
    rt.block_tables[:] = 0
    vectors[0][:] = False
    np.testing.assert_array_equal(np.asarray(toks)[slot], want)
    np.testing.assert_array_equal(np.asarray(staged[0]), table)
    assert np.asarray(staged[1])[slot]
    c = _delta(before)
    assert c['generation.launches'] == 1
    assert c['generation.launches_staged'] == 1
    assert 'window' not in rt._staged          # spent

    # staged, then the table GROWS before the launch: it is uploaded
    # again and the launch is right; the staged vectors are still used
    rt.reset()
    slot, vectors = _one_stream(rt, prompt)
    first = np.asarray(rt.decode_window(WINDOW, *vectors))[slot]
    np.testing.assert_array_equal(first, want)
    rt.stage_window(*vectors)
    assert rt.ensure_capacity(slot, len(prompt) + 2 * WINDOW + PAGE)
    before = dict(obs.counters())
    second = np.asarray(rt.decode_window(WINDOW, *vectors))[slot]
    c = _delta(before)
    assert c['generation.launches'] == 1
    assert c.get('generation.launches_staged', 0) == 0
    rt.reset()
    full = rt.generate(prompt, 1 + 2 * WINDOW, steps_per_window=WINDOW)
    assert list(want) + list(second) == full[1:]


def test_a_staged_chunk_is_the_chunk_it_was_staged_as(block):
    rt = _runtime(block)
    prompt = np.asarray(_prompt(51, 7, block), np.int32)
    want = _oracle(rt, prompt, 1)
    slot = rt.alloc_slot()
    assert rt.try_begin(slot, prompt, WINDOW) == 0
    p = SamplingParams()
    before = dict(obs.counters())
    rt.stage_prefill(slot, prompt[:CHUNK], 0, p)
    rt.prefill(slot, prompt[:CHUNK], 0, p)
    # staged for another offset than the one launched: not used
    rt.stage_prefill(slot, prompt[CHUNK:], 0, p)
    first, logits = rt.prefill(slot, prompt[CHUNK:], CHUNK, p)
    c = _delta(before)
    assert c['generation.launches'] == 2
    assert c['generation.launches_staged'] == 1
    assert rt.host_len[slot] == len(prompt)       # moved at the launch
    assert rt.host_tok[slot] == 0                 # nothing read yet
    assert int(first) == want[0]
    assert rt.host_tok[slot] == want[0]
    assert np.asarray(logits).shape == (CFGS[block]['vocab'],)
    assert c['generation.prefill_s'] > 0


def test_a_steady_stream_finds_its_windows_staged(block):
    """One stream alone: the chunk and the window behind it go up with
    nothing running; every later window was uploaded under the one
    before it."""
    rt = _runtime(block)
    before = dict(obs.counters())
    got, _ = _drive(GenerationEngine, rt,
                    {0: [_submit('a', _prompt(52, 3, block),
                                 1 + 4 * WINDOW)]})
    c = _delta(before)
    assert got['a'][:2] == ('ok', 'max_tokens')
    assert c['generation.launches'] == 5
    assert c['generation.launches_staged'] == 3
    assert c.get('generation.restaged', 0) == 0
    assert c.get('generation.overrun_slot_steps', 0) == 0
    # the fetch is timed wherever it happens, inside window_s as before
    assert 0 < c['generation.window_fetch_s'] < c['generation.window_s']
    assert c['generation.prefill_s'] + c['generation.window_s'] \
        < c['generation.round_s']


# ------------------------------------------- (d) the programs are pinned

# sha256 of the lowered StableHLO of the three programs at this file's
# sizes.  PR 36 pinned them to show it had moved host code only; PR 38
# changed the programs on purpose (q, k and v contracted in their
# prepared form, the rotation over halves: `_lowered` below, run on its
# tree) and replaced them.  A PR that changes a program on purpose
# replaces them again; one that means to move host code only must not.
PINNED_SHA256 = {
    ('dense', 'prefill'):
        '6bac5846a0f42a6c46eb77149a6cb5c0920825f818098f7a273ca57b767c799e',
    ('dense', 'decode'):
        'a3372b00ffb2d2e3ffde3adf3fe84600ebf899ee92fc6fade56a7da60170f33b',
    ('dense', 'verify'):
        'f7059e51c1b24482b0b2d75e0e66b9a69b029ee1b0489f9de3f8c26a421c9157',
    ('falcon_h1', 'prefill'):
        '2b524a69c3c033414b22f1a7c302ba27bc6dee214e2461d857d988c9f7d6f02a',
    ('falcon_h1', 'decode'):
        'ab9caec757f8bdabc90cb080be5800350d505e496b1358c49266c6a163bcd64e',
}


def _lowered(rt, kind):
    sds = rt._sds
    i32, f32 = sds((), jnp.int32), sds((), jnp.float32)
    params = rt._param_structs()          # the executables' own
    S = rt.slots
    if kind == 'prefill':
        fn = decode._prefill_fn(rt.cfg, rt.cache, CHUNK)
        args = [params, rt._state_structs(),
                sds((rt.cache.max_pages,), jnp.int32),
                sds((CHUNK,), jnp.int32), i32, i32, i32, i32, f32, i32]
    else:
        make = decode._verify_fn if kind == 'verify' else decode._decode_fn
        fn = make(rt.cfg, rt.cache, WINDOW, rt.kernels)
        args = [params, rt._state_structs(), rt._bt_struct(S)]
        if kind == 'verify':
            args.append(sds((WINDOW, S), jnp.int32))
        args += [sds((S,), jnp.bool_), sds((S,), jnp.int32),
                 sds((S,), jnp.float32), sds((S,), jnp.int32)]
    text = jax.jit(fn, donate_argnums=(1,)).lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize('which', sorted(PINNED_SHA256), ids='-'.join)
def test_the_lowered_programs_are_the_pinned_ones(which):
    assert _lowered(_runtime(which[0]), which[1]) == PINNED_SHA256[which]
