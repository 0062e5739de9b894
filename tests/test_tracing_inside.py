"""The serving round and the training launch measured from INSIDE the
program (PR 24): exact counter arithmetic of a scripted serving run, the
three phase spans that tile every request, the program's spans on the
profiler's own timeline, the timeline reduction on hand-built intervals,
and the executor's own clock under Executor and ParallelExecutor; the
round's boundary, where the chip has nothing (PR 59): its span, its five
counters and the four benchmark metrics that read them.

Everything here runs on the CPU at toy widths: it checks counts, span
structure and arithmetic, never a time."""
import glob
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
import paddle_tpu.observability as obs
from paddle_tpu.observability import timeline, tracing
from paddle_tpu.serving.engine import READY, ServingConfig
from paddle_tpu.serving.generation import (DecodeRuntime, GenerationConfig,
                                           GenerationEngine)
from paddle_tpu.serving.generation.decode import random_weights

CFG = dict(vocab=64, d_model=32, n_layer=2, n_head=4, n_kv_head=2,
           d_ffn=64, theta=10000.0, max_len=32)
SLOTS, CHUNK, WINDOW, PAGE = 3, 4, 4, 8


@pytest.fixture(autouse=True)
def _fresh_trace():
    tracing.reset()
    yield
    tracing.reset()


def _engine():
    rt = DecodeRuntime(random_weights(CFG, seed=0), CFG, slots=SLOTS,
                       prefill_chunk=CHUNK, page_len=PAGE, prefix_cache=True)
    eng = GenerationEngine(rt, config=ServingConfig(),
                           gen_config=GenerationConfig(decode_window=WINDOW))
    return eng.start()


def _delta(after, before):
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if isinstance(v, (int, float))}


def _spans_of(trace_id):
    return [e for e in obs.recorder().events() if e['ph'] == 'X'
            and e.get('args', {}).get('trace_id') == trace_id]


def _recorded():
    """{span name: [(start, end)]} of the recorder's complete events."""
    rec = {}
    for e in obs.recorder().events():
        if e['ph'] == 'X':
            rec.setdefault(e['name'], []).append(
                (e['ts'], e['ts'] + e['dur']))
    return rec


# ------------------------------------------- (a) exact counter arithmetic

def test_scripted_serving_run_counts_exactly():
    """Three requests, one at a time, so every count is arithmetic: the
    second shares the first's first page, which the prefix cache skips."""
    shared = list(range(1, PAGE + 1))
    script = [(shared + [9, 10, 11], 6),          # 11 tokens, 3 chunks
              (shared + [20, 21, 22, 23, 24], 5),  # 13 tokens, 8 skipped
              ([30, 31, 32, 33, 34, 35], 1)]       # finishes on its first
    eng = _engine()
    before = dict(obs.counters())
    try:
        for prompt, max_new in script:
            reply = eng.generate(prompt, max_new=max_new).result(60)
            assert reply.ok, reply
    finally:
        eng.stop(timeout=10)
    c = _delta(obs.counters(), before)

    skipped = [0, PAGE, 0]
    chunks = windows = pad = live_tokens = rows_read = 0
    for (prompt, max_new), skip in zip(script, skipped):
        todo = len(prompt) - skip
        n_chunks = -(-todo // CHUNK)
        chunks += n_chunks
        pad += n_chunks * CHUNK - todo
        n_windows = -(-(max_new - 1) // WINDOW)
        for w in range(n_windows):
            length = len(prompt) + w * WINDOW     # the stream's rows so far
            live_tokens += WINDOW * length + WINDOW * (WINDOW + 1) // 2
            # the paged step fetches the whole pages its positions cover
            rows_read += sum(-(-(length + j + 1) // PAGE) * PAGE
                             for j in range(WINDOW))
        windows += n_windows
    prompt_tokens = sum(len(p) for p, _ in script)

    assert c['generation.admitted'] == 3
    assert c['generation.first_tokens'] == 3
    assert c['generation.prefill_chunks'] == chunks
    assert c['generation.prefill_tokens'] == prompt_tokens - sum(skipped)
    assert c['generation.prefill_pad_tokens'] == pad
    assert c['generation.decode_windows'] == windows
    assert c['generation.decode_live_slot_steps'] == windows * WINDOW
    assert c['generation.decode_slot_steps'] == windows * WINDOW * SLOTS
    assert c['generation.kv_tokens_live'] == live_tokens
    # the live stream's pages alone: the two idle slots read nothing
    assert c['generation.kv_rows_read'] == rows_read
    assert rows_read < windows * WINDOW * CFG['max_len']
    # alone in the engine a request's chunks run in consecutive rounds,
    # the first in the round of its grant; never fewer rounds than chunks
    assert c['generation.rounds_to_first_token'] == chunks
    # times: only that each boundary's clock moved, and nests
    for name in ('queue_wait_s', 'prefill_phase_s', 'round_s',
                 'prefill_s', 'window_s'):
        assert c['generation.' + name] > 0, name
    assert c['generation.prefill_fetch_s'] < c['generation.prefill_s']
    assert c['generation.window_fetch_s'] < c['generation.window_s']
    assert c['generation.prefill_s'] + c['generation.window_s'] \
        < c['generation.round_s']


def test_kv_rows_read_follows_the_executables_own_shapes():
    """Paged (a floating pool): the count is what the kernel fetches,
    whole pages up to each ACTIVE slot's last position, step by step.
    Composed (an int8 pool): every slot's ``max_len`` rows a step, read
    off what `_logical_rows` returns for the structs the executable was
    built over: a narrower table or a shorter gather moves it."""
    from paddle_tpu.serving.generation import decode
    weights = random_weights(CFG, seed=0)
    rt = DecodeRuntime(weights, CFG, slots=SLOTS, prefill_chunk=CHUNK,
                       page_len=PAGE)
    assert rt.paged
    rt.host_len[:] = [7, 20, 8]
    act = np.array([True, False, True])
    # slot 0 attends 8 then 9 positions (1 then 2 pages), slot 2 9 then 10
    assert rt._window_rows_read(2, act) == (8 + 16) + (16 + 16)
    assert rt._window_rows_read(3, ~act) == 3 * 24
    assert rt._window_rows_read(2, np.zeros(SLOTS, bool)) == 0

    q8 = DecodeRuntime(weights, CFG, slots=SLOTS, prefill_chunk=CHUNK,
                       page_len=PAGE, kv_quant='int8')
    assert not q8.paged
    q8.host_len[:] = rt.host_len
    assert q8._window_rows_read(2, act) == 2 * SLOTS * CFG['max_len']
    assert q8._window_rows_read(3, ~act) == 3 * SLOTS * CFG['max_len']
    state = q8._state_structs()
    assert decode._gathered_rows(q8.cache, state, q8._bt_struct(1)) \
        == CFG['max_len']
    half = jax.ShapeDtypeStruct((SLOTS, q8.cache.max_pages // 2), 'int32')
    assert decode._gathered_rows(q8.cache, state, half) \
        == SLOTS * CFG['max_len'] // 2


# ------------------------------------------ (a') the boundary, by hand

class _Readiness(object):
    """Stands where a flown window's device array stands in its handle:
    says what the script says, and counts how often it was asked."""

    def __init__(self, ready):
        self.ready, self.asked = ready, 0

    def is_ready(self):
        self.asked += 1
        return self.ready


def _by_hand(script, ready=(), slots=1, speculative=False, trace=None):
    """Run ``script`` ([(prompt, max_new)], all submitted before the
    first round) round by round on THIS thread over a runtime of
    ``slots`` slots.  Window n's handle says ``ready[n]`` (False past
    the script's end) when asked whether it had landed.  Returns (counter
    deltas, the windows' `_Readiness`, the streams)."""
    rt = DecodeRuntime(random_weights(CFG, seed=0), CFG, slots=slots,
                       prefill_chunk=CHUNK, page_len=PAGE)
    asked, window = [], rt.decode_window

    def scripted(*args):
        toks = window(*args)
        asked.append(_Readiness(len(asked) < len(ready)
                                and ready[len(asked)]))
        toks._dev = asked[-1]
        return toks

    rt.decode_window = scripted
    eng = GenerationEngine(
        rt, config=ServingConfig(),
        gen_config=GenerationConfig(decode_window=WINDOW,
                                    speculative=speculative))
    eng._set_state(READY)
    before = dict(obs.counters())
    streams = [eng.generate(p, max_new=n) for p, n in script]
    if trace is not None:
        jax.profiler.start_trace(trace)
    try:
        rounds = 0
        while eng._queue or eng._active:
            assert eng._round()
            rounds += 1
            assert rounds < 100
    finally:
        if trace is not None:
            jax.profiler.stop_trace()
    assert all(s.result(0).ok for s in streams)
    return _delta(obs.counters(), before), asked, streams


def _boundary_spans():
    return [e['args'] for e in obs.recorder().events()
            if e['name'] == 'serving.boundary']


def test_scripted_boundaries_count_exactly():
    """One slot, two requests.  Round 2 lands A's only window with B
    queued and A leaving: the boundary keeps the serial order and
    launches B's chunk; its tokens had landed before the read (the
    script says so): late.  Round 3 lands B's first window and launches
    its second; round 4 lands that and launches nothing: no boundary."""
    c, asked, _ = _by_hand([([1, 2, 3], 1 + WINDOW),
                            ([4, 5, 6], 1 + 2 * WINDOW)],
                           ready=[True, False, True])
    assert c['generation.decode_windows'] == 3
    assert c['generation.boundaries'] == 2
    assert c['generation.boundary_serial'] == 1
    assert c['generation.boundary_late'] == 1
    assert 0 < c['generation.boundary_check_s'] \
        <= c['generation.boundary_dry_s'] < c['generation.round_s']
    # one `is_ready()` a landed window, whatever it launched
    assert [r.asked for r in asked] == [1, 1, 1]
    assert [(a['first'], a['serial'], a['late'])
            for a in _boundary_spans()] == [
        ('chunk', True, True), ('window', False, False),
        ('none', False, True)]
    # the check is a child of the boundary, the serial emit of the check
    rec = _recorded()
    assert len(rec['serving.boundary.check']) == 3
    assert all(_enclosed(s, rec['serving.boundary'])
               for s in rec['serving.boundary.check'])
    assert sum(_enclosed(s, rec['serving.boundary.check'])
               for s in rec['serving.emit']) == 1
    # the counter stops where the first dispatch span ended: inside the
    # boundary span, which ends when the launch call has returned
    dry = c['generation.boundary_dry_s']
    spans = sum(e - s for s, e in rec['serving.boundary'][:2]) / 1e6
    assert dry < spans


def test_a_steady_stream_has_a_boundary_a_window_but_the_last():
    c, asked, _ = _by_hand([([1, 2, 3, 4, 5], 1 + 3 * WINDOW)])
    assert c['generation.decode_windows'] == 3
    assert c['generation.boundaries'] == 2
    assert c.get('generation.boundary_serial', 0) == 0
    assert c.get('generation.boundary_late', 0) == 0
    assert [a['first'] for a in _boundary_spans()] == [
        'window', 'window', 'none']


def test_a_speculative_engine_counts_no_boundary():
    """Every launch of a speculative round is read at once: nothing is
    ever in flight, so no round has a boundary to count."""
    c, asked, _ = _by_hand([([1, 2, 3], 1 + 2 * WINDOW)], speculative=True)
    assert c['generation.spec_proposed'] > 0
    assert not any(v for k, v in c.items()
                   if k.startswith('generation.boundar'))
    assert not _boundary_spans() and not asked


# --------------------------------------------- (b) the phases tile the root

def test_phase_spans_share_the_trace_id_and_tile_the_request():
    """Five requests over three slots (two must queue): each finished
    request's queue / prefill_phase / decode_phase spans carry its trace
    id, name serving.request as parent, and tile it end to end."""
    eng = _engine()
    try:
        streams = [eng.generate([1 + i, 2, 3, 4, 5, 6 + i][:3 + i],
                                max_new=1 + 2 * i) for i in range(5)]
        replies = [s.result(60) for s in streams]
    finally:
        eng.stop(timeout=10)
    assert all(r.ok for r in replies)
    names = ('serving.queue', 'serving.prefill_phase',
             'serving.decode_phase')
    for i, s in enumerate(streams):
        trace_id = s.traceparent.split('-')[1]
        spans = {e['name']: e for e in _spans_of(trace_id)
                 if e['name'] in names + ('serving.request',)}
        assert set(spans) == set(names) | {'serving.request'}, (i, spans)
        root = spans['serving.request']
        phases = [spans[n] for n in names]
        for p in phases:
            assert p['args']['parent_span_id'] == root['args']['span_id']
        assert phases[0]['ts'] == pytest.approx(root['ts'], abs=1e-3)
        for a, b in zip(phases, phases[1:]):
            assert a['ts'] + a['dur'] == pytest.approx(b['ts'], abs=1e-3)
        assert phases[-1]['ts'] + phases[-1]['dur'] == pytest.approx(
            root['ts'] + root['dur'], abs=1e-3)
        # self time of the root (guide section 4): nothing left over
        assert sum(p['dur'] for p in phases) == pytest.approx(
            root['dur'], abs=1e-3)
        prefill = spans['serving.prefill_phase']['args']
        assert prefill['chunks'] == -(-min(3 + i, 6) // CHUNK)
        assert prefill['rounds'] >= prefill['chunks']
        assert spans['serving.decode_phase']['args']['tokens'] == 1 + 2 * i
    assert not [e for e in obs.recorder().events()
                if e['name'] == 'serving.token']


# ------------------------------- (c) the spans on the profiler's timeline

def _enclosed(inner, outers):
    return any(o[0] <= inner[0] and inner[1] <= o[1] for o in outers)


def test_profiler_trace_holds_the_program_spans_nested(tmp_path):
    """A jax.profiler trace taken around a serving request and a training
    launch holds pt:serving.round, pt:decode.window and
    pt:executor.dispatch on the host plane, nested as in the recorder."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[4], dtype='float32')
            y = fluid.layers.fc(x, 3)
    exe, scope = fluid.Executor(), fluid.Scope()
    eng = _engine()
    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
            feed = {'x': np.ones((2, 4), 'float32')}
            exe.run(main, feed=feed, fetch_list=[y])          # warm
            assert eng.generate([1, 2, 3], max_new=2).result(60).ok
            jax.profiler.start_trace(str(tmp_path))
            try:
                assert eng.generate([4, 5, 6, 7, 8], max_new=6).result(60).ok
                exe.run(main, feed=feed, fetch_list=[y])
                # the reply leaves from INSIDE the last round: join the
                # scheduler thread so that round's annotation is closed
                # (an annotation open at stop_trace is not in the trace)
                assert eng.stop(timeout=10)
            finally:
                jax.profiler.stop_trace()
    finally:
        eng.stop(timeout=10)
    path, = glob.glob(str(tmp_path / '**' / '*.xplane.pb'), recursive=True)
    _, _, thread_spans = timeline.load_trace(path)
    by_name = {}
    for thread, spans in thread_spans.items():
        for s, e, name in spans:
            by_name.setdefault(name, []).append((s, e, thread))
    for name in ('serving.round', 'serving.admit', 'serving.prefill',
                 'serving.decode_step', 'serving.emit', 'decode.prefill',
                 'decode.prefill.upload', 'decode.prefill.dispatch',
                 'decode.prefill.fetch', 'decode.window',
                 'decode.window.upload', 'decode.window.dispatch',
                 'decode.window.fetch', 'executor.dispatch',
                 'executor.fetch_sync'):
        assert by_name.get(name), 'no pt:%s in the trace' % name
    # the scheduler's spans sit on ONE thread, the executor's on another
    sched = {t for _, _, t in by_name['serving.round']}
    assert len(sched) == 1
    assert sched.isdisjoint(t for _, _, t in by_name['executor.dispatch'])
    # a launch is its upload and dispatch; its fetch is whoever reads the
    # result, a round later for a window (and the upload of a launch that
    # was staged under the running window sits outside it, in the round)
    for child, parent in [('decode.window.dispatch', 'decode.window'),
                          ('decode.window.fetch', 'serving.round'),
                          ('decode.window.upload', 'serving.round'),
                          ('decode.prefill.fetch', 'serving.round'),
                          ('decode.window', 'serving.decode_step'),
                          ('serving.decode_step', 'serving.round'),
                          ('decode.prefill.dispatch', 'decode.prefill'),
                          ('decode.prefill.upload', 'serving.round'),
                          ('decode.prefill', 'serving.prefill'),
                          ('serving.prefill', 'serving.round'),
                          ('serving.emit', 'serving.round')]:
        for span in by_name[child]:
            assert span[2] in sched
            assert _enclosed(span, by_name[parent]), (child, parent)
    # ... exactly as the recorder nests them
    rec = _recorded()
    for child, parent in [('decode.window', 'serving.decode_step'),
                          ('serving.decode_step', 'serving.round')]:
        assert all(_enclosed(s, rec[parent]) for s in rec[child])


def test_profiler_trace_holds_the_boundary_around_the_first_dispatch(
        tmp_path):
    """Two slots, two requests, the second three chunks long: while it
    prefills, the first one's boundaries launch a chunk FIRST and the
    window behind it; then one launches a window alone, and the last
    nothing.  On the profiler's timeline `pt:serving.boundary` lies in
    the round, holds the read, the check and the first dispatch alone,
    and ends before the landed window is emitted."""
    _by_hand([([1, 2, 3], 1 + 5 * WINDOW),
              (list(range(10, 10 + 3 * CHUNK)), 2)], slots=2,
             trace=str(tmp_path))
    path, = glob.glob(str(tmp_path / '**' / '*.xplane.pb'), recursive=True)
    _, _, thread_spans = timeline.load_trace(path)
    (_, spans), = [(t, sp) for t, sp in thread_spans.items()
                   if any(n == 'serving.boundary' for _, _, n in sp)]
    named = lambda *names: sorted(  # noqa: E731
        sp for sp in spans if sp[2] in names)
    boundaries, rounds = named('serving.boundary'), named('serving.round')
    dispatches = named('decode.prefill.dispatch', 'decode.window.dispatch')
    firsts = [a['first'] for a in _boundary_spans()]
    assert firsts == ['chunk', 'chunk', 'chunk', 'window', 'none']
    assert len(boundaries) == len(firsts)
    for b, first in zip(boundaries, firsts):
        rnd, = [r for r in rounds if _enclosed(b, [r])]
        inside = lambda sp: _enclosed(sp, [b])  # noqa: E731
        fetch, = filter(inside, named('decode.window.fetch'))
        check, = filter(inside, named('serving.boundary.check'))
        assert b[0] <= fetch[0] and fetch[1] <= check[0]
        mine = [d for d in dispatches if _enclosed(d, [rnd])]
        held = list(filter(inside, mine))
        if first == 'none':
            assert not mine
            continue
        # the first dispatch of the round and no other
        assert held == mine[:1] and check[1] <= held[0][0]
        assert held[0][2] == 'decode.%s.dispatch' % (
            'prefill' if first == 'chunk' else 'window')
        assert len(mine) == (2 if first == 'chunk' else 1)
        emits = [e for e in named('serving.emit') if _enclosed(e, [rnd])]
        assert emits and all(e[0] >= b[1] for e in emits)


# --------------------------------- (d) the timeline on hand-built intervals

MS = 1e6   # nanoseconds


def _hand_built(skew_ns):
    """Two rounds of a launching thread and the chip they feed.  Chip
    time: ops 10-20 ms, 30-40 ms, 41-50 ms.  Host (true) time: the
    launches start 0.2 ms before their modules; between them the thread
    fetches, emits, admits, uploads.  Host stamps are then moved by
    `skew_ns` (the host's clock running ahead of the chip's)."""
    ops = [(10 * MS, 20 * MS), (30 * MS, 40 * MS), (41 * MS, 50 * MS)]
    modules = list(ops)
    host = [
        (9.5 * MS, 28.0 * MS, 'serving.round'),
        (9.8 * MS, 10.1 * MS, 'decode.window.dispatch'),
        (10.1 * MS, 20.2 * MS, 'decode.window.fetch'),
        (20.2 * MS, 27.9 * MS, 'serving.emit'),         # most of gap 1
        (28.0 * MS, 60.0 * MS, 'serving.round'),
        (28.1 * MS, 29.0 * MS, 'serving.admit'),
        (29.0 * MS, 29.7 * MS, 'decode.prefill.upload'),
        (29.8 * MS, 30.1 * MS, 'decode.prefill.dispatch'),
        (30.1 * MS, 40.1 * MS, 'decode.prefill.fetch'),
        (40.15 * MS, 40.7 * MS, 'decode.window.upload'),  # most of gap 2
        (40.8 * MS, 41.1 * MS, 'decode.window.dispatch'),
    ]
    other = [(0.0, 100 * MS, 'prefetch.pack')]       # not the launcher
    shift = lambda spans: [(s + skew_ns, e + skew_ns, n)  # noqa: E731
                           for s, e, n in spans]
    return ops, modules, {'scheduler': shift(host), 'reader': shift(other)}


@pytest.mark.parametrize('skew_ms', [0.0, 0.9, 3.0])
def test_timeline_names_gaps_after_the_innermost_span(skew_ms):
    ops, modules, threads = _hand_built(skew_ms * MS)
    out = timeline.analyse(ops, modules, threads)
    assert out['launching_thread'] == 'scheduler'
    assert out['launches'] == out['modules'] == out['paired'] == 3
    assert out['gaps'] == 2
    assert out['idle_s'] == pytest.approx(0.011)
    assert out['window_s'] == pytest.approx(0.040)
    # gap 1 (20-30 ms) is mostly the first round's emit; gap 2 (40-41 ms)
    # mostly the second window's upload: neither goes to serving.round,
    # which covers both, nor to the other thread's span, which covers all
    assert out['idle_s_by_span'] == {
        'serving.emit': pytest.approx(0.010),
        'decode.window.upload': pytest.approx(0.001)}
    assert out['named_share'] == pytest.approx(1.0)
    # the planted skew comes back, less the 0.2 ms a launch takes to start
    assert out['clock_skew_ms'] == pytest.approx(0.2 - skew_ms, abs=1e-6)
    text = timeline.report(out)
    assert 'clock_skew_ms' in text and 'serving.emit' in text


def _without_first_dispatch(threads):
    spans = threads['scheduler']
    first = min(sp for sp in spans if sp[2].endswith('.dispatch'))
    return dict(threads, scheduler=[sp for sp in spans if sp != first])


@pytest.mark.parametrize('cut,paired', [
    # the trace began after the first call had: its module has no span
    (lambda o, m, t: (o, m, _without_first_dispatch(t)), 2),
    # and stopped before the last launch ran: its span has no module
    (lambda o, m, t: (o[:2], m[:2], t), 2),
    # both at once: as many modules as spans, and the ranks one apart
    (lambda o, m, t: (o[:2], m[:2], _without_first_dispatch(t)), 1),
    # argument conversions that ran as tiny executables of their own, in
    # the upload before a launch, are nobody's launch
    (lambda o, m, t: (o, m + [(29.1 * MS, 29.1 * MS + 3000),
                              (29.3 * MS, 29.3 * MS + 3000)], t), 3),
], ids=['orphan_module', 'orphan_span', 'both', 'tiny_modules'])
def test_timeline_pairs_by_position_at_the_windows_edges(cut, paired):
    """A module without its span or a span without its module must not
    slip the pairing: the skew stays the planted one, not a round."""
    ops, modules, threads = cut(*_hand_built(0.9 * MS))
    out = timeline.analyse(ops, modules, threads)
    assert out['paired'] == paired
    assert out['clock_skew_ms'] == pytest.approx(0.2 - 0.9, abs=1e-6)
    assert out['idle_s_by_span'].get('serving.emit') == pytest.approx(0.010)


def test_timeline_unattributed_and_unpaired():
    ops, modules, threads = _hand_built(0.0)
    # clocks further apart than any seen (or the wrong chip's modules):
    # nothing pairs, and the host spans are not moved by a guess
    far = [(s + 500 * MS, e + 500 * MS) for s, e in modules]
    out = timeline.analyse(ops, far, threads)
    assert out['paired'] == 0 and out['clock_skew_ms'] is None
    assert 'unpaired' in timeline.report(out)
    assert out['idle_s_by_span'] == {
        'serving.emit': pytest.approx(0.010),
        'decode.window.upload': pytest.approx(0.001)}
    # no host span at all: every gap is unattributed
    out = timeline.analyse(ops, modules, {})
    assert out['idle_s_by_span'] == {'unattributed': pytest.approx(0.011)}
    assert out['named_share'] == 0.0
    assert timeline.analyse([], [], threads) is None
    assert timeline.idle_gaps([(0, 10), (10.0 + 50, 20)]) == []   # < 100 ns


def _boundary_built():
    """One round whose boundary the chip waits through: ops 10-20 ms and
    24-30 ms.  The gap (20-24 ms) lies 0.5 ms under the fetch's tail,
    1.5 ms under the check, 0.5 ms under the staged upload's look-up and
    1.5 ms under the dispatch: no child holds most of it."""
    ops = [(10 * MS, 20 * MS), (24 * MS, 30 * MS)]
    host = [
        (5.0 * MS, 40.0 * MS, 'serving.round'),
        (9.7 * MS, 10.0 * MS, 'decode.window.dispatch'),
        (12.0 * MS, 24.05 * MS, 'serving.boundary'),
        (12.1 * MS, 20.5 * MS, 'decode.window.fetch'),
        (20.5 * MS, 22.0 * MS, 'serving.boundary.check'),
        (22.0 * MS, 24.1 * MS, 'serving.decode_step'),
        (22.0 * MS, 24.05 * MS, 'decode.window'),
        (22.0 * MS, 22.5 * MS, 'decode.window.upload'),
        (22.5 * MS, 24.0 * MS, 'decode.window.dispatch'),
        (24.2 * MS, 26.0 * MS, 'serving.emit'),
    ]
    return ops, list(ops), {'scheduler': host}


def test_idle_under_rolls_a_gap_up_to_the_boundary():
    ops, modules, threads = _boundary_built()
    # the launches start with their calls: no skew to take off
    modules = [(9.7 * MS, 20 * MS), (22.5 * MS, 30 * MS)]
    out = timeline.analyse(ops, modules, threads)
    assert out['clock_skew_ms'] == pytest.approx(0.0)
    assert out['idle_s'] == pytest.approx(0.004)
    # innermost naming as it was: the whole gap to the span that, as the
    # innermost, holds most of it (the check and the dispatch tie at
    # 1.5 ms; `max` keeps the first it met)
    (name, seconds), = out['idle_s_by_span'].items()
    assert name in ('serving.boundary.check', 'decode.window.dispatch')
    assert seconds == pytest.approx(0.004)
    under = out['idle_under']
    assert under['serving.boundary'] == pytest.approx(0.004)
    assert under['serving.round'] == pytest.approx(0.004)
    assert under['serving.boundary.check'] == pytest.approx(0.0015)
    assert under['decode.window.fetch'] == pytest.approx(0.0005)
    assert under['decode.window.upload'] == pytest.approx(0.0005)
    assert under['decode.window.dispatch'] == pytest.approx(0.0015)
    assert under['decode.window'] == pytest.approx(0.002)
    assert under['serving.decode_step'] == pytest.approx(0.002)
    assert 'serving.emit' not in under and 'unattributed' not in under
    text = timeline.report(out)
    assert 'idle under span and descendants' in text
    assert text.index('named_share') < text.rindex('serving.boundary.check')


def test_idle_under_counts_no_instant_twice_and_names_the_rest():
    gaps = [(0.0, 10.0), (20.0, 30.0), (50.0, 60.0)]
    spans = [(5.0, 25.0, 'a'),           # 5 of gap 1, 5 of gap 2
             (6.0, 8.0, 'a'),            # inside an `a`: counted already
             (22.0, 24.0, 'b'),          # a child of the first `a`
             (28.0, 29.0, 'a'),          # a later `a`, top level
             (100.0, 200.0, 'c')]        # under no gap
    out = timeline.idle_under(gaps, spans)
    assert out == {'a': 11.0, 'b': 2.0, 'c': 0.0, 'unattributed': 19.0}
    assert timeline.idle_under(gaps, []) == {'unattributed': 30.0}
    assert timeline.idle_under([], spans)['unattributed'] == 0.0
    # the hand-built rounds of (d): every gap under the one round alive
    ops, modules, threads = _hand_built(0.0)
    under = timeline.analyse(ops, modules, threads)['idle_under']
    assert under['serving.round'] == pytest.approx(0.011)
    assert under['serving.emit'] == pytest.approx(0.0077)


# ----------------------------------- (d') the metrics that read the counters

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmarks')


def _metric(name):
    if _BENCH not in sys.path:           # the readers import `lib.program`
        sys.path.insert(0, _BENCH)
    spec = importlib.util.spec_from_file_location(
        'metric_' + name.replace('.', '_'),
        os.path.join(_BENCH, 'metrics', name + '.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize('name,unit,want', [
    ('scheduler.boundary_ms', 'ms', 2.5),
    ('scheduler.boundary_check_ms', 'ms', 1.0),
    ('scheduler.boundary_dry_share', '%', 2.0),
    ('scheduler.boundary_late_share', '%', 25.0)])
def test_boundary_metrics_read_the_counters(name, unit, want):
    mod = _metric(name)
    assert mod.META == {
        'name': name, 'unit': unit, 'better': 'lower',
        'source': 'program_counter',
        'layer': 'scheduler (continuous batching)', 'moves': 'tpot_p50_ms'}
    counters = {'generation.boundaries': 400.0,
                'generation.boundary_dry_s': 1.0,
                'generation.boundary_check_s': 0.4,
                'generation.boundary_late': 100.0,
                'generation.boundary_serial': 7.0,
                'generation.round_s': 45.0, 'generation.idle_wait_s': 5.0}
    assert mod.read({'counters': counters}) == pytest.approx(want)
    # a cell without boundaries (training; the parent of this PR's
    # program): no reading, and the result line leaves the metric out
    assert mod.read({'counters': {'generation.round_s': 45.0,
                                  'generation.idle_wait_s': 5.0}}) is None
    assert mod.read({'counters': {}}) is None
    if 'late' in name:
        assert mod.read({'counters': {'generation.boundaries': 3.0}}) == 0.0


# ------------------------------ (e) the executor's own clock, both entries

def _train_model():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 3
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[8], dtype='float32')
            lbl = fluid.layers.data('lbl', shape=[1], dtype='int64')
            logits = fluid.layers.fc(fluid.layers.fc(x, 16, act='relu'), 4)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, lbl))
            fluid.optimizer.Adam(0.01).minimize(loss)
    return main, startup, loss


@pytest.mark.parametrize('entry', ['executor', 'parallel_executor'])
def test_executor_counters_move_under_run_steps(entry):
    K, launches = 4, 3
    rng = np.random.RandomState(0)
    feeds = [{'x': rng.randn(16, 8).astype('float32'),
              'lbl': rng.randint(0, 4, (16, 1)).astype('int64')}
             for _ in range(K)]
    main, startup, loss = _train_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        if entry == 'parallel_executor':
            pe = fluid.ParallelExecutor(loss_name=loss.name,
                                        main_program=main, scope=scope)
            # the mesh of the virtual devices (8 under tests/conftest.py)
            assert pe.device_count == len(jax.devices()) > 1
            launch = lambda: pe.run_steps(  # noqa: E731
                feed_list=feeds, fetch_list=[loss])
        else:
            launch = lambda: exe.run_steps(  # noqa: E731
                main, feed_list=feeds, fetch_list=[loss])
        launch()                                              # compiles
        before = dict(obs.counters())
        for _ in range(launches):
            launch()
    c = _delta(obs.counters(), before)
    assert c['executor.launches'] == launches
    assert c['executor.steps'] == launches * K
    # return_numpy: the fetch blocks inside the call, and is counted there
    assert 0 < c['executor.host_blocked_s'] < c['executor.run_s']
    assert 'executor.fetch_sync_s' not in obs.counters()
    names = {e['name'] for e in obs.recorder().events()}
    assert {'executor.dispatch', 'executor.fetch_sync'} <= names
