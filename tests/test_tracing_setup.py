"""Set-up measured from INSIDE the program (PR 37): every phase between
process start and the first warm launch is one live span with one seconds
counter, the phases nest, and the children of a phase account for it.

Everything here runs on the CPU at toy widths: it checks that a phase's
clock moved, how the spans nest and what tiles what, never a time."""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
import paddle_tpu.observability as obs
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.observability import export as obs_export
from paddle_tpu.observability import timeline, tracing
from paddle_tpu.serving.generation import DecodeRuntime
from paddle_tpu.serving.generation.decode import random_weights

CFG = dict(vocab=64, d_model=32, n_layer=2, n_head=4, n_kv_head=2,
           d_ffn=64, theta=10000.0, max_len=32)

# the children of `executor.prepare`: span -> the counters it moves
PREPARE_CHILDREN = {
    'executor.lint': ('executor.lint_s',),
    'executor.optimize': ('executor.optimize_s',),
    'executor.emit_build': ('executor.emit_build_s',),
    'executor.lower': ('executor.lower_s',),
    'executor.gather_params': ('executor.gather_params_s',),
    'compile_cache.fingerprint': ('compile_cache.fingerprint_s',),
    'executor.aot_load': ('compile_cache.load_s',),
    'executor.trace_compile': ('executor.emit_s', 'executor.trace_s',
                               'executor.backend_compile_s'),
    'compile_cache.store': ('compile_cache.store_s',),
}
COMPILE_CHILDREN = ('compile_cache.fingerprint', 'decode.aot_load',
                    'decode.trace_compile', 'compile_cache.store')
# every counter this PR added, and the one it began to move in serving
NEW_COUNTERS = (
    'program.build_s', 'executor.prepare_s', 'executor.lint_s',
    'executor.optimize_s', 'executor.emit_build_s', 'executor.lower_s',
    'executor.gather_params_s', 'compile_cache.fingerprint_s',
    'compile_cache.store_s', 'compile_cache.load_s', 'generation.init_s',
    'generation.compile_s', 'generation.warmup_s', 'process.import_s')
NEW_SPANS = (set(PREPARE_CHILDREN) | set(COMPILE_CHILDREN)
             | {'executor.prepare', 'decode.init', 'decode.compile',
                'decode.warmup', 'program.build', 'process.import'})


@pytest.fixture(autouse=True)
def _fresh_trace():
    tracing.reset()
    yield
    tracing.reset()


@pytest.fixture
def disk_cache(tmp_path, monkeypatch):
    monkeypatch.setenv('PT_CACHE', '1')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path / 'cache'))
    return tmp_path


def _delta(after, before):
    return {k: v - (before.get(k) or 0.0) for k, v in after.items()
            if isinstance(v, (int, float))}


def _spans():
    return [e for e in obs.recorder().events() if e['ph'] == 'X']


def _inside(child, parent):
    return (child['tid'] == parent['tid']
            and parent['ts'] <= child['ts'] + 1e-3
            and child['ts'] + child['dur'] <= parent['ts'] + parent['dur']
            + 1e-3)


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


def _feed(rng):
    return {'x': rng.randn(16, 8).astype('float32'),
            'lbl': rng.randint(0, 4, (16, 1)).astype('int64')}


# ----------------------------------------- (a) the executor's cold path

def _launches(exe, main, loss):
    rng = np.random.RandomState(0)
    return {'run': lambda: exe.run(main, feed=_feed(rng), fetch_list=[loss]),
            'run_steps': lambda: exe.run_steps(
                main, feed_list=[_feed(rng) for _ in range(3)],
                fetch_list=[loss])}


@pytest.mark.parametrize('launch', ['run', 'run_steps'])
@pytest.mark.parametrize('start', ['cold', 'warm_disk'])
def test_executor_prepare_is_tiled_by_its_children(disk_cache, start,
                                                   launch):
    """A new signature moves `executor.prepare_s` once, its children's
    counters account for 95-100 % of it, every child span lies inside
    the umbrella on its thread, and the verdict says whether the
    executable was compiled or came from the disk cache.  A second
    launch of the signature is the hot path: nothing new moves."""
    main, startup, loss = _train_model()
    if start == 'warm_disk':
        # an earlier process: the same programs over the same directory
        exe0, scope0 = fluid.Executor(), fluid.Scope()
        with fluid.scope_guard(scope0):
            exe0.run(startup)
            _launches(exe0, main, loss)[launch]()
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        once = _launches(exe, main, loss)[launch]
        tracing.reset()
        before = dict(obs.counters())
        once()
        c = _delta(obs.counters(), before)
        spans = _spans()
        tracing.reset()
        before = dict(obs.counters())
        once()                                            # the hot path
        hot = _delta(obs.counters(), before)
        hot_spans = {e['name'] for e in _spans()}

    prepare, = [e for e in spans if e['name'] == 'executor.prepare']
    assert c['executor.prepare_s'] == pytest.approx(prepare['dur'] / 1e6)
    assert prepare['args']['verdict'] == ('compiled' if start == 'cold'
                                          else 'disk_hit')
    assert prepare['args']['steps'] == (3 if launch == 'run_steps'
                                        else None)
    children = [e for e in spans if e['name'] in PREPARE_CHILDREN]
    names = [e['name'] for e in children]
    assert len(names) == len(set(names)), names
    compiled = {'executor.trace_compile', 'compile_cache.store'}
    assert set(names) == set(PREPARE_CHILDREN) - (
        compiled if start == 'warm_disk' else set())
    for child in children:
        assert _inside(child, prepare), child['name']
    moved = sum(c.get(k, 0.0) for ks in PREPARE_CHILDREN.values()
                for k in ks)
    # the children never sum past the umbrella, whatever the machine does.
    # That they sum to 95 % of it is held where the umbrella is long
    # enough to carry it: the gaps between the children of a warm
    # prepare of some 40 ms are the scheduler's under six workers, not
    # the code's (it failed a floor run on the clock: ROADMAP D6)
    assert moved <= c['executor.prepare_s']
    if c['executor.prepare_s'] >= 0.25:
        assert 0.95 * c['executor.prepare_s'] <= moved
    if start == 'cold':
        tc, = [e for e in children if e['name'] == 'executor.trace_compile']
        assert tc['args']['kind'] in ('first_compile', 'new_program_compile',
                                      'retrace')
        assert tc['args']['lowering'] == 'emit'
        assert c['executor.backend_compile_s'] > 0
        assert c.get('compile_cache.load_s', 0.0) == 0.0
    else:
        assert c['compile_cache.load_s'] > 0
        assert c.get('executor.backend_compile_s', 0.0) == 0.0
    # the set-up's launch on the program's own clock holds its cold path
    assert c['executor.run_s'] > c['executor.prepare_s']
    # ... and the second launch of a known signature moves nothing new
    assert not [k for k in NEW_COUNTERS if hot.get(k)], hot
    assert not hot_spans & NEW_SPANS, hot_spans
    assert hot['executor.launches'] == 1


def test_a_prefetched_launch_is_the_hot_path():
    """The prefetcher's pack and the launches it feeds, once the
    signature is known, enter none of the set-up spans."""
    main, startup, loss = _train_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    rng = np.random.RandomState(0)

    def epoch():
        pf = fluid.FeedPrefetcher(iter([_feed(rng) for _ in range(4)]),
                                  steps=2, capacity=1, to_device=False)
        for feed, k in pf:
            exe.run_steps(main, feed_list=feed, steps=k, fetch_list=[loss])
        pf.close()
    with fluid.scope_guard(scope):
        exe.run(startup)
        epoch()                                          # the cold path
        tracing.reset()
        before = dict(obs.counters())
        epoch()
    hot = _delta(obs.counters(), before)
    names = {e['name'] for e in _spans()}
    assert hot['executor.launches'] == 2 and 'prefetch.pack' in names
    assert not [k for k in NEW_COUNTERS if hot.get(k)], hot
    assert not names & NEW_SPANS, names


def test_executor_optimize_is_recorded_once_a_rewrite():
    main, startup, loss = _train_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    before = dict(obs.counters())
    with fluid.scope_guard(scope):
        exe.run(startup)
        tracing.reset()
        exe.run(main, feed=_feed(np.random.RandomState(0)),
                fetch_list=[loss])
    events = [e for e in obs.recorder().events()
              if e['name'] == 'executor.optimize']
    assert [e['ph'] for e in events] == ['X']
    args = events[0]['args']
    assert args['raw'] >= args['opt'] > 0 and args['pass_ms'] >= 0
    c = _delta(obs.counters(), before)
    assert c['opt.runs'] == 2                 # the startup program's too
    assert c['opt.pass_ms'] > 0


# ------------------------------------------- (b) the serving runtime

@pytest.mark.parametrize('start', ['cold', 'warm_disk'])
def test_decode_runtime_setup_phases(disk_cache, start):
    """`decode.init` once; a cold `warmup` compiles (and moves
    `generation.compile_s`), one over a warm cache loads (and moves
    `compile_cache.load_s`); the children of every `decode.compile` tile
    it and the compiles tile `decode.warmup`; a launch after `warmup`
    is the hot path."""
    weights = random_weights(CFG, seed=0)
    make = lambda: DecodeRuntime(weights, CFG, slots=3,  # noqa: E731
                                 prefill_chunk=4, page_len=8)
    if start == 'warm_disk':
        make().warmup(steps=4)                  # an earlier process
    tracing.reset()
    before = dict(obs.counters())
    rt = make()
    rt.warmup(steps=4)
    c = _delta(obs.counters(), before)
    spans = _spans()

    init, = [e for e in spans if e['name'] == 'decode.init']
    assert c['generation.init_s'] == pytest.approx(init['dur'] / 1e6)
    warmup, = [e for e in spans if e['name'] == 'decode.warmup']
    assert c['generation.warmup_s'] == pytest.approx(warmup['dur'] / 1e6)
    compiles = [e for e in spans if e['name'] == 'decode.compile']
    assert [(e['args']['fn'], e['args']['shape']) for e in compiles] \
        == [('prefill', [4]), ('decode', [4])]
    verdict = 'compiled' if start == 'cold' else 'disk_hit'
    assert [e['args']['verdict'] for e in compiles] == [verdict] * 2
    if start == 'cold':
        assert c['generation.compiles'] == 2
        assert c['generation.compile_s'] > 0
        assert c['compile_cache.store_s'] > 0
        assert c.get('compile_cache.load_s', 0.0) == 0.0
    else:
        assert c.get('generation.compiles', 0) == 0
        assert c.get('generation.compile_s', 0.0) == 0.0
        assert c['compile_cache.load_s'] > 0
        assert c['compile_cache.disk_hits'] == 2
    for comp in compiles:
        assert _inside(comp, warmup)
        kids = [e for e in spans if e['name'] in COMPILE_CHILDREN
                and _inside(e, comp)]
        want = set(COMPILE_CHILDREN) - (
            {'decode.trace_compile', 'compile_cache.store'}
            if start == 'warm_disk' else set())
        assert {e['name'] for e in kids} == want
        assert 0.95 * comp['dur'] <= sum(e['dur'] for e in kids) \
            <= comp['dur']
    assert 0.95 * warmup['dur'] <= sum(e['dur'] for e in compiles) \
        <= warmup['dur']
    moved = sum(c.get(k, 0.0) for k in (
        'compile_cache.fingerprint_s', 'compile_cache.load_s',
        'generation.compile_s', 'compile_cache.store_s'))
    assert 0.95 * c['generation.warmup_s'] <= moved \
        <= c['generation.warmup_s']

    # launches after warmup: the executables are in `_execs`
    from paddle_tpu.serving.generation import SamplingParams
    tracing.reset()
    before = dict(obs.counters())
    slot = rt.alloc_slot()
    assert rt.try_begin(slot, np.arange(1, 5, dtype=np.int32), 4) == 0
    rt.prefill(slot, np.arange(1, 5, dtype=np.int32), 0, SamplingParams())
    active = np.zeros(rt.slots, bool)
    active[slot] = True
    zeros = np.zeros(rt.slots, np.int32)
    np.asarray(rt.decode_window(4, active, zeros,
                                np.zeros(rt.slots, np.float32), zeros))
    hot = _delta(obs.counters(), before)
    assert hot['generation.launches'] == 2
    assert not [k for k in NEW_COUNTERS if hot.get(k)], hot
    assert not {e['name'] for e in _spans()} & NEW_SPANS


# ------------------------------------------------ (c) program.build

def test_program_build_counts_the_outermost_guard_once():
    before = dict(obs.counters())
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[4], dtype='float32')
            with fluid.program_guard(main, startup):      # nested: nothing
                y = fluid.layers.fc(x, 3)
            loss = fluid.layers.reduce_mean(y)
            fluid.optimizer.SGD(0.1).minimize(loss)
    build, = [e for e in _spans() if e['name'] == 'program.build']
    block = main.global_block()
    c = _delta(obs.counters(), before)
    # its own delta of the memo of abstract evaluation (PR 67)
    assert build['args'] == {
        'ops': len(block.ops), 'vars': len(block.vars),
        'infer_hits': c.get('infer.memo_hits', 0),
        'infer_misses': c.get('infer.memo_misses', 0)}
    assert build['args']['infer_hits'] + build['args']['infer_misses'] > 0
    # append_backward and minimize ran inside it
    assert any(op.type == 'sgd' for op in block.ops)
    assert c['program.build_s'] == pytest.approx(build['dur'] / 1e6)
    # a second outermost guard is a second build
    with fluid.program_guard(main, startup):
        pass
    assert len([e for e in _spans() if e['name'] == 'program.build']) == 2


# ------------------------------------------------- (d) the process

def test_process_counters_after_import():
    """In a process of its own: the registry of this one may have been
    reset by an earlier test of its worker."""
    code = ('import json, time; t0 = time.perf_counter(); '
            'import paddle_tpu.observability as obs; '
            't1 = time.perf_counter(); c = obs.counters(); '
            'ev = [e for e in obs.recorder().events() '
            '      if e["name"] == "process.import"]; '
            'print(json.dumps({"import_s": c.get("process.import_s"), '
            '"before_s": c.get("process.before_import_s"), '
            '"age_s": obs.metrics.process_age_s(), "wall_s": t1 - t0, '
            '"spans": [e["dur"] for e in ev]}))')
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    out = subprocess.run([sys.executable, '-c', code], env=env, check=True,
                         capture_output=True, text=True).stdout
    got = json.loads(out.strip().splitlines()[-1])
    # `import paddle_tpu` is within the import statement that caused it
    assert 0 < got['import_s'] <= got['wall_s']
    assert got['spans'] == [pytest.approx(got['import_s'] * 1e6)]
    if sys.platform.startswith('linux'):
        # process start -> the package's first line -> now, one clock
        assert 0 <= got['before_s']
        assert got['before_s'] + got['import_s'] <= got['age_s']
    else:
        assert got['before_s'] is None and got['age_s'] is None


# -------------------------------- (e) the phases on the profiler's timeline

def test_profiler_trace_holds_the_cold_path_nested(tmp_path):
    """Under a jax.profiler trace a cold launch leaves pt:executor.prepare
    on the host plane with pt:executor.lower and
    pt:executor.trace_compile inside it, and a runtime's warm-up
    pt:decode.warmup > pt:decode.compile > pt:decode.trace_compile."""
    main, startup, loss = _train_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        jax.profiler.start_trace(str(tmp_path))
        try:
            exe.run(main, feed=_feed(np.random.RandomState(0)),
                    fetch_list=[loss])
            DecodeRuntime(random_weights(CFG, seed=0), CFG, slots=3,
                          prefill_chunk=4, page_len=8).warmup(steps=4)
        finally:
            jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / '**' / '*.xplane.pb'), recursive=True)
    _, _, thread_spans = timeline.load_trace(path)
    by_name = {}
    for thread, spans in thread_spans.items():
        for s, e, name in spans:
            by_name.setdefault(name, []).append((s, e, thread))
    for child, parent in [('executor.lint', 'executor.prepare'),
                          ('executor.optimize', 'executor.prepare'),
                          ('executor.lower', 'executor.prepare'),
                          ('executor.trace_compile', 'executor.prepare'),
                          ('decode.compile', 'decode.warmup'),
                          ('decode.trace_compile', 'decode.compile'),
                          ('compile_cache.fingerprint', 'decode.compile')]:
        assert by_name.get(child), 'no pt:%s in the trace' % child
        assert by_name.get(parent), 'no pt:%s in the trace' % parent
        for s, e, thread in by_name[child]:
            assert any(ps <= s and e <= pe and thread == pt
                       for ps, pe, pt in by_name[parent]), (child, parent)
    assert by_name.get('decode.init')


# ----------------------------------------------- (f) PT_OBS=0: no work

def _cold_launch(main, startup, loss):
    def once():
        # a fresh executor and scope: every launch is a cold path
        exe, scope = fluid.Executor(), fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.run(main, feed=_feed(np.random.RandomState(0)),
                    fetch_list=[loss])
            exe.run_steps(main, feed_list=[_feed(np.random.RandomState(1))
                                           for _ in range(2)],
                          fetch_list=[loss])
    return once


def _runtime_setup(main, startup, loss):
    weights = random_weights(CFG, seed=0)

    def once():
        rt = DecodeRuntime(weights, CFG, slots=2, prefill_chunk=4,
                           page_len=8)
        rt.warmup(steps=2)
    return once


def _program_build(main, startup, loss):
    return lambda: _train_model()


@pytest.mark.parametrize('path', [_cold_launch, _runtime_setup,
                                  _program_build],
                         ids=['cold_launch', 'runtime_setup',
                              'program_build'])
def test_disabled_mode_does_no_setup_telemetry(monkeypatch, path):
    """With telemetry disabled a cold launch, a runtime's construction
    and warm-up and a program build enter no span, look up no counter or
    gauge and read no clock for telemetry: every entry point is patched
    to raise, and the recorder and registry must not grow."""
    main, startup, loss = _train_model()
    once = path(main, startup, loss)
    once()                                                       # warm
    events_before = obs.recorder().event_count()
    counters_before = dict(obs.counters())
    obs.disable()
    try:
        def boom(*a, **k):
            raise AssertionError('telemetry invoked while disabled')
        monkeypatch.setattr(obs.stall, 'on_launch_start', boom)
        monkeypatch.setattr(obs.stall, 'on_launch_end', boom)
        monkeypatch.setattr(obs.tracing, 'add_span', boom)
        monkeypatch.setattr(obs.tracing, '_annotation', boom)
        monkeypatch.setattr(obs.tracing, '_counter', boom)
        monkeypatch.setattr(obs.tracing.TraceRecorder, 'add_complete', boom)
        for kind in ('counter', 'histogram'):
            monkeypatch.setattr(obs.metrics, kind, boom)

        class _NoClock(object):
            perf_counter = staticmethod(boom)
        monkeypatch.setattr(executor_mod, 'time', _NoClock)
        once()
    finally:
        obs.enable()
    assert obs.recorder().event_count() == events_before
    assert obs.counters() == counters_before


# ------------------------------------------- (g) the operator's reading

def test_telemetry_snapshot_setup_holds_the_schema(disk_cache):
    main, startup, loss = _train_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed(np.random.RandomState(0)),
                fetch_list=[loss])
    before = obs.counters()
    DecodeRuntime(random_weights(CFG, seed=0), CFG, slots=2,
                  prefill_chunk=4, page_len=8).warmup(steps=2)
    snap = obs.telemetry_snapshot('setup')
    assert list(snap) == obs_export.schema_keys('setup') == [
        'process_s', 'training_s', 'serving_s', 'compile_cache_s',
        'executables']
    named = {k for block in snap.values() for k in block}
    assert named >= set(NEW_COUNTERS) | {'process.before_import_s'}
    train, serve = snap['training_s'], snap['serving_s']
    assert 0 < train['executor.lower_s'] < train['executor.prepare_s'] \
        < train['executor.run_s']
    # counters are the process's: a file this worker ran earlier may have
    # compiled outside any warm-up, so hold what THIS runtime moved
    moved = _delta(serve, before)
    assert 0 < moved['generation.compile_s'] < moved['generation.warmup_s']
    assert snap['executables']['generation.compiles'] >= 2
    assert snap['compile_cache_s']['compile_cache.store_s'] > 0
    # /varz carries the same block
    assert obs_export._varz()['setup'].keys() == snap.keys()
