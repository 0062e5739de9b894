"""Telemetry subsystem tests: retrace explainer, Chrome-trace export,
pipeline-stall + prefetcher gauges, no-op-mode overhead, profiler fixes."""
import json
import time

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.observability as obs
from paddle_tpu import layers


def _build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data('x', shape=[4], dtype='float32')
            y = layers.fc(x, 3)
            z = layers.reduce_mean(y)
    return main, startup, y, z


def _run(exe, prog, feed, fetch):
    return exe.run(prog, feed=feed, fetch_list=fetch)


def test_retrace_explainer_names_shape_change():
    main, startup, y, _ = _build()
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        _run(exe, main, {'x': np.ones((2, 4), 'float32')}, [y])
        before = obs.counters().get('executor.retraces') or 0
        # warm shape: NO retrace
        _run(exe, main, {'x': np.ones((2, 4), 'float32')}, [y])
        assert (obs.counters().get('executor.retraces') or 0) == before
        # changed feed shape mid-loop: counted, and the cause is named
        _run(exe, main, {'x': np.ones((5, 4), 'float32')}, [y])
    assert (obs.counters().get('executor.retraces') or 0) == before + 1
    rep = obs.explainer().last_report()
    assert rep['kind'] == 'retrace'
    assert rep['changed'] == ['feed_shapes']
    assert any('x' in d and '(2, 4)' in d and '(5, 4)' in d
               for d in rep['details']), rep['details']
    # the rendered report is human-readable text naming the component
    assert 'feed_shape:x' in obs.explainer().render_report(rep)


def test_retrace_explainer_names_fetch_set_change():
    main, startup, y, z = _build()
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        _run(exe, main, {'x': np.ones((2, 4), 'float32')}, [y])
        _run(exe, main, {'x': np.ones((2, 4), 'float32')}, [z])
    rep = obs.explainer().last_report()
    assert rep['kind'] == 'retrace'
    assert rep['changed'] == ['fetch_set']
    assert any(z.name in d for d in rep['details']), rep['details']


def test_retrace_explainer_fused_steps_change():
    """run -> run_steps on the same program is a retrace whose named cause
    is steps (and the stacked feed shape)."""
    main, startup, y, _ = _build()
    exe = fluid.Executor()
    scope = fluid.Scope()
    feed = {'x': np.ones((2, 4), 'float32')}
    with fluid.scope_guard(scope):
        exe.run(startup)
        _run(exe, main, feed, [y])
        exe.run_steps(main, feed_list=[feed, feed, feed], fetch_list=[y])
    rep = obs.explainer().last_report()
    assert rep['kind'] == 'retrace'
    assert 'steps' in rep['changed']
    assert any('steps' in d and '3' in d for d in rep['details'])


def test_chrome_trace_json_valid(tmp_path):
    obs.tracing.reset()    # serving tests earlier in this process left flows
    main, startup, y, _ = _build()
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(3):
            _run(exe, main, {'x': np.ones((2, 4), 'float32')}, [y])
    path = str(tmp_path / 'trace.json')
    obs.export_chrome_trace(path)
    with open(path) as f:
        data = json.load(f)
    evs = data['traceEvents']
    assert evs, 'no events exported'
    ts = [e['ts'] for e in evs]
    assert ts == sorted(ts), 'ts must be monotonic in the exported file'
    for e in evs:
        assert e['ph'] in ('X', 'i'), e
        assert {'name', 'ts', 'pid', 'tid'} <= set(e), e
        if e['ph'] == 'X':
            assert e['dur'] >= 0
    names = {e['name'] for e in evs}
    assert 'executor.dispatch' in names or 'executor.trace_compile' in names
    assert 'executor.fetch_sync' in names


def test_stall_detection_fires_on_launch_gap():
    main, startup, y, _ = _build()
    exe = fluid.Executor()
    scope = fluid.Scope()
    old = obs.stall_threshold_ms()
    obs.set_stall_threshold_ms(30)
    try:
        with fluid.scope_guard(scope):
            exe.run(startup)
            _run(exe, main, {'x': np.ones((2, 4), 'float32')}, [y])
            before = obs.counters().get('executor.stall_count') or 0
            time.sleep(0.06)   # the "pipeline" drains
            _run(exe, main, {'x': np.ones((2, 4), 'float32')}, [y])
    finally:
        obs.set_stall_threshold_ms(old)
    assert (obs.counters().get('executor.stall_count') or 0) == before + 1
    stalls = [e for e in obs.recorder().events()
              if e['name'] == 'pipeline.stall']
    assert stalls and stalls[-1]['args']['gap_ms'] > 30
    hist = obs.metrics.histogram('executor.launch_gap_ms').snapshot()
    assert hist['count'] >= 2 and hist['max'] > 30


def test_prefetch_starvation_gauge_fires_under_slow_reader():
    def slow_feeds():
        for _ in range(4):
            time.sleep(0.05)
            yield {'x': np.ones((2, 2), 'float32')}

    before = obs.counters().get('prefetch.starvation_count') or 0
    pf = fluid.FeedPrefetcher(slow_feeds(), steps=2, capacity=2,
                              to_device=False)
    got = list(pf)
    pf.close()
    assert len(got) == 2 and got[0][1] == 2
    c = obs.counters()
    assert (c.get('prefetch.starvation_count') or 0) > before
    assert (c.get('prefetch.starvation_s') or 0) > 0
    assert 'prefetch.queue_depth' in c
    assert (c.get('prefetch.upload_s') or 0) > 0


def _disabled_run(exe, main, y):
    return lambda: _run(exe, main, {'x': np.ones((2, 4), 'float32')}, [y])


def _disabled_run_steps(exe, main, y):
    feeds = [{'x': np.ones((2, 4), 'float32')} for _ in range(2)]
    return lambda: exe.run_steps(main, feed_list=feeds, fetch_list=[y])


def _disabled_prefetch(exe, main, y):
    def once():
        pf = fluid.FeedPrefetcher(
            iter([{'x': np.ones((2, 4), 'float32')}] * 2), steps=2,
            capacity=1, to_device=False)
        for feed, k in pf:
            exe.run_steps(main, feed_list=feed, steps=k, fetch_list=[y])
        pf.close()
    return once


def _disabled_generation(exe, main, y):
    from paddle_tpu.serving.engine import ServingConfig
    from paddle_tpu.serving.generation import (DecodeRuntime,
                                               GenerationConfig,
                                               GenerationEngine)
    from paddle_tpu.serving.generation.decode import random_weights
    cfg = dict(vocab=64, d_model=32, n_layer=1, n_head=4, n_kv_head=2,
               d_ffn=64, theta=10000.0, max_len=32)
    rt = DecodeRuntime(random_weights(cfg), cfg, slots=2, prefill_chunk=4)

    def once():
        eng = GenerationEngine(
            rt, config=ServingConfig(),
            gen_config=GenerationConfig(decode_window=2)).start()
        try:
            stream = eng.generate([1, 2, 3, 4, 5], max_new=4)
            assert stream.result(60).ok
            assert stream.traceparent is None or obs.enabled()
        finally:
            eng.stop(timeout=10)
    return once


# the serving engine's older metrics are looked up by name on every call
# and drop the update themselves; no other lookup may happen when disabled
_UNGUARDED_LOOKUPS = ('serving.', 'generation.tokens',
                      'generation.prefill_chunks',
                      'generation.decode_windows',
                      'generation.mixed_dispatches')


@pytest.mark.parametrize('path', [
    _disabled_run, _disabled_run_steps, _disabled_prefetch,
    _disabled_generation],
    ids=['run', 'run_steps', 'prefetch', 'generation'])
def test_disabled_mode_does_no_telemetry_work(monkeypatch, path):
    """With telemetry disabled the hot paths (a launch, a K-step launch, a
    prefetched launch, a serving round) must not touch the subsystem at
    all: every entry point, the span's profiler annotation among them,
    is patched to raise, and the recorder/registry must not grow — i.e.
    no per-launch telemetry allocations beyond the constant `enabled()`
    branch."""
    main, startup, y, _ = _build()
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        once = path(exe, main, y)
        once()                                                   # warm
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
            monkeypatch.setattr(obs.tracing.TraceRecorder, 'add_complete',
                                boom)
            for kind in ('counter', 'histogram'):
                real = getattr(obs.metrics, kind)
                monkeypatch.setattr(
                    obs.metrics, kind,
                    lambda name, real=real: real(name) if name.startswith(
                        _UNGUARDED_LOOKUPS) else boom())
            for _ in range(3):
                once()
        finally:
            obs.enable()
    assert obs.recorder().event_count() == events_before
    assert obs.counters() == counters_before


def test_metrics_registry_basics():
    obs.counter('t.ctr').inc()
    obs.counter('t.ctr').inc(2.5)
    obs.gauge('t.g').set(7)
    h = obs.histogram('t.h')
    for v in (0.5, 3.0, 100.0):
        h.observe(v)
    snap = obs.metrics_snapshot()
    assert snap['counters']['t.ctr'] == 3.5
    assert snap['gauges']['t.g'] == 7
    hs = snap['histograms']['t.h']
    assert hs['count'] == 3 and hs['min'] == 0.5 and hs['max'] == 100.0
    with pytest.raises(TypeError):
        obs.gauge('t.ctr')   # kind mismatch is an error, not a silent alias
    full = obs.snapshot()
    assert 'spans' in full and 'retrace_reports' in full


def test_profiler_restores_trace_dir_and_reset_clears(tmp_path, capsys):
    import paddle_tpu.profiler as prof
    old_dir = prof._trace_dir[0]
    main, startup, y, _ = _build()
    exe = fluid.Executor()
    scope = fluid.Scope()
    d = str(tmp_path / 'prof')
    with fluid.scope_guard(scope):
        exe.run(startup)
        with prof.profiler('All', sorted_key='total', profile_path=d):
            _run(exe, main, {'x': np.ones((2, 4), 'float32')}, [y])
    # state-leak fix: the scoped profile_path must not stick
    assert prof._trace_dir[0] == old_dir
    out = capsys.readouterr().out
    assert 'Profiling Report' in out
    assert 'executor.' in out   # recorded spans appear in the table
    # our chrome trace landed inside the trace dir alongside the xplane dump
    import os
    assert os.path.exists(os.path.join(d, 'paddle_tpu_trace.json'))
    with open(os.path.join(d, 'paddle_tpu_trace.json')) as f:
        assert json.load(f)['traceEvents']
    # reset_profiler is no longer a silent no-op
    assert obs.recorder().event_count() > 0
    prof.reset_profiler()
    assert obs.recorder().event_count() == 0
    assert obs.counters() == {}
    assert obs.explainer().last_report() is None


def test_trainer_end_step_event_carries_telemetry():
    def train_func():
        x = layers.data('x', shape=[3], dtype='float32')
        yv = layers.data('y', shape=[1], dtype='float32')
        pred = layers.fc(x, 1)
        return layers.reduce_mean(layers.square(pred - yv))

    def reader():
        rng = np.random.RandomState(0)
        for _ in range(4):
            yield [(rng.rand(3).astype('float32'),
                    rng.rand(1).astype('float32')) for _ in range(4)]

    seen = []

    def handler(ev):
        if isinstance(ev, fluid.EndStepEvent):
            seen.append(ev.telemetry)

    trainer = fluid.Trainer(train_func,
                            lambda: fluid.optimizer.SGDOptimizer(0.1))
    trainer.train(1, handler, reader=reader, feed_order=['x', 'y'],
                  steps_per_launch=2)
    assert seen
    assert all(isinstance(t, dict) for t in seen)
    assert all('executor.launches' in t for t in seen)
    # counters are cumulative: later snapshots never go backwards
    launches = [t['executor.launches'] for t in seen]
    assert launches == sorted(launches)
