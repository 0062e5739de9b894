"""Observability plane (PR 9): request-level tracing, flight recorder,
metrics export (Prometheus + HTTP), device-memory hooks, bounded
histograms, stall attribution under degraded serving, and the shared
telemetry-snapshot schema."""
import json
import os
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu.observability as obs
from paddle_tpu.data_feeder import FeedBucketer
from paddle_tpu.observability import export as obs_export
from paddle_tpu.observability import flight as obs_flight
from paddle_tpu.observability import memory as obs_memory
from paddle_tpu.observability import trace_context as tc
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.testing import faults


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _cnt(name):
    return obs.counters().get(name) or 0


def _echo_backend(feed):
    x = np.asarray(feed['x'])
    return [x * 2.0]


def _feed(rows, cols=3):
    return {'x': np.arange(rows * cols,
                           dtype='float32').reshape(rows, cols)}


# ------------------------------------------------- bounded histograms

def test_histogram_million_observations_bounded_memory_stable_quantiles():
    """Satellite pin: the bounded log-bucket backing store.  A million
    observations spanning six decades must keep O(1) memory (bucket
    count bounded by the VALUE RANGE, not the observation count) and
    still answer p50/p99 within a few percent."""
    h = obs.histogram('t.h_million')
    rng = np.random.RandomState(7)
    vals = np.exp(rng.standard_normal(1_000_000) * 2.0 + 1.0)
    for v in vals.tolist():
        h.observe(v)
    # log buckets with 4 mantissa sub-buckets: ~40 octaves of range
    # would still be < 200 buckets; 1M observations add ZERO
    assert h.bucket_count() < 200
    snap = h.snapshot()
    assert snap['count'] == 1_000_000
    for q in (0.50, 0.99):
        true = float(np.percentile(vals, q * 100))
        est = h.quantile(q)
        assert abs(est - true) / true < 0.05, (q, est, true)
    # Prometheus cumulative buckets are monotone and end at the count
    cum = h.cumulative_buckets()
    counts = [c for _, c in cum]
    assert counts == sorted(counts) and counts[-1] == 1_000_000


def test_histogram_nonpositive_bucket_and_quantile_clamp():
    h = obs.histogram('t.h_edge')
    for v in (0.0, -3.5, 2.0, 4.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap['count'] == 4 and snap['buckets']['le_0'] == 2
    q = h.quantile(0.99)
    assert snap['min'] <= q <= snap['max']
    assert obs.histogram('t.h_never').quantile(0.5) is None


# --------------------------------------------------- trace context

def test_traceparent_roundtrip_and_malformed():
    ctx = tc.TraceContext.new()
    hdr = ctx.to_traceparent()
    back = tc.TraceContext.from_traceparent(hdr)
    assert (back.trace_id, back.span_id) == (ctx.trace_id, ctx.span_id)
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.parent_span_id == ctx.span_id
    assert child.span_id != ctx.span_id
    for bad in (None, '', 'junk', '00-' + '0' * 32 + '-' + 'a' * 16 + '-01',
                '00-' + 'a' * 32 + '-' + '0' * 16 + '-01'):
        assert tc.TraceContext.from_traceparent(bad) is None
    args = child.span_args(rows=3)
    assert args['trace_id'] == ctx.trace_id and args['rows'] == 3
    assert args['parent_span_id'] == ctx.span_id


def test_ambient_context_stamps_spans():
    ctx = tc.TraceContext.new()
    with tc.use(ctx):
        obs.tracing.add_span('t.ambient', 0.0, 0.001, cat='test')
    evs = [e for e in obs.recorder().events() if e['name'] == 't.ambient']
    assert evs and evs[-1]['args']['trace_id'] == ctx.trace_id


def test_root_span_noop_when_disabled():
    obs.disable()
    try:
        before = obs.recorder().event_count()
        with tc.root_span('t.root_off') as ctx:
            assert ctx is None
            assert tc.current() is None
        assert obs.recorder().event_count() == before
    finally:
        obs.enable()


# --------------------------------------- serving request decomposition

def test_request_trace_decomposes_into_linked_child_spans():
    import time as _time

    def backend(feed):
        _time.sleep(0.004)   # a measurable device window: the >=90%
        return _echo_backend(feed)   # coverage bound is about real time

    eng = ServingEngine(backend,
                        bucketer=FeedBucketer(boundaries=[1, 2, 4, 8]),
                        config=ServingConfig(max_queue=16))
    eng.start()
    futs = [eng.submit(_feed(1 + (i % 3)), timeout_s=5.0) for i in range(6)]
    assert eng.stop(timeout=10)
    events = obs.recorder().events()
    ok = [f for f in futs if f.result(0).status == 'ok']
    assert ok and all(f.traceparent for f in futs)
    verified = 0
    for f in ok:
        tid = f.traceparent.split('-')[1]
        roots = [e for e in events if e['name'] == 'serving.request'
                 and e.get('args', {}).get('trace_id') == tid]
        assert len(roots) == 1, (tid, roots)
        assert roots[0]['args']['status'] == 'ok'
        kids = {e['name']: e for e in events
                if e['name'] in ('serving.queue_wait', 'serving.dispatch',
                                 'serving.device')
                and e.get('args', {}).get('trace_id') == tid}
        assert set(kids) == {'serving.queue_wait', 'serving.dispatch',
                             'serving.device'}
        batch_sid = kids['serving.queue_wait']['args']['batch_span_id']
        batches = [e for e in events if e['name'] == 'serving.batch'
                   and e['args'].get('span_id') == batch_sid]
        assert len(batches) == 1
        assert tid in batches[0]['args']['links']
        covered = sum(k['dur'] for k in kids.values())
        assert covered >= 0.9 * roots[0]['dur'], (covered, roots[0]['dur'])
        verified += 1
    assert verified == len(ok)


def test_chaos_dispatch_failure_one_root_span_status_matches_reply():
    """Satellite pin: under serve_dispatch chaos every request still
    yields EXACTLY one root span, and its status IS the terminal
    reply's status."""
    faults.configure('serve_dispatch:at=1:times=1')
    eng = ServingEngine(_echo_backend,
                        bucketer=FeedBucketer(boundaries=[1, 2, 4, 8]),
                        config=ServingConfig(max_queue=16))
    eng.start()
    futs = [eng.submit(_feed(1), timeout_s=5.0) for i in range(8)]
    assert eng.stop(timeout=10)
    statuses = [f.result(0).status for f in futs]
    assert 'error' in statuses   # the injected batch failure surfaced
    events = obs.recorder().events()
    for f, status in zip(futs, statuses):
        tid = f.traceparent.split('-')[1]
        roots = [e for e in events if e['name'] == 'serving.request'
                 and e.get('args', {}).get('trace_id') == tid]
        assert len(roots) == 1, (tid, status, len(roots))
        assert roots[0]['args']['status'] == status


def test_obs_disabled_new_surfaces_do_zero_work():
    obs.disable()
    try:
        ring_before = len(obs_flight.flight().events())
        events_before = obs.recorder().event_count()
        gauges_before = dict(obs.metrics_snapshot()['gauges'])
        eng = ServingEngine(_echo_backend,
                            bucketer=FeedBucketer(boundaries=[1, 2]),
                            config=ServingConfig(metrics_port=0))
        eng.start()
        fut = eng.submit(_feed(1), timeout_s=5.0)
        assert eng.stop(timeout=10)
        assert fut.result(0).status == 'ok'
        assert fut.traceparent is None          # no trace minted
        assert eng.metrics_port is None         # no HTTP server started
        obs_flight.record('t.should_not_record')
        obs_memory.on_launch()
        assert len(obs_flight.flight().events()) == ring_before
        assert obs.recorder().event_count() == events_before
        assert obs.metrics_snapshot()['gauges'] == gauges_before
    finally:
        obs.enable()


def test_obs_disabled_serving_boundary_does_zero_work(monkeypatch):
    """The round's boundary with the plane off: `serving.boundary` and
    its check enter no annotation and write no event, none of the five
    `generation.boundary*` counters is looked up, the landed window's
    handle is not asked whether it was ready (`is_ready()`), and the
    scheduler reads no clock for telemetry, over rounds that land a
    window and launch a chunk or a window behind it."""
    from paddle_tpu.serving.engine import READY
    from paddle_tpu.serving.generation import (DecodeRuntime,
                                               GenerationConfig,
                                               GenerationEngine, decode,
                                               scheduler)
    cfg = dict(vocab=64, d_model=32, n_layer=1, n_head=4, n_kv_head=2,
               d_ffn=64, theta=10000.0, max_len=32)
    rt = DecodeRuntime(decode.random_weights(cfg), cfg, slots=2,
                       prefill_chunk=4)

    def serve():
        eng = GenerationEngine(rt, config=ServingConfig(),
                               gen_config=GenerationConfig(decode_window=2))
        eng._set_state(READY)
        streams = [eng.generate([1, 2, 3], max_new=7),
                   eng.generate(list(range(4, 14)), max_new=3)]
        while eng._queue or eng._active:
            assert eng._round()
        assert all(s.result(0).ok for s in streams)
        return eng

    boundaries, dry = (_cnt('generation.boundaries'),
                       _cnt('generation.boundary_dry_s'))
    serve()                                     # warm, and the plane ON:
    assert _cnt('generation.boundaries') >= boundaries + 3
    assert _cnt('generation.boundary_dry_s') > dry
    events_before = obs.recorder().event_count()
    counters_before = dict(obs.counters())
    obs.disable()
    try:
        def boom(*a, **k):
            raise AssertionError('telemetry invoked while disabled')
        monkeypatch.setattr(decode._Pending, 'landed', boom)
        monkeypatch.setattr(obs.tracing, '_annotation', boom)
        monkeypatch.setattr(obs.tracing.TraceRecorder, 'add_complete', boom)
        real = obs.metrics.counter
        monkeypatch.setattr(
            obs.metrics, 'counter',
            lambda name: boom() if name.startswith('generation.boundar')
            else real(name))

        class _NoClock(object):
            perf_counter = staticmethod(boom)
        monkeypatch.setattr(scheduler, 'time', _NoClock)
        monkeypatch.setattr(decode, 'time', _NoClock, raising=False)
        serve()
    finally:
        obs.enable()
    assert obs.recorder().event_count() == events_before
    assert all((v or 0) == (counters_before.get(k) or 0)
               for k, v in obs.counters().items())


# ----------------------------------------------------- flight recorder

def test_flight_ring_bounded_and_tap_mirrors_trace_events():
    fr = obs_flight.FlightRecorder(max_events=16)
    for i in range(100):
        fr.record('t.ev', i=i)
    assert len(fr.events()) == 16
    assert fr.events()[-1]['i'] == 99
    # the installed global tap mirrors every trace event into the ring
    obs.instant('t.flight_mirror', cat='test')
    names = [e.get('name') for e in obs_flight.flight().events()]
    assert 't.flight_mirror' in names


def test_flight_dump_artifact_and_maybe_dump_gating(tmp_path, monkeypatch):
    monkeypatch.delenv('PT_FLIGHT_DIR', raising=False)
    assert obs_flight.maybe_dump('no_dir_no_dump') is None
    obs_flight.record('t.dumped', detail='x')
    path = obs_flight.dump('unit_test', path=str(tmp_path / 'f.json'))
    art = json.load(open(path))
    assert art['reason'] == 'unit_test' and art['pid'] == os.getpid()
    assert any(e.get('kind') == 't.dumped' for e in art['events'])
    assert 'counters' in art['metrics'] and 'env' in art
    monkeypatch.setenv('PT_FLIGHT_DIR', str(tmp_path))
    p2 = obs_flight.maybe_dump('gated', extra={'k': 1})
    assert p2 and os.path.dirname(p2) == str(tmp_path)
    assert json.load(open(p2))['extra'] == {'k': 1}


def test_flight_dump_budget_cap(monkeypatch):
    monkeypatch.setattr(obs_flight, '_MAX_DUMPS', 2)
    fr = obs_flight.FlightRecorder(max_events=4)
    import tempfile
    d = tempfile.mkdtemp(prefix='pt_flight_cap.')
    assert fr.dump('a', path=os.path.join(d, 'a.json'))
    assert fr.dump('b', path=os.path.join(d, 'b.json'))
    assert fr.dump('c', path=os.path.join(d, 'c.json')) is None


def test_serving_batch_failure_leaves_flight_dump(tmp_path, monkeypatch):
    monkeypatch.setenv('PT_FLIGHT_DIR', str(tmp_path))
    faults.configure('serve_dispatch:at=1:times=1')
    eng = ServingEngine(_echo_backend,
                        bucketer=FeedBucketer(boundaries=[1, 2]),
                        config=ServingConfig(max_queue=8))
    eng.start()
    futs = [eng.submit(_feed(1), timeout_s=5.0) for _ in range(4)]
    assert eng.stop(timeout=10)
    assert any(f.result(0).status == 'error' for f in futs)
    dumps = [fn for fn in os.listdir(str(tmp_path))
             if 'serving_batch_failure' in fn]
    assert dumps
    art = json.load(open(str(tmp_path / dumps[0])))
    evs = art['events']
    assert any(e.get('kind') == 'serving.batch_failure' for e in evs)
    assert any(e.get('name') == 'fault.injected'
               and e.get('args', {}).get('site') == 'serve_dispatch'
               for e in evs)


# -------------------------------------------------- prometheus + HTTP

def test_prometheus_rendering():
    obs.counter('promtest.ctr').inc(3)
    obs.gauge('promtest.g').set(1.5)
    h = obs.histogram('promtest.h')
    for v in (0.5, 1.0, 8.0):
        h.observe(v)
    text = obs.render_prometheus()
    assert 'promtest_ctr_total 3' in text
    assert '# TYPE promtest_ctr_total counter' in text
    assert 'promtest_g 1.5' in text
    assert 'promtest_h_bucket{le="+Inf"} 3' in text
    assert 'promtest_h_count 3' in text
    assert 'promtest_h_sum 9.5' in text


def _get(url):
    with urllib.request.urlopen(url, timeout=5.0) as r:
        return r.status, r.read().decode(), r.headers.get('Content-Type')


def test_metrics_http_server_endpoints():
    obs.counter('httptest.ctr').inc()
    srv = obs_export.start_http_server(port=0)
    try:
        code, body, ctype = _get(srv.url('/metrics'))
        assert code == 200 and 'httptest_ctr_total' in body
        assert ctype == obs_export.PROM_CONTENT_TYPE
        code, body, _ = _get(srv.url('/healthz'))
        assert code == 200 and json.loads(body)['accepting'] is True
        code, body, _ = _get(srv.url('/varz'))
        assert code == 200 and 'counters' in json.loads(body)
        with pytest.raises(urllib.error.HTTPError):
            _get(srv.url('/nope'))
    finally:
        srv.stop()


def test_engine_owns_metrics_server_lifecycle():
    eng = ServingEngine(_echo_backend,
                        bucketer=FeedBucketer(boundaries=[1, 2]),
                        config=ServingConfig(metrics_port=0))
    assert eng.metrics_port is None   # not started before start()
    eng.start()
    try:
        port = eng.metrics_port
        assert isinstance(port, int) and port > 0
        code, _, _ = _get('http://127.0.0.1:%d/healthz' % port)
        assert code == 200
        fut = eng.submit(_feed(1), timeout_s=5.0)
        assert fut.result(5).status == 'ok'
        assert eng.drain(timeout=10)
        # the endpoint must survive the drain so post-drain scrapes can
        # verify the accounting identity...
        code, body, _ = _get('http://127.0.0.1:%d/metrics' % port)
        assert code == 200 and 'serving_admitted_total' in body
        # ...and /healthz now refuses
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get('http://127.0.0.1:%d/healthz' % port)
        assert ei.value.code == 503
    finally:
        eng.stop_metrics_server()
    with pytest.raises(urllib.error.URLError):
        _get('http://127.0.0.1:%d/healthz' % port)


def test_resolve_metrics_port_precedence(monkeypatch):
    monkeypatch.delenv('PT_METRICS_PORT', raising=False)
    assert obs_export.resolve_metrics_port(None) is None
    assert obs_export.resolve_metrics_port(9100) == 9100
    monkeypatch.setenv('PT_METRICS_PORT', '9200')
    assert obs_export.resolve_metrics_port(None) == 9200
    assert obs_export.resolve_metrics_port(0) == 0   # config beats env


# -------------------------------------------------------- memory hooks

def test_memory_hooks_graceful_on_cpu():
    obs_memory._reset_probe()
    obs_memory.on_launch()
    gauges = obs.metrics_snapshot()['gauges']
    # CPU: no memory_stats() -> no HBM gauges, but live buffers always
    assert 'exec.live_buffers' in gauges
    assert gauges['exec.live_buffers'] >= 0
    assert obs_memory.device_memory_stats() is None
    assert obs_memory._STATS_SUPPORTED[0] is False   # cached verdict
    assert obs_memory.host_rss_bytes() > 0


def test_checkpoint_snapshot_host_bytes_accounting(tmp_path):
    import paddle_tpu as fluid
    from paddle_tpu.train import CheckpointConfig, Checkpointer
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[4], dtype='float32')
            fluid.layers.fc(x, 8)
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        ck = Checkpointer(CheckpointConfig(str(tmp_path / 'ckpt'),
                                           handle_signals=False), exe)
        ck.save(0, 0, blocking=True)
    g = obs.metrics_snapshot()['gauges']
    assert g.get('ckpt.snapshot_host_bytes', 0) > 0
    assert _cnt('ckpt.snapshot_bytes_total') >= g['ckpt.snapshot_host_bytes']


# ------------------------------------------ stall attribution (satellite)

def test_stall_suppression_counts_suppressed_not_stall():
    old = obs.stall_threshold_ms()
    obs.set_stall_threshold_ms(50)
    owner = types.SimpleNamespace()
    try:
        stalls0, supp0 = _cnt('executor.stall_count'), \
            _cnt('executor.stall_suppressed')
        obs.on_launch_end(owner, 0.0)
        with obs.stall.suppress('breaker_slow'):
            assert obs.stall.suppressed()
            obs.on_launch_start(owner, 1.0)   # 1000 ms gap, suppressed
        assert not obs.stall.suppressed()
        assert _cnt('executor.stall_count') == stalls0
        assert _cnt('executor.stall_suppressed') == supp0 + 1
        sup = [e for e in obs.recorder().events()
               if e['name'] == 'pipeline.stall_suppressed']
        assert sup and sup[-1]['args']['reason'] == 'breaker_slow'
        # the same gap WITHOUT suppression is a real stall
        obs.on_launch_end(owner, 2.0)
        obs.on_launch_start(owner, 3.0)
        assert _cnt('executor.stall_count') == stalls0 + 1
    finally:
        obs.set_stall_threshold_ms(old)


def test_breaker_slow_path_dispatches_run_suppressed():
    """Satellite pin (fault-injected): while the breaker serves the
    degraded slow path, the dispatch window is marked suppressed so
    backend-side launch gaps don't pollute the stall SLO."""
    faults.configure('serve_dispatch:at=2:times=1')
    seen = []

    def backend(feed):
        seen.append(obs.stall.suppressed())
        x = np.asarray(feed['x'])
        return [x * 2.0]

    eng = ServingEngine(backend,
                        bucketer=FeedBucketer(boundaries=[1, 2]),
                        config=ServingConfig(
                            max_queue=16, breaker_failure_threshold=1,
                            breaker_cooldown_s=30.0))
    eng.start()
    # first wave: dispatch 1 succeeds (normal mode, NOT suppressed),
    # dispatch 2 takes the injected failure and trips the breaker
    assert eng.submit(_feed(1), timeout_s=5.0).result(5).status == 'ok'
    assert eng.submit(_feed(1), timeout_s=5.0).result(5).status == 'error'
    assert eng.breaker.trips >= 1
    # cooldown_s=30 keeps the breaker OPEN: every dispatch from here on
    # is a slow-path batch and must run inside the suppressed window
    futs = [eng.submit(_feed(1), timeout_s=5.0) for _ in range(4)]
    assert eng.stop(timeout=10)
    assert all(f.result(0).status == 'ok' for f in futs)
    assert seen[0] is False           # normal-mode dispatch: not marked
    assert seen[-1] is True           # slow-path dispatch: suppressed
    assert _cnt('executor.stall_suppressed') >= 0


def test_recovery_rollback_clears_stall_window_and_traces():
    from paddle_tpu.train.recovery import RecoveryPolicy
    exe = types.SimpleNamespace(_obs_prev_launch_end=123.0)

    class _Ckpt(object):
        executor = exe

        def restore(self):
            return {'step_id': 7}

    cleared0 = _cnt('executor.stall_windows_cleared')
    pol = RecoveryPolicy(_Ckpt())
    meta = pol.rollback(reason='unit')
    assert meta['step_id'] == 7
    assert exe._obs_prev_launch_end is None
    assert _cnt('executor.stall_windows_cleared') == cleared0 + 1
    roots = [e for e in obs.recorder().events()
             if e['name'] == 'recovery.rollback' and e['ph'] == 'X']
    assert roots and 'trace_id' in roots[-1]['args']


def test_recovery_giveup_dumps_flight(tmp_path, monkeypatch):
    from paddle_tpu.train.recovery import DivergenceError, RecoveryPolicy
    monkeypatch.setenv('PT_FLIGHT_DIR', str(tmp_path))
    exe = types.SimpleNamespace()

    class _Ckpt(object):
        executor = exe

        def restore(self):
            return {'step_id': 1}

    pol = RecoveryPolicy(_Ckpt(), max_retries=1)

    def diverge():
        raise DivergenceError('loss is non-finite')

    assert pol.run(diverge) is None          # first: rollback + skip
    with pytest.raises(DivergenceError):
        pol.run(diverge)                     # second: give up, re-raise
    dumps = [fn for fn in os.listdir(str(tmp_path))
             if 'recovery_giveup' in fn]
    assert dumps
    art = json.load(open(str(tmp_path / dumps[0])))
    assert any(e.get('kind') == 'recovery.giveup' for e in art['events'])


# ------------------------------------------- shared telemetry schema

def test_telemetry_snapshot_rejects_what_the_schema_does_not_declare(
        monkeypatch):
    with pytest.raises(KeyError):
        obs.telemetry_snapshot('no_such_section')
    monkeypatch.setitem(obs_export.SCHEMA, 'drifted',
                        (('x', ('no_such_kind', 'executor.compiles')),))
    with pytest.raises(ValueError, match='unknown telemetry spec kind'):
        obs.telemetry_snapshot('drifted')


def test_telemetry_snapshot_sections_match_schema():
    obs.histogram('serving.latency_ms').observe(5.0)
    obs.counter('serving.admitted').inc(0)
    srv = obs.telemetry_snapshot('serving')
    assert list(srv) == obs_export.schema_keys('serving')
    assert srv['p50_ms'] is not None
    res = obs.telemetry_snapshot('resilience')
    assert set(res['counters']) >= {'faults.injected', 'recovery.rollbacks',
                                    'executor.retraces'}


def test_prom_name_sanitization():
    assert obs_export.prom_name('serving.admitted', '_total') == \
        'serving_admitted_total'
    assert obs_export.prom_name('a-b/c d') == 'a_b_c_d'
    assert obs_export.prom_name('1abc') == '_1abc'
