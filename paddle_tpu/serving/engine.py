"""ServingEngine — continuous batching over the Predictor/AOT-cache/
FeedBucketer stack, built to degrade instead of collapse.

Shape of the thing (docs/serving.md has the full architecture):

  * Clients ``submit()`` single feed dicts (leading dim = rows) and get
    a :class:`ServeFuture`.  Every admitted request is GUARANTEED a
    terminal reply — result, deadline-exceeded, shed, or error — even
    through drain and engine stop; a request that never resolves is a
    bug and is counted as ``serving.deadlocks``.
  * A dedicated dispatch thread coalesces queued requests with the same
    feed signature into one superbatch, pads it onto a FeedBucketer
    boundary (so every batch hits a warm AOT executable), runs the
    backend once, and scatters per-request row slices back out.
  * Admission control happens in the CLIENT's thread, before a request
    costs the dispatcher anything: state gate (draining engines refuse),
    shape sanity (batch=0 and bigger-than-the-largest-bucket requests
    are rejected with a clear error, never truncated), per-request
    deadlines (an already-expired deadline is refused at the door;
    queued requests past deadline are dropped PRE-dispatch — compute is
    never spent on an answer nobody is waiting for), a token-bucket
    rate limiter, and a bounded queue with a configurable overflow
    policy (``reject`` / ``block`` / ``shed_oldest``).
  * A :class:`~paddle_tpu.serving.breaker.CircuitBreaker` trips on
    consecutive batch failures or compile-miss storms and flips the
    engine to a one-request-at-a-time slow path until a probe batch
    succeeds; health moves ``STARTING → READY → (DEGRADED) → DRAINING
    → STOPPED``, and SIGTERM begins a drain that finishes in-flight
    work while refusing new requests (chained with the PR-6 checkpoint
    flush handlers via core/signals.py).

Chaos-tested: the ``serve_dispatch`` / ``serve_slow_batch`` /
``queue_overflow`` / ``compile_storm`` PT_FAULT sites break each layer
deterministically, and ``tools/serve_soak.py`` asserts the SLOs while
they fire.
"""
import collections
import contextlib
import signal as _sigmod
import threading
import time

import numpy as np

from .. import observability as _obs
from ..observability import flight as _flight
from ..observability import trace_context as _tc
from ..core import signals as _signals
from ..testing import faults as _faults
from .admission import OVERFLOW_POLICIES, TokenBucket
from .breaker import CLOSED, CircuitBreaker

__all__ = ['ServingConfig', 'ServingEngine', 'ServeFuture', 'ServeResult',
           'STARTING', 'READY', 'DEGRADED', 'DRAINING', 'STOPPED',
           'OK', 'REJECTED', 'SHED', 'DEADLINE_EXCEEDED', 'ERROR']

# engine health states
STARTING, READY, DEGRADED = 'starting', 'ready', 'degraded'
DRAINING, STOPPED = 'draining', 'stopped'
_STATE_GAUGE = {STARTING: 0, READY: 1, DEGRADED: 2, DRAINING: 3, STOPPED: 4}

# terminal reply statuses
OK, REJECTED, SHED = 'ok', 'rejected', 'shed'
DEADLINE_EXCEEDED, ERROR = 'deadline_exceeded', 'error'


class ServingConfig(object):
    """Knobs for one engine.  Everything has a serving-shaped default;
    the env-var table lives in docs/serving.md."""

    def __init__(self, max_queue=64, overflow_policy='reject',
                 block_timeout_s=1.0, max_batch_rows=64,
                 batch_linger_s=0.0, default_timeout_s=None,
                 rate_qps=None, rate_burst=None,
                 breaker_failure_threshold=3, breaker_storm_threshold=3,
                 breaker_cooldown_s=0.25, drain_timeout_s=10.0,
                 metrics_port=None):
        if overflow_policy not in OVERFLOW_POLICIES:
            raise ValueError('overflow_policy must be one of %s, got %r'
                             % (OVERFLOW_POLICIES, overflow_policy))
        if int(max_queue) < 1:
            raise ValueError('max_queue must be >= 1')
        if int(max_batch_rows) < 1:
            raise ValueError('max_batch_rows must be >= 1')
        self.max_queue = int(max_queue)
        self.overflow_policy = overflow_policy
        self.block_timeout_s = float(block_timeout_s)
        self.max_batch_rows = int(max_batch_rows)
        self.batch_linger_s = float(batch_linger_s)
        self.default_timeout_s = default_timeout_s
        self.rate_qps = rate_qps
        self.rate_burst = rate_burst
        self.breaker_failure_threshold = int(breaker_failure_threshold)
        self.breaker_storm_threshold = int(breaker_storm_threshold)
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        self.drain_timeout_s = float(drain_timeout_s)
        # /metrics endpoint port: explicit int beats PT_METRICS_PORT
        # (0 = ephemeral, for tests); None + no env = no server
        self.metrics_port = metrics_port


class ServeResult(object):
    """One terminal reply.  ``status`` is one of ``ok`` / ``rejected`` /
    ``shed`` / ``deadline_exceeded`` / ``error``; ``outputs`` is the
    per-request list of fetch arrays (``ok`` only); ``error`` carries
    the exception (``error``) or a human-readable refusal message
    (``rejected`` / ``shed``); ``reason`` is the machine-readable
    refusal tag mirrored in ``serving.rejected.<reason>``."""
    __slots__ = ('status', 'outputs', 'error', 'reason', 'latency_s')

    def __init__(self, status, outputs=None, error=None, reason=None,
                 latency_s=None):
        self.status = status
        self.outputs = outputs
        self.error = error
        self.reason = reason
        self.latency_s = latency_s

    @property
    def ok(self):
        return self.status == OK

    def __repr__(self):
        return ('ServeResult(%s%s%s)'
                % (self.status,
                   ', reason=%r' % self.reason if self.reason else '',
                   ', latency=%.1fms' % (self.latency_s * 1e3)
                   if self.latency_s is not None else ''))


class ServeFuture(object):
    """Client handle: blocks in ``result()`` until the terminal reply.
    ``traceparent`` is the request's W3C trace header (None with
    PT_OBS=0) — the id to look up in a Perfetto export."""
    __slots__ = ('_event', '_result', '_lock', 'traceparent')

    def __init__(self):
        self._event = threading.Event()
        self._result = None
        self._lock = threading.Lock()
        self.traceparent = None

    def _resolve(self, result):
        with self._lock:
            if self._result is not None:
                return False
            self._result = result
        self._event.set()
        return True

    def done(self):
        return self._event.is_set()

    def result(self, timeout=None):
        if not self._event.wait(timeout):
            raise TimeoutError('serving reply not ready within %r s'
                               % timeout)
        return self._result

    @property
    def status(self):
        return self._result.status if self._result is not None else None


class _Request(object):
    __slots__ = ('feed', 'rows', 'signature', 'deadline', 'future',
                 't_submit', 'trace', 't_pc')

    def __init__(self, feed, rows, signature, deadline, t_submit,
                 trace=None, t_pc=None):
        self.feed = feed
        self.rows = rows
        self.signature = signature
        self.deadline = deadline
        self.future = ServeFuture()
        self.t_submit = t_submit
        # tracing: the request's root TraceContext and the perf_counter
        # submit mark its spans measure from (both None with PT_OBS=0)
        self.trace = trace
        self.t_pc = t_pc
        if trace is not None:
            self.future.traceparent = trace.to_traceparent()


class ServingEngine(object):
    """See module docstring.  ``backend`` is any callable
    ``feed_dict -> list of per-row output arrays`` — usually a
    :class:`~paddle_tpu.inference.Predictor` (whose per-shape AOT cache
    + single-flight compile lock this engine was built around), but a
    plain function works, which is how the unit tests chaos-test the
    engine without compiling anything."""

    def __init__(self, backend, bucketer=None, config=None,
                 clock=time.monotonic):
        self._backend = backend
        self._bucketer = bucketer
        self._cfg = config or ServingConfig()
        self._clock = clock
        self._cond = threading.Condition()
        self._queue = collections.deque()
        self._state = STARTING
        self._stopping = False
        self._thread = None
        self._stopped = threading.Event()
        self._out_lock = threading.Lock()
        self._outstanding = set()
        self._rate = (TokenBucket(self._cfg.rate_qps, self._cfg.rate_burst,
                                  clock=clock)
                      if self._cfg.rate_qps else None)
        self.breaker = CircuitBreaker(
            failure_threshold=self._cfg.breaker_failure_threshold,
            storm_threshold=self._cfg.breaker_storm_threshold,
            cooldown_s=self._cfg.breaker_cooldown_s, clock=clock)
        # the hard per-request size ceiling: a request that cannot fit
        # the largest bucket would silently retrace per shape (or worse,
        # invite truncation); refuse it at the door instead
        self._row_limit = self._cfg.max_batch_rows
        if bucketer is not None:
            self._row_limit = min(self._row_limit,
                                  int(bucketer.boundaries[-1]))
        self._http = None
        _obs.metrics.gauge('serving.state').set(_STATE_GAUGE[STARTING])

    @classmethod
    def from_predictor(cls, predictor, bucketer=None, config=None, **kw):
        eng = cls(predictor.run, bucketer=bucketer, config=config, **kw)
        eng._predictor = predictor
        return eng

    # ----------------------------------------------------------- state
    def _set_state(self, state):
        self._state = state
        _obs.metrics.gauge('serving.state').set(_STATE_GAUGE[state])
        _obs.tracing.instant('serving.state', cat='serving',
                             args={'state': state})

    @property
    def state(self):
        """Health state; READY shows as DEGRADED while the breaker is
        not closed (still serving, but on the slow path)."""
        with self._cond:
            s = self._state
        if s == READY and self.breaker.state != CLOSED:
            return DEGRADED
        return s

    def ready(self):
        """Readiness probe: accepting new requests?"""
        return self.state in (READY, DEGRADED)

    def health(self):
        with self._cond:
            depth = len(self._queue)
        with self._out_lock:
            outstanding = len(self._outstanding)
        return {'state': self.state, 'queue_depth': depth,
                'outstanding': outstanding, 'breaker': self.breaker.state,
                'accepting': self.ready()}

    # ----------------------------------------------------- lifecycle
    def start(self):
        with self._cond:
            if self._thread is not None:
                return self
            self._thread = threading.Thread(target=self._loop,
                                            name='ServingDispatch',
                                            daemon=True)
            self._set_state(READY)
            self._thread.start()
        self._start_metrics_server()
        return self

    def _start_metrics_server(self):
        """/metrics + /healthz + /varz, engine-owned: up at start(),
        down at stop().  Enabled by ServingConfig.metrics_port or
        PT_METRICS_PORT; inert under PT_OBS=0."""
        if self._http is not None or not _obs.enabled():
            return
        port = _obs.export.resolve_metrics_port(self._cfg.metrics_port)
        if port is None:
            return
        self._http = _obs.export.start_http_server(port, engine=self)

    @property
    def metrics_port(self):
        """Bound /metrics port, or None when no server is running."""
        return self._http.port if self._http is not None else None

    def stop_metrics_server(self):
        http, self._http = self._http, None
        if http is not None:
            http.stop()

    def begin_drain(self):
        """Refuse new requests, keep dispatching until the queue is
        empty, then stop.  Non-blocking (signal-handler safe)."""
        with self._cond:
            if self._state in (DRAINING, STOPPED):
                return
            started = self._thread is not None
            self._set_state(DRAINING)
            self._cond.notify_all()
        if not started:
            self._finish_stop()

    def wait_drained(self, timeout=None):
        return self._stopped.wait(timeout)

    def drain(self, timeout=None):
        """begin_drain + wait; returns True when fully stopped."""
        self.begin_drain()
        ok = self.wait_drained(self._cfg.drain_timeout_s
                               if timeout is None else timeout)
        if self._thread is not None:
            self._thread.join(timeout=1.0)
        return ok

    def stop(self, timeout=None):
        """Drain, then force the dispatch loop down if the drain budget
        expires — leftover queued requests still get terminal (shed)
        replies."""
        self.begin_drain()
        budget = self._cfg.drain_timeout_s if timeout is None else timeout
        if not self.wait_drained(budget):
            with self._cond:
                self._stopping = True
                self._cond.notify_all()
            self.wait_drained(5.0)
        if self._thread is not None:
            self._thread.join(timeout=1.0)
        self.stop_metrics_server()
        return self._stopped.is_set()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def install_signal_handlers(self, signums=(_sigmod.SIGTERM,)):
        """SIGTERM → graceful drain: in-flight and queued requests
        finish, new ones are refused, then the previous handler (e.g.
        the Checkpointer's final flush) runs via the core/signals chain.
        With no previous handler the signal is NOT re-delivered — the
        process is expected to exit once ``wait_drained()`` returns.
        Idempotent and main-thread-guarded (worker threads warn once
        and skip)."""

        def make(signum, prev):
            def _handler(s, frame):
                _obs.metrics.counter('serving.signal_drains').inc()
                _flight.record('serving.signal_drain', signum=int(s))
                self.begin_drain()
                _flight.maybe_dump('sigterm')
                _signals.chain_previous(prev, s, frame, redeliver=False)
            return _handler

        return _signals.install(('serving', id(self)), signums,
                                make) is not None

    def uninstall_signal_handlers(self):
        _signals.uninstall(('serving', id(self)))

    # ----------------------------------------------------- admission
    def submit(self, feed, timeout_s=None):
        """Submit one request (dict name -> array with a leading batch
        dim).  Always returns a :class:`ServeFuture`; refusals come back
        as an already-terminal ``rejected`` result with a named reason,
        never an exception and never silence."""
        t_submit = self._clock()
        obs_on = _obs.enabled()
        trace = _tc.TraceContext.new() if obs_on else None
        t_pc = time.perf_counter() if obs_on else None
        _obs.metrics.counter('serving.submitted').inc()
        try:
            arrays = {k: np.asarray(v) for k, v in dict(feed).items()}
        except Exception as e:
            return self._rejected(t_submit, 'bad_request',
                                  'unfeedable request: %r' % (e,),
                                  trace, t_pc)
        if not arrays:
            return self._rejected(t_submit, 'bad_request',
                                  'empty feed dict', trace, t_pc)
        dims = {a.shape[0] for a in arrays.values() if a.ndim >= 1}
        if len(dims) != 1 or any(a.ndim == 0 for a in arrays.values()):
            return self._rejected(
                t_submit, 'bad_request',
                'request feeds need one shared leading batch dim; got '
                'shapes %s' % {k: a.shape for k, a in arrays.items()},
                trace, t_pc)
        rows = dims.pop()
        if rows == 0:
            return self._rejected(
                t_submit, 'empty_batch',
                'batch=0 request rejected: a serving request must carry '
                'at least one row (got leading dim 0)', trace, t_pc)
        if rows > self._row_limit:
            return self._rejected(
                t_submit, 'too_large',
                'request batch %d exceeds the serving limit %d (largest '
                'bucket boundary / max_batch_rows); split the request — '
                'nothing is silently truncated' % (rows, self._row_limit),
                trace, t_pc)
        if timeout_s is None:
            timeout_s = self._cfg.default_timeout_s
        deadline = None
        if timeout_s is not None:
            if timeout_s <= 0:
                return self._rejected(
                    t_submit, 'deadline',
                    'deadline already expired at admission '
                    '(timeout_s=%r)' % timeout_s, trace, t_pc)
            deadline = t_submit + float(timeout_s)
        if self._rate is not None and not self._rate.try_acquire():
            return self._rejected(t_submit, 'rate',
                                  'token-bucket rate limit exceeded '
                                  '(rate_qps=%r)' % self._cfg.rate_qps,
                                  trace, t_pc)
        signature = tuple(sorted((k, str(a.dtype), a.shape[1:])
                                 for k, a in arrays.items()))
        req = _Request(arrays, int(rows), signature, deadline, t_submit,
                       trace=trace, t_pc=t_pc)
        fut = self._admit(req, t_submit)
        if trace is not None:
            # the caller-thread slice the Perfetto flow arrow starts
            # from; the matching 'f' binds to the batch slice
            t_now = time.perf_counter()
            _obs.tracing.recorder().add_complete(
                'serving.submit', t_pc, t_now, cat='serving',
                args=trace.span_args(rows=int(rows)))
            _obs.tracing.add_flow(trace.trace_id[:16], 's', t_pc,
                                  name='serving.link', cat='serving')
        return fut

    def _admit(self, req, t_submit):
        cfg = self._cfg
        with self._cond:
            if self._state != READY:
                reason = ('not_ready' if self._state == STARTING
                          else 'draining')
                return self._rejected_locked(
                    req, reason, 'engine is %s; request refused'
                    % self._state)
            overflow = len(self._queue) >= cfg.max_queue
            if not overflow and _faults.any_active() \
                    and _faults.fire('queue_overflow'):
                overflow = True
            if overflow and cfg.overflow_policy == 'block':
                limit = t_submit + cfg.block_timeout_s
                while len(self._queue) >= cfg.max_queue \
                        and self._state == READY:
                    left = limit - self._clock()
                    if left <= 0:
                        break
                    self._cond.wait(left)
                if self._state != READY:
                    return self._rejected_locked(
                        req, 'draining', 'engine began draining while '
                        'blocked on a full queue')
                overflow = len(self._queue) >= cfg.max_queue
            shed_req = None
            if overflow:
                if cfg.overflow_policy == 'shed_oldest' and self._queue:
                    shed_req = self._queue.popleft()
                elif cfg.overflow_policy != 'shed_oldest':
                    return self._rejected_locked(
                        req, 'full', 'request queue full '
                        '(max_queue=%d, policy=%s)'
                        % (cfg.max_queue, cfg.overflow_policy))
            self._queue.append(req)
            with self._out_lock:
                self._outstanding.add(req)
            _obs.metrics.counter('serving.admitted').inc()
            _obs.metrics.gauge('serving.queue_depth').set(len(self._queue))
            self._cond.notify_all()
        if shed_req is not None:
            self._resolve(shed_req, SHED, reason='overflow',
                          error='shed: newest request displaced the '
                                'oldest queued one (shed_oldest policy)')
        return req.future

    def _emit_request_spans(self, req, status, reason):
        """What one request leaves in the trace at its terminal reply: the
        root span (a subclass adds the phases that tile it)."""
        self._emit_root_span(req.trace, req.t_pc, status, reason=reason,
                             rows=req.rows)

    def _emit_root_span(self, trace, t_pc, status, reason=None, rows=None,
                        t_end=None):
        """The request's single root span, `serving.request` — emitted
        exactly once, at terminal resolution, so its status IS the
        terminal reply's status.  `t_end` (perf_counter) lets the caller
        end it on the reading its last child ended on."""
        if trace is None or t_pc is None:
            return
        args = trace.span_args(status=status)
        if reason:
            args['reason'] = reason
        if rows is not None:
            args['rows'] = int(rows)
        _obs.tracing.recorder().add_complete(
            'serving.request', t_pc,
            time.perf_counter() if t_end is None else t_end, cat='serving',
            args=args)

    def _rejected(self, t_submit, reason, message, trace=None, t_pc=None):
        fut = ServeFuture()
        if trace is not None:
            fut.traceparent = trace.to_traceparent()
        fut._resolve(ServeResult(REJECTED, error=message, reason=reason,
                                 latency_s=self._clock() - t_submit))
        _obs.metrics.counter('serving.rejected').inc()
        _obs.metrics.counter('serving.rejected.%s' % reason).inc()
        self._emit_root_span(trace, t_pc, REJECTED, reason=reason)
        return fut

    def _rejected_locked(self, req, reason, message):
        # admission refusals for an already-built request (still not in
        # the queue/outstanding set, so plain reject accounting applies)
        fut = req.future
        fut._resolve(ServeResult(REJECTED, error=message, reason=reason,
                                 latency_s=self._clock() - req.t_submit))
        _obs.metrics.counter('serving.rejected').inc()
        _obs.metrics.counter('serving.rejected.%s' % reason).inc()
        self._emit_root_span(req.trace, req.t_pc, REJECTED, reason=reason,
                             rows=req.rows)
        return fut

    def infer(self, feed, timeout_s=None, wait_timeout=None):
        """Blocking convenience: ``submit().result()``."""
        return self.submit(feed, timeout_s=timeout_s).result(wait_timeout)

    # ----------------------------------------------------- dispatch
    def _loop(self):
        try:
            while True:
                expired, batch, mode = self._next_batch()
                for r in expired:
                    self._resolve(
                        r, DEADLINE_EXCEEDED, reason='queue_wait',
                        error='deadline expired while queued; dropped '
                              'pre-dispatch (no compute was spent)')
                if batch is None:
                    return
                if batch:
                    self._run_batch(batch, mode)
        finally:
            self._finish_stop()

    def _next_batch(self):
        """Returns (expired_requests, batch|None, mode); batch None means
        the loop should exit (drained or force-stopped)."""
        cfg = self._cfg
        with self._cond:
            while True:
                if self._stopping:
                    return [], None, None
                if self._queue:
                    break
                if self._state == DRAINING:
                    return [], None, None
                self._cond.wait(0.05)
            if cfg.batch_linger_s > 0 and self._state == READY \
                    and sum(r.rows for r in self._queue) \
                    < cfg.max_batch_rows:
                self._cond.wait(cfg.batch_linger_s)
            now = self._clock()
            expired = [r for r in self._queue
                       if r.deadline is not None and r.deadline <= now]
            if expired:
                gone = set(map(id, expired))
                self._queue = collections.deque(
                    r for r in self._queue if id(r) not in gone)
            mode = self.breaker.mode()
            batch, taken_rows = [], 0
            if self._queue:
                if mode == 'slow':
                    batch.append(self._queue.popleft())
                else:
                    sig = self._queue[0].signature
                    keep = collections.deque()
                    for r in self._queue:
                        if r.signature == sig \
                                and taken_rows + r.rows \
                                <= cfg.max_batch_rows:
                            batch.append(r)
                            taken_rows += r.rows
                        else:
                            keep.append(r)
                    self._queue = keep
            _obs.metrics.gauge('serving.queue_depth').set(len(self._queue))
            self._cond.notify_all()   # wake blocked submitters
        return expired, batch, mode

    def _compile_marks(self):
        if not _obs.enabled():
            return 0
        c = _obs.metrics.counters()
        return sum(int(c.get(k) or 0)
                   for k in ('executor.compiles', 'executor.retraces',
                             'compile_cache.disk_misses'))

    def _emit_batch_span(self, batch, batch_ctx, t0, t_end, mode,
                         total_rows, pad_rows, cold, status):
        """The `serving.batch` span: one per dispatch, *linking* every
        coalesced request's trace (args.links + a flow 'f' per request),
        so a Perfetto export walks request root -> batch -> executor."""
        rec = _obs.tracing.recorder()
        args = batch_ctx.span_args(
            links=[r.trace.trace_id for r in batch if r.trace is not None],
            requests=len(batch), rows=int(total_rows),
            pad_rows=int(pad_rows), mode=mode or 'normal',
            cold=bool(cold), status=status)
        rec.add_complete('serving.batch', t0, t_end, cat='serving',
                         args=args)
        for r in batch:
            if r.trace is not None:
                rec.add_flow(r.trace.trace_id[:16], 'f', t0,
                             name='serving.link', cat='serving')

    def _run_batch(self, batch, mode):
        t0 = time.perf_counter()
        now = self._clock()
        obs_on = _obs.enabled()
        batch_ctx = _tc.TraceContext.new() if obs_on else None
        for r in batch:
            _obs.metrics.histogram('serving.queue_wait_ms').observe(
                max(0.0, (now - r.t_submit) * 1e3))
            if batch_ctx is not None and r.trace is not None:
                # queue-wait child: submit -> dispatch pick
                _obs.tracing.recorder().add_complete(
                    'serving.queue_wait', r.t_pc, t0, cat='serving',
                    args={'trace_id': r.trace.trace_id,
                          'parent_span_id': r.trace.span_id,
                          'batch_span_id': batch_ctx.span_id})
        total_rows = sum(r.rows for r in batch)
        cold = False
        if _faults.any_active():
            _faults.maybe_sleep('serve_slow_batch')
            if _faults.maybe_sleep('compile_storm'):
                cold = True
        marks = self._compile_marks()
        if len(batch) == 1:
            feed = batch[0].feed
        else:
            feed = {k: np.concatenate([r.feed[k] for r in batch])
                    for k in batch[0].feed}
        if self._bucketer is not None:
            feed, _true = self._bucketer.bucket_feed(feed)
        pad_rows = 0
        for a in feed.values():
            if getattr(a, 'ndim', 0) >= 1:
                pad_rows = max(0, int(a.shape[0]) - total_rows)
                break
        t_dev0 = time.perf_counter()
        if batch_ctx is not None:
            for r in batch:
                if r.trace is not None:
                    # dispatch child: coalesce + pad onto the bucket
                    _obs.tracing.recorder().add_complete(
                        'serving.dispatch', t0, t_dev0, cat='serving',
                        args={'trace_id': r.trace.trace_id,
                              'parent_span_id': r.trace.span_id,
                              'batch_span_id': batch_ctx.span_id,
                              'pad_rows': int(pad_rows)})
        try:
            if _faults.any_active():
                _faults.maybe_fail('serve_dispatch')
            with contextlib.ExitStack() as ctxs:
                if batch_ctx is not None:
                    # executor/predictor spans under this dispatch join
                    # the batch trace via the ambient context
                    ctxs.enter_context(_tc.use(batch_ctx))
                if mode in ('slow', 'probe'):
                    # degraded-mode dispatches are intentionally slow —
                    # their launch gaps are not pipeline stalls
                    ctxs.enter_context(
                        _obs.stall.suppress('breaker_%s' % mode))
                outs = self._backend(feed)
        except BaseException as e:  # noqa: BLE001 - replied per request
            self.breaker.record_failure()
            _obs.metrics.counter('serving.batch_failures').inc()
            t_fail = time.perf_counter()
            if batch_ctx is not None:
                self._emit_device_spans(batch, batch_ctx, t_dev0, t_fail)
                self._emit_batch_span(batch, batch_ctx, t0, t_fail, mode,
                                      total_rows, pad_rows, cold, ERROR)
            _flight.record('serving.batch_failure', error=repr(e)[:300],
                           rows=int(total_rows), requests=len(batch),
                           mode=mode or 'normal')
            for r in batch:
                self._resolve(r, ERROR, error=e, reason='dispatch')
            _flight.maybe_dump('serving_batch_failure')
            return
        t_dev1 = time.perf_counter()
        if self._compile_marks() > marks:
            cold = True
        if cold:
            _obs.metrics.counter('serving.cold_compiles').inc()
            self.breaker.record_cold()
        self.breaker.record_success(cold=cold)
        outs = [np.asarray(o) for o in outs]
        if batch_ctx is not None:
            self._emit_device_spans(batch, batch_ctx, t_dev0, t_dev1)
        # scatter: per-row outputs slice back to their request; outputs
        # without the batch leading dim (batch-aggregate fetches) are
        # handed to every request whole
        off = 0
        for r in batch:
            slices = []
            for o in outs:
                if o.ndim >= 1 and o.shape[0] >= total_rows:
                    slices.append(o[off:off + r.rows])
                else:
                    slices.append(o)
            off += r.rows
            self._resolve(r, OK, outputs=slices)
        _obs.metrics.counter('serving.batches').inc()
        if mode == 'slow':
            _obs.metrics.counter('serving.slow_path_batches').inc()
        _obs.metrics.histogram('serving.batch_rows').observe(total_rows)
        t_end = time.perf_counter()
        if batch_ctx is not None:
            self._emit_batch_span(batch, batch_ctx, t0, t_end, mode,
                                  total_rows, pad_rows, cold, OK)
        _obs.metrics.histogram('serving.batch_ms').observe(
            (t_end - t0) * 1e3)

    def _emit_device_spans(self, batch, batch_ctx, t_dev0, t_dev1):
        """Per-request `serving.device` child: the backend-call window
        (compile miss + device time) the request rode in."""
        rec = _obs.tracing.recorder()
        for r in batch:
            if r.trace is not None:
                rec.add_complete(
                    'serving.device', t_dev0, t_dev1, cat='serving',
                    args={'trace_id': r.trace.trace_id,
                          'parent_span_id': r.trace.span_id,
                          'batch_span_id': batch_ctx.span_id})

    # ----------------------------------------------------- resolution
    def _resolve(self, req, status, outputs=None, error=None, reason=None):
        res = ServeResult(status, outputs=outputs, error=error,
                          reason=reason,
                          latency_s=self._clock() - req.t_submit)
        if not req.future._resolve(res):
            return
        with self._out_lock:
            self._outstanding.discard(req)
        # exactly one root span per request, status = the terminal reply
        self._emit_request_spans(req, status, reason)
        if status == OK:
            _obs.metrics.counter('serving.completed').inc()
            _obs.metrics.histogram('serving.latency_ms').observe(
                res.latency_s * 1e3)
        elif status == SHED:
            _obs.metrics.counter('serving.shed').inc()
        elif status == DEADLINE_EXCEEDED:
            _obs.metrics.counter('serving.deadline_exceeded').inc()
        elif status == ERROR:
            _obs.metrics.counter('serving.errors').inc()
        elif status == REJECTED:
            _obs.metrics.counter('serving.rejected').inc()
            if reason:
                _obs.metrics.counter('serving.rejected.%s' % reason).inc()

    def _finish_stop(self):
        with self._cond:
            leftovers = list(self._queue)
            self._queue.clear()
            self._set_state(STOPPED)
            self._cond.notify_all()
        for r in leftovers:
            self._resolve(r, SHED, reason='shutdown',
                          error='engine stopped before dispatch; request '
                                'shed during shutdown')
        # the deadlock audit: every admitted request was either batched
        # (resolved by _run_batch), expired (resolved by the loop), or a
        # leftover (just shed).  Anything still outstanding fell through
        # a crack — give it a terminal reply and make the bug loud.
        with self._out_lock:
            stragglers = list(self._outstanding)
            self._outstanding.clear()
        for r in stragglers:
            _obs.metrics.counter('serving.deadlocks').inc()
            self._resolve(r, ERROR, reason='deadlock',
                          error='engine stopped with this request '
                                'unresolved — serving bug (counted in '
                                'serving.deadlocks)')
        self._stopped.set()
