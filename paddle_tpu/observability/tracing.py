"""Span/event recorder emitting Chrome-trace / Perfetto-compatible JSON.

Reference parity: Fluid's profiler writes a chrome-tracing timeline
(`python/paddle/fluid/profiler.py` + tools/timeline.py); here the
recorder is in-process and always-on-cheap — spans are plain dicts in a
bounded deque, exported on demand as a `{"traceEvents": [...]}` file
that loads directly in Perfetto (ui.perfetto.dev) or chrome://tracing.

Timestamps are microseconds relative to a per-process perf_counter
epoch, so `ts` is monotonic and durations are wall-accurate; events are
sorted by `ts` at export time (completion order != start order for
nested spans).

`span(name)` is on two timelines at once: it records into the recorder
AND enters a `jax.profiler.TraceAnnotation('pt:' + name)`, so an xplane
taken by anyone (`paddle_tpu.profiler`, a benchmark's own
`jax.profiler.start_trace`) holds the program's spans on the host
plane, on the profiler's clock, beside the device's `XLA Ops`
(observability/timeline.py reads them back).
"""
import json
import os
import threading
import time
from collections import deque

from .metrics import counter as _counter, enabled
from . import trace_context as _tc

__all__ = ['TraceRecorder', 'recorder', 'span', 'instant', 'add_span',
           'add_flow', 'export_chrome_trace', 'span_summary', 'reset',
           'set_tap']

# Optional event tap (the flight recorder's feed).  One slot, called
# outside the recorder lock with the already-built event dict.
_TAP = [None]


def set_tap(fn):
    """Install `fn(event_dict)` to observe every recorded event; pass
    None to remove.  Returns the previous tap."""
    prev = _TAP[0]
    _TAP[0] = fn
    return prev


def _attach_ctx(args):
    """Merge the ambient TraceContext (if any) into span args so spans
    recorded deep in the stack (executor, compile pipeline) join the
    request trace that dispatched them."""
    ctx = _tc.current()
    if ctx is None:
        return args
    if args is None:
        return {'trace_id': ctx.trace_id, 'parent_span_id': ctx.span_id}
    if 'trace_id' in args:
        return args
    args = dict(args)
    args['trace_id'] = ctx.trace_id
    args['parent_span_id'] = ctx.span_id
    return args

_EPOCH = time.perf_counter()
_PID = os.getpid()
_MAX_EVENTS = int(os.environ.get('PT_OBS_MAX_EVENTS', '200000'))


def _us(pc_seconds):
    """perf_counter seconds -> microseconds since the recorder epoch."""
    return (pc_seconds - _EPOCH) * 1e6


class TraceRecorder(object):
    def __init__(self, max_events=_MAX_EVENTS):
        self._lock = threading.Lock()
        self._events = deque(maxlen=max_events)
        self._dropped = 0

    def add_complete(self, name, start_pc, end_pc, cat='runtime', args=None):
        """One 'X' (complete) event spanning [start_pc, end_pc] — raw
        time.perf_counter() values."""
        args = _attach_ctx(args)
        ev = {'name': name, 'ph': 'X', 'cat': cat,
              'ts': _us(start_pc), 'dur': max(0.0, (end_pc - start_pc) * 1e6),
              'pid': _PID, 'tid': threading.get_ident()}
        if args:
            ev['args'] = args
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1
            self._events.append(ev)
        tap = _TAP[0]
        if tap is not None:
            tap(ev)

    def add_instant(self, name, cat='runtime', args=None):
        args = _attach_ctx(args)
        ev = {'name': name, 'ph': 'i', 's': 't', 'cat': cat,
              'ts': _us(time.perf_counter()),
              'pid': _PID, 'tid': threading.get_ident()}
        if args:
            ev['args'] = args
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1
            self._events.append(ev)
        tap = _TAP[0]
        if tap is not None:
            tap(ev)

    def add_flow(self, flow_id, phase, ts_pc, name='link', cat='flow'):
        """Flow ('s' start / 'f' finish) event — the Perfetto arrow
        linking a request's submit-side slice to its batch slice."""
        ev = {'name': name, 'ph': 's' if phase == 's' else 'f',
              'id': flow_id, 'cat': cat, 'ts': _us(ts_pc),
              'pid': _PID, 'tid': threading.get_ident()}
        if ev['ph'] == 'f':
            ev['bp'] = 'e'
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1
            self._events.append(ev)

    def events(self):
        with self._lock:
            evs = list(self._events)
        return sorted(evs, key=lambda e: e['ts'])

    def event_count(self):
        with self._lock:
            return len(self._events)

    def export(self, path):
        """Write Chrome-trace JSON (Perfetto-loadable).  Returns the path."""
        payload = {'traceEvents': self.events(), 'displayTimeUnit': 'ms'}
        if self._dropped:
            payload['otherData'] = {'dropped_events': self._dropped}
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, 'w') as f:
            json.dump(payload, f)
        return path

    def summary(self):
        """Aggregate complete events by name:
        {name: {calls, total_us, min_us, max_us, ave_us}} — the table
        behind profiler.profiler(sorted_key=...)."""
        agg = {}
        for ev in self.events():
            if ev['ph'] != 'X':
                continue
            s = agg.setdefault(ev['name'], {'calls': 0, 'total_us': 0.0,
                                            'min_us': None, 'max_us': 0.0})
            d = ev['dur']
            s['calls'] += 1
            s['total_us'] += d
            s['min_us'] = d if s['min_us'] is None else min(s['min_us'], d)
            s['max_us'] = max(s['max_us'], d)
        for s in agg.values():
            s['ave_us'] = s['total_us'] / s['calls']
        return agg

    def reset(self):
        with self._lock:
            self._events.clear()
            self._dropped = 0


_RECORDER = TraceRecorder()


def recorder():
    return _RECORDER


ANNOTATION_PREFIX = 'pt:'
_ANNOTATION = [None]     # jax.profiler.TraceAnnotation, imported on first use


def _annotation(name):
    cls = _ANNOTATION[0]
    if cls is None:
        from jax.profiler import TraceAnnotation as cls
        _ANNOTATION[0] = cls
    return cls(ANNOTATION_PREFIX + name)


class span(object):
    """Record a complete event around the with-block AND annotate the
    profiler's host timeline with ``pt:<name>`` for its duration (a
    no-op when telemetry is disabled).

    ``with span(...) as sp`` hands the block its own handle: ``sp.args``
    may gain keys and ``sp.name`` may change before the block ends (the
    recorder takes both at exit; the annotation keeps the name it was
    entered with), and after the block ``sp.seconds`` is the duration on
    the span's clock, so a counter fed from it agrees with the span.
    ``counter='x.phase_s'`` names the seconds counter of a PHASE: it is
    looked up at exit and moved by exactly ``sp.seconds``."""
    __slots__ = ('name', 'cat', 'args', 'counter', 't0', 't1', '_ann')

    def __init__(self, name, cat='runtime', counter=None, **args):
        self.name = name
        self.cat = cat
        self.args = args
        self.counter = counter
        self.t0 = self.t1 = None
        self._ann = None

    def __enter__(self):
        if enabled():
            self._ann = _annotation(self.name)
            self._ann.__enter__()
            self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self.t0 is None:
            return False
        self.t1 = time.perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        _RECORDER.add_complete(self.name, self.t0, self.t1, self.cat,
                               self.args or None)
        if self.counter is not None:
            _counter(self.counter).inc(self.t1 - self.t0)
        return False

    @property
    def seconds(self):
        """Duration of the finished block; 0.0 when telemetry was off."""
        return 0.0 if self.t1 is None else self.t1 - self.t0


def add_span(name, start_pc, end_pc, cat='runtime', args=None):
    if enabled():
        _RECORDER.add_complete(name, start_pc, end_pc, cat, args)


def instant(name, cat='runtime', args=None):
    if enabled():
        _RECORDER.add_instant(name, cat, args)


def add_flow(flow_id, phase, ts_pc, name='link', cat='flow'):
    if enabled():
        _RECORDER.add_flow(flow_id, phase, ts_pc, name, cat)


def export_chrome_trace(path):
    return _RECORDER.export(path)


def span_summary():
    return _RECORDER.summary()


def reset():
    _RECORDER.reset()
