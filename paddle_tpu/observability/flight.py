"""Black-box flight recorder: a bounded, lock-cheap ring of recent
telemetry, dumped as one JSON artifact when something dies.

The ring is fed two ways: a tap on the trace recorder mirrors every
span/instant (fault injections, breaker transitions, stalls, retraces
and lint events all already flow through tracing), and subsystems can
`record()` explicit structured events (dump triggers, health
transitions).  Appends are bare `deque.append` calls — no lock on the
hot path, bounded by `PT_FLIGHT_EVENTS` (default 4096).

A dump (`dump()` / `maybe_dump()`) writes the ring plus a full metrics
snapshot, retrace reports, and the PT_* environment to
`$PT_FLIGHT_DIR/flight_<pid>_<seq>_<reason>.json` (atomic tmp+rename).
`maybe_dump` is the trigger every crash path calls — it no-ops unless
`PT_FLIGHT_DIR` is set and telemetry is enabled, so unit tests and
library users never get surprise files.  Trigger sites: serving batch
failure, circuit-breaker trip, recovery give-up re-raise, SIGTERM
drain, soak watchdog fire, and the `install()` excepthook for
uncaught crashes in soak tools.
"""
import json
import os
import sys
import threading
import time
from collections import deque

from . import metrics
from . import retrace
from . import tracing

__all__ = ['FlightRecorder', 'flight', 'record', 'dump', 'maybe_dump',
           'flight_dir', 'install', 'install_tap']

_MAX_EVENTS = int(os.environ.get('PT_FLIGHT_EVENTS', '4096'))
_MAX_DUMPS = int(os.environ.get('PT_FLIGHT_MAX_DUMPS', '20'))


def flight_dir():
    """Dump destination, or None (auto-dumps disabled)."""
    return os.environ.get('PT_FLIGHT_DIR') or None


class FlightRecorder(object):
    def __init__(self, max_events=_MAX_EVENTS):
        self._ring = deque(maxlen=max_events)
        self._lock = threading.Lock()   # dump bookkeeping only
        self._dump_seq = 0
        self.last_dump_path = None

    # -- feed --------------------------------------------------------
    def tap(self, event):
        """Trace-recorder tap: mirror an already-built event dict."""
        self._ring.append(event)

    def record(self, kind, **data):
        """Explicit structured event (no-op when telemetry disabled)."""
        if not metrics.enabled():
            return
        ev = {'kind': kind, 't': time.time()}
        if data:
            ev.update(data)
        self._ring.append(ev)

    def events(self):
        return list(self._ring)

    def reset(self):
        self._ring.clear()

    # -- dump --------------------------------------------------------
    def dump(self, reason, path=None, extra=None):
        """Write the postmortem artifact; returns the path (or None if
        the per-process dump budget is exhausted)."""
        with self._lock:
            if self._dump_seq >= _MAX_DUMPS:
                return None
            self._dump_seq += 1
            seq = self._dump_seq
        if path is None:
            d = flight_dir() or '.'
            safe = ''.join(c if c.isalnum() or c in '-_' else '_'
                           for c in str(reason))
            path = os.path.join(d, 'flight_%d_%03d_%s.json'
                                % (os.getpid(), seq, safe))
        artifact = {
            'reason': reason,
            'time_unix': time.time(),
            'pid': os.getpid(),
            'events': self.events(),
            'metrics': metrics.metrics_snapshot(),
            'retrace_reports': list(retrace.explainer().reports),
            'env': {k: v for k, v in os.environ.items()
                    if k.startswith('PT_') or k == 'JAX_PLATFORMS'},
        }
        if extra:
            artifact['extra'] = extra
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + '.tmp'
        with open(tmp, 'w') as f:
            json.dump(artifact, f, default=str)
        os.replace(tmp, path)
        self.last_dump_path = path
        metrics.counter('flight.dumps').inc()
        return path

    def maybe_dump(self, reason, extra=None):
        """Auto-dump trigger: only fires when PT_FLIGHT_DIR is set and
        telemetry is on.  Never raises — a postmortem writer that takes
        the process down is worse than no postmortem."""
        if not metrics.enabled() or flight_dir() is None:
            return None
        try:
            return self.dump(reason, extra=extra)
        except Exception:
            return None


_FLIGHT = FlightRecorder()


def flight():
    return _FLIGHT


def record(kind, **data):
    _FLIGHT.record(kind, **data)


def dump(reason, path=None, extra=None):
    return _FLIGHT.dump(reason, path=path, extra=extra)


def maybe_dump(reason, extra=None):
    return _FLIGHT.maybe_dump(reason, extra=extra)


def install_tap():
    """Mirror every trace event into the flight ring (idempotent)."""
    tracing.set_tap(_FLIGHT.tap)


_HOOKED = [False]


def install():
    """Wrap sys.excepthook so an uncaught crash in a tool/soak process
    leaves a flight dump (idempotent; the original hook still runs)."""
    if _HOOKED[0]:
        return
    _HOOKED[0] = True
    prev = sys.excepthook

    def _hook(exc_type, exc, tb):
        _FLIGHT.record('uncaught_exception', exc_type=exc_type.__name__,
                       message=str(exc)[:500])
        _FLIGHT.maybe_dump('crash')
        prev(exc_type, exc, tb)

    sys.excepthook = _hook
