"""Metrics registry: counters / gauges / histograms, thread-safe,
snapshot-to-dict, with a near-zero-overhead no-op mode.

The registry is process-global (one training process = one telemetry
stream, matching the one-executable-per-step execution model).  Hot
paths guard with `enabled()` ONCE per launch and skip every telemetry
call when off, so disabled mode costs a single branch — individual
metric mutators also check the flag as a second line of defense for
call sites that don't batch their guard.

Histogram buckets are log-spaced (frexp exponent refined by a fixed
linear subdivision of the mantissa): cheap to compute, wide dynamic
range, and *bounded* — the backing store is a dict keyed by bucket
index, so a week-long soak recording millions of observations holds a
few dozen buckets, never a sample list.  Quantiles (`quantile(q)`,
p50/p99 in `snapshot()`) interpolate within the target bucket; with 4
sub-buckets per octave the worst-case relative error is ~12%, plenty to
steer an SLO gate.
"""
import math
import os
import threading
import time

__all__ = ['enabled', 'enable', 'disable', 'Counter', 'Gauge', 'Histogram',
           'MetricsRegistry', 'registry', 'counter', 'gauge', 'histogram',
           'metrics_snapshot', 'counters', 'reset', 'process_age_s']

_ENABLED = [os.environ.get('PT_OBS', '1') not in ('0', 'false', 'False')]


def enabled():
    return _ENABLED[0]


def enable():
    _ENABLED[0] = True


def disable():
    _ENABLED[0] = False


class Counter(object):
    """Monotonic accumulator (float, so it also serves as a seconds sink)."""
    __slots__ = ('name', 'value', '_lock')

    def __init__(self, name):
        self.name = name
        self.value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount=1.0):
        if not _ENABLED[0]:
            return
        with self._lock:
            self.value += amount

    def snapshot(self):
        return self.value


class Gauge(object):
    """Last-value metric (queue depth, overlap fraction)."""
    __slots__ = ('name', 'value', 'updates', '_lock')

    def __init__(self, name):
        self.name = name
        self.value = None
        self.updates = 0
        self._lock = threading.Lock()

    def set(self, value):
        if not _ENABLED[0]:
            return
        with self._lock:
            self.value = value
            self.updates += 1

    def snapshot(self):
        return self.value


_SUBBUCKETS = 4  # linear mantissa subdivisions per power-of-two octave


def _bucket_index(value):
    """Bucket index for a positive value: frexp exponent refined by a
    linear split of the mantissa into _SUBBUCKETS ranges."""
    m, e = math.frexp(value)          # value = m * 2^e, m in [0.5, 1)
    sub = int((m * 2.0 - 1.0) * _SUBBUCKETS)
    if sub >= _SUBBUCKETS:
        sub = _SUBBUCKETS - 1
    return e * _SUBBUCKETS + sub


def _bucket_bounds(idx):
    """(low, high] value range covered by bucket `idx`."""
    e, sub = divmod(idx, _SUBBUCKETS)
    lo = math.ldexp(1.0 + sub / float(_SUBBUCKETS), e - 1)
    hi = math.ldexp(1.0 + (sub + 1) / float(_SUBBUCKETS), e - 1)
    return lo, hi


class Histogram(object):
    """count/sum/min/max plus bounded log-spaced buckets (see module
    docstring).  Non-positive observations land in a dedicated slot so
    they can't alias a real bucket."""
    __slots__ = ('name', 'count', 'total', 'min', 'max', 'buckets',
                 'nonpos', '_lock')

    def __init__(self, name):
        self.name = name
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self.buckets = {}
        self.nonpos = 0
        self._lock = threading.Lock()

    def observe(self, value):
        if not _ENABLED[0]:
            return
        value = float(value)
        idx = _bucket_index(value) if value > 0.0 else None
        with self._lock:
            self.count += 1
            self.total += value
            self.min = value if self.min is None else min(self.min, value)
            self.max = value if self.max is None else max(self.max, value)
            if idx is None:
                self.nonpos += 1
            else:
                self.buckets[idx] = self.buckets.get(idx, 0) + 1

    def _quantile_locked(self, q):
        if not self.count:
            return None
        target = q * self.count
        run = float(self.nonpos)
        if self.nonpos and run >= target:
            return min(self.min, 0.0)
        for idx in sorted(self.buckets):
            n = self.buckets[idx]
            if run + n >= target:
                lo, hi = _bucket_bounds(idx)
                lo = max(lo, self.min)
                hi = min(hi, self.max)
                frac = (target - run) / n
                return lo + (hi - lo) * frac
            run += n
        return self.max

    def quantile(self, q):
        """Interpolated quantile estimate in [min, max]; None when empty."""
        with self._lock:
            return self._quantile_locked(q)

    def bucket_count(self):
        with self._lock:
            return len(self.buckets) + (1 if self.nonpos else 0)

    def snapshot(self):
        with self._lock:
            if not self.count:
                return {'count': 0}
            out = {'count': self.count, 'sum': self.total,
                   'min': self.min, 'max': self.max,
                   'mean': self.total / self.count,
                   'p50': self._quantile_locked(0.50),
                   'p99': self._quantile_locked(0.99),
                   'buckets': {'le_%g' % _bucket_bounds(idx)[1]: n
                               for idx, n in sorted(self.buckets.items())}}
            if self.nonpos:
                out['buckets']['le_0'] = self.nonpos
            return out

    def cumulative_buckets(self):
        """[(upper_bound, cumulative_count)] ascending — the Prometheus
        `le` rendering shape (observability/export.py)."""
        with self._lock:
            items = sorted(self.buckets.items())
            nonpos = self.nonpos
        out = []
        run = nonpos
        if nonpos:
            out.append((0.0, run))
        for idx, n in items:
            run += n
            out.append((_bucket_bounds(idx)[1], run))
        return out


class MetricsRegistry(object):
    def __init__(self):
        self._lock = threading.Lock()
        self._metrics = {}

    def _get(self, name, cls):
        m = self._metrics.get(name)
        if m is None:
            with self._lock:
                m = self._metrics.get(name)
                if m is None:
                    m = cls(name)
                    self._metrics[name] = m
        if not isinstance(m, cls):
            raise TypeError('metric %r already registered as %s'
                            % (name, type(m).__name__))
        return m

    def counter(self, name):
        return self._get(name, Counter)

    def gauge(self, name):
        return self._get(name, Gauge)

    def histogram(self, name):
        return self._get(name, Histogram)

    def snapshot(self):
        """Full structured dump: {'counters': {...}, 'gauges': {...},
        'histograms': {...}}."""
        with self._lock:
            items = list(self._metrics.items())
        out = {'counters': {}, 'gauges': {}, 'histograms': {}}
        for name, m in items:
            kind = ('counters' if isinstance(m, Counter) else
                    'gauges' if isinstance(m, Gauge) else 'histograms')
            out[kind][name] = m.snapshot()
        return out

    def items(self):
        """Sorted [(name, metric_object)] — the export renderer walks
        live objects (cumulative buckets need more than snapshot())."""
        with self._lock:
            return sorted(self._metrics.items())

    def counters(self):
        """Flat {name: value} over counters AND gauges (the shape the soak
        tools and tests diff against)."""
        with self._lock:
            items = list(self._metrics.items())
        return {name: m.snapshot() for name, m in items
                if isinstance(m, (Counter, Gauge))}

    def reset(self):
        with self._lock:
            self._metrics.clear()


_REGISTRY = MetricsRegistry()


def registry():
    return _REGISTRY


def counter(name):
    return _REGISTRY.counter(name)


def gauge(name):
    return _REGISTRY.gauge(name)


def histogram(name):
    return _REGISTRY.histogram(name)


def metrics_snapshot():
    return _REGISTRY.snapshot()


def counters():
    return _REGISTRY.counters()


def reset():
    _REGISTRY.reset()


def process_age_s():
    """Seconds since the kernel started this process: the boot clock now
    less field 22 (`starttime`, in clock ticks) of ``/proc/self/stat``;
    None where that cannot be read (no Linux /proc)."""
    try:
        with open('/proc/self/stat') as f:
            # the command (field 2) may hold spaces: count from its ')'
            ticks = int(f.read().rsplit(')', 1)[1].split()[19])
        return max(0.0, time.clock_gettime(time.CLOCK_BOOTTIME)
                   - ticks / os.sysconf('SC_CLK_TCK'))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
