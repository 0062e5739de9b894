"""Retrace explainer: names WHY an executable was (re)traced.

The Julia->TPU compile-the-loop model (arxiv 1810.09868) has one silent
failure mode: an unnoticed recompile.  Under whole-block lowering a
retrace can come from two layers — an executor-cache miss (new fetch
set, steps=K, program edit) or a jax.jit shape/dtype miss under an
existing cache entry — and both surface here the same way: the executor
detects a trace via `_TRACE_COUNT`, builds a `LaunchSignature` of every
cache-key component, and the explainer diffs it against the NEAREST
prior signature (fewest differing components) to record which component
changed: feed shapes, feed dtypes, fetch set, steps, program serial,
check_nan, scope.

A signature whose nearest prior differs in `program` is a new-program
compile (expected; counted in `executor.compiles`); anything else is a
retrace (`executor.retraces`) with its cause named in the report and an
instant event dropped on the timeline.  `executor.compile_s` accumulates
trace+compile wall time for both kinds.
"""
import threading
from collections import deque

from . import metrics
from . import tracing

__all__ = ['LaunchSignature', 'RetraceExplainer', 'explainer', 'reset']

_COMPONENTS = ('program', 'feed_shapes', 'feed_dtypes', 'fetch_set',
               'steps', 'check_nan', 'scope', 'opt', 'emit')


class LaunchSignature(object):
    """Structured cache key: one attribute per component the executor's
    lowering cache (and jax.jit underneath it) keys on.  `opt` is the
    program-rewriter config token (core/passes.config_token()): toggling
    PT_OPT / PT_OPT_SKIP mid-process changes what the tracer sees for the
    same raw program, and must be named, not a mystery retrace.  `emit`
    is the direct-emitter token (core/emit.config_token()) — flipping
    PT_EMIT is likewise a named signature change."""
    __slots__ = _COMPONENTS

    def __init__(self, program, feed_shapes, feed_dtypes, fetch_set,
                 steps, check_nan, scope, opt=None, emit=None):
        self.program = program            # (serial, version)
        self.feed_shapes = dict(feed_shapes)   # name -> tuple
        self.feed_dtypes = dict(feed_dtypes)   # name -> str
        self.fetch_set = tuple(fetch_set)
        self.steps = steps
        self.check_nan = bool(check_nan)
        self.scope = scope
        self.opt = opt
        self.emit = emit

    def changed_components(self, other):
        return [c for c in _COMPONENTS
                if getattr(self, c) != getattr(other, c)]

    def explain_against(self, other):
        """Human-readable per-component details of self vs other."""
        details = []
        if self.program != other.program:
            details.append('program: %r -> %r' % (other.program,
                                                  self.program))
        for label, new, old in (('feed_shape', self.feed_shapes,
                                 other.feed_shapes),
                                ('feed_dtype', self.feed_dtypes,
                                 other.feed_dtypes)):
            for n in sorted(set(new) | set(old)):
                if n not in old:
                    details.append('%s:%s added %r' % (label, n, new[n]))
                elif n not in new:
                    details.append('%s:%s removed (was %r)'
                                   % (label, n, old[n]))
                elif new[n] != old[n]:
                    details.append('%s:%s %r -> %r'
                                   % (label, n, old[n], new[n]))
        if self.fetch_set != other.fetch_set:
            added = [n for n in self.fetch_set if n not in other.fetch_set]
            removed = [n for n in other.fetch_set if n not in self.fetch_set]
            details.append('fetch_set: %s%s' % (
                ' '.join('+' + n for n in added),
                (' ' if added else '') + ' '.join('-' + n for n in removed)))
        if self.steps != other.steps:
            details.append('steps: %r -> %r' % (other.steps, self.steps))
        if self.check_nan != other.check_nan:
            details.append('check_nan: %r -> %r'
                           % (other.check_nan, self.check_nan))
        if self.scope != other.scope:
            details.append('scope: serial %r -> %r'
                           % (other.scope, self.scope))
        if self.opt != other.opt:
            details.append('opt: PT_OPT config %r -> %r (program rewriter '
                           'toggled/reconfigured)' % (other.opt, self.opt))
        if self.emit != other.emit:
            details.append('emit: PT_EMIT config %r -> %r (direct '
                           'emitter toggled or versioned)'
                           % (other.emit, self.emit))
        return details


def _bucketable(sig, prior):
    """True when every differing feed shape differs only in its leading
    (batch) and/or second (sequence) dim — the exact raggedness
    FeedBucketer pads away."""
    names = set(sig.feed_shapes) | set(prior.feed_shapes)
    saw_diff = False
    for n in names:
        a = sig.feed_shapes.get(n)
        b = prior.feed_shapes.get(n)
        if a == b:
            continue
        if a is None or b is None or len(a) != len(b):
            return False
        if any(x != y for x, y in zip(a[2:], b[2:])):
            return False
        saw_diff = True
    return saw_diff


class RetraceExplainer(object):
    def __init__(self, max_reports=1000):
        self._lock = threading.Lock()
        self._seen = []
        self.reports = deque(maxlen=max_reports)

    def observe(self, sig, compile_s=0.0, label=None, cache=None,
                lowering=None):
        """Record one (re)trace; returns the report dict.  `cache` names
        the disk-cache verdict for this trace ('miss' / 'stablehlo_hit' /
        'disabled') so every retrace is annotated with whether the
        persistent tier could have prevented it.  `lowering` names HOW
        the program lowered: 'emit' (direct emitter), 'trace' (classic
        per-op tracing), or 'emit_fallback:<op>' (the emitter hit that
        op and this program degraded to tracing)."""
        with self._lock:
            if not self._seen:
                kind, changed, details = 'initial_compile', [], []
            else:
                nearest = min(self._seen,
                              key=lambda s: len(sig.changed_components(s)))
                changed = sig.changed_components(nearest)
                details = sig.explain_against(nearest)
                if 'program' in changed:
                    kind = 'new_program_compile'
                elif changed:
                    kind = 'retrace'
                else:
                    # identical signature traced again: the executor cache
                    # was bypassed or jit's own cache dropped the trace
                    kind = 'retrace'
                    details = ['identical signature retraced (cache '
                               'bypassed or jit cache evicted)']
            if kind == 'retrace' and changed and \
                    set(changed) <= {'feed_shapes'} and \
                    _bucketable(sig, nearest):
                details.append(
                    'bucketable: shapes differ only in batch/sequence '
                    'dims — a FeedBucketer (data_feeder.py) would map '
                    'this feed onto an existing bucket signature')
            self._seen.append(sig)
        report = {'kind': kind, 'changed': changed, 'details': details,
                  'compile_s': compile_s, 'label': label, 'cache': cache,
                  'lowering': lowering}
        self.reports.append(report)
        if kind == 'retrace':
            metrics.counter('executor.retraces').inc()
            tracing.instant('executor.retrace', cat='compile',
                            args={'cause': '; '.join(details) or 'unknown'})
        else:
            metrics.counter('executor.compiles').inc()
        metrics.counter('executor.compile_s').inc(compile_s)
        return report

    def observe_disk_load(self, sig, load_s=0.0):
        """Record a warm start: this signature's executable came from the
        persistent cache, so NO trace/compile happened — the signature
        still joins the nearest-prior pool so later real retraces diff
        against it."""
        with self._lock:
            self._seen.append(sig)
        report = {'kind': 'disk_load', 'changed': [], 'details': [],
                  'compile_s': 0.0, 'load_s': load_s, 'label': None,
                  'cache': 'hit'}
        self.reports.append(report)
        return report

    def last_report(self):
        return self.reports[-1] if self.reports else None

    def render_report(self, report=None):
        """One retrace-explainer report as text (docs/observability.md
        shows the shape)."""
        report = report or self.last_report()
        if report is None:
            return '<no traces recorded>'
        lines = ['[%s] compile_s=%.3f%s%s%s'
                 % (report['kind'], report['compile_s'],
                    ' cache=%s' % report['cache']
                    if report.get('cache') else '',
                    ' lowering=%s' % report['lowering']
                    if report.get('lowering') else '',
                    ' label=%s' % report['label'] if report['label']
                    else '')]
        for d in report['details']:
            lines.append('  changed: %s' % d)
        return '\n'.join(lines)

    def reset(self):
        with self._lock:
            self._seen = []
            self.reports.clear()


_EXPLAINER = RetraceExplainer()


def explainer():
    return _EXPLAINER


def reset():
    _EXPLAINER.reset()
