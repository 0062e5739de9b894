"""One timeline: the chip's idle time, named after the program's spans.

    python -m paddle_tpu.observability.timeline <trace_dir | file.xplane.pb>

`tracing.span(name)` writes a ``pt:<name>`` annotation into any profiler
trace that is running, so an xplane taken by anyone holds the program's
spans on the host plane, one line per thread, on the profiler's clock,
beside the chip's ``XLA Ops`` and ``XLA Modules`` lines.  This module
reads such a trace back with `jax.profiler.ProfileData` and answers the
operator's question about an idle chip: what was the thread that
launches work doing while the chip had none?

* The chip's idle gaps are what the union of its ``XLA Ops`` intervals
  leaves uncovered between the first operation's start and the last
  one's end; a gap under 100 ns is rounding (starts are whole
  nanoseconds), not idleness.
* The launching thread is the host line with the most ``pt:*.dispatch``
  spans.  Every instant of a gap belongs to the INNERMOST of that
  thread's ``pt:`` spans alive then; the gap is named, whole, after the
  span that holds most of it so, ``unattributed`` when most of it lies
  under no span.
* ``idle_under`` rolls the same idle time UP: the idle nanoseconds that
  lie under each span name, its descendants' included and no gap
  rounded to a whole, so a parent (`serving.boundary`) reads what its
  children (fetch, check, upload, dispatch) read together, beside
  ``unattributed``, the idle time under no span at all.  The names
  overlap (a round holds its boundary): the column does not sum.
* The host's and the chip's clocks agree only to about a millisecond.
  A ``pt:*.dispatch`` span is paired with the executable on ``XLA
  Modules`` that it launched BY POSITION, never by rank: the first
  module not yet paired that starts within `MAX_SKEW_NS` of the span
  and has not ended before the span begins (the small argument
  conversions of the upload before it, executables of their own a few
  microseconds long, have).  A span with no such module, or a module
  with no span (a trace that starts or stops mid-round), stays
  unpaired and moves nothing.  A launch cannot start before the call
  that made it has begun: the most negative (module start - dispatch
  start) over the pairs is how far the chip's clock runs behind the
  host's, ``clock_skew_ms``.  The host spans are moved onto the chip's
  clock by it before any gap is named; with no pair they are not moved
  and the report says ``unpaired``.  (Measured against the dispatch
  span's END the same minimum would also hold the call's own length:
  the chip may begin before an asynchronous dispatch returns.)

The reduction works on plain ``(start_ns, end_ns[, name])`` tuples, so
it is tested on hand-built intervals; only `load_trace` touches a file.
"""
import bisect
import glob
import os
import sys

from .tracing import ANNOTATION_PREFIX as SPAN_PREFIX

__all__ = ['idle_gaps', 'name_gap', 'idle_by_span', 'idle_under',
           'pair_launches',
           'clock_skew_ns', 'analyse', 'load_trace', 'report', 'main']

DEVICE_PREFIX = '/device:TPU:'
HOST_PLANE = '/host:CPU'
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
DISPATCH_SUFFIX = '.dispatch'
MIN_GAP_NS = 100.0
MAX_SKEW_NS = 5e6      # the clocks never differed by more than ~1 ms
UNATTRIBUTED = 'unattributed'


def idle_gaps(intervals, min_gap_ns=MIN_GAP_NS):
    """[(start, end)] of the stretches no interval covers, between the
    first start and the last end, each at least `min_gap_ns` long."""
    out, cursor = [], None
    for s, e in sorted(intervals):
        if cursor is None:
            cursor = e
            continue
        if s - cursor >= min_gap_ns:
            out.append((cursor, s))
        cursor = max(cursor, e)
    return out


def name_gap(gap, spans):
    """Name `gap` after the span that, as the INNERMOST one alive, holds
    most of it.  Every instant of the gap belongs to the innermost of the
    (start, end, name) spans alive then (to `unattributed` when none
    is); the name that holds the largest part wins."""
    gs, ge = gap
    clipped = sorted(
        ((max(gs, s), min(ge, e), s - e, name) for s, e, name in spans
         if min(ge, e) > max(gs, s)),
        key=lambda c: (c[0], -c[1], c[2]))    # an enclosing span first
    own, stack = {UNATTRIBUTED: ge - gs}, []

    def close(upto):
        while stack and stack[-1][1] <= upto:
            s, e, name, inner = stack.pop()
            own[name] = own.get(name, 0.0) + (e - s) - inner
            if stack:
                stack[-1][3] += e - s
            else:
                own[UNATTRIBUTED] -= e - s

    for s, e, _, name in clipped:
        close(s)
        stack.append([s, e, name, 0.0])
    close(float('inf'))
    return max(own, key=own.get)


def idle_by_span(gaps, spans):
    """{span name: idle nanoseconds} over `gaps`, each named whole.  One
    sweep: `live` holds the spans that can still touch the current gap
    (a thread's spans nest, so it stays a few deep)."""
    spans = sorted(spans)
    out, live, nxt = {}, [], 0
    for gap in sorted(gaps):
        while nxt < len(spans) and spans[nxt][0] < gap[1]:
            live.append(spans[nxt])
            nxt += 1
        live = [sp for sp in live if sp[1] > gap[0]]
        name = name_gap(gap, live)
        out[name] = out.get(name, 0.0) + (gap[1] - gap[0])
    return out


def idle_under(gaps, spans):
    """{span name: idle nanoseconds of `gaps` under the (start, end,
    name) spans of that name, descendants included}, and under
    `unattributed` what lies under no span.  A span inside one of its
    own name (or, for `unattributed`, inside any span) adds nothing: its
    ancestor has counted it."""
    gaps = sorted(gaps)
    starts = [g[0] for g in gaps]
    before = [0.0]                      # idle time before each gap
    for s, e in gaps:
        before.append(before[-1] + e - s)

    def idle_until(t):
        i = bisect.bisect_right(starts, t)
        return before[i] - (max(0.0, gaps[i - 1][1] - t) if i else 0.0)

    out, covered = {}, {}               # covered: name -> end of the last
    for s, e, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        under = idle_until(e) - idle_until(s)
        for key in (name, None):        # None: any span at all
            if s >= covered.get(key, s):
                covered[key] = e
                out[key] = out.get(key, 0.0) + under
    out[UNATTRIBUTED] = before[-1] - out.pop(None, 0.0)
    return out


def pair_launches(modules, dispatches, tol_ns=MAX_SKEW_NS):
    """[(module, dispatch)]: each (start, end[, name]) dispatch span, in
    order, with the first (start, end) module not yet paired that starts
    no more than `tol_ns` before the span's start or after its end, and
    is still running (or yet to start) when the span begins.  A module
    that fits no span is passed over, a span that no module fits is left
    out: an event the trace cut off never shifts the pairs after it."""
    modules = sorted(modules)
    pairs, i = [], 0
    for d in sorted(dispatches):
        while i < len(modules) and (modules[i][0] < d[0] - tol_ns
                                    or modules[i][1] < d[0]):
            i += 1
        if i < len(modules) and modules[i][0] <= d[1] + tol_ns:
            pairs.append((modules[i], d))
            i += 1
    return pairs


def clock_skew_ns(pairs):
    """How far the chip's clock runs from the host's: the most negative
    (module start - start of the dispatch span that launched it) over
    `pair_launches`' pairs, a launch never starting before its call.
    None without a pair; a positive minimum (no skew to be seen) is the
    launch latency."""
    if not pairs:
        return None
    return min(m[0] - d[0] for m, d in pairs)


def analyse(ops, modules, thread_spans):
    """Reduce one chip's timeline.

    ops / modules: [(start_ns, end_ns)] of ``XLA Ops`` / ``XLA Modules``;
    thread_spans: {thread: [(start_ns, end_ns, name)]} of the host's
    ``pt:`` spans with the prefix taken off.  Returns None when no
    operation ran."""
    if not ops:
        return None
    launcher, dispatches = None, []
    for thread, spans in thread_spans.items():
        mine = [s for s in spans if s[2].endswith(DISPATCH_SUFFIX)]
        if len(mine) > len(dispatches):
            launcher, dispatches = thread, mine
    pairs = pair_launches(modules, dispatches)
    skew = clock_skew_ns(pairs)
    shift = skew if skew is not None and skew < 0 else 0.0
    spans = [(s + shift, e + shift, name)
             for s, e, name in thread_spans.get(launcher, [])]
    gaps = idle_gaps(ops)
    lo = min(s for s, _ in ops)
    hi = max(e for _, e in ops)
    idle = sum(e - s for s, e in gaps)
    by_span = idle_by_span(gaps, spans)
    named = idle - by_span.get(UNATTRIBUTED, 0.0)
    under = idle_under(gaps, spans)
    return {
        'window_s': (hi - lo) / 1e9,
        'idle_s': idle / 1e9,
        'idle_share': idle / (hi - lo) if hi > lo else 0.0,
        'gaps': len(gaps),
        'idle_s_by_span': {k: v / 1e9 for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])},
        'named_share': named / idle if idle else 1.0,
        'idle_under': {k: v / 1e9 for k, v in sorted(
            under.items(), key=lambda kv: -kv[1]) if v > 0.0},
        'launching_thread': launcher,
        'launches': len(dispatches),
        'paired': len(pairs),
        'modules': len(modules),
        'clock_skew_ms': None if skew is None else skew / 1e6,
    }


def load_trace(path):
    """(ops, modules, thread_spans) of the FIRST chip in an xplane file
    (or the newest one under a trace directory)."""
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, '**', '*.xplane.pb'),
                                 recursive=True))
        if not found:
            raise FileNotFoundError('no .xplane.pb under %s' % path)
        path = found[-1]
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)

    def intervals(line):
        return [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                for ev in line.events]

    ops, modules, thread_spans = [], [], {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and not ops:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = intervals(line)
                elif line.name == MODULES_LINE:
                    modules = intervals(line)
        elif plane.name.startswith(HOST_PLANE):
            for i, line in enumerate(plane.lines):
                spans = [(float(ev.start_ns),
                          float(ev.start_ns + ev.duration_ns),
                          ev.name[len(SPAN_PREFIX):])
                         for ev in line.events
                         if ev.name.startswith(SPAN_PREFIX)]
                if spans:
                    thread_spans['%s#%d' % (line.name, i)] = spans
    return ops, modules, thread_spans


def report(result):
    """The text an operator reads: idle seconds by innermost span name,
    the same rolled up under each span and its descendants, then the
    clock skew."""
    if result is None:
        return 'no operation ran on a chip in this trace'
    rows = ['window_s %.6f  idle_s %.6f  idle_share %.4f  gaps %d'
            % (result['window_s'], result['idle_s'], result['idle_share'],
               result['gaps']),
            'launching thread %s: %d dispatch spans, %d modules on the chip'
            % (result['launching_thread'], result['launches'],
               result['modules'])]

    def table(title, seconds_by_name):
        rows.append('%-36s %12s %8s' % (title, 'seconds', 'share'))
        for name, seconds in seconds_by_name.items():
            rows.append('%-36s %12.6f %7.2f%%' % (
                name, seconds, 100.0 * seconds / result['idle_s']
                if result['idle_s'] else 0.0))

    table('idle under span', result['idle_s_by_span'])
    rows.append('named_share %.4f' % result['named_share'])
    table('idle under span and descendants', result['idle_under'])
    skew = result['clock_skew_ms']
    rows.append('clock_skew_ms %s' % (
        'unpaired (no dispatch span has its module within %g ms: host '
        'spans left on their own clock)' % (MAX_SKEW_NS / 1e6)
        if skew is None
        else '%.4f over %d of %d launches paired'
        % (skew, result['paired'], result['launches'])))
    return '\n'.join(rows)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split('\n\n')[1], file=sys.stderr)
        return 2
    print(report(analyse(*load_trace(argv[0]))))
    return 0


if __name__ == '__main__':
    sys.exit(main())
