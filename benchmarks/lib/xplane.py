"""From a profiler trace (.xplane.pb) to busy time, idle gaps and op times.

Read with `jax.profiler.ProfileData`, nothing else.  What the reduction
assumes about a TPU trace, checked on this installation (PR 23, first chip
call; the recorded trace under benchmarks/tests/data pins it):

* each chip is a plane named ``/device:TPU:<n>``; its line ``XLA Ops``
  holds the operations the TensorCore ran, one after another, a `while`
  or `conditional` enclosing the operations of its body; ``XLA Modules``
  holds one event per launched executable;
* host threads are lines of the plane ``/host:CPU``; a
  `jax.profiler.TraceAnnotation` shows there under its own name.  The
  host's and the chip's clocks agree only to about a millisecond (in the
  recorded trace the chip starts a launch 0.9 ms "before" the host made
  it), so the traced window is taken from the chip's own events, first
  start to last end, and a gap shorter than a few milliseconds may be
  named after the neighbouring host span.

`summarize` returns, beside the ten costliest operations (`device_ops`,
what the result line's `breakdown` shows), EVERY operation of the window
under `ops`: label -> {'seconds', 'count'}, so that a metric file can
read its own kernel's time (`op_seconds`).  The `op_name` an instruction
was lowered from (its `jax.named_scope`) is a stat of the event's
METADATA, which `ProfileData` does not hand out; a reader of it comes
with the first metric that sums by scope (PERF.md, Open questions).

Busy time is the UNION of the op intervals (so nesting counts once); an
operation's own time is its duration less what it encloses.  An idle gap
is a stretch of the traced window with no operation on the chip; it is
named after the benchmark's host span (``bench:<name>``) that overlaps
it most, or ``unattributed``.  Over several chips, times are averaged.

An event's name is the HLO instruction's text, ``%fusion.12 = bf16[96,8,
256,64]{...} fusion(...), kind=kOutput, ...``.  Its label here is the
opcode (with the fusion kind), the instruction's name without its number
and the result's shape, so that six layers' copies of one fusion add up
and two fusions of different shapes do not.
"""
import glob
import os
import re

DEVICE_PREFIX = '/device:TPU:'
HOST_PLANE = '/host:CPU'
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
SPAN_PREFIX = 'bench:'
WINDOW_SPAN = 'bench:traced_window'
MIN_GAP_NS = 100.0
COLLECTIVES = ('all-reduce', 'all-gather', 'reduce-scatter', 'all-to-all',
               'collective-permute', 'collective-broadcast')


def find_trace(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                             recursive=True))
    return paths[-1] if paths else None


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _events(line):
    out = []
    for ev in line.events:
        start = float(ev.start_ns)
        out.append((start, start + float(ev.duration_ns), ev.name, ev))
    out.sort(key=lambda e: (e[0], -e[1]))
    return out


def union_length(intervals, lo=None, hi=None):
    """Total length covered by [start, end) intervals, clipped to
    [lo, hi] when given."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi):
    """The stretches of [lo, hi] that no interval covers."""
    out, cursor = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def self_times(events):
    """{name: own nanoseconds} for (start, end, name, ...) events sorted
    by (start, -end): an enclosing event gives up what it encloses."""
    own, stack = {}, []

    def close(upto):
        while stack and stack[-1][1] <= upto:
            s, e, name, inner = stack.pop()
            own[name] = own.get(name, 0.0) + (e - s) - inner
            if stack:
                stack[-1][3] += e - s
    for ev in events:
        s, e, name = ev[0], ev[1], ev[2]
        close(s)
        stack.append([s, e, name, 0.0])
    close(float('inf'))
    return own


_OPCODE = re.compile(r'\s([a-z][a-z0-9\-]*)\(')
_LAYOUT = re.compile(r'\{[^{}]*\}')
_KIND = re.compile(r'kind=k(\w+)')


def parse_op(text):
    """(label, opcode) of an event name.  A name that is not HLO text (a
    kernel's own name, say) is its own label, with opcode None."""
    head, eq, rest = text.partition(' = ')
    name = head.strip().lstrip('%')
    base = name.rstrip('0123456789').rstrip('.') or name
    if not eq:
        return base, None
    found = _OPCODE.search(' ' + rest)
    opcode = found.group(1) if found else None
    shape = _LAYOUT.sub('', rest[:found.start()] if found else rest[:48])
    shape = shape.strip()[:48]
    kind = _KIND.search(rest)
    what = opcode or 'op'
    if kind:
        what += ':' + kind.group(1)
    label = what if base == opcode else '%s %s' % (what, base)
    return ('%s %s' % (label, shape)).strip(), opcode


def host_spans(pd):
    out = []
    for plane in pd.planes:
        if not plane.name.startswith(HOST_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    s = float(ev.start_ns)
                    out.append((s, s + float(ev.duration_ns), ev.name))
    return out


def summarize(pd, top=10, top_gaps=10):
    """Reduce one trace.  Returns None when no chip ran an operation."""
    spans = host_spans(pd)
    per_chip = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = _events(line)
            elif line.name == MODULES_LINE:
                modules = _events(line)
        if ops:
            per_chip.append((plane.name, ops, modules))
    if not per_chip:
        return None
    lo = min(ops[0][0] for _, ops, _ in per_chip)
    hi = max(max(e[1] for e in ops) for _, ops, _ in per_chip)
    n = float(len(per_chip))
    busy = 0.0
    op_ns, op_count, module_ns, module_count = {}, {}, {}, {}
    collective_ns = custom_ns = 0.0
    for _, ops, modules in per_chip:
        inside = [e for e in ops if e[1] > lo and e[0] < hi]
        busy += union_length([(e[0], e[1]) for e in inside], lo, hi)
        parsed = {}
        for e in inside:
            if e[2] not in parsed:
                parsed[e[2]] = parse_op(e[2])
        labelled = [(e[0], e[1], parsed[e[2]]) for e in inside]
        for _, _, (label, _) in labelled:
            op_count[label] = op_count.get(label, 0) + 1
        for (label, opcode), ns in self_times(labelled).items():
            op_ns[label] = op_ns.get(label, 0.0) + ns
            if opcode and opcode.startswith(COLLECTIVES):
                collective_ns += ns
            if opcode == 'custom-call':
                custom_ns += ns
        for s, e, name, _ in modules:
            if e <= lo or s >= hi:
                continue
            key = name.split('(')[0]
            module_ns[key] = module_ns.get(key, 0.0) + (e - s)
            module_count[key] = module_count.get(key, 0) + 1
    first_ops = per_chip[0][1]
    # event starts are whole nanoseconds: back-to-back operations leave
    # "gaps" of a nanosecond or two, which are rounding and not idleness
    idle = [g for g in gaps([(e[0], e[1]) for e in first_ops], lo, hi)
            if g[1] - g[0] >= MIN_GAP_NS]
    idle.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in idle[:top_gaps]:
        best, best_overlap = 'unattributed', 0.0
        for hs, he, hname in spans:
            if hname == WINDOW_SPAN:
                continue
            overlap = min(e, he) - max(s, hs)
            if overlap > best_overlap:
                best, best_overlap = hname[len(SPAN_PREFIX):], overlap
        named.append([best, (e - s) / 1e9])
    ranked = sorted(op_ns.items(), key=lambda kv: -kv[1])
    ops = {label: {'seconds': ns / n / 1e9, 'count': op_count[label] / n}
           for label, ns in ranked}
    top_ops = ranked[:top]
    return {
        'chips': int(n),
        'window_s': (hi - lo) / 1e9,
        'busy_s': busy / n / 1e9,
        'device_ops': [[k, v / n / 1e9] for k, v in top_ops],
        'ops': ops,
        'idle_gaps': named,
        'collective_s': collective_ns / n / 1e9,
        'custom_call_s': custom_ns / n / 1e9,
        'modules': {k: {'seconds': v / n / 1e9,
                        'count': module_count[k] / n}
                    for k, v in module_ns.items()},
    }


def op_seconds(summary, prefix):
    """Own seconds of the operations whose label starts with `prefix`
    (a kernel's `custom-call <name>`), or None where the trace has none."""
    hits = [op['seconds'] for label, op in (summary or {}).get('ops',
                                                               {}).items()
            if label.startswith(prefix)]
    return sum(hits) if hits else None


def module_time(summary, word):
    """(seconds, launches) of the traced executables whose name holds
    `word` (the serving runtime jits functions named `window` and
    `prefill`), or None where the trace has none."""
    hits = [m for name, m in (summary or {}).get('modules', {}).items()
            if word in name]
    if not hits:
        return None
    return sum(m['seconds'] for m in hits), sum(m['count'] for m in hits)


def describe(pd, limit=6):
    """A short text view of a trace's planes, lines and first events: what
    a builder looks at by hand before trusting `summarize`."""
    rows = []
    for plane in pd.planes:
        rows.append('plane %r' % plane.name)
        for line in plane.lines:
            evs = list(line.events)
            rows.append('  line %r: %d events' % (line.name, len(evs)))
            for ev in evs[:limit]:
                stats = ', '.join('%s=%s' % (k, str(v)[:40])
                                  for k, v in list(ev.stats)[:8])
                rows.append('    %s  start=%d dur=%d  {%s}' % (
                    ev.name[:90], ev.start_ns, ev.duration_ns, stats))
    return '\n'.join(rows)
