"""NaN forensics: deterministic divergence bisection after a trip.

When RecoveryPolicy catches a ``check_nan`` trip — synchronous or a
deferred ``nan_window_steps`` window — it knows only that *some* step
since the last clean checkpoint went non-finite.  This module spends the
repo's bitwise rerun-determinism (counter-folded RNG streams, step-exact
checkpoints, deterministic fault injection) to turn that into a named
verdict, in three bisection phases:

  1. **steps** — replay the condemned window from the restored
     checkpoint one step at a time (the forensic runner is a
     single-step lowering, so every step gets a synchronous verdict —
     PT_NAN_POLL=1 semantics regardless of the production cadence);
  2. **ops** — the replay runner is lowered with a
     :class:`~paddle_tpu.core.executor.ForensicProbes` collector
     (``PT_FORENSIC`` probe variant): every op's inexact outputs carry a
     fused [all_finite, nonfinite_count, max_abs] probe, fetched as one
     stacked array per step.  The first false probe names the op, its
     output var and the D-style ``source_loc`` the analyzer stamped.
     The RAW program is lowered (no passes, no emit engine), so
     fused groups are seen at sub-program granularity while the
     production path keeps its kernels — RNG parity is by construction,
     since optimized twins pin each op's raw position in
     ``rng_stream``;
  3. **batch rows** — the tripped step's (re-poisoned) feed is scanned
     on host for non-finite rows; when the poison is state-borne
     instead of data-borne, a bounded zero-substitution bisection over
     batch rows decides between "these rows did it" and "the state was
     already poisoned".

The verdict is a structured :class:`ForensicReport` attached to the
flight recorder (``forensics.report`` + a ``forensics`` dump trigger)
and the ``recovery.forensics_*`` metrics/spans.  RecoveryPolicy feeds
the named sample indices into the data plane's quarantine
(data_feeder.SampleQuarantine) — see docs/robustness.md.

Scope note: single-chip executors only (``exe.mesh is None``); a pod
trip aborts forensics (counted) and falls back to plain rollback.
"""
import os

import numpy as np

from .. import observability as _obs
from ..observability import flight as _flight
from ..observability import trace_context as _tc
from ..testing import faults as _faults

__all__ = ['LaunchRecord', 'ForensicReport', 'investigate', 'enabled']


def enabled():
    """PT_FORENSIC gate: on by default, ``PT_FORENSIC=0`` disables."""
    return os.environ.get('PT_FORENSIC', '1') not in ('0', 'false', 'False')


class LaunchRecord(object):
    """What RecoveryPolicy must remember about one launch to replay it:
    the program, the launch's feed (one per-step dict, a stacked
    superbatch dict, or a list of per-step dicts), the step count, the
    fetch list, and ``step0`` — the same step id the caller passes to
    ``checkpointer.save``/``maybe_save``, so the window can be aligned
    against the restored checkpoint's ``step_id``."""
    __slots__ = ('program', 'feed', 'steps', 'fetch_list', 'step0')

    def __init__(self, program, feed, steps, fetch_list, step0):
        self.program = program
        self.feed = feed
        self.steps = None if steps is None else int(steps)
        self.fetch_list = fetch_list
        self.step0 = int(step0)

    @property
    def nsteps(self):
        return 1 if self.steps is None else max(1, self.steps)


class ForensicReport(object):
    """Structured verdict of one forensic investigation."""

    def __init__(self):
        self.tripped = False         # did the replay reproduce the trip?
        self.step = None             # step id (caller convention) that tripped
        self.counter = None          # RNG/run counter of the tripped step
        self.window = []             # step ids replayed
        self.op_pos = None           # program position of the first bad op
        self.op_type = None
        self.var = None              # first non-finite output var
        self.source_loc = None       # D-style file:line from the analyzer
        self.nonfinite_count = None  # elements gone non-finite in that var
        self.max_abs_finite = None   # largest finite |x| in that var
        self.rows = None             # batch rows named (None: not data-borne)
        self.row_method = None       # 'feed_scan' | 'substitution' | 'state'
        self.sample_indices = None   # reader indices of the named rows
        self.batch_size = None
        self.replayed_steps = 0
        self.probe_launches = 0      # extra row-probe launches

    def to_dict(self):
        return {k: getattr(self, k) for k in (
            'tripped', 'step', 'counter', 'window', 'op_pos', 'op_type',
            'var', 'source_loc', 'nonfinite_count', 'max_abs_finite',
            'rows', 'row_method', 'sample_indices', 'batch_size',
            'replayed_steps', 'probe_launches')}

    def __repr__(self):
        if not self.tripped:
            return '<ForensicReport: trip not reproduced over window %s>' \
                % (self.window,)
        return ('<ForensicReport step=%s op=%s(%s) var=%s rows=%s '
                'samples=%s loc=%s>' % (self.step, self.op_type,
                                        self.op_pos, self.var, self.rows,
                                        self.sample_indices,
                                        self.source_loc))


def _per_step_feeds(exe, records):
    """Flatten window records into [(step_id, {name: np/dev array})] in
    launch order, unstacking superbatches and normalizing LoD feeds the
    way the original launches did."""
    out = []
    for rec in records:
        block = rec.program.global_block()
        if isinstance(rec.feed, (list, tuple)):
            per = [exe._normalize_feed(block, f) for f in rec.feed]
        elif rec.steps is None:
            per = [exe._normalize_feed(block, rec.feed)]
        else:
            stacked = {k: np.asarray(v) for k, v in rec.feed.items()}
            per = [{k: v[i] for k, v in stacked.items()}
                   for i in range(rec.nsteps)]
        for i, f in enumerate(per):
            out.append((rec.step0 + i, f))
    return out


def _batch_size(feed):
    """The consistent leading batch dim across this step's arrays, or
    None when the feed has no single batch axis to bisect over."""
    dims = {np.asarray(v).shape[0] for v in feed.values()
            if np.asarray(v).ndim >= 1}
    return dims.pop() if len(dims) == 1 else None


def _scan_feed_rows(feed, batch):
    """Host scan: batch rows carrying any non-finite float value."""
    bad = set()
    for v in feed.values():
        a = np.asarray(v)
        if a.ndim < 1 or a.shape[0] != batch or \
                not np.issubdtype(a.dtype, np.floating):
            continue
        flat = a.reshape(batch, -1)
        bad.update(int(r) for r in
                   np.nonzero(~np.isfinite(flat).all(axis=1))[0])
    return sorted(bad)


def _substitute_rows(feed, rows, batch):
    """Zero out the given batch rows of every float feed array — the
    substitution probe: if the step runs clean without these rows, the
    poison was data-borne and lived in them."""
    rows = list(rows)
    out = {}
    for k, v in feed.items():
        a = np.asarray(v)
        if np.issubdtype(a.dtype, np.floating) and a.ndim >= 1 and \
                a.shape[0] == batch:
            b = np.array(a, copy=True)
            b[rows] = 0
            out[k] = b
        else:
            out[k] = v
    return out


class _Runner(object):
    """One compiled forensic probe executable, reused for every replayed
    step and row probe of an investigation (same shapes -> one trace)."""

    def __init__(self, exe, program, feed_names, fetch_names):
        from ..core import executor as _ex
        self.exe = exe
        self.program = program
        self.collector = _ex.ForensicProbes()
        # RAW program, no emit engine, no donation: maximum probe
        # granularity (fused groups replay their sub-ops), per-op
        # source_loc intact, and a pure step we can re-run at will.
        # check_nan keeps the production trip criterion as output #3.
        self.fn, self.params_in, self.writeback = _ex._lower(
            program, tuple(feed_names), tuple(fetch_names),
            donate=False, mesh=None, check_nan=True, steps=None,
            forensic=self.collector)

    def step(self, scope, feed, counter):
        """Run one probed step.  Returns (ok, probes, updates)."""
        params = self.exe._gather_params(self.program, self.params_in,
                                         scope, None)
        fetches, updates, ok, probes = self.fn(
            params, feed, np.uint32(int(counter) & 0xffffffff))
        return bool(ok), np.asarray(probes), updates


def investigate(checkpointer, records, meta=None, sample_index_of=None,
                max_row_probes=24):
    """Replay the condemned window from the restored checkpoint and name
    the first non-finite op, step and (when data-borne) batch rows.

    Preconditions: the caller (RecoveryPolicy.rollback) has ALREADY
    restored the checkpoint ``meta`` describes — scope and RNG counters
    sit at the window's start.  On return the checkpoint is restored
    AGAIN, so the investigation's own state advances never leak into
    the resumed run.  Returns a ForensicReport, or None when forensics
    cannot run here (no executor, a pod mesh, a window that does not
    align with the restored step)."""
    exe = getattr(checkpointer, 'executor', None)
    if exe is None or not records:
        return None
    if getattr(exe, 'mesh', None) is not None:
        _obs.metrics.counter('recovery.forensics_aborted').inc()
        _flight.record('forensics.aborted', reason='mesh')
        return None
    if meta is None:
        _obs.metrics.counter('recovery.forensics_aborted').inc()
        _flight.record('forensics.aborted', reason='no_meta')
        return None
    ckpt_step = int(meta.get('step_id', -1))
    live = [r for r in records if r.step0 + r.nsteps - 1 > ckpt_step]
    if not live or live[0].step0 != ckpt_step + 1:
        # the buffered window has a gap against the restored checkpoint
        # (records rotated out, or a save landed mid-window without the
        # caller pruning) — replaying would mis-align RNG streams
        _obs.metrics.counter('recovery.forensics_aborted').inc()
        _flight.record('forensics.aborted', reason='window_gap',
                       ckpt_step=ckpt_step,
                       window=[r.step0 for r in records])
        return None

    scope = checkpointer._scope()
    program = live[0].program
    fetch_names = tuple(exe._resolve_fetch(live[0].fetch_list))
    steps = _per_step_feeds(exe, live)
    feed_names = tuple(sorted(steps[0][1]))
    # the restore re-armed the stream's counter at the window start: the
    # i-th replayed step consumes exactly the counter the original did
    ctr0 = exe.stream_counter(feed_names, fetch_names)

    report = ForensicReport()
    report.window = [s for s, _ in steps]
    _obs.metrics.counter('recovery.forensics_runs').inc()

    with _tc.root_span('recovery.forensics', cat='recovery',
                       args={'window_steps': len(steps),
                             'ckpt_step': ckpt_step}):
        try:
            runner = _Runner(exe, program, feed_names, fetch_names)
            with _faults.forensic_replay():
                _bisect(runner, scope, steps, ctr0, report,
                        sample_index_of, max_row_probes)
        finally:
            # leave no trace: the investigation advanced scope state up
            # to the poisoned step — put everything back as rollback left
            # it before the resumed run continues
            checkpointer.restore()
            if hasattr(exe, 'reset_nan_window'):
                exe.reset_nan_window()

    _obs.metrics.counter(
        'recovery.forensics_named' if report.tripped
        else 'recovery.forensics_unattributed').inc()
    _flight.record('forensics.report', **report.to_dict())
    _flight.maybe_dump('forensics')
    _obs.tracing.instant(
        'forensics.verdict', cat='recovery',
        args={'step': report.step, 'op': report.op_type,
              'var': report.var, 'rows': report.rows})
    return report


def _bisect(runner, scope, steps, ctr0, report, sample_index_of,
            max_row_probes):
    """Phases 1-3 against a prepared runner; fills ``report`` in place."""
    for i, (step_id, feed) in enumerate(steps):
        ctr = ctr0 + i
        # reproduce the original poison: the nan_step site replays its
        # armed window without consuming budget (forensic_replay ctx)
        pfeed = _faults.poison_nan(dict(feed), ctr, 1)
        ok, probes, updates = runner.step(scope, pfeed, ctr)
        report.replayed_steps += 1
        _obs.metrics.counter('recovery.forensics_replay_steps').inc()
        if ok:
            # clean step: commit its updates so the next replayed step
            # sees exactly the state the original run gave it
            for n, v in updates.items():
                scope.vars[n] = v
            continue
        # ---- phase 1 verdict: this is the step -----------------------
        report.tripped = True
        report.step = int(step_id)
        report.counter = int(ctr)
        # ---- phase 2: first false probe names the op -----------------
        meta = runner.collector.meta
        if probes.shape[0] == len(meta):
            for j in range(probes.shape[0]):
                if probes[j, 0] < 0.5:
                    m = meta[j]
                    report.op_pos = m['pos']
                    report.op_type = m['op_type']
                    report.var = m['var']
                    report.source_loc = m['source_loc']
                    report.nonfinite_count = int(probes[j, 1])
                    report.max_abs_finite = float(probes[j, 2])
                    break
        # ---- phase 3: batch rows -------------------------------------
        _bisect_rows(runner, scope, pfeed, ctr, report, step_id,
                     sample_index_of, max_row_probes)
        return
    # window replayed clean end to end: the trip did not reproduce
    # (non-deterministic hardware fault, or state the checkpoint already
    # cleaned) — report it as such rather than inventing a culprit
    report.tripped = False


def _bisect_rows(runner, scope, pfeed, ctr, report, step_id,
                 sample_index_of, max_row_probes):
    from ..data_feeder import default_sample_index
    index_of = sample_index_of or default_sample_index
    batch = _batch_size(pfeed)
    report.batch_size = batch
    if batch is None or batch < 1:
        report.row_method = 'no_batch_axis'
        return
    # fast path: the poison is visible in the (re-poisoned) feed itself
    rows = _scan_feed_rows(pfeed, batch)
    if rows:
        report.rows = rows
        report.row_method = 'feed_scan'
        report.sample_indices = [int(index_of(step_id, r, batch))
                                 for r in rows]
        return
    # substitution probes: does removing rows clean the step?
    budget = [int(max_row_probes)]

    def clean_without(rows_out):
        if budget[0] <= 0:
            raise _BudgetSpent()
        budget[0] -= 1
        report.probe_launches += 1
        _obs.metrics.counter('recovery.forensics_probes').inc()
        ok, _, _ = runner.step(
            scope, _substitute_rows(pfeed, rows_out, batch), ctr)
        return ok

    try:
        if not clean_without(range(batch)):
            # even a fully-neutralized batch trips: the poison is in the
            # carried state (params/optimizer), not in this batch's data
            report.rows = None
            report.row_method = 'state'
            return
        culprits = _delta_rows(list(range(batch)), [], clean_without)
    except _BudgetSpent:
        report.row_method = 'substitution_budget_spent'
        return
    report.rows = sorted(int(r) for r in culprits)
    report.row_method = 'substitution'
    report.sample_indices = [int(index_of(step_id, r, batch))
                             for r in report.rows]


class _BudgetSpent(Exception):
    pass


def _delta_rows(cand, fixed, clean_without):
    """Minimal culprit set by recursive halving.  Invariant: substituting
    ``cand + fixed`` runs clean.  Returns the rows of ``cand`` that must
    stay substituted (culprits may live in both halves)."""
    if len(cand) <= 1:
        return list(cand)
    mid = len(cand) // 2
    left, right = cand[:mid], cand[mid:]
    if clean_without(left + fixed):
        return _delta_rows(left, fixed, clean_without)
    if clean_without(right + fixed):
        return _delta_rows(right, fixed, clean_without)
    lf = _delta_rows(left, right + fixed, clean_without)
    rf = _delta_rows(right, lf + fixed, clean_without)
    return lf + rf
