"""Performance-lab record plumbing: schema-validated scenario records,
the mandatory provenance block, the append-only ledger, and the
baseline comparison math.

The lab exists because perf numbers without provenance are unreliable
evidence.  Every record written through this module carries the
platform it ACTUALLY ran on, the device kind, jax/jaxlib versions and
the git sha — and ``compare_records`` refuses to diff records from
different platforms instead of passing them.  Nothing falls back: a
tool that wants the chip and finds none fails before it writes a record
(tools/_harness.require_device).

Metric classes (declared per scenario in ``export.SCHEMA`` under the
``perflab.<scenario>`` sections — see that table for the spec
vocabulary):

  * deterministic counters — exact integers, zero tolerance: any move
    in the worse direction is a regression.  CI-enforceable on CPU.
  * timing metrics — best-of-K floats with the raw samples recorded in
    the ``spread`` block; compared only on a matching device kind,
    within a relative threshold widened by the observed spread.
  * info — descriptive context, never compared.

Consumers: ``tools/perflab.py`` (the scenario matrix CLI), and the
``maybe_ledger`` writer that bench.py / serve_soak.py / pod_soak.py
call so their telemetry lands in the same ``PERF_HISTORY.jsonl``.
"""
import json
import os
import subprocess
import sys
import time

from .export import SCHEMA

__all__ = ['RECORD_SCHEMA', 'BASELINE_SCHEMA', 'PROVENANCE_KEYS',
           'DEFAULT_TIMING_TOLERANCE', 'scenario_names', 'metric_specs',
           'git_sha', 'provenance', 'build_record', 'error_record',
           'validate_record', 'append_record', 'read_ledger',
           'latest_per_scenario', 'maybe_ledger', 'compare_records',
           'compare_ledger', 'bless']

RECORD_SCHEMA = 'perflab/1'
BASELINE_SCHEMA = 'perflab-baseline/1'
# timing thresholds are deliberately loose by default: smoke-geometry
# CPU timings in CI containers are noisy, and the zero-tolerance gate
# is the counters'.  Baselines carry per-metric overrides for the
# metrics a PR is actually expected to hold (TPU tokens/s, MFU).
DEFAULT_TIMING_TOLERANCE = 0.5

PROVENANCE_KEYS = ('backend', 'device_kind', 'platform', 'jax', 'jaxlib',
                   'git_sha', 'python')


def scenario_names():
    """Every scenario with a declared record section."""
    return sorted(k[len('perflab.'):] for k in SCHEMA
                  if k.startswith('perflab.'))


def metric_specs(scenario):
    """{metric: spec} for one scenario's record section."""
    key = 'perflab.%s' % scenario
    if key not in SCHEMA:
        raise KeyError('perflab: no SCHEMA section %r (known scenarios: %s)'
                       % (key, ', '.join(scenario_names())))
    return dict(SCHEMA[key])


def git_sha():
    """HEAD sha of the repo this module lives in; PT_GIT_SHA overrides
    (detached CI checkouts), 'unknown' when neither resolves."""
    env = os.environ.get('PT_GIT_SHA')
    if env:
        return env
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        r = subprocess.run(['git', 'rev-parse', 'HEAD'], cwd=root,
                           capture_output=True, text=True, timeout=10)
        sha = r.stdout.strip()
        if r.returncode == 0 and sha:
            return sha
    except Exception:
        pass
    return 'unknown'


def provenance():
    """The mandatory provenance block: the backend the calling process
    ACTUALLY initialized, jax/jaxlib versions and the git sha."""
    import jax
    import jaxlib
    dev0 = jax.devices()[0]
    return {
        'backend': dev0.platform,
        'platform': dev0.platform,
        'device_kind': str(dev0.device_kind),
        'jax': jax.__version__,
        'jaxlib': jaxlib.__version__,
        'git_sha': git_sha(),
        'python': '%d.%d.%d' % sys.version_info[:3],
    }


def build_record(scenario, metrics, spread=None, config=None,
                 prov=None, ts=None):
    """Assemble + validate one ledger record.  ``spread`` maps timing
    metrics to their raw best-of-K samples; ``config`` is the geometry
    the scenario ran at (compared records must match it exactly)."""
    rec = {
        'schema': RECORD_SCHEMA,
        'scenario': scenario,
        'ts': round(time.time() if ts is None else ts, 3),
        'provenance': prov if prov is not None else provenance(),
        'config': dict(config or {}),
        'metrics': dict(metrics),
        'spread': {k: list(v) for k, v in (spread or {}).items()},
    }
    validate_record(rec)
    return rec


def error_record(scenario, kind, stage=None, detail=None, prov=None,
                 ts=None):
    """A structured failure record: the scenario died (timeout, crash,
    schema violation) but the round keeps its ledger row."""
    return {
        'schema': RECORD_SCHEMA,
        'scenario': scenario,
        'ts': round(time.time() if ts is None else ts, 3),
        'provenance': prov,
        'error': kind,
        'stage': stage,
        'detail': str(detail)[:2000] if detail is not None else None,
    }


def _fail(scenario, msg):
    raise ValueError('perflab record (%s): %s' % (scenario, msg))


def validate_record(rec):
    """Validate one record against its scenario's SCHEMA section and the
    provenance contract.  Raises ValueError; returns the record."""
    if not isinstance(rec, dict):
        raise ValueError('perflab record: not a dict: %r' % type(rec))
    scenario = rec.get('scenario')
    if not scenario:
        raise ValueError('perflab record: missing "scenario"')
    if rec.get('schema') != RECORD_SCHEMA:
        _fail(scenario, 'schema %r != %r' % (rec.get('schema'),
                                             RECORD_SCHEMA))
    if not isinstance(rec.get('ts'), (int, float)):
        _fail(scenario, 'missing/non-numeric "ts"')
    if 'error' in rec:
        # failure records skip metric validation but keep the shape:
        # the {"error", "stage"} contract from tools/_harness.py
        if not rec['error']:
            _fail(scenario, 'empty "error" kind')
        return rec
    prov = rec.get('provenance')
    if not isinstance(prov, dict):
        _fail(scenario, 'missing provenance block')
    for k in PROVENANCE_KEYS:
        if k not in prov:
            _fail(scenario, 'provenance missing %r' % k)
        if prov[k] in (None, ''):
            _fail(scenario, 'provenance[%r] is null' % k)
    specs = metric_specs(scenario)
    metrics = rec.get('metrics')
    if not isinstance(metrics, dict):
        _fail(scenario, 'missing metrics block')
    unknown = set(metrics) - set(specs)
    if unknown:
        _fail(scenario, 'unknown metric keys %s' % sorted(unknown))
    missing = set(specs) - set(metrics)
    if missing:
        _fail(scenario, 'missing metric keys %s' % sorted(missing))
    for key, spec in specs.items():
        v = metrics[key]
        if spec[0] == 'counter':
            if not isinstance(v, int) or isinstance(v, bool):
                _fail(scenario, 'counter %r must be an int, got %r'
                      % (key, v))
        elif spec[0] == 'timing':
            if v is not None and not isinstance(v, (int, float)):
                _fail(scenario, 'timing %r must be a number or null, '
                      'got %r' % (key, v))
    spread = rec.get('spread', {})
    timing_keys = {k for k, s in specs.items() if s[0] == 'timing'}
    bad = set(spread) - timing_keys
    if bad:
        _fail(scenario, 'spread recorded for non-timing keys %s'
              % sorted(bad))
    return rec


# ------------------------------------------------------------- ledger
def append_record(path, rec):
    """Append one validated record to the JSONL ledger (append-only:
    history is never rewritten, a new baseline is a new bless)."""
    validate_record(rec)
    line = json.dumps(rec, sort_keys=True)
    d = os.path.dirname(os.path.abspath(path))
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, 'a') as f:
        f.write(line + '\n')
        f.flush()
        os.fsync(f.fileno())
    return rec


def read_ledger(path):
    """All parseable records, in append order.  A torn final line (a
    killed writer) is skipped, not fatal — the ledger must always be
    readable."""
    records = []
    if not os.path.exists(path):
        return records
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and rec.get('scenario'):
                records.append(rec)
    return records


def latest_per_scenario(records):
    """Newest record per scenario (append order breaks ts ties)."""
    latest = {}
    for rec in records:
        latest[rec['scenario']] = rec
    return latest


def maybe_ledger(scenario, metrics, spread=None, config=None,
                 ledger=None):
    """The shared scenario-record writer for the bench/soak tools: if a
    ledger path is given (or PT_PERF_LEDGER is set), build a provenanced
    record and append it.  Never raises — a broken ledger must not kill
    the bench that was asked to feed it."""
    path = ledger or os.environ.get('PT_PERF_LEDGER')
    if not path:
        return None
    try:
        rec = build_record(scenario, metrics, spread=spread,
                           config=config)
        return append_record(path, rec)
    except Exception as e:  # noqa: BLE001 - telemetry is best-effort here
        print('perflab: ledger append failed for %r: %s' % (scenario, e),
              file=sys.stderr)
        return None


# ------------------------------------------------------------ compare
def _rel_spread(samples):
    vals = [float(v) for v in (samples or []) if v is not None]
    if len(vals) < 2:
        return 0.0
    lo, hi = min(vals), max(vals)
    denom = max(abs(lo), abs(hi))
    return (hi - lo) / denom if denom else 0.0


def compare_records(base, cand, thresholds=None,
                    default_timing_tolerance=DEFAULT_TIMING_TOLERANCE):
    """Diff one candidate record against its baseline record.

    Returns {'scenario', 'status': 'ok'|'regression'|'refused',
    'regressions': [...], 'improvements': [...], 'skipped': [...],
    'reason': ...}.  Refusals are structural: a comparison that would
    be meaningless (different platform, different geometry) is REFUSED
    with a reason, never silently passed."""
    scenario = cand.get('scenario') or base.get('scenario')
    out = {'scenario': scenario, 'status': 'ok', 'reason': None,
           'regressions': [], 'improvements': [], 'skipped': []}

    def refuse(reason):
        out['status'] = 'refused'
        out['reason'] = reason
        return out

    if 'error' in cand:
        out['status'] = 'regression'
        out['reason'] = 'candidate is a failure record: %s (stage=%s)' % (
            cand.get('error'), cand.get('stage'))
        out['regressions'].append({'metric': '(record)', 'kind': 'error',
                                   'detail': out['reason']})
        return out
    if 'error' in base:
        return refuse('baseline is a failure record: %s'
                      % base.get('error'))
    bp, cp = base.get('provenance') or {}, cand.get('provenance') or {}
    if bp.get('platform') != cp.get('platform'):
        return refuse('backend mismatch: baseline platform %r vs '
                      'candidate %r — timings and counters are not '
                      'comparable across backends'
                      % (bp.get('platform'), cp.get('platform')))
    if (base.get('config') or {}) != (cand.get('config') or {}):
        return refuse('config mismatch: baseline %r vs candidate %r — '
                      'different geometry, not a regression signal'
                      % (base.get('config'), cand.get('config')))

    specs = metric_specs(scenario)
    thresholds = thresholds or {}
    same_device = bp.get('device_kind') == cp.get('device_kind')
    for key, spec in sorted(specs.items()):
        kind = spec[0]
        bv = (base.get('metrics') or {}).get(key)
        cv = (cand.get('metrics') or {}).get(key)
        if kind == 'info':
            continue
        if kind == 'counter':
            better = spec[1]
            delta = int(cv) - int(bv)
            worse = delta > 0 if better == 'lower' else delta < 0
            if worse:
                out['regressions'].append({
                    'metric': key, 'kind': 'counter', 'baseline': bv,
                    'candidate': cv,
                    'detail': '%s moved %+d (%s is better, zero '
                              'tolerance)' % (key, delta, better)})
            elif delta:
                out['improvements'].append({
                    'metric': key, 'kind': 'counter', 'baseline': bv,
                    'candidate': cv, 'detail': '%s moved %+d — consider '
                    're-blessing the baseline' % (key, delta)})
            continue
        # timing
        if not same_device:
            out['skipped'].append({'metric': key, 'detail':
                                   'device kind differs (%s vs %s)'
                                   % (bp.get('device_kind'),
                                      cp.get('device_kind'))})
            continue
        if bv is None or cv is None:
            out['skipped'].append({'metric': key, 'detail':
                                   'null on %s side' % (
                                       'both' if bv is None and cv is None
                                       else ('baseline' if bv is None
                                             else 'candidate'))})
            continue
        better = spec[1]
        tol = float(thresholds.get(key, default_timing_tolerance))
        tol_eff = max(tol,
                      _rel_spread((base.get('spread') or {}).get(key)),
                      _rel_spread((cand.get('spread') or {}).get(key)))
        bv, cv = float(bv), float(cv)
        if better == 'higher':
            bad = cv < bv * (1.0 - tol_eff)
            good = cv > bv * (1.0 + tol_eff)
        else:
            bad = cv > bv * (1.0 + tol_eff)
            good = cv < bv * (1.0 - tol_eff)
        entry = {'metric': key, 'kind': 'timing', 'baseline': bv,
                 'candidate': cv, 'tolerance': round(tol_eff, 4),
                 'detail': '%s %.4g -> %.4g (%s is better, tol %.0f%%)'
                           % (key, bv, cv, better, 100 * tol_eff)}
        if bad:
            out['regressions'].append(entry)
        elif good:
            out['improvements'].append(entry)
    if out['regressions']:
        out['status'] = 'regression'
    return out


def compare_ledger(baseline_doc, records, fail_on='regression',
                   scenarios=None):
    """Diff the newest ledger record per scenario against the baseline.

    Returns (rc, reports): rc 0 = clean, 1 = regression (or a scenario
    missing from the ledger), 2 = structured refusal.  ``fail_on=None``
    always returns rc 0 (report-only mode)."""
    if baseline_doc.get('schema') != BASELINE_SCHEMA:
        raise ValueError('perflab baseline: schema %r != %r'
                         % (baseline_doc.get('schema'), BASELINE_SCHEMA))
    latest = latest_per_scenario(records)
    wanted = scenarios or sorted(baseline_doc.get('scenarios', {}))
    default_tol = float(baseline_doc.get(
        'default_timing_tolerance', DEFAULT_TIMING_TOLERANCE))
    all_thresholds = baseline_doc.get('thresholds', {})
    reports = []
    for name in wanted:
        base = baseline_doc['scenarios'].get(name)
        if base is None:
            reports.append({'scenario': name, 'status': 'refused',
                            'reason': 'no baseline record', 'regressions': [],
                            'improvements': [], 'skipped': []})
            continue
        cand = latest.get(name)
        if cand is None:
            reports.append({'scenario': name, 'status': 'missing',
                            'reason': 'no ledger record for scenario',
                            'regressions': [], 'improvements': [],
                            'skipped': []})
            continue
        reports.append(compare_records(
            base, cand, thresholds=all_thresholds.get(name, {}),
            default_timing_tolerance=default_tol))
    rc = 0
    if fail_on:
        if any(r['status'] == 'refused' for r in reports):
            rc = 2
        elif any(r['status'] in ('regression', 'missing')
                 for r in reports):
            rc = 1
    return rc, reports


def bless(records, default_timing_tolerance=DEFAULT_TIMING_TOLERANCE,
          thresholds=None):
    """Build a baseline doc from the newest non-error record per
    scenario (how a new baseline is committed — see docs/perflab.md)."""
    latest = latest_per_scenario(
        [r for r in records if 'error' not in r])
    if not latest:
        raise ValueError('perflab bless: no non-error records to bless')
    for rec in latest.values():
        validate_record(rec)
    return {
        'schema': BASELINE_SCHEMA,
        'blessed_ts': round(time.time(), 3),
        'blessed_git_sha': git_sha(),
        'default_timing_tolerance': default_timing_tolerance,
        'thresholds': dict(thresholds or {}),
        'scenarios': latest,
    }
