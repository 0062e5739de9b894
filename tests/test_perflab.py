"""Perf-lab contract tests: record schema validation, ledger
round-trip, counter-vs-timing comparison math, backend-mismatch
refusal, provenance completeness, and subprocess scenario isolation
(a hung child times out into a structured ledger record without
killing the round)."""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from paddle_tpu.observability import perflab as pl
from paddle_tpu.observability.export import SCHEMA

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERFLAB = os.path.join(REPO, 'tools', 'perflab.py')

PROV = {'backend': 'cpu', 'device_kind': 'cpu', 'platform': 'cpu',
        'jax': '0.0', 'jaxlib': '0.0', 'git_sha': 'deadbeef',
        'python': '3.10'}


def _metrics(scenario, **over):
    """A minimal valid metrics dict for a scenario: 0 for counters,
    1.0 for timings, 0 for info."""
    m = {}
    for key, spec in pl.metric_specs(scenario).items():
        m[key] = 0 if spec[0] == 'counter' else \
            (1.0 if spec[0] == 'timing' else 0)
    m.update(over)
    return m


def _rec(scenario='fused_adam_micro', prov=None, ts=1.0, **over):
    return pl.build_record(scenario, _metrics(scenario, **over),
                           prov=dict(PROV, **(prov or {})), ts=ts)


# ------------------------------------------------------------- schema
def test_every_scenario_has_a_schema_section():
    names = pl.scenario_names()
    # the run-matrix scenarios plus the tool-bridge sections
    for want in ('train_transformer', 'train_resnet', 'decode_stream',
                 'pod_parallel', 'fused_adam_micro', 'bench',
                 'serve_soak', 'pod_soak'):
        assert want in names
    for name in names:
        specs = pl.metric_specs(name)
        assert specs, name
        for key, spec in specs.items():
            assert spec[0] in ('counter', 'timing', 'info'), (name, key)
            if spec[0] in ('counter', 'timing'):
                assert spec[1] in ('lower', 'higher'), (name, key)


def test_build_record_validates_and_round_trips(tmp_path):
    path = str(tmp_path / 'ledger.jsonl')
    recs = [_rec(ts=1.0), _rec('decode_stream', ts=2.0)]
    for r in recs:
        pl.append_record(path, r)
    back = pl.read_ledger(path)
    assert back == recs
    latest = pl.latest_per_scenario(back)
    assert set(latest) == {'fused_adam_micro', 'decode_stream'}


def test_read_ledger_skips_corrupt_lines(tmp_path):
    path = str(tmp_path / 'ledger.jsonl')
    pl.append_record(path, _rec(ts=1.0))
    with open(path, 'a') as f:
        f.write('not json\n\n{"truncated": \n')
    pl.append_record(path, _rec(ts=2.0))
    back = pl.read_ledger(path)
    assert [r['ts'] for r in back] == [1.0, 2.0]


def test_unknown_scenario_and_metric_rejected():
    with pytest.raises(KeyError):
        pl.metric_specs('no_such_scenario')
    with pytest.raises(ValueError, match='unknown metric'):
        pl.build_record('fused_adam_micro',
                        dict(_metrics('fused_adam_micro'), bogus=1),
                        prov=dict(PROV))
    with pytest.raises(ValueError, match='missing metric'):
        m = _metrics('fused_adam_micro')
        del m['retraces']
        pl.build_record('fused_adam_micro', m, prov=dict(PROV))


def test_counter_must_be_int_timing_may_be_null():
    with pytest.raises(ValueError, match='int'):
        _rec(retraces=1.5)
    with pytest.raises(ValueError, match='int'):
        _rec(retraces=True)
    rec = _rec(fused_adam_ms=None)
    assert rec['metrics']['fused_adam_ms'] is None


def test_provenance_completeness_enforced():
    with pytest.raises(ValueError, match='provenance'):
        pl.validate_record(dict(_rec(), provenance=None))
    for key in pl.PROVENANCE_KEYS:
        with pytest.raises(ValueError, match=key):
            _rec(prov={key: None})


def test_error_record_validates_without_metrics():
    rec = pl.error_record('train_resnet', 'timeout', stage='warmup',
                          detail='child exceeded 5s budget', ts=3.0)
    pl.validate_record(rec)
    assert rec['error'] == 'timeout' and rec['stage'] == 'warmup'


# ----------------------------------------------------------- compare
def test_counter_regression_is_exact_zero_tolerance():
    base = _rec(ts=1.0)
    cand = _rec(ts=2.0, kernelgen_fallbacks=1)
    rep = pl.compare_records(base, cand)
    assert rep['status'] == 'regression'
    assert any('kernelgen_fallbacks' in r['metric']
               for r in rep['regressions'])
    # a 'higher'-direction counter regresses on a DROP
    b2 = _rec(ts=1.0, kernelgen_ops=4)
    c2 = _rec(ts=2.0, kernelgen_ops=3)
    assert pl.compare_records(b2, c2)['status'] == 'regression'
    # and improves (not regresses) on a rise
    c3 = _rec(ts=2.0, kernelgen_ops=5)
    rep3 = pl.compare_records(b2, c3)
    assert rep3['status'] == 'ok' and rep3['improvements']


def test_timing_is_noise_bounded_not_exact():
    base = pl.build_record(
        'fused_adam_micro', _metrics('fused_adam_micro',
                                     fused_adam_ms=1.0),
        spread={'fused_adam_ms': [1.0, 1.1]}, prov=dict(PROV), ts=1.0)
    # within the default 50% tolerance: ok
    cand = _rec(ts=2.0, fused_adam_ms=1.3)
    assert pl.compare_records(base, cand)['status'] == 'ok'
    # way past it: regression
    cand = _rec(ts=2.0, fused_adam_ms=4.0)
    rep = pl.compare_records(base, cand)
    assert rep['status'] == 'regression'
    assert any('fused_adam_ms' in r['metric'] for r in rep['regressions'])
    # a null timing on either side is skipped, never a regression
    cand = _rec(ts=2.0, fused_adam_ms=None)
    rep = pl.compare_records(base, cand)
    assert rep['status'] == 'ok'
    assert any('fused_adam_ms' in s['metric'] for s in rep['skipped'])


def test_recorded_spread_widens_timing_tolerance():
    base = pl.build_record(
        'fused_adam_micro', _metrics('fused_adam_micro',
                                     fused_adam_ms=1.0),
        spread={'fused_adam_ms': [1.0, 3.0]},  # 67% observed noise
        prov=dict(PROV), ts=1.0)
    cand = _rec(ts=2.0, fused_adam_ms=1.6)  # past 50%, inside spread
    assert pl.compare_records(base, cand)['status'] == 'ok'


def test_platform_mismatch_is_refused_not_compared():
    base = _rec(ts=1.0, prov={'platform': 'tpu', 'backend': 'tpu'})
    cand = _rec(ts=2.0)  # a cpu record
    rep = pl.compare_records(base, cand)
    assert rep['status'] == 'refused'
    assert 'backend mismatch' in rep['reason']


def test_timing_skipped_across_device_kinds_counters_still_gate():
    base = _rec(ts=1.0, prov={'device_kind': 'TPU v4',
                              'platform': 'tpu', 'backend': 'tpu'})
    cand = _rec(ts=2.0, prov={'device_kind': 'TPU v5e',
                              'platform': 'tpu', 'backend': 'tpu'},
                fused_adam_ms=99.0, retraces=3)
    rep = pl.compare_records(base, cand)
    assert rep['status'] == 'regression'  # the counter still gates
    assert any('retraces' in r['metric'] for r in rep['regressions'])
    assert any('device kind differs' in s['detail']
               for s in rep['skipped'])


def test_error_candidate_is_a_regression():
    base = _rec(ts=1.0)
    cand = pl.error_record('fused_adam_micro', 'timeout', ts=2.0)
    rep = pl.compare_records(base, cand)
    assert rep['status'] == 'regression'


def test_compare_ledger_rcs(tmp_path):
    base_doc = pl.bless([_rec(ts=1.0), _rec('train_resnet', ts=1.0)])
    # clean: rc 0
    rc, reps = pl.compare_ledger(
        base_doc, [_rec(ts=2.0), _rec('train_resnet', ts=2.0)])
    assert rc == 0 and all(r['status'] == 'ok' for r in reps)
    # regression: rc 1
    rc, _ = pl.compare_ledger(
        base_doc, [_rec(ts=2.0, retraces=1), _rec('train_resnet', ts=2.0)])
    assert rc == 1
    # a scenario missing from the ledger: rc 1
    rc, reps = pl.compare_ledger(base_doc, [_rec(ts=2.0)])
    assert rc == 1
    assert any(r['status'] == 'missing' for r in reps)
    # refusal outranks regression: rc 2
    rc, _ = pl.compare_ledger(
        base_doc,
        [_rec(ts=2.0, prov={'platform': 'tpu', 'backend': 'tpu'}),
         _rec('train_resnet', ts=2.0, retraces=1)])
    assert rc == 2
    # fail_on=None reports but never fails
    rc, _ = pl.compare_ledger(
        base_doc, [_rec(ts=2.0, retraces=1),
                   _rec('train_resnet', ts=2.0)], fail_on=None)
    assert rc == 0


def test_bless_takes_newest_non_error_record():
    doc = pl.bless([_rec(ts=1.0, retraces=0),
                    _rec(ts=2.0, retraces=2),
                    pl.error_record('fused_adam_micro', 'crash', ts=3.0)])
    assert doc['scenarios']['fused_adam_micro']['metrics']['retraces'] == 2
    assert doc['schema'] == pl.BASELINE_SCHEMA
    with pytest.raises(ValueError):
        pl.bless([pl.error_record('fused_adam_micro', 'crash', ts=1.0)])


# ------------------------------------------- subprocess isolation (CLI)
def _register_test_sections():
    """Mirror tools/perflab.py's PERFLAB_TEST_SCENARIOS=1 registration so
    this process can validate the records its CLI children produce."""
    SCHEMA.setdefault('perflab._quick', (
        ('widgets', ('counter', 'lower')),
        ('widget_ms', ('timing', 'lower', 'ms')),
        ('note', ('info',)),
    ))
    SCHEMA.setdefault('perflab._sleep', (('widgets', ('counter',
                                                      'lower')),))


def _run_cli(args, env_over=None, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               PERFLAB_TEST_SCENARIOS='1')
    env.update(env_over or {})
    return subprocess.run(
        [sys.executable, PERFLAB] + args, env=env, cwd=REPO,
        capture_output=True, text=True, timeout=timeout)


def test_hung_child_times_out_into_structured_record(tmp_path):
    _register_test_sections()
    """One hung scenario gets killed at its budget and leaves a
    {"error": "timeout"} ledger record with stage attribution — and the
    NEXT scenario in the round still runs."""
    ledger = str(tmp_path / 'ledger.jsonl')
    p = _run_cli(['run', '--scenarios', '_sleep,_quick',
                  '--ledger', ledger, '--budget-s', '10'])
    assert p.returncode == 1, p.stderr  # the round reports the failure
    recs = pl.read_ledger(ledger)
    assert [r['scenario'] for r in recs] == ['_sleep', '_quick']
    assert recs[0]['error'] == 'timeout'
    assert recs[0]['stage'] == 'sleeping'
    assert 'budget' in recs[0]['detail']
    assert 'error' not in recs[1]


def test_quick_scenario_record_has_full_provenance(tmp_path):
    _register_test_sections()
    ledger = str(tmp_path / 'ledger.jsonl')
    p = _run_cli(['run', '--scenarios', '_quick', '--ledger', ledger])
    assert p.returncode == 0, p.stderr
    rec, = pl.read_ledger(ledger)
    pl.validate_record(rec)
    prov = rec['provenance']
    for key in pl.PROVENANCE_KEYS:
        assert prov[key], key
    assert prov['platform'] == 'cpu'   # deliberate JAX_PLATFORMS=cpu run
    # and `check` accepts it
    p = _run_cli(['check', '--ledger', ledger, '--scenarios', '_quick'])
    assert p.returncode == 0, p.stderr


def test_cli_compare_gate_and_refusal(tmp_path):
    _register_test_sections()
    ledger = str(tmp_path / 'ledger.jsonl')
    baseline = str(tmp_path / 'base.json')
    p = _run_cli(['run', '--scenarios', '_quick', '--ledger', ledger])
    assert p.returncode == 0, p.stderr
    p = _run_cli(['bless', '--ledger', ledger, '--out', baseline])
    assert p.returncode == 0, p.stderr
    p = _run_cli(['compare', '--ledger', ledger, '--baseline', baseline,
                  '--fail-on', 'regression'])
    assert p.returncode == 0, p.stdout + p.stderr
    # regress the counter in a fresh ledger record -> exit 1
    rec, = pl.read_ledger(ledger)
    worse = json.loads(json.dumps(rec))
    worse['metrics']['widgets'] = 5
    worse['ts'] += 1
    pl.append_record(ledger, worse)
    p = _run_cli(['compare', '--ledger', ledger, '--baseline', baseline,
                  '--fail-on', 'regression'])
    assert p.returncode == 1, p.stdout + p.stderr
    # cpu record vs tpu-blessed baseline -> structured refusal
    doc = json.load(open(baseline))
    for r in doc['scenarios'].values():
        r['provenance'].update(platform='tpu', backend='tpu')
    json.dump(doc, open(baseline, 'w'))
    p = _run_cli(['compare', '--ledger', ledger, '--baseline', baseline,
                  '--fail-on', 'regression'])
    assert p.returncode == 2, p.stdout + p.stderr
    assert any(json.loads(l).get('status') == 'refused'
               for l in p.stdout.splitlines() if l.startswith('{'))


# --------------------------------------- int64 warn-and-truncate (bench)
def test_fill_constant_int64_overflow_is_silent():
    """The documented warn-and-truncate contract: an overflowing int64
    fill wraps like the reference C++ cast with NO numpy RuntimeWarning
    (which would be fatal under warnings-as-errors CI)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            c = layers.fill_constant(shape=[2], dtype='int64',
                                     value=2 ** 40)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with warnings.catch_warnings():
            warnings.simplefilter('error')
            out, = exe.run(main_prog, fetch_list=[c])
    # int64 stores as int32 (the TPU warn-and-truncate policy); the
    # out-of-range value truncates (wrap or saturate is backend-defined)
    # — the contract under test is that NO warning escaped above
    assert out.dtype == np.int32
    assert int(out[0]) != 2 ** 40


def test_bench_tiny_warmup_is_warning_clean():
    """The bench code path itself (model build + AMP train step) must
    survive warnings-as-errors — the regression the perf lab's CI gate
    runs under."""
    sys.path.insert(0, REPO)
    try:
        import bench
        import paddle_tpu as fluid
        with warnings.catch_warnings():
            warnings.simplefilter('error', UserWarning)
            warnings.simplefilter('error', RuntimeWarning)
            bench._tiny_warmup(fluid, 128)
    finally:
        sys.path.remove(REPO)
