"""BENCHMARK.json against the files it names and the contract's shape."""
import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')


@pytest.fixture(scope='module')
def manifest():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


def test_keys_and_limits(manifest):
    assert set(manifest) == {'command', 'paths', 'run_seconds', 'configs',
                             'workloads', 'end_to_end', 'per_layer'}
    assert manifest['paths'] == ['benchmarks']
    assert 1 <= manifest['run_seconds'] <= 51
    cells = manifest['workloads']
    assert (2 + 14 * 24) * (manifest['run_seconds'] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert sum(c['chips'] == 4 for c in cells) <= max(1, len(cells) // 4)
    assert os.path.getsize(os.path.join(ROOT, 'BENCHMARK.json')) < 64 * 1024


def test_names_units_and_files(manifest):
    configs = {c['name']: c for c in manifest['configs']}
    for c in manifest['configs']:
        assert NAME.match(c['name']) and set(c) == {
            'name', 'source', 'file', 'reduced', 'why'}
        with open(os.path.join(ROOT, c['file'])) as f:
            body = json.load(f)
        assert body['reduced'] == c['reduced']
        assert os.path.exists(os.path.join(
            BENCH, 'runners', body['runner'] + '.py'))
        assert os.path.exists(os.path.join(
            BENCH, 'references', body.get('reference', c['name']) + '.py'))
    seen = set()
    for w in manifest['workloads']:
        assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert NAME.match(w['name']) and NAME.match(w['traffic'])
        assert w['config'] in configs and w['chips'] in (1, 4)
        assert len(w['why']) <= 200 and '\n' not in w['why']
        assert (w['config'], w['traffic']) not in seen
        seen.add((w['config'], w['traffic']))
        assert os.path.exists(os.path.join(BENCH, 'traffic',
                                           w['traffic'] + '.json'))
    assert {w['config'] for w in manifest['workloads']} == set(configs)


def test_every_metric_has_a_reader_that_agrees(manifest):
    import run
    e2e = {m['name']: m for m in manifest['end_to_end']}
    assert 'setup_s' in e2e and e2e['setup_s']['bound'] <= 0.1
    cells = [w['name'] for w in manifest['workloads']]
    for kind in ('end_to_end', 'per_layer'):
        for m in manifest[kind]:
            assert NAME.match(m['name']) and UNIT.match(m['unit'])
            assert m['better'] in ('lower', 'higher')
            reader = run.load_module('metrics', m['name'])
            assert reader is not None, m['name']
            for key in ('unit', 'better', 'source'):
                assert reader.META[key] == m[key], (m['name'], key)
            assert set(m.get('workloads', cells)) <= set(cells)
            if kind == 'end_to_end':
                assert 0.01 <= m['bound'] <= 0.1
                assert m['source'] in ('host_clock', 'device_trace')
                continue
            assert reader.META['layer'] == m['layer']
            assert reader.META['moves'] == m['moves']
            moved = e2e[m['moves']]
            # the moved metric is reported wherever this one is
            assert set(m.get('workloads', cells)) <= set(
                moved.get('workloads', cells)), m['name']


def test_every_cell_reports_setup_another_metric_and_a_layer(manifest):
    import run
    for w in manifest['workloads']:
        e2e = [m['name'] for m in run.wanted_metrics(manifest, w['name'], 0)]
        assert 'setup_s' in e2e and len(e2e) >= 2
        assert run.wanted_metrics(manifest, w['name'], 1)


def test_a_configuration_may_set_only_the_named_switches(manifest,
                                                         monkeypatch):
    """`env` in a configuration file is a closed list (run.py's
    PROGRAM_SWITCHES): every file keeps to it, and another key is refused
    before anything runs."""
    import run
    for c in manifest['configs']:
        with open(os.path.join(ROOT, c['file'])) as f:
            assert set(json.load(f).get('env', {})) <= set(
                run.PROGRAM_SWITCHES)
    real = run.load_json

    def with_another_switch(*parts):
        body = real(*parts)
        if parts[-1] == 'tbase.json':
            body = dict(body, env={'PT_OBS': '0'})
        return body
    monkeypatch.setattr(run, 'prepare_environment', lambda: None)
    monkeypatch.setattr(run, 'load_json', with_another_switch)
    monkeypatch.delenv('PT_OBS', raising=False)
    with pytest.raises(SystemExit, match='PT_OBS'):
        run.load_cell(ROOT, 'tbase.train_1chip')
    assert 'PT_OBS' not in os.environ
