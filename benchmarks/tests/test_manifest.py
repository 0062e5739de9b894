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
            # the contract's keys and no other (a `group`, a `why`)
            assert set(m) - {'workloads'} == (
                {'name', 'unit', 'better', 'source', 'bound'}
                if kind == 'end_to_end' else
                {'name', 'unit', 'better', 'source', 'layer', 'moves'})
            if kind == 'end_to_end':
                assert 0.01 <= m['bound'] <= 0.1
                assert m['source'] in ('host_clock', 'device_trace')
                continue
            assert reader.META['layer'] == m['layer']
            assert reader.META['moves'] == m['moves']
            moved = e2e[m['moves']]
            # the moved metric is reported wherever this one is listed
            # (an entry that lists nothing follows it by construction)
            assert set(m.get('workloads', ())) <= set(
                moved.get('workloads', cells)), m['name']


def test_every_cell_reports_setup_another_metric_and_a_layer(manifest):
    import run
    for w in manifest['workloads']:
        e2e = [m['name'] for m in run.wanted_metrics(manifest, w['name'], 0)]
        assert 'setup_s' in e2e and len(e2e) >= 2
        assert run.wanted_metrics(manifest, w['name'], 1)


# what each cell reported at the parent of PR 26 (558fc82), by kind: the
# three cells that existed must report exactly these names still
TRAIN_LAYERS = {
    'input.wait_share', 'executor.host_ms_per_step', 'executor.stall_share',
    'executor.segment_median_rate', 'setup.compile_s', 'setup.cache_load_s',
    'step.mfu', 'kernels.pallas_share', 'device.idle_share',
    'executor.inside_host_ms_per_step', 'input.starved_share'}
SERVE_LAYERS = {
    'setup.compile_s', 'setup.cache_load_s', 'scheduler.batch_occupancy',
    'decode.step_ms', 'decode_step_roofline', 'prefill.chunk_ms',
    'ttft_p50_ms', 'ttft_p90_ms', 'serve_tokens_per_s',
    'generator.late_ms_p90', 'serve.device_idle_share',
    'scheduler.queue_wait_ms', 'scheduler.prefill_phase_ms',
    'scheduler.rounds_to_first_token', 'scheduler.host_gap_share',
    'scheduler.idle_wait_share', 'scheduler.live_slot_share',
    'prefill.host_ms_per_chunk', 'prefill.useful_token_share',
    'decode.host_ms_per_window', 'decode.kv_read_useful_share'}
WANTED = {
    'tbase.train_1chip': ({'train_rate', 'setup_s'}, TRAIN_LAYERS),
    'resnet50.train_1chip': ({'train_rate', 'setup_s'}, TRAIN_LAYERS),
    'mistral7b.chat_steady': ({'tpot_p50_ms', 'setup_s'},
                              SERVE_LAYERS | {'decode.paged_attention_share'}),
    'tbase.train_dp4': ({'train_rate', 'setup_s'},
                        TRAIN_LAYERS | {'collective.exposed_share'}),
}


@pytest.mark.parametrize('cell', sorted(WANTED))
def test_a_cell_reports_the_names_it_reported_before(manifest, cell):
    import run
    end_to_end, layers = WANTED[cell]
    assert {m['name'] for m in run.wanted_metrics(manifest, cell, 0)} \
        == end_to_end
    assert {m['name'] for m in run.wanted_metrics(manifest, cell, 1)} \
        == layers


def test_only_arithmetic_tied_to_an_architecture_lists_cells(manifest):
    """The rule that lets a later PR append a cell: a per-layer entry
    names cells only where its arithmetic belongs to one architecture or
    to a mesh, and an end-to-end entry only as the contract makes it
    ("An end-to-end metric that exists only in some cells lists them")."""
    listed = {m['name'] for m in manifest['per_layer'] if 'workloads' in m}
    assert listed == {'decode_step_roofline', 'decode.paged_attention_share',
                      'collective.exposed_share'}
    assert {m['name'] for m in manifest['end_to_end'] if 'workloads' in m} \
        == {'train_rate', 'tpot_p50_ms'}


def test_an_appended_cell_takes_its_kinds_readers_and_declines_the_rest(
        manifest):
    """wanted_metrics is a pure function of the manifest: a cell appended
    with nothing but its name in its end-to-end metric's list gets every
    reader that follows that metric, and none that lists cells."""
    import copy
    import run
    later = copy.deepcopy(manifest)
    later['workloads'].append({'name': 'moe.chat', 'config': 'moe',
                               'traffic': 'chat', 'chips': 1, 'why': 'x'})
    for m in later['end_to_end']:
        if m['name'] == 'tpot_p50_ms':
            m['workloads'].append('moe.chat')
    assert {m['name'] for m in run.wanted_metrics(later, 'moe.chat', 0)} \
        == {'tpot_p50_ms', 'setup_s'}
    assert {m['name'] for m in run.wanted_metrics(later, 'moe.chat', 1)} \
        == SERVE_LAYERS - {'decode_step_roofline'}
    # and the cells that were there report what they did
    for cell, (end_to_end, layers) in WANTED.items():
        assert {m['name'] for m in run.wanted_metrics(later, cell, 1)} \
            == layers


def test_the_four_chip_cell_keeps_the_one_chip_cells_batch_a_chip(manifest):
    cells = {w['name']: w for w in manifest['workloads']}
    import run
    one = run.load_json(BENCH, 'traffic',
                        cells['tbase.train_1chip']['traffic'] + '.json')
    four = run.load_json(BENCH, 'traffic',
                         cells['tbase.train_dp4']['traffic'] + '.json')
    assert cells['tbase.train_dp4']['chips'] == 4 == four['mesh']['data']
    assert four['batch'] == 4 * one['batch']
    for key in ('seq', 'steps_per_launch', 'launches_per_segment', 'feeds',
                'generator', 'pool_batches', 'warm_launches'):
        assert four[key] == one[key], key


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
