"""A later PR adds configurations, traffic mixes, metrics and cells as NEW
files and APPENDED manifest entries, and edits no file that is there.
This test does exactly that in a temporary copy: the copy's manifest is
the real one with entries appended (and each new cell's name appended to
the list of the ONE end-to-end metric it reports, the form the contract
gives such a metric); every file and every entry that was there is
compared afterwards; and the new cells run on the CPU at tiny sizes,
which also checks the result line's keys.

What the new files bring: a training configuration the fall-back builds,
one that builds its own Program (builds/<config>.py) over a mesh of four
devices, a dense serving configuration, one whose builds/ file shapes a
weight the program does not name (refused, loudly), a reader that follows
`train_rate` into both training cells, and one that reads
ctx['trace']['ops'] (None on the CPU, not a crash).
"""
import copy as _copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

RESULT_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}

DRIVER = '''
import json, os, sys
sys.path.insert(0, %(root)r)
sys.path.insert(0, os.path.join(%(copy)r, 'benchmarks'))
import run
result, ctx = run.run_cell(sys.argv[1], int(sys.argv[2]), float(sys.argv[3]),
                           int(sys.argv[4]), allow_cpu=True, root=%(copy)r)
print(json.dumps(result))
'''

BUILD_TRAIN = '''"""A configuration that builds its own Program: no `model` key for the
runner's table to look up."""
SIZE_KEY = 'tokens'


def build_program(fluid, config, traffic):
    from paddle_tpu.models import transformer as tr
    out = tr.build(src_vocab=config['tokens'], trg_vocab=config['tokens'],
                   max_len=int(traffic['seq']), n_layer=config['n_layer'],
                   n_head=config['n_head'], d_model=config['d_model'],
                   d_inner=config['d_inner'], dropout=0.0, lr=config['lr'],
                   warmup_steps=config['warmup_steps'], use_flash=False)
    return out['loss']


def train_flops_per_item(config, traffic):
    return 1234.5
'''

BUILD_SERVE = '''"""Routed experts as a later PR would declare them: the dense decoder's
dict and shapes plus a router a layer, which THIS program does not name."""
from runners.serve import dense_weight_shapes, model_dict as dense_dict


def model_dict(config, traffic):
    return dict(dense_dict(config, traffic), n_expert=config['num_experts'])


def weight_shapes(model):
    shapes = dense_weight_shapes(model)
    for i in range(model['n_layer']):
        shapes['layer_%d_moe_router_w' % i] = (model['d_model'],
                                               model['n_expert'])
    return shapes
'''

NEW_CELLS = [
    {'name': 'dummy.train', 'config': 'dummy', 'traffic': 'dummy_mix',
     'chips': 1, 'why': 'test'},
    {'name': 'dummy.mesh', 'config': 'dummy_built', 'traffic': 'dummy_mesh',
     'chips': 4, 'why': 'test'},
    {'name': 'dummy.chat', 'config': 'dummy_served',
     'traffic': 'dummy_chat', 'chips': 1, 'why': 'test'},
    {'name': 'dummy.moe', 'config': 'dummy_moe', 'traffic': 'dummy_chat2',
     'chips': 1, 'why': 'test'}]
END_TO_END_OF = {'dummy.train': 'train_rate', 'dummy.mesh': 'train_rate',
                 'dummy.chat': 'tpot_p50_ms', 'dummy.moe': 'tpot_p50_ms'}


def _write(path, obj):
    with open(path, 'w') as f:
        json.dump(obj, f)


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def later_manifest(manifest):
    """The manifest as the later PR leaves it: appended entries only."""
    later = _copy.deepcopy(manifest)
    later['configs'] += [
        {'name': name, 'source': 'test', 'reduced': [], 'why': 'test',
         'file': 'benchmarks/configs/%s.json' % name}
        for name in ('dummy', 'dummy_built', 'dummy_served', 'dummy_moe')]
    later['workloads'] += NEW_CELLS
    for m in later['end_to_end']:
        m.get('workloads', []).extend(
            cell for cell, metric in END_TO_END_OF.items()
            if metric == m['name'])
    later['per_layer'] += [
        {'name': 'dummy.launches', 'unit': 'count', 'better': 'higher',
         'source': 'program_counter', 'layer': 'entry: executor and parallel '
         'executor', 'moves': 'train_rate'},
        {'name': 'dummy.ops_seen', 'unit': 'count', 'better': 'higher',
         'source': 'device_trace', 'layer': 'device',
         'moves': 'tpot_p50_ms'}]
    return later


def edits(before, after):
    """What `after` did to `before` besides appending: [] for a later PR
    that kept the rule.  Entries that were there must be deep-equal, but
    for cell names appended to a metric's `workloads`."""
    found = []
    for key, old in before.items():
        new = after.get(key)
        if not isinstance(old, list) or key in ('command', 'paths'):
            if new != old:
                found.append(key)
            continue
        if len(new) < len(old):
            found.append('%s: entries removed' % key)
        for a, b in zip(old, new):
            if a == b:
                continue
            listed, now = a.get('workloads'), b.get('workloads')
            rest = {k: v for k, v in a.items() if k != 'workloads'} \
                == {k: v for k, v in b.items() if k != 'workloads'}
            if not (rest and listed is not None and now is not None
                    and now[:len(listed)] == listed):
                found.append('%s: %s' % (key, a.get('name')))
    return found + sorted(set(after) - set(before))


@pytest.fixture(scope='module')
def copy(tmp_path_factory):
    """A copy of the benchmark with a later PR's files and entries added."""
    top = tmp_path_factory.mktemp('later_pr')
    bench = os.path.join(str(top), 'benchmarks')
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        '__pycache__', 'tests', 'data'))
    before = {}
    for folder, _, files in os.walk(bench):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, 'rb') as f:
                before[path] = f.read()

    config = _read(bench, 'configs', 'tbase.json')
    # amp off: at 64 tokens a batch bf16 rounding is as large as the
    # tolerance that the real batch of 24,576 tokens earns
    config.update(name='dummy', reference='tbase', n_layer=1, d_model=32,
                  n_head=2, d_inner=64, vocab=128, amp=False)
    _write(os.path.join(bench, 'configs', 'dummy.json'), config)
    built = dict(config, name='dummy_built', tokens=128, use_flash=False)
    del built['model']
    _write(os.path.join(bench, 'configs', 'dummy_built.json'), built)
    served = _read(bench, 'configs', 'mistral7b.json')
    served.update(name='dummy_served', reference='mistral7b', hidden_size=64,
                  intermediate_size=128, num_attention_heads=4,
                  num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)
    _write(os.path.join(bench, 'configs', 'dummy_served.json'), served)
    _write(os.path.join(bench, 'configs', 'dummy_moe.json'),
           dict(served, name='dummy_moe', num_experts=4))
    os.mkdir(os.path.join(bench, 'builds'))
    with open(os.path.join(bench, 'builds', 'dummy_built.py'), 'w') as f:
        f.write(BUILD_TRAIN)
    with open(os.path.join(bench, 'builds', 'dummy_moe.py'), 'w') as f:
        f.write(BUILD_SERVE)

    traffic = _read(bench, 'traffic', 'wmt_b96_t256.json')
    traffic.update(batch=4, seq=16, steps_per_launch=2, pool_batches=3,
                   trace_launches=2)
    _write(os.path.join(bench, 'traffic', 'dummy_mix.json'), traffic)
    mesh = _read(bench, 'traffic', 'wmt_b384_t256_dp4.json')
    mesh.update(batch=8, seq=16, steps_per_launch=2, pool_batches=3,
                trace_launches=2)
    _write(os.path.join(bench, 'traffic', 'dummy_mesh.json'), mesh)
    chat = _read(bench, 'traffic', 'chat_steady.json')
    chat.update(rate_per_s=5.0, pairs=16, shared_prefix=4,
                prompt={'median': 12, 'sigma': 0.5, 'min': 6, 'max': 30},
                output={'median': 6, 'sigma': 0.5, 'min': 3, 'max': 12},
                slots=4, slot_tokens=48, page_len=4, pages=49,
                prefill_chunk=8, decode_window=4, drain_seconds=30)
    _write(os.path.join(bench, 'traffic', 'dummy_chat.json'), chat)
    _write(os.path.join(bench, 'traffic', 'dummy_chat2.json'), chat)

    with open(os.path.join(bench, 'metrics', 'dummy.launches.py'), 'w') as f:
        f.write("META = {'name': 'dummy.launches', 'unit': 'count'}\n\n\n"
                "def read(ctx):\n"
                "    return ctx.get('launched_steps')\n")
    with open(os.path.join(bench, 'metrics', 'dummy.ops_seen.py'), 'w') as f:
        f.write("META = {'name': 'dummy.ops_seen', 'unit': 'count'}\n\n\n"
                "def read(ctx):\n"
                "    trace = ctx.get('trace')\n"
                "    return len(trace['ops']) if trace else None\n")

    manifest = _read(ROOT, 'BENCHMARK.json')
    _write(os.path.join(str(top), 'BENCHMARK.json'), later_manifest(manifest))

    driver = os.path.join(str(top), 'drive.py')
    with open(driver, 'w') as f:
        f.write(DRIVER % {'root': ROOT, 'copy': str(top)})
    yield str(top), driver
    for path, body in before.items():       # nothing that was there changed
        with open(path, 'rb') as f:
            assert f.read() == body, path


_RUNS = {}


def _start(copy, cell, trace, seconds):
    top, driver = copy
    env = dict(os.environ, JAX_PLATFORMS='cpu', PT_CACHE='0',
               JAX_COMPILATION_CACHE_DIR=os.path.join(top, '.jax_cache'))
    return subprocess.run(
        [sys.executable, driver, cell, str(2 ** 31 + 17), seconds,
         str(trace)], env=env, cwd=top, capture_output=True, text=True,
        timeout=600)


def _run(copy, cell, trace, seconds='2'):
    """(result, earlier lines) of one run, made once for the module."""
    if (cell, trace) not in _RUNS:
        done = _start(copy, cell, trace, seconds)
        assert done.returncode == 0, done.stderr[-3000:]
        lines = done.stdout.strip().splitlines()
        _RUNS[cell, trace] = json.loads(lines[-1]), lines[:-1]
    return _RUNS[cell, trace]


def test_the_later_pr_appends_and_edits_nothing(copy):
    """The deep comparison a driver would make, on the manifest the cells
    below run from."""
    before = _read(ROOT, 'BENCHMARK.json')
    after = _read(copy[0], 'BENCHMARK.json')
    assert edits(before, after) == []
    for key in ('configs', 'workloads', 'per_layer'):
        assert after[key][:len(before[key])] == before[key]
        assert len(after[key]) > len(before[key])
    # the only entries that differ: the two end-to-end lists, by a suffix
    changed = [a['name'] for a, b in zip(before['end_to_end'],
                                         after['end_to_end']) if a != b]
    assert changed == ['train_rate', 'tpot_p50_ms']


@pytest.mark.parametrize('what', ['a_bound', 'a_list_cut', 'an_entry_gone',
                                  'a_moved_entry', 'a_group_key'])
def test_the_comparison_finds_an_edit(what):
    before = _read(ROOT, 'BENCHMARK.json')
    after = later_manifest(before)
    if what == 'a_bound':
        after['end_to_end'][0]['bound'] = 0.05
    elif what == 'a_list_cut':
        after['end_to_end'][0]['workloads'] = \
            after['end_to_end'][0]['workloads'][1:]
    elif what == 'an_entry_gone':
        del after['per_layer'][3]
    elif what == 'a_moved_entry':
        after['workloads'].insert(0, after['workloads'].pop())
    else:
        after['per_layer'][0]['group'] = 'train'
    assert edits(before, after)


def test_a_new_training_cell_runs_without_an_edit(copy):
    result, earlier = _run(copy, 'dummy.train', 0)
    assert set(result) == RESULT_KEYS
    assert result['correct'] is True and result['failed'] == 0
    assert set(result['metrics']) == {'train_rate', 'setup_s'}
    for m in result['metrics'].values():
        assert set(m) == {'value', 'unit'} and m['value'] > 0
    assert set(result['device']) == {'platform', 'kind', 'count',
                                     'memory_peak_bytes'}
    # the comparison with the plain reference is said before the result
    said = [ln for ln in earlier if ln.startswith('compared: ')]
    assert said and 'references/tbase.py' in said[0]
    # the fall-back's Program hands out every token's loss, the reference
    # has probes: the two vector comparisons are made and printed
    compared = json.loads(said[0][10:])
    assert compared['checks']['every_item_loss_matches_reference'] is True
    assert compared['checks']['probed_gradients_match_reference'] is True
    assert 0 < compared['distance_per_item'] <= compared['per_item_tol']
    assert 0 < compared['distance_grads'] <= compared['grads_tol']
    assert any(ln.startswith('segments: ') for ln in earlier)


def test_the_new_metric_is_read_in_the_traced_run(copy):
    result, _ = _run(copy, 'dummy.train', 1)
    assert result['metrics']['dummy.launches']['value'] > 0
    assert {'executor.stall_share', 'input.wait_share',
            'executor.host_ms_per_step'} <= set(result['metrics'])
    # no chip, so nothing that needs a device trace is reported
    assert 'device.idle_share' not in result['metrics']
    assert 'train_rate' not in result['metrics']
    # and nothing that lists cells, or follows another end-to-end metric
    assert 'collective.exposed_share' not in result['metrics']
    assert 'scheduler.batch_occupancy' not in result['metrics']


def test_a_configuration_builds_its_own_program_over_a_mesh(copy):
    """builds/dummy_built.py and a traffic file with a mesh: the train
    runner's table is not asked (the file has no `model`), four virtual
    devices run ParallelExecutor, the loss matches the plain reference."""
    result, earlier = _run(copy, 'dummy.mesh', 1)
    assert result['correct'] is True
    assert result['metrics']['dummy.launches']['value'] > 0
    assert 'executor.inside_host_ms_per_step' in result['metrics']
    assert result['device']['count'] >= 4
    said = json.loads([ln for ln in earlier
                       if ln.startswith('compared: ')][0][10:])
    assert said['checks']['first_loss_matches_reference'] is True
    # its build file hands out the loss alone: no probes, nothing judged
    assert said['distance_per_item'] is None and said['grads_tol'] is None
    result, _ = _run(copy, 'dummy.mesh', 0)
    assert set(result['metrics']) == {'train_rate', 'setup_s'}


def test_a_configuration_that_neither_builds_nor_falls_back_is_refused(
        copy, monkeypatch):
    import run
    from runners import train
    monkeypatch.setattr(train, 'HERE', os.path.join(copy[0], 'benchmarks'))
    with pytest.raises(ValueError, match='builds/nobody.py'):
        train.program_builder({'name': 'nobody', 'model': 'mamba'}, None)
    assert train.load_build({'name': 'dummy'}) is None
    built = train.load_build({'name': 'dummy_built'})
    assert train.program_builder({'name': 'dummy_built'}, built) \
        == (built.build_program, 'tokens')
    mfu = run.load_module('metrics', 'step.mfu')
    assert mfu.flops_per_item({}, {}, built) == 1234.5
    assert mfu.flops_per_item({'model': 'mamba'}, {}, None) is None


def test_a_new_serving_cell_runs_without_an_edit(copy):
    result, earlier = _run(copy, 'dummy.chat', 0, seconds='3')
    assert set(result) == RESULT_KEYS
    assert result['correct'] is True
    assert result['attempted'] >= 3 and result['failed'] == 0
    assert set(result['metrics']) == {'tpot_p50_ms', 'setup_s'}
    assert any(ln.startswith('compared: ') for ln in earlier)


def test_the_serving_tails_are_read_in_the_traced_run(copy):
    result, _ = _run(copy, 'dummy.chat', 1, seconds='3')
    assert {'ttft_p50_ms', 'ttft_p90_ms', 'serve_tokens_per_s',
            'scheduler.batch_occupancy'} <= set(result['metrics'])
    assert result['metrics']['ttft_p90_ms']['value'] >= \
        result['metrics']['ttft_p50_ms']['value'] > 0
    assert 'tpot_p50_ms' not in result['metrics']


def test_a_reader_of_every_operation_reads_nothing_on_the_cpu(copy):
    """dummy.ops_seen follows tpot_p50_ms into the new serving cell and
    reads ctx['trace']['ops']; without a chip the trace is None: the
    metric is left out of the line, and the run does not crash."""
    import run
    later = _read(copy[0], 'BENCHMARK.json')
    wanted = {m['name'] for m in run.wanted_metrics(later, 'dummy.chat', 1)}
    assert 'dummy.ops_seen' in wanted
    # the new cell declines the dense roofline and the kernel's share:
    # their entries list cells, and it is not among them
    assert not {'decode_step_roofline', 'decode.paged_attention_share'} \
        & wanted
    result, _ = _run(copy, 'dummy.chat', 1, seconds='3')
    assert 'dummy.ops_seen' not in result['metrics']
    assert 'breakdown' not in result


def test_weights_the_program_does_not_name_are_refused(copy):
    """builds/dummy_moe.py shapes a router a layer: the serve runner takes
    the configuration's shapes, and the check against the program's own
    weight_names then fails loudly, naming the weight."""
    done = _start(copy, 'dummy.moe', 0, '2')
    assert done.returncode != 0
    assert 'weight layout drifted' in done.stderr
    assert 'layer_0_moe_router_w' in done.stderr
    assert 'which the program does not name' in done.stderr
    assert not [ln for ln in done.stdout.splitlines() if ln.startswith('{')]


@pytest.mark.parametrize('cell', ['tbase.train_1chip', 'tbase.train_dp4'])
def test_the_command_refuses_a_machine_without_a_chip(cell):
    """No accelerator: a non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, 'run.py'), '--workload',
         cell, '--seed', '1', '--seconds', '1', '--trace',
         '0'], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    assert not [ln for ln in done.stdout.splitlines()
                if ln.startswith('{')]


def test_a_cell_that_is_not_there_is_refused_at_once():
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, 'run.py'), '--workload',
         'tbase.train_dp8', '--seed', '1', '--seconds', '1', '--trace',
         '0'], env=dict(os.environ, JAX_PLATFORMS='cpu'), cwd=ROOT,
        capture_output=True, text=True, timeout=60)
    assert done.returncode == 1
    assert 'no cell' in done.stderr and not done.stdout.strip()
