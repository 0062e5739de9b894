"""A later PR adds a configuration, a traffic mix, a metric and a cell as
NEW files and new manifest entries, and edits nothing that is there.  This
test does exactly that in a temporary copy and runs the new cells on the
CPU at tiny sizes, which also checks the result line's keys."""
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


def _write(path, obj):
    with open(path, 'w') as f:
        json.dump(obj, f)


@pytest.fixture(scope='module')
def copy(tmp_path_factory):
    """A copy of the benchmark with four files and four entries added."""
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

    with open(os.path.join(bench, 'configs', 'tbase.json')) as f:
        config = json.load(f)
    # amp off: at 64 tokens a batch bf16 rounding is as large as the
    # tolerance that the real batch of 24,576 tokens earns
    config.update(name='dummy', reference='tbase', n_layer=1, d_model=32,
                  n_head=2, d_inner=64, vocab=128, amp=False)
    _write(os.path.join(bench, 'configs', 'dummy.json'), config)
    with open(os.path.join(bench, 'configs', 'mistral7b.json')) as f:
        served = json.load(f)
    served.update(name='dummy_served', reference='mistral7b', hidden_size=64,
                  intermediate_size=128, num_attention_heads=4,
                  num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)
    _write(os.path.join(bench, 'configs', 'dummy_served.json'), served)

    with open(os.path.join(bench, 'traffic', 'wmt_b96_t256.json')) as f:
        traffic = json.load(f)
    traffic.update(batch=4, seq=16, steps_per_launch=2, pool_batches=3,
                   trace_launches=2)
    _write(os.path.join(bench, 'traffic', 'dummy_mix.json'), traffic)
    with open(os.path.join(bench, 'traffic', 'chat_steady.json')) as f:
        chat = json.load(f)
    chat.update(rate_per_s=5.0, pairs=16, shared_prefix=4,
                prompt={'median': 12, 'sigma': 0.5, 'min': 6, 'max': 30},
                output={'median': 6, 'sigma': 0.5, 'min': 3, 'max': 12},
                slots=4, slot_tokens=48, page_len=4, pages=49,
                prefill_chunk=8, decode_window=4, drain_seconds=30)
    _write(os.path.join(bench, 'traffic', 'dummy_chat.json'), chat)

    with open(os.path.join(bench, 'metrics', 'dummy.launches.py'), 'w') as f:
        f.write("META = {'name': 'dummy.launches', 'unit': 'count'}\n\n\n"
                "def read(ctx):\n"
                "    return ctx.get('launched_steps')\n")

    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        manifest = json.load(f)
    manifest['configs'] += [
        {'name': 'dummy', 'source': 'test', 'reduced': [], 'why': 'test',
         'file': 'benchmarks/configs/dummy.json'},
        {'name': 'dummy_served', 'source': 'test', 'reduced': [],
         'why': 'test', 'file': 'benchmarks/configs/dummy_served.json'}]
    manifest['workloads'] += [
        {'name': 'dummy.train', 'config': 'dummy', 'traffic': 'dummy_mix',
         'chips': 1, 'why': 'test'},
        {'name': 'dummy.chat', 'config': 'dummy_served',
         'traffic': 'dummy_chat', 'chips': 1, 'why': 'test'}]
    for m in manifest['end_to_end'] + manifest['per_layer']:
        if 'workloads' not in m:
            continue
        family = 'dummy.chat' if 'mistral7b.chat_steady' in m['workloads'] \
            else 'dummy.train'
        m['workloads'] = m['workloads'] + [family]
    manifest['per_layer'].append(
        {'name': 'dummy.launches', 'unit': 'count', 'better': 'higher',
         'source': 'program_counter', 'layer': 'entry: executor and parallel '
         'executor', 'moves': 'train_rate', 'workloads': ['dummy.train']})
    _write(os.path.join(str(top), 'BENCHMARK.json'), manifest)

    driver = os.path.join(str(top), 'drive.py')
    with open(driver, 'w') as f:
        f.write(DRIVER % {'root': ROOT, 'copy': str(top)})
    yield str(top), driver
    for path, body in before.items():       # nothing that was there changed
        with open(path, 'rb') as f:
            assert f.read() == body, path


def _run(copy, cell, trace, seconds='2'):
    top, driver = copy
    env = dict(os.environ, JAX_PLATFORMS='cpu', PT_CACHE='0',
               JAX_COMPILATION_CACHE_DIR=os.path.join(top, '.jax_cache'))
    done = subprocess.run(
        [sys.executable, driver, cell, str(2 ** 31 + 17), seconds,
         str(trace)], env=env, cwd=top, capture_output=True, text=True,
        timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines[:-1]


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
    assert any(ln.startswith('segments: ') for ln in earlier)


def test_the_new_metric_is_read_in_the_traced_run(copy):
    result, _ = _run(copy, 'dummy.train', 1)
    assert result['metrics']['dummy.launches']['value'] > 0
    assert {'executor.stall_share', 'input.wait_share',
            'executor.host_ms_per_step'} <= set(result['metrics'])
    # no chip, so nothing that needs a device trace is reported
    assert 'device.idle_share' not in result['metrics']
    assert 'train_rate' not in result['metrics']


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


def test_the_command_refuses_a_machine_without_a_chip():
    """No accelerator: a non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, 'run.py'), '--workload',
         'tbase.train_1chip', '--seed', '1', '--seconds', '1', '--trace',
         '0'], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    assert not [ln for ln in done.stdout.splitlines()
                if ln.startswith('{')]
