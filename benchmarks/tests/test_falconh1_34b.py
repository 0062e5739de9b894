"""The `falconh1_34b` configuration's own files (PR 32): its build file, its
plain reference and its three metric readers.

The cell itself runs on the CPU in a temporary copy of the benchmark whose
configuration and traffic files are overridden to tiny sizes (float32, so
the comparison with the reference is tight); the arithmetic of the build
file and of the readers is checked at the PUBLISHED sizes and on synthetic
contexts; and the configuration file is held to the catalog row it was
copied from.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
from test_add_files_only import DRIVER, _read, _write

import run

CELL = 'falconh1_34b.chat_long_answers'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
TINY = {'hidden_size': 32, 'head_dim': 16, 'num_attention_heads': 4,
        'num_key_value_heads': 2, 'intermediate_size': 64, 'vocab_size': 256,
        'num_hidden_layers': 2, 'mamba_d_ssm': 48, 'mamba_n_heads': 6,
        'mamba_d_head': 8, 'mamba_n_groups': 2, 'mamba_d_state': 8,
        'mamba_chunk_size': 4, 'torch_dtype': 'float32'}
TINY_TRAFFIC = {'rate_per_s': 5.0, 'pairs': 16,
                'prompt': {'median': 12, 'sigma': 0.5, 'min': 6, 'max': 30},
                'output': {'median': 6, 'sigma': 0.5, 'min': 3, 'max': 12},
                'slots': 4, 'slot_tokens': 48, 'page_len': 4, 'pages': 49,
                'prefill_chunk': 8, 'decode_window': 4, 'drain_seconds': 30}

@pytest.fixture(scope='module')
def build():
    return run.load_module('builds', 'falconh1_34b')


@pytest.fixture(scope='module')
def model(build):
    return build.model_dict(_read(BENCH, 'configs', 'falconh1_34b.json'),
                            _read(BENCH, 'traffic', 'chat_long_answers.json'))


@pytest.fixture(scope='module')
def tiny_copy(tmp_path_factory):
    top = str(tmp_path_factory.mktemp('falconh1_tiny'))
    bench = os.path.join(top, 'benchmarks')
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        '__pycache__', 'tests', 'data'))
    for folder, name, override in (
            ('configs', 'falconh1_34b.json', TINY),
            ('traffic', 'chat_long_answers.json', TINY_TRAFFIC)):
        body = _read(bench, folder, name)
        body.update(override)
        _write(os.path.join(bench, folder, name), body)
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), top)
    with open(os.path.join(top, 'drive.py'), 'w') as f:
        f.write(DRIVER % {'root': ROOT, 'copy': top})
    return top


def _run(top, trace):
    env = dict(os.environ, JAX_PLATFORMS='cpu', PT_CACHE='0',
               JAX_COMPILATION_CACHE_DIR=os.path.join(top, '.jax_cache'))
    done = subprocess.run(
        [sys.executable, os.path.join(top, 'drive.py'), CELL,
         str(2 ** 31 + 32), '3', str(trace)], env=env, cwd=top,
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines[:-1]


def test_the_cell_runs_on_the_cpu_at_a_tiny_size(tiny_copy):
    result, earlier = _run(tiny_copy, 0)
    assert result['correct'] is True
    assert result['attempted'] >= 3 and result['failed'] == 0
    assert set(result['metrics']) == {'tpot_p50_ms', 'setup_s'}
    said = json.loads([ln for ln in earlier
                       if ln.startswith('compared: ')][0][10:])
    assert 'references/falconh1_34b.py' in said['reference']
    # float32 end to end: the program's chunked and stepwise scans against
    # the reference's sequential one
    assert 0 < said['worst_rel_err'] < 1e-3 < said['rtol']


def test_the_traced_run_reads_what_a_cpu_can_give(tiny_copy):
    """The counter's share is read; the two device_trace readers find no
    trace on the CPU and are left out, without a crash; the cell takes
    every unlisted reader of tpot_p50_ms and declines the dense decoder's
    listed ones."""
    manifest = _read(tiny_copy, 'BENCHMARK.json')
    wanted = {m['name'] for m in run.wanted_metrics(manifest, CELL, 1)}
    assert {'falconh1_34b.decode_step_roofline', 'falconh1_34b.ssm_share',
            'falconh1_34b.state_useful_share', 'decode.step_ms',
            'scheduler.live_slot_share'} <= wanted
    assert not {'decode_step_roofline', 'decode.paged_attention_share'} \
        & wanted
    other = {m['name'] for m in run.wanted_metrics(
        manifest, 'mistral7b.chat_steady', 1)}
    assert not any(name.startswith('falconh1_34b.') for name in other)
    result, _ = _run(tiny_copy, 1)
    assert result['correct'] is True
    share = result['metrics']['falconh1_34b.state_useful_share']
    assert share['unit'] == '%' and 0 < share['value'] <= 100
    live = result['metrics']['scheduler.live_slot_share']['value']
    assert share['value'] == pytest.approx(live)
    assert 'falconh1_34b.decode_step_roofline' not in result['metrics']
    assert 'falconh1_34b.ssm_share' not in result['metrics']


def test_the_configuration_holds_every_published_number():
    config = _read(BENCH, 'configs', 'falconh1_34b.json')
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r['name'] == 'Falcon-H1-34B-Instruct')
    assert config['source'] == row['source_url']
    differs = sorted(k for k, v in row['config'].items()
                     if config.get(k, 'missing') != v)
    assert differs == config['reduced'] == ['num_hidden_layers']
    assert config['num_hidden_layers'] == 6 and row['layers'] == 72
    entry = next(c for c in _read(ROOT, 'BENCHMARK.json')['configs']
                 if c['name'] == 'falconh1_34b')
    assert entry['source'] == row['source_url']
    assert entry['reduced'] == config['reduced']


def test_shapes_are_the_programs_names_at_the_published_widths(build, model):
    from paddle_tpu.serving.generation import weight_names
    shapes = build.weight_shapes(model)
    assert sorted(shapes) == sorted(weight_names(model))
    assert shapes['layer_0_att_o_w'] == (2560, 5120)        # 20 x 128 != D
    assert shapes['layer_0_ssm_in_w'] == (5120, 9248)
    assert shapes['layer_5_ssm_conv_w'] == (4, 5120)
    assert shapes['lm_proj_w'] == (5120, 261120)

    def count(names):
        total = 0
        for n in names:
            size = 1
            for extent in shapes[n]:
                size *= extent
            total += size
        return total
    layer = [n for n in shapes if n.startswith('layer_0_')]
    assert round(count(layer) / 1e6, 1) == 430.1
    assert round(2 * count(shapes) / 1e9, 2) == 10.51       # bf16, all six
    assert build.state_bytes_per_slot(model) == 6 * 4 * (
        32 * 128 * 256 + 3 * 5120)


def test_bytes_per_decode_step_counts_live_state_twice(build, model):
    idle = build.bytes_per_decode_step(model, 0, 0)
    assert round(idle / 1e9, 2) == 7.83          # six blocks and the head
    one = build.bytes_per_decode_step(model, 1, 0) - idle
    assert one == 2 * build.state_bytes_per_slot(model) + 2 * 5120
    token = build.bytes_per_decode_step(model, 0, 1) - idle
    assert token == 2 * 6 * 4 * 128 * 2                     # 12,288 B


def _ctx(model, build, ops, step_ms=16.0):
    K, launches = 8, 10
    return {
        'model': model, 'build': build,
        'traffic': {'decode_window': K},
        'windows': [(20, 12000)] * 4,
        'peaks': {'hbm_bytes_per_s': 819e9, 'bf16_flops': 197e12},
        'counters': {'generation.state_slot_steps': 3200.0,
                     'generation.state_live_slot_steps': 2000.0},
        'trace': {'busy_s': 2.0, 'ops': ops, 'modules': {
            'jit_window': {'seconds': step_ms * 1e-3 * K * launches,
                           'count': launches}}}}


def test_the_three_readers_on_a_synthetic_context(build, model):
    ops = {'fusion:Loop f32[32,6,32,128,256]': {'seconds': 0.3, 'count': 60},
           'fusion f32[32,32,128,256]': {'seconds': 0.1, 'count': 60},
           'fusion:Loop f32[32,32,128]': {'seconds': 0.2, 'count': 60},
           'custom-call paged_attention f32[32,20,128]':
               {'seconds': 0.5, 'count': 60},
           'fusion:Output bf16[32,4,128]': {'seconds': 0.5, 'count': 60},
           'fusion:Output bf16[32,21504]': {'seconds': 1.0, 'count': 60}}
    ctx = _ctx(model, build, ops)
    roofline = run.load_module('metrics',
                               'falconh1_34b.decode_step_roofline')
    least = build.bytes_per_decode_step(model, 20, 12000 + 20 * 3.5) / 819e9
    assert roofline.read(ctx) == pytest.approx(100 * least / 16e-3)
    assert 50 < roofline.read(ctx) < 100
    share = run.load_module('metrics', 'falconh1_34b.ssm_share')
    assert share.read(ctx) == pytest.approx(100 * 0.6 / 2.0)
    useful = run.load_module('metrics', 'falconh1_34b.state_useful_share')
    assert useful.read(ctx) == pytest.approx(62.5)


def test_the_readers_return_nothing_where_there_is_nothing_to_read(build,
                                                                  model):
    """The parent's program under this PR's benchmark files, a dense
    model, a CPU run: None, never a raise."""
    readers = [run.load_module('metrics', 'falconh1_34b.' + name)
               for name in ('decode_step_roofline', 'ssm_share',
                            'state_useful_share')]
    dense = {k: v for k, v in model.items() if k != 'ssm'}
    for ctx in (
            dict(_ctx(model, build, {}), trace=None, counters={}),
            dict(_ctx(dense, None, {'fusion bf16[32,4096]':
                                    {'seconds': 1.0, 'count': 1}}),
                 counters={'generation.decode_slot_steps': 10.0})):
        assert [r.read(ctx) for r in readers] == [None, None, None]
