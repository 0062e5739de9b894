"""The `lfm2_8b_a1b` configuration's own files (PR 63): its build file, its
plain reference with its controls, its traffic file and its seven metric
readers.

The cell itself runs on the CPU in a temporary copy of the benchmark whose
configuration and traffic files are overridden to tiny sizes (float32, so the
comparison with the reference is tight and no near-tie flips a pick); the
arithmetic of the build file and of the readers is checked at the PUBLISHED
sizes and on synthetic contexts; and the configuration file is held to the
catalog row it was copied from.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT
from test_add_files_only import DRIVER, _read, _write, edits

import run

CELL = 'lfm2_8b_a1b.many_streams_medium_prompts'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
TINY = {'hidden_size': 32, 'num_attention_heads': 4,
        'num_key_value_heads': 2, 'intermediate_size': 48,
        'moe_intermediate_size': 24, 'num_experts': 8,
        'num_experts_per_tok': 2, 'vocab_size': 256,
        'num_hidden_layers': 8,
        'layer_types': ['conv', 'conv', 'full_attention', 'conv'] * 2,
        'torch_dtype': 'float32', 'initializer_range': 0.3}
TINY_TRAFFIC = {'rate_per_s': 5.0, 'pairs': 16, 'shared_prefix': 0,
                'prompt': {'median': 16, 'sigma': 0.5, 'min': 6, 'max': 30},
                'output': {'median': 6, 'sigma': 0.5, 'min': 3, 'max': 12},
                'slots': 4, 'slot_tokens': 48, 'page_len': 4, 'pages': 49,
                'prefill_chunk': 8, 'decode_window': 4, 'drain_seconds': 30}
READERS = ('decode_step_roofline', 'paged_attention_roofline',
           'attention_share', 'shortconv_share', 'moe_share',
           'expert_touched_share', 'state_useful_share')


@pytest.fixture(scope='module')
def build():
    return run.load_module('builds', 'lfm2_8b_a1b')


@pytest.fixture(scope='module')
def model(build):
    return build.model_dict(_read(BENCH, 'configs', 'lfm2_8b_a1b.json'),
                            _read(BENCH, 'traffic',
                                  'many_streams_medium_prompts.json'))


@pytest.fixture(scope='module')
def tiny_copy(tmp_path_factory):
    top = str(tmp_path_factory.mktemp('lfm2_8b_a1b_tiny'))
    bench = os.path.join(top, 'benchmarks')
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        '__pycache__', 'tests', 'data'))
    for folder, name, override in (
            ('configs', 'lfm2_8b_a1b.json', TINY),
            ('traffic', 'many_streams_medium_prompts.json', TINY_TRAFFIC)):
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
         str(2 ** 31 + 63), '3', str(trace)], env=env, cwd=top,
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
    assert 'references/lfm2_8b_a1b.py' in said['reference']
    # float32 end to end: chunked prefill (the shifted multiply-add from the
    # slot's tail, the gathered attention), a decode window (the step, the
    # paged kernel in interpret mode) and one more chunk through pool and
    # tails against the reference's full forward
    assert 0 < said['worst_rel_err'] < 1e-3 < said['rtol']
    routing = [json.loads(ln[9:]) for ln in earlier
               if ln.startswith('routing: ')]
    assert len(routing) == 4 and all(
        len(r['margin_at_compared_position']) == 6 for r in routing)
    assert all(r['compared_with_logits'] for r in routing)


def test_the_traced_run_reads_what_a_cpu_can_give(tiny_copy):
    """The counters' ratios are read; the five device_trace readers find no
    trace on the CPU and are left out, without a crash; the cell takes every
    unlisted reader of tpot_p50_ms and declines the other models' listed
    ones, and no other cell takes this one's."""
    manifest = _read(tiny_copy, 'BENCHMARK.json')
    wanted = {m['name'] for m in run.wanted_metrics(manifest, CELL, 1)}
    assert {'lfm2_8b_a1b.' + name for name in READERS} <= wanted
    assert {'decode.step_ms', 'decode.kv_read_useful_share',
            'scheduler.live_slot_share'} <= wanted
    assert not {'decode_step_roofline', 'decode.paged_attention_share',
                'falconh1_34b.ssm_share', 'axk1.moe_share',
                'kimi_linear.kda_share'} & wanted
    for cell in ('mistral7b.chat_steady', 'falconh1_34b.chat_long_answers',
                 'axk1.shared_context_answers',
                 'kimi_linear.many_streams_long_answers'):
        other = {m['name'] for m in run.wanted_metrics(manifest, cell, 1)}
        assert not any(name.startswith('lfm2_8b_a1b.') for name in other)
    result, _ = _run(tiny_copy, 1)
    assert result['correct'] is True
    # the tails have no in-place kernel: every slot's are read and written
    useful = result['metrics']['lfm2_8b_a1b.state_useful_share']
    assert 0 < useful['value'] < 100.0
    touched = result['metrics']['lfm2_8b_a1b.expert_touched_share']
    assert touched['unit'] == '%' and 0 < touched['value'] <= 100.0
    # the step attends in place: whole pages of the live tokens
    assert result['metrics']['decode.kv_read_useful_share']['value'] > 50
    for name in READERS[:5]:
        assert 'lfm2_8b_a1b.' + name not in result['metrics']


def test_the_control_script_rehearses_at_a_tiny_size(tiny_copy):
    """benchmarks/tests/lfm2_8b_a1b_control.py, the chip run's controls, on
    the CPU: float32, so every control is far out."""
    tests = os.path.join(tiny_copy, 'benchmarks', 'tests')
    os.makedirs(tests, exist_ok=True)
    shutil.copy(os.path.join(BENCH, 'tests', 'lfm2_8b_a1b_control.py'),
                tests)
    env = dict(os.environ, JAX_PLATFORMS='cpu', PT_CACHE='0',
               PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=os.path.join(tiny_copy,
                                                      '.jax_cache'))
    done = subprocess.run(
        [sys.executable, os.path.join(tests, 'lfm2_8b_a1b_control.py'),
         '--seed', str(2 ** 31 + 64), '--allow-cpu'], env=env, cwd=tiny_copy,
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    summary = json.loads([ln for ln in lines
                          if ln.startswith('summary: ')][0][9:])
    assert summary['sound_worst'] < 1e-3
    prompts = [json.loads(ln[8:]) for ln in lines
               if ln.startswith('prompt: ')]
    assert len(prompts) == 4
    ref = run.load_module('references', 'lfm2_8b_a1b')
    for row in prompts:
        assert min(row[c] for c in ref.CONTROLS) > 5 * row['sound']


def test_the_manifest_gained_entries_and_lost_none():
    """What this PR did to BENCHMARK.json, against the parent's copy in git
    where there is one: appended entries and the cell's name in
    tpot_p50_ms's list."""
    done = subprocess.run(['git', 'show', 'HEAD:BENCHMARK.json'], cwd=ROOT,
                          capture_output=True, text=True)
    if done.returncode != 0:
        pytest.skip('no git history here')
    before, after = json.loads(done.stdout), _read(ROOT, 'BENCHMARK.json')
    assert edits(before, after) == []
    cell = next(c for c in after['workloads'] if c['name'] == CELL)
    assert cell['chips'] == 1 and len(cell['why']) <= 200
    mine = [m for m in after['per_layer']
            if m['name'].startswith('lfm2_8b_a1b.')]
    assert [m['name'] for m in mine] \
        == ['lfm2_8b_a1b.' + name for name in READERS]
    assert all(m['workloads'] == [CELL] for m in mine)


def test_the_configuration_holds_every_published_number():
    config = _read(BENCH, 'configs', 'lfm2_8b_a1b.json')
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r['name'] == 'LFM2-8B-A1B')
    assert config['source'] == row['source_url']
    differs = sorted(k for k, v in row['config'].items()
                     if config.get(k, 'missing') != v)
    assert differs == sorted(config['reduced']) == [
        'layer_types', 'num_hidden_layers']
    published = row['config']['layer_types']
    kept = config['num_hidden_layers']
    assert config['layer_types_published'] == published
    assert config['layer_types'] == published[:kept]
    assert (kept, config['num_hidden_layers_published'], row['layers']) \
        == (16, 24, 24)
    # the published three to one, in whole periods of four
    assert config['layer_types'].count('full_attention') * 3 \
        == config['layer_types'].count('conv') == 12
    assert published.count('full_attention') * 3 == published.count('conv')
    # the floors: a whole period, four layers after the dense ones, every
    # expert, the whole vocabulary
    assert kept - config['num_dense_layers'] >= 4 and kept % 4 == 0
    assert config['num_experts'] == row['config']['num_experts'] == 32
    assert config['vocab_size'] == row['vocab_size'] == 65536
    assert {'rotary_pairs', 'tie_word_embeddings', 'choice_bias',
            'conv_filter', 'initializer_range', 'weights', 'torch_dtype',
            'context'} <= set(config['assumed'])
    entry = next(c for c in _read(ROOT, 'BENCHMARK.json')['configs']
                 if c['name'] == 'lfm2_8b_a1b')
    assert entry['source'] == row['source_url']
    assert entry['reduced'] == config['reduced']


def test_the_build_file_counts_what_the_issue_counts(build, model):
    """ISSUE 63, section 2: 11.07 GB of weights, 8 kB of pool a token, 196 kB
    of tails a stream (two rows kept), 2.82 GB of pages."""
    assert model['mixer'] == ['conv', 'conv', 'gqa', 'conv'] * 4
    assert model['ffn'] == ['dense'] * 2 + ['experts'] * 14
    assert model['head_dim'] == 64 and model['qk_norm']
    assert model['moe']['n_shared'] == 0 and model['moe']['ranks'] == 1
    assert model['moe']['norm_eps'] == 1e-6 and model['rms_eps'] == 1e-5
    shapes = build.weight_shapes(model)
    from paddle_tpu.serving.generation import weight_shapes
    assert shapes == weight_shapes(model)
    import numpy as np
    params = sum(int(np.prod(s)) for s in shapes.values())
    assert round(params / 1e9, 2) == 5.53
    assert round(2 * params / 1e9, 2) == 11.07
    assert build.expert_bytes(model) == 3 * 2048 * 1792 * 2
    assert build.kv_bytes_per_token(model) == 2048
    assert build.attention_layers(model) * build.kv_bytes_per_token(model) \
        == 8192
    assert build.tail_bytes(model) == 2 * 2048 * 4
    assert build.conv_layers(model) * build.tail_bytes(model) == 196608
    t = _read(BENCH, 'traffic', 'many_streams_medium_prompts.json')
    assert round(t['pages'] * t['page_len'] * 8192 / 1e9, 2) == 2.82
    # a step at 40 live streams over 50k cached tokens, 31 of 32 experts a
    # layer touched: resident 1.2 GB, experts 9.6 GB
    live, touched = 40, 14 * 31
    total = build.bytes_per_decode_step(model, live, 50000, touched)
    resident = build.resident_bytes(model)
    assert 0.9e9 < resident < 1.3e9
    assert total == pytest.approx(
        resident + touched * build.expert_bytes(model) + live * 2048 * 2
        + 2 * 196608 * live + 8192 * 50000)
    assert touched * build.expert_bytes(model) / total > 0.85
    assert build.attention_bytes(model, 10) == 4 * 2048 * 10


def _ctx(build, model, ops, counters, windows, busy=1.0):
    return {'build': build, 'model': model,
            'traffic': {'decode_window': 8, 'slots': 96,
                        'prefill_chunk': 512},
            'peaks': {'hbm_bytes_per_s': 819e9, 'bf16_flops': 197e12},
            'counters': counters, 'windows': windows,
            'trace': {'busy_s': busy, 'ops': ops,
                      'modules': {'jit_window': {'seconds': 1.2,
                                                 'count': 10}}}}


def test_the_readers_arithmetic_on_a_synthetic_trace(build, model):
    ops = {
        'custom-call paged_attention bf16[96,32,128]': {'seconds': 0.02,
                                                        'count': 320},
        # the projection into B, C and x, a step's and a chunk's
        'fusion f32[96,6144]': {'seconds': 0.04, 'count': 960},
        'fusion f32[512,6144]': {'seconds': 0.03, 'count': 24},
        # the taps over [tail ; u] and the tail written back
        'fusion f32[96,3,2048]': {'seconds': 0.01, 'count': 960},
        'fusion f32[96,12,2,2048]': {'seconds': 0.01, 'count': 960},
        'fusion f32[514,2048]': {'seconds': 0.005, 'count': 24},
        # a decode step's batched expert products at 96 slots
        'fusion:Output convolution_multiply_fusion f32[32,64,2048]':
            {'seconds': 0.2, 'count': 1120},
        'fusion f32[32,64,1792]': {'seconds': 0.3, 'count': 2240},
        'custom-call ragged-dot f32[2048,1792]': {'seconds': 0.1,
                                                  'count': 28},
        'fusion f32[96,32]': {'seconds': 0.01, 'count': 1120},
        'fusion bf16[96,2048]': {'seconds': 0.1, 'count': 100}}
    steps = 10 * 8
    counters = {'generation.window_moe_experts_touched': 14 * 31 * steps,
                'generation.state_live_slot_steps': 40 * steps,
                'generation.state_slot_steps': 96 * steps}
    ctx = _ctx(build, model, ops, counters, [(40, 50000)] * 10)

    def read(name):
        return run.load_module('metrics', 'lfm2_8b_a1b.' + name).read(ctx)

    assert read('attention_share') == pytest.approx(100 * 0.02)
    assert read('shortconv_share') == pytest.approx(100 * 0.095)
    assert read('moe_share') == pytest.approx(100 * 0.61)
    assert read('attention_share') + read('shortconv_share') \
        + read('moe_share') <= 100
    assert read('state_useful_share') == pytest.approx(100 * 40 / 96)
    assert read('expert_touched_share') == pytest.approx(100 * 31 / 32)
    kv_token_steps = 10 * (8 * 50000 + 40 * 36)
    least = build.attention_bytes(model, kv_token_steps) / 819e9
    assert read('paged_attention_roofline') == pytest.approx(
        100 * least / 0.02)
    step_s = 1.2 / (10 * 8)
    want = build.bytes_per_decode_step(
        model, 40, 50000 + 40 * 3.5, 14 * 31) / 819e9
    assert read('decode_step_roofline') == pytest.approx(
        100 * want / step_s)
    assert 0 < read('decode_step_roofline') < 100
    # a program without the mixer (the parent, another model's cell):
    # nothing, and no crash
    bare = _ctx(build, {k: v for k, v in model.items() if k != 'conv'}, {},
                {}, [(40, 50000)])
    for name in READERS:
        assert run.load_module('metrics', 'lfm2_8b_a1b.' + name) \
            .read(bare) is None, name


def test_the_traffic_file_holds_the_issues_parameters():
    t = _read(BENCH, 'traffic', 'many_streams_medium_prompts.json')
    assert t['generator'] == 'open_loop'
    assert t['prompt'] == {'median': 512, 'sigma': 0.8, 'min': 64,
                           'max': 2048}
    assert t['output'] == {'median': 768, 'sigma': 0.35, 'min': 384,
                           'max': 1536}
    assert (t['slots'], t['slot_tokens'], t['page_len'], t['pages']) \
        == (96, 3584, 16, 21505)
    assert t['pages'] == t['slots'] * (t['slot_tokens'] // t['page_len']) + 1
    assert (t['prefill_chunk'], t['decode_window'], t['max_queue'],
            t['drain_seconds'], t['compare_prompts']) == (512, 8, 256, 60, 4)
    assert t['shared_prefix'] == 0 and t['prefix_cache'] is False
    # four fifths of the knee; one cycle of pairs (the next multiple of 8
    # over rate x 50) fills a run
    assert t['rate_per_s'] == pytest.approx(0.8 * t['knee_per_s'])
    assert t['pairs'] % 8 == 0 \
        and 0 <= t['pairs'] - t['rate_per_s'] * 50 < 8
    assert t['prompt']['max'] + t['output']['max'] <= t['slot_tokens']
