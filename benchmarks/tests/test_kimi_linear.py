"""The `kimi_linear` configuration's own files (PR 61): its build file, its
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

CELL = 'kimi_linear.many_streams_long_answers'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
TINY = {'hidden_size': 32, 'num_attention_heads': 4, 'kv_lora_rank': 16,
        'qk_nope_head_dim': 8, 'qk_rope_head_dim': 4, 'v_head_dim': 8,
        'intermediate_size': 48, 'moe_intermediate_size': 24,
        'num_experts_published': 32, 'num_experts': 8,
        'num_experts_per_token': 4, 'vocab_size': 256,
        'num_hidden_layers': 4,
        'linear_attn_config': {'kda_layers': [1, 2, 3],
                               'full_attn_layers': [4], 'head_dim': 8,
                               'num_heads': 3, 'short_conv_kernel_size': 4},
        'torch_dtype': 'float32', 'initializer_range': 0.3,
        'kda_dt_bias_mean': -2.0}
TINY_TRAFFIC = {'rate_per_s': 5.0, 'pairs': 16, 'shared_prefix': 0,
                'prompt': {'median': 16, 'sigma': 0.5, 'min': 6, 'max': 30},
                'output': {'median': 6, 'sigma': 0.5, 'min': 3, 'max': 12},
                'slots': 4, 'slot_tokens': 48, 'page_len': 4, 'pages': 49,
                'prefill_chunk': 8, 'decode_window': 4, 'drain_seconds': 30}
READERS = ('decode_step_roofline', 'kda_step_roofline', 'kda_share',
           'moe_share', 'latent_attention_share', 'state_useful_share',
           'expert_touched_share')


@pytest.fixture(scope='module')
def build():
    return run.load_module('builds', 'kimi_linear')


@pytest.fixture(scope='module')
def model(build):
    return build.model_dict(_read(BENCH, 'configs', 'kimi_linear.json'),
                            _read(BENCH, 'traffic',
                                  'many_streams_long_answers.json'))


@pytest.fixture(scope='module')
def tiny_copy(tmp_path_factory):
    top = str(tmp_path_factory.mktemp('kimi_linear_tiny'))
    bench = os.path.join(top, 'benchmarks')
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        '__pycache__', 'tests', 'data'))
    for folder, name, override in (
            ('configs', 'kimi_linear.json', TINY),
            ('traffic', 'many_streams_long_answers.json', TINY_TRAFFIC)):
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
         str(2 ** 31 + 61), '3', str(trace)], env=env, cwd=top,
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
    assert 'references/kimi_linear.py' in said['reference']
    # float32 end to end: chunked prefill (the chunk form), a decode window
    # (the step, the kernels in interpret mode) and one more chunk through
    # pool and state against the reference's token-by-token full forward
    assert 0 < said['worst_rel_err'] < 1e-3 < said['rtol']
    routing = [json.loads(ln[9:]) for ln in earlier
               if ln.startswith('routing: ')]
    assert len(routing) == 4 and all(
        len(r['margin_at_compared_position']) == 3 for r in routing)
    assert all(r['compared_with_logits'] for r in routing)


def test_the_traced_run_reads_what_a_cpu_can_give(tiny_copy):
    """The counters' ratios are read; the five device_trace readers find no
    trace on the CPU and are left out, without a crash; the cell takes every
    unlisted reader of tpot_p50_ms and declines the other models' listed
    ones, and no other cell takes this one's."""
    manifest = _read(tiny_copy, 'BENCHMARK.json')
    wanted = {m['name'] for m in run.wanted_metrics(manifest, CELL, 1)}
    assert {'kimi_linear.' + name for name in READERS} <= wanted
    assert {'decode.step_ms', 'decode.kv_read_useful_share',
            'scheduler.live_slot_share'} <= wanted
    assert not {'decode_step_roofline', 'decode.paged_attention_share',
                'falconh1_34b.ssm_share', 'axk1.moe_share'} & wanted
    for cell in ('mistral7b.chat_steady', 'falconh1_34b.chat_long_answers',
                 'axk1.shared_context_answers'):
        other = {m['name'] for m in run.wanted_metrics(manifest, cell, 1)}
        assert not any(name.startswith('kimi_linear.') for name in other)
    result, _ = _run(tiny_copy, 1)
    assert result['correct'] is True
    # the kernel steps the live slots alone
    assert result['metrics']['kimi_linear.state_useful_share']['value'] \
        == 100.0
    touched = result['metrics']['kimi_linear.expert_touched_share']
    assert touched['unit'] == '%' and 0 < touched['value'] <= 100.0
    for name in READERS[:5]:
        assert 'kimi_linear.' + name not in result['metrics']


def test_the_control_script_rehearses_at_a_tiny_size(tiny_copy):
    """benchmarks/tests/kimi_linear_control.py, the chip run's controls, on
    the CPU: float32, so every control is far out."""
    tests = os.path.join(tiny_copy, 'benchmarks', 'tests')
    os.makedirs(tests, exist_ok=True)
    shutil.copy(os.path.join(BENCH, 'tests', 'kimi_linear_control.py'), tests)
    env = dict(os.environ, JAX_PLATFORMS='cpu', PT_CACHE='0',
               PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=os.path.join(tiny_copy,
                                                      '.jax_cache'))
    done = subprocess.run(
        [sys.executable, os.path.join(tests, 'kimi_linear_control.py'),
         '--seed', str(2 ** 31 + 62), '--allow-cpu'], env=env, cwd=tiny_copy,
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    summary = json.loads([ln for ln in lines
                          if ln.startswith('summary: ')][0][9:])
    assert summary['sound_worst'] < 1e-3
    prompts = [json.loads(ln[8:]) for ln in lines
               if ln.startswith('prompt: ')]
    assert len(prompts) == 4
    for row in prompts:
        assert min(row[c] for c in ('no_delta', 'mean_decay', 'bf16_state',
                                    'no_pe', 'chunk_reset', 'fp8_weights')) \
            > 5 * row['sound']


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
            if m['name'].startswith('kimi_linear.')]
    assert [m['name'] for m in mine] \
        == ['kimi_linear.' + name for name in READERS]
    assert all(m['workloads'] == [CELL] for m in mine)


def test_the_configuration_holds_every_published_number():
    config = _read(BENCH, 'configs', 'kimi_linear.json')
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r['name'] == 'Kimi-Linear-48B-A3B-Instruct')
    assert config['source'] == row['source_url']
    differs = sorted(k for k, v in row['config'].items()
                     if config.get(k, 'missing') != v)
    assert differs == sorted(config['reduced']) == [
        'linear_attn_config', 'num_experts', 'num_hidden_layers',
        'vocab_size']
    # the group's widths stand; its layer lists are cut to the layers kept
    lin, published = config['linear_attn_config'], \
        row['config']['linear_attn_config']
    assert config['linear_attn_config_published'] == published
    for key in ('head_dim', 'num_heads', 'short_conv_kernel_size'):
        assert lin[key] == published[key]
    kept = config['num_hidden_layers']
    assert lin['kda_layers'] == [i for i in published['kda_layers']
                                 if i <= kept] == [1, 2, 3, 5, 6, 7]
    assert lin['full_attn_layers'] == [
        i for i in published['full_attn_layers'] if i <= kept] == [4, 8]
    assert (kept, row['layers']) == (8, 27)
    assert config['num_experts'] * config['expert_parallel_ranks'] \
        == config['num_experts_published'] == row['config']['num_experts']
    assert config['vocab_size'] * config['vocab_parallel_ranks'] \
        == config['vocab_size_published'] == row['vocab_size']
    # the floors: two whole periods, four layers after the dense one, 8
    # experts, 1/8 vocabulary
    assert kept - config['first_k_dense_replace'] >= 4 and kept % 4 == 0
    assert config['num_experts'] >= 8
    assert config['vocab_size'] * 8 >= row['vocab_size']
    assert {'choice_bias', 'initializer_range', 'weights',
            'kda_dt_bias_mean', 'conv_bias'} <= set(config['assumed'])
    entry = next(c for c in _read(ROOT, 'BENCHMARK.json')['configs']
                 if c['name'] == 'kimi_linear')
    assert entry['source'] == row['source_url']
    assert entry['reduced'] == config['reduced']


def test_the_build_file_counts_what_the_issue_counts(build, model):
    """ISSUE 61, section 2: 7.54 GB of weights, 13.5 MB of state a stream,
    2,560 B of pool a token."""
    assert model['mixer'] == ['kda', 'kda', 'kda', 'latent'] * 2
    assert model['ffn'] == ['dense'] + ['experts'] * 7
    assert model['latent']['q_rank'] is None and not model['latent']['rotate']
    shapes = build.weight_shapes(model)
    from paddle_tpu.serving.generation import weight_shapes
    assert shapes == weight_shapes(model)
    import numpy as np
    params = sum(int(np.prod(s)) for s in shapes.values())
    assert round(2 * params / 1e9, 2) == 7.54
    assert build.held_experts(model) == 64
    assert build.expert_bytes(model) == 3 * 2304 * 1024 * 2
    assert build.state_bytes(model) == 32 * 128 * 128 * 4
    per_stream = 6 * (build.state_bytes(model) + build.tail_bytes(model))
    assert round(per_stream / 1e6, 1) == 13.5
    assert 2 * 640 * 2 == 2560 and build.row_bytes(model) == 1152
    # a step at 80 live streams over 100k cached tokens, 60 of 64 experts a
    # layer touched: resident 1.0 GB, experts 5.9 GB, state 2.2 GB, rows
    live, touched = 80, 7 * 60
    total = build.bytes_per_decode_step(model, live, 100000, touched)
    resident = build.resident_bytes(model)
    assert 0.9e9 < resident < 1.15e9
    assert total == pytest.approx(
        resident + touched * build.expert_bytes(model) + live * 2304 * 2
        + 2 * per_stream * live + 2 * 1152 * 100000)
    assert build.kda_step_bytes(model, 10) == 2 * 6 * 2097152 * 10


def _ctx(build, model, ops, counters, windows, busy=1.0):
    return {'build': build, 'model': model,
            'traffic': {'decode_window': 8, 'slots': 128},
            'peaks': {'hbm_bytes_per_s': 819e9, 'bf16_flops': 197e12},
            'counters': counters, 'windows': windows,
            'trace': {'busy_s': busy, 'ops': ops,
                      'modules': {'jit_window': {'seconds': 0.8,
                                                 'count': 10}}}}


def test_the_readers_arithmetic_on_a_synthetic_trace(build, model):
    ops = {
        'custom-call kda_step (f32[128,6,32,128,128], f32[128,32,128])':
            {'seconds': 0.2, 'count': 480},
        # the chunk's end state written in place: the scan's, not the step's
        'fusion:Loop f32[128,6,32,128,128]': {'seconds': 0.01, 'count': 6},
        # a decode step's batched expert products at 128 slots
        'fusion:Output convolution_multiply_fusion f32[64,64,2304]':
            {'seconds': 0.15, 'count': 560},
        'fusion f32[8,32,64,64]': {'seconds': 0.03, 'count': 6},
        'fusion f32[32,128,128]': {'seconds': 0.02, 'count': 48},
        'custom-call latent_attention f32[128,32,512]': {'seconds': 0.05,
                                                         'count': 160},
        'custom-call ragged-dot f32[128,1024]': {'seconds': 0.3,
                                                 'count': 560},
        'fusion f32[128,256]': {'seconds': 0.01, 'count': 560},
        'fusion bf16[128,2304]': {'seconds': 0.1, 'count': 100}}
    steps = 10 * 8
    counters = {'generation.window_moe_experts_touched': 7 * 60 * steps,
                'generation.state_live_slot_steps': 80 * steps,
                'generation.state_slot_steps': 80 * steps}
    ctx = _ctx(build, model, ops, counters, [(80, 100000)] * 10)

    def read(name):
        return run.load_module('metrics', 'kimi_linear.' + name).read(ctx)

    assert read('kda_share') == pytest.approx(100 * 0.26)
    assert read('moe_share') == pytest.approx(100 * 0.46)
    assert read('latent_attention_share') == pytest.approx(100 * 0.05)
    assert read('state_useful_share') == 100.0
    assert read('expert_touched_share') == pytest.approx(100 * 60 / 64)
    least = build.kda_step_bytes(model, 80 * steps) / 819e9
    assert read('kda_step_roofline') == pytest.approx(100 * least / 0.2)
    step_s = 0.8 / (10 * 8)
    want = build.bytes_per_decode_step(
        model, 80, 100000 + 80 * 3.5, 7 * 60) / 819e9
    assert read('decode_step_roofline') == pytest.approx(
        100 * want / step_s)
    # a program without the spans and counters (the parent): nothing, and
    # no crash
    bare = _ctx(build, dict(model, kda=None), {}, {}, [(80, 100000)])
    bare['model'].pop('kda')
    for name in READERS:
        assert run.load_module('metrics', 'kimi_linear.' + name) \
            .read(bare) is None, name


def test_the_traffic_file_holds_the_issues_parameters():
    t = _read(BENCH, 'traffic', 'many_streams_long_answers.json')
    assert t['generator'] == 'open_loop'
    assert t['prompt'] == {'median': 256, 'sigma': 0.8, 'min': 64,
                           'max': 2048}
    assert t['output']['sigma'] == 0.35 and t['output']['min'] == 512 \
        and t['output']['max'] == 2048
    assert 1024 <= t['output']['median'] <= 1536
    assert (t['slots'], t['slot_tokens'], t['page_len'], t['pages']) \
        == (128, 4112, 16, 32897)
    assert t['pages'] == t['slots'] * (t['slot_tokens'] // t['page_len']) + 1
    assert (t['prefill_chunk'], t['decode_window'], t['max_queue'],
            t['drain_seconds'], t['compare_prompts']) == (512, 8, 256, 60, 4)
    assert t['shared_prefix'] == 0 and t['prefix_cache'] is False
    # four fifths of the knee; one cycle fills a run
    assert t['rate_per_s'] == pytest.approx(0.8 * t['knee_per_s'])
    assert t['pairs'] == round(t['rate_per_s'] * 50)
    assert t['prompt']['max'] + t['output']['max'] <= t['slot_tokens']
