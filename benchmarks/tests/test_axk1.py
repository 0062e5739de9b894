"""The `axk1` configuration's own files (PR 47): its build file, its plain
reference with its controls, its traffic file and its five metric readers.

The cell itself runs on the CPU in a temporary copy of the benchmark whose
configuration and traffic files are overridden to tiny sizes (float32, so
the comparison with the reference is tight and no near-tie flips a pick);
the arithmetic of the build file and of the readers is checked at the
PUBLISHED sizes and on synthetic contexts; and the configuration file is
held to the catalog row it was copied from.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT
from test_add_files_only import DRIVER, _read, _write, edits

import run

CELL = 'axk1.shared_context_answers'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
TINY = {'hidden_size': 32, 'num_attention_heads': 4, 'q_lora_rank': 24,
        'kv_lora_rank': 16, 'qk_nope_head_dim': 8, 'qk_rope_head_dim': 4,
        'v_head_dim': 8, 'intermediate_size': 48, 'moe_intermediate_size': 24,
        'n_routed_experts_published': 32, 'n_routed_experts': 2,
        'num_experts_per_tok': 4, 'vocab_size': 256, 'num_hidden_layers': 3,
        'torch_dtype': 'float32', 'initializer_range': 0.3}
TINY_TRAFFIC = {'rate_per_s': 5.0, 'pairs': 16, 'shared_prefix': 8,
                'prompt': {'median': 16, 'sigma': 0.3, 'min': 10, 'max': 30},
                'output': {'median': 6, 'sigma': 0.5, 'min': 3, 'max': 12},
                'slots': 4, 'slot_tokens': 48, 'page_len': 4, 'pages': 49,
                'prefill_chunk': 8, 'decode_window': 4, 'drain_seconds': 30}
READERS = ('decode_step_roofline', 'latent_attention_roofline',
           'latent_attention_share', 'moe_share', 'expert_load_ratio')


@pytest.fixture(scope='module')
def build():
    return run.load_module('builds', 'axk1')


@pytest.fixture(scope='module')
def model(build):
    return build.model_dict(_read(BENCH, 'configs', 'axk1.json'),
                            _read(BENCH, 'traffic',
                                  'shared_context_answers.json'))


@pytest.fixture(scope='module')
def tiny_copy(tmp_path_factory):
    top = str(tmp_path_factory.mktemp('axk1_tiny'))
    bench = os.path.join(top, 'benchmarks')
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        '__pycache__', 'tests', 'data'))
    for folder, name, override in (
            ('configs', 'axk1.json', TINY),
            ('traffic', 'shared_context_answers.json', TINY_TRAFFIC)):
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
         str(2 ** 31 + 47), '3', str(trace)], env=env, cwd=top,
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
    assert 'references/axk1.py' in said['reference']
    # float32 end to end: chunked prefill (expanded), a decode window
    # (absorbed, the kernel in interpret mode) and one more chunk through
    # the latent pool against the reference's plain full forward
    assert 0 < said['worst_rel_err'] < 1e-3 < said['rtol']
    routing = [json.loads(ln[9:]) for ln in earlier
               if ln.startswith('routing: ')]
    assert len(routing) == 4 and all(
        len(r['margin_at_compared_position']) == 2 for r in routing)
    # the reference found the logits the runner compares its own with, and
    # in float32 the plain selection is the program's
    assert all(r['compared_with_logits'] and r['taken'] == []
               for r in routing)


def test_the_traced_run_reads_what_a_cpu_can_give(tiny_copy):
    """The counters' ratio is read; the four device_trace readers find no
    trace on the CPU and are left out, without a crash; the cell takes
    every unlisted reader of tpot_p50_ms and declines the other models'
    listed ones, and no other cell takes this one's."""
    manifest = _read(tiny_copy, 'BENCHMARK.json')
    wanted = {m['name'] for m in run.wanted_metrics(manifest, CELL, 1)}
    assert {'axk1.' + name for name in READERS} <= wanted
    assert {'decode.step_ms', 'decode.kv_read_useful_share',
            'scheduler.live_slot_share'} <= wanted
    assert not {'decode_step_roofline', 'decode.paged_attention_share',
                'falconh1_34b.ssm_share'} & wanted
    for cell in ('mistral7b.chat_steady', 'falconh1_34b.chat_long_answers'):
        other = {m['name'] for m in run.wanted_metrics(manifest, cell, 1)}
        assert not any(name.startswith('axk1.') for name in other)
    result, _ = _run(tiny_copy, 1)
    assert result['correct'] is True
    ratio = result['metrics']['axk1.expert_load_ratio']
    assert ratio['unit'] == 'ratio' and ratio['value'] >= 1.0
    # in place over the live slots: the tail page's padding and no more
    assert result['metrics']['decode.kv_read_useful_share']['value'] > 60
    for name in READERS[:4]:
        assert 'axk1.' + name not in result['metrics']


def test_the_control_script_rehearses_at_a_tiny_size(tiny_copy):
    """benchmarks/tests/axk1_control.py, the chip run's controls, on the
    CPU: float32, so no near-tie flips a pick and every control is far
    out."""
    tests = os.path.join(tiny_copy, 'benchmarks', 'tests')
    os.makedirs(tests, exist_ok=True)
    shutil.copy(os.path.join(BENCH, 'tests', 'axk1_control.py'), tests)
    env = dict(os.environ, JAX_PLATFORMS='cpu', PT_CACHE='0',
               PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=os.path.join(tiny_copy,
                                                      '.jax_cache'))
    done = subprocess.run(
        [sys.executable, os.path.join(tests, 'axk1_control.py'), '--seed',
         str(2 ** 31 + 48), '--allow-cpu'], env=env, cwd=tiny_copy,
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
        assert row['routing']['pairs'] == 2 * (row['context'] - 5)
        assert row['routing']['picks_differ_share'] == 0
        assert min(row[c] for c in ('no_rope', 'unnormalised', 'int8_rows',
                                    'fp8_weights')) > 10 * row['sound']


def test_the_manifest_gained_entries_and_lost_none():
    """What this PR did to BENCHMARK.json, against the parent's copy in
    git where there is one: appended entries and the cell's name in
    tpot_p50_ms's list."""
    done = subprocess.run(['git', 'show', 'HEAD:BENCHMARK.json'], cwd=ROOT,
                          capture_output=True, text=True)
    if done.returncode != 0:
        pytest.skip('no git history here')
    before, after = json.loads(done.stdout), _read(ROOT, 'BENCHMARK.json')
    assert edits(before, after) == []
    cell = next(c for c in after['workloads'] if c['name'] == CELL)
    assert cell['chips'] == 1 and len(cell['why']) <= 200
    assert all(m['workloads'] == [CELL] for m in after['per_layer']
               if m['name'].startswith('axk1.'))


def test_the_configuration_holds_every_published_number():
    config = _read(BENCH, 'configs', 'axk1.json')
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r['name'] == 'A.X-K1')
    assert config['source'] == row['source_url']
    differs = sorted(k for k, v in row['config'].items()
                     if config.get(k, 'missing') != v)
    assert differs == sorted(config['reduced']) == [
        'n_routed_experts', 'num_hidden_layers', 'vocab_size']
    assert (config['num_hidden_layers'], row['layers']) == (7, 61)
    assert config['n_routed_experts'] * config['expert_parallel_ranks'] \
        == config['n_routed_experts_published'] \
        == row['config']['n_routed_experts']
    assert config['vocab_size'] * config['vocab_parallel_ranks'] \
        == config['vocab_size_published'] == row['vocab_size']
    # the floors: four layers after the dense one, 8 experts, 1/8 vocabulary
    assert config['num_hidden_layers'] - config['first_k_dense_replace'] >= 4
    assert config['n_routed_experts'] >= 8
    assert {'topk_method', 'initializer_range', 'weights', 'rope'} \
        <= set(config['assumed'])
    entry = next(c for c in _read(ROOT, 'BENCHMARK.json')['configs']
                 if c['name'] == 'axk1')
    assert entry['source'] == row['source_url']
    assert entry['reduced'] == config['reduced']


def test_shapes_are_the_programs_names_at_the_published_widths(build, model):
    from paddle_tpu.serving.generation import weight_names
    from paddle_tpu.serving.generation.decode import weight_shapes
    shapes = build.weight_shapes(model)
    assert sorted(shapes) == sorted(weight_names(model))
    assert shapes == weight_shapes(model)         # the program's own count
    assert model['ffn'] == ['dense'] + ['experts'] * 6
    assert shapes['layer_0_att_qb_w'] == (1536, 64 * 192)
    assert shapes['layer_0_att_kva_w'] == (7168, 576)
    assert shapes['layer_3_att_kvb_w'] == (512, 64 * 256)
    assert shapes['layer_0_ffn_fc1_w'] == (7168, 18432)
    assert shapes['layer_6_moe_router_w'] == (7168, 192)
    assert shapes['layer_1_moe_fc2_w'] == (12, 2048, 7168)
    assert shapes['lm_proj_w'] == (7168, 20480)

    def count(prefix, skip=()):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.startswith(prefix) and not any(k in n for k in skip))
    routed = ('moe_fc1_w', 'moe_fc3_w', 'moe_fc2_w')
    # ISSUE 47's table: attention 101.1 M, an expert layer outside its
    # routed experts 146.5 M, its 12 experts 528.5 M, the dense layer 497.5 M
    assert count('layer_1_att_') / 1e6 == pytest.approx(101.1, abs=0.06)
    assert count('layer_1_', routed) / 1e6 == pytest.approx(146.5, abs=0.06)
    assert (count('layer_1_') - count('layer_1_', routed)) / 1e6 \
        == pytest.approx(528.5, abs=0.06)
    assert count('layer_0_') / 1e6 == pytest.approx(497.5, abs=0.06)
    assert round(2 * count('') / 1e9, 2) == 9.68           # bf16, all seven


def test_bytes_per_decode_step_counts_touched_experts_and_live_rows(build,
                                                                    model):
    idle = build.bytes_per_decode_step(model, 0, 0, 0)
    # everything but the routed experts and the embedding: 9.68 GB less
    # 72 experts of 88.1 MB and 0.29 GB of embedding rows
    assert idle == build.resident_bytes(model)
    assert round(idle / 1e9, 2) == 3.05
    assert build.expert_bytes(model) == 3 * 7168 * 2048 * 2
    assert build.bytes_per_decode_step(model, 0, 0, 5) - idle \
        == 5 * build.expert_bytes(model)
    assert build.bytes_per_decode_step(model, 0, 1, 0) - idle == 7 * 1152
    assert build.bytes_per_decode_step(model, 1, 0, 0) - idle == 2 * 7168
    ops, nbytes = build.latent_attention_cost(model, 1000)
    assert (ops, nbytes) == (1000 * 2 * 64 * (1024 + 64), 1000 * 1152)


def _ctx(model, build, ops, step_ms=12.0):
    K, launches = 8, 10
    return {
        'model': model, 'build': build,
        'traffic': {'decode_window': K},
        'windows': [(12, 60000)] * launches,
        'peaks': {'hbm_bytes_per_s': 819e9, 'bf16_flops': 197e12},
        'counters': {'generation.window_moe_experts_touched': 80 * 28.0,
                     'generation.window_latent_rows_read': 80 * 7 * 60100.0,
                     'generation.moe_assignments': 6000.0,
                     'generation.moe_busiest_expert_tokens': 1500.0},
        'trace': {'busy_s': 2.0, 'ops': ops, 'modules': {
            'jit_window': {'seconds': step_ms * 1e-3 * K * launches,
                           'count': launches}}}}


def test_the_five_readers_on_a_synthetic_context(build, model):
    # the first five labels as a chip trace of this cell spells them (my
    # chip runs, PR 47: chiprun_out/trace3.log)
    ops = {'custom-call latent_attention f32[64,64,512]':
               {'seconds': 0.1, 'count': 560},
           'custom-call ragged-dot-none f32[64,2048]':
               {'seconds': 0.15, 'count': 18},
           'custom-call ragged-dot-none f32[64,7168]':
               {'seconds': 0.05, 'count': 9},
           'fusion:Output multiply_reduce_fusion (f32[64], f32[64,7168])':
               {'seconds': 0.6, 'count': 60},
           'fusion:Output f32[64,20480]': {'seconds': 0.4, 'count': 9},
           'fusion:Output f32[512,2048]': {'seconds': 0.1, 'count': 9},
           'fusion:Loop f32[64,192]': {'seconds': 0.05, 'count': 9}}
    ctx = _ctx(model, build, ops)
    read = {name: run.load_module('metrics', 'axk1.' + name).read
            for name in READERS}
    least = build.bytes_per_decode_step(model, 12, 60000 + 12 * 3.5,
                                        28.0) / 819e9
    assert read['decode_step_roofline'](ctx) == pytest.approx(
        100 * least / 12e-3)
    assert 50 < read['decode_step_roofline'](ctx) < 100
    rows = 80 * 7 * 60100.0
    assert read['latent_attention_roofline'](ctx) == pytest.approx(
        100 * (rows * 1152 / 819e9) / 0.1)
    assert read['latent_attention_share'](ctx) == pytest.approx(5.0)
    assert read['moe_share'](ctx) == pytest.approx(100 * 0.35 / 2.0)
    assert read['expert_load_ratio'](ctx) == pytest.approx(3.0)


def test_the_readers_return_nothing_where_there_is_nothing_to_read(build,
                                                                  model):
    """The parent's program under this PR's benchmark files, a dense
    model, a CPU run: None, never a raise."""
    readers = [run.load_module('metrics', 'axk1.' + name) for name in READERS]
    dense = {k: v for k, v in model.items() if k not in ('moe', 'latent')}
    for ctx in (
            dict(_ctx(model, build, {}), trace=None, counters={}),
            dict(_ctx(dense, None, {'fusion bf16[32,4096]':
                                    {'seconds': 1.0, 'count': 1}}),
                 counters={'generation.decode_slot_steps': 10.0})):
        assert [r.read(ctx) for r in readers] == [None] * 5


def test_the_reference_controls_move_the_logits_at_a_tiny_size():
    """Each control of references/axk1.py makes the reference wrong by
    more than float32 rounding, on the tiny model and the program's own
    draw of weights; the chip run reads them at the cell's size."""
    import jax
    jax.config.update('jax_platforms', 'cpu')
    from paddle_tpu.serving.generation import random_weights
    build = run.load_module('builds', 'axk1')
    ref = run.load_module('references', 'axk1')
    config = dict(_read(BENCH, 'configs', 'axk1.json'), **TINY)
    model = build.model_dict(config, {'slot_tokens': 48})
    w = random_weights(model, seed=3, scale=0.3)
    context = np.random.RandomState(0).randint(1, 256, 40)
    sound = ref.last_logits(w, model, context)
    assert np.isfinite(sound).all()
    for control in ref.CONTROLS:
        wrong = ref.last_logits(w, model, context, control=control)
        err = np.linalg.norm(wrong - sound) / np.linalg.norm(sound)
        assert err > 1e-3, (control, err)
