"""chip_smoke.py, rehearsed: every phase at the tiny size on the CPU
(Pallas in interpret mode) — the same functions the chip runs at full
width — plus the contracts the smoke leans on: where the compile cache
lives, what its keys cover, and that without a TPU the script runs
nothing and fails.
"""
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import pytest                                          # noqa: E402

import chip_smoke                                      # noqa: E402
import paddle_tpu.observability as obs                 # noqa: E402
from paddle_tpu.core import compile_cache as cc        # noqa: E402

TINY = chip_smoke.SIZES['tiny']


@pytest.fixture(autouse=True)
def _own_counters():
    """The phases assert ABSOLUTE fallback counts, as on the chip where
    the process is theirs alone: fallbacks another file's tests left in
    this xdist worker's process are not theirs."""
    obs.metrics.reset()


def test_sizes_name_the_same_phases():
    assert set(chip_smoke.SIZES['full']) == set(TINY)
    for phase, cfg in chip_smoke.SIZES['full'].items():
        assert set(cfg) == set(TINY[phase]), phase


def test_phase_train_transformer():
    out = chip_smoke.train_transformer(TINY['transformer'])
    assert out['loss_last'] < out['loss_first']
    assert out['steps'] == 3 + 2 * TINY['transformer']['fused_steps']


def test_phase_train_resnet50():
    out = chip_smoke.train_resnet50(TINY['resnet'])
    assert out['loss_last'] < out['loss_first'] and out['steps'] == 3


def test_phase_kernels(monkeypatch):
    """The shape gates lowered so that the tiny shapes take the routes
    the full shapes take: Pallas forward, both dK/dV kernels, and the
    composed route's loop over four tiles of the batch."""
    from paddle_tpu.ops import attention
    flash = TINY['kernels']['flash']
    loop = TINY['kernels']['tile_loop']
    monkeypatch.setattr(
        attention, '_COMPOSED_TILE_BYTES', loop['batch'] // loop['tiles']
        * loop['heads'] * loop['seq'] ** 2 * 4)
    monkeypatch.setattr(attention, '_FWD_PALLAS_MIN_T',
                        flash['seq_resident'])
    monkeypatch.setattr(attention, '_BWD_PALLAS_SCORE_BYTES', 0)
    monkeypatch.setattr(attention, '_DKV_RESIDENT_MAX_T',
                        flash['seq_resident'])
    out = chip_smoke.kernels(TINY['kernels'])
    assert sorted(out) == ['expert_route', 'flash_resident',
                           'flash_streamed', 'kda_step', 'latent_attention',
                           'latent_prefill', 'paged_narrow', 'ssm_step',
                           'tile_loop', 'wall_s']
    assert out['paged_narrow']['slots'] == 3
    assert sorted(out['expert_route']) == ['with_rows_3', 'with_rows_8']
    assert sorted(out['expert_route']['with_rows_8']['ms']) == [
        'batched', 'grouped', 'grouped_ragged_dot', 'unbatched']
    assert sorted(out['kda_step']) == ['o_err_live_1', 'o_err_live_2']
    assert sorted(out['tile_loop']) == [
        '%s_%d' % (n, run) for n in ('dk', 'dq', 'dv', 'out')
        for run in range(2)]
    assert sorted(out['latent_prefill']) == ['err_0_8', 'err_24_8',
                                             'err_40_3']


def test_phase_serve():
    out = chip_smoke.serve(TINY['serve'])
    n = len(TINY['serve']['prompt_lens'])
    # the probe prefill emits no token through the engine
    assert out['tokens'] == 2 * n * TINY['serve']['max_new']
    assert out['compiles'] == 2 and out['cut'] is None


def test_phase_multichip():
    """Four of conftest's eight virtual CPU devices."""
    out = chip_smoke.multichip(TINY['multichip'])
    assert out['devices'] == 4 and len(out['state_bytes_per_device']) == 4
    assert abs(out['zero_share_per_device'] - 0.25) < 0.01


# ------------------------------------------------------ the cache contract

def test_cache_dir_is_the_variable_or_the_checkout(monkeypatch, tmp_path):
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path / 'x'))
    assert cc.cache_dir() == str(tmp_path / 'x')
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR')
    assert cc.cache_dir() == os.path.join(REPO, '.jax_cache')


_CACHE_RUN = r"""
import json, os, sys
sys.path.insert(0, sys.argv[1])
import numpy as np
import jax
import paddle_tpu as fluid
import paddle_tpu.observability as obs

main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    with fluid.unique_name.guard():
        x = fluid.layers.data('x', shape=[16], dtype='float32')
        h = fluid.layers.layer_norm(fluid.layers.fc(x, 16, act='relu'))
        loss = fluid.layers.reduce_mean(h * h)
        fluid.optimizer.Adam(1e-3).minimize(loss)
exe, scope = fluid.Executor(), fluid.Scope()
with fluid.scope_guard(scope):
    exe.run(startup)
    exe.run(main, feed={'x': np.ones((64, 16), 'float32')},
            fetch_list=[loss])
c = obs.counters()
print(json.dumps({'hits': c.get('compile_cache.disk_hits') or 0,
                  'backend_compile_s':
                      c.get('executor.backend_compile_s') or 0,
                  'jax_dir': jax.config.jax_compilation_cache_dir}))
"""


def test_everything_cached_lands_under_the_variable(tmp_path):
    """Two fresh processes, JAX_COMPILATION_CACHE_DIR=X, a HOME of their
    own: cache files appear only under X, under stable names; nothing of
    ours is created in HOME; the second process compiles nothing."""
    import json
    cache, home = tmp_path / 'X', tmp_path / 'home'
    home.mkdir()
    env = dict(os.environ, JAX_PLATFORMS='cpu', PT_CACHE='1',
               HOME=str(home),
               JAX_COMPILATION_CACHE_DIR=str(cache))
    runs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, '-c', _CACHE_RUN, REPO],
                           capture_output=True, text=True, timeout=300,
                           env=env, cwd=str(tmp_path))
        assert r.returncode == 0, r.stderr[-2000:]
        runs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    assert runs[0]['jax_dir'] == str(cache), 'JAX did not follow the variable'
    assert runs[0]['hits'] == 0 and runs[0]['backend_compile_s'] > 0
    assert runs[1]['hits'] >= 2 and runs[1]['backend_compile_s'] == 0
    assert not (home / '.cache' / 'paddle_tpu').exists()
    assert sorted(p.name for p in tmp_path.iterdir()) == ['X', 'home']
    store = str(cache / ('v%d' % cc.CACHE_FORMAT))
    ours = [os.path.relpath(os.path.join(root, f), str(cache))
            for root, _, files in os.walk(store) for f in files]
    assert ours
    # content-addressed: a digest and nothing else — no pid, no time, no
    # temporary name survives in a path
    stable = re.compile(r'^v\d+/[0-9a-f]{2}/[0-9a-f]{64}\.pkl$')
    assert all(stable.match(p) for p in ours), ours


def test_l2_key_covers_the_package_source(monkeypatch, tmp_path):
    """An edit to any paddle_tpu/**/*.py changes every L2 key: a cache
    that outlives the edit must not serve the old executable."""
    blob = cc._environment_blob()
    assert blob['source'] == cc.source_digest() == cc.source_digest()
    pkg = tmp_path / 'pkg'
    (pkg / 'ops').mkdir(parents=True)
    (pkg / '__init__.py').write_text('')
    (pkg / 'ops' / 'math.py').write_text('def f(x):\n    return x + 1\n')
    (pkg / 'ops' / 'notes.txt').write_text('not source')
    monkeypatch.setattr(cc, '_PACKAGE_DIR', str(pkg))

    def key():
        monkeypatch.setattr(cc, '_SOURCE_DIGEST', [])
        return cc.callable_fingerprint('generation', {'fn': 'decode'})

    before = key()
    assert key() == before
    (pkg / 'ops' / 'notes.txt').write_text('still not source')
    assert key() == before
    (pkg / 'ops' / 'math.py').write_text('def f(x):\n    return x + 2\n')
    assert key() != before


# ------------------------------------------------------- the exit contract

def test_chip_smoke_without_a_tpu_runs_nothing_and_fails():
    r = subprocess.run([sys.executable, os.path.join(REPO, 'chip_smoke.py')],
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert r.returncode != 0
    assert 'no TPU' in r.stderr
    assert r.stdout.strip() == '', 'printed a result without a chip'


def test_last_line_is_the_result_the_driver_parses(monkeypatch, capsys):
    """main() with the phases stubbed out: whatever the phases print, the
    last stdout line is one JSON object with exactly `ok` and `device`,
    the device exactly `platform`, `kind` (text), `count` (an int)."""
    import json
    device = {'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1}
    monkeypatch.setattr(chip_smoke, 'device_report', lambda: device)
    ran = []
    for phase in ('train_transformer', 'train_resnet50', 'kernels', 'serve',
                  'multichip'):
        monkeypatch.setattr(chip_smoke, phase,
                            lambda size, phase=phase: ran.append(phase))
    chip_smoke.main()
    assert ran == ['train_transformer', 'train_resnet50', 'kernels', 'serve']
    lines = capsys.readouterr().out.splitlines()
    assert 'multichip: not run (1 device)' in lines
    last = json.loads(lines[-1])
    assert set(last) == {'ok', 'device'} and last['ok'] is True
    assert set(last['device']) == {'platform', 'kind', 'count'}
    assert last['device'] == device and type(last['device']['count']) is int
