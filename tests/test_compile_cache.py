"""Compilation-persistence subsystem (core/compile_cache.py):

  * fingerprint stability — the same program+launch signature hashes the
    same across processes; any keyed component (fetch set, K, AMP,
    check_nan, feed shapes) changes the key
  * warm start — a second FRESH PROCESS over a shared JAX_COMPILATION_CACHE_DIR loads
    executables from disk instead of compiling (asserted on both the
    cache-hit counters and the compile-time collapse)
  * the in-process LRU bound (PT_EXEC_CACHE_MAX) + eviction counter
  * corrupt disk entries are misses, never errors
  * the two int64 warn-and-truncate regressions stay silent
"""
import json
import os
import pickle
import subprocess
import sys
import warnings

import numpy as np

import paddle_tpu as fluid
import paddle_tpu.observability as obs
from paddle_tpu.core import compile_cache as cc


def _build(seed=7):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[4], dtype='float32')
            lbl = fluid.layers.data('lbl', shape=[1], dtype='int64')
            h = fluid.layers.fc(x, 8, act='relu')
            logits = fluid.layers.fc(h, 3)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, lbl))
            fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    return main, startup, loss


FEEDS = {'x': (((2, 4)), 'float32'), 'lbl': ((2, 1), 'int64')}


def _specs():
    return {n: (tuple(s), d) for n, (s, d) in FEEDS.items()}


# ------------------------------------------------------------- fingerprints

def test_fingerprint_components_change_the_key():
    main, _, loss = _build()
    base = cc.launch_fingerprint(main, _specs(), (loss.name,), None, False)
    # same inputs -> same key (and the per-program hash is memoized)
    assert base == cc.launch_fingerprint(main, _specs(), (loss.name,),
                                         None, False)
    # each keyed component perturbs the hash
    assert base != cc.launch_fingerprint(main, _specs(), (loss.name, 'x'),
                                         None, False)       # fetch set
    assert base != cc.launch_fingerprint(main, _specs(), (loss.name,),
                                         4, False)          # steps=K
    assert base != cc.launch_fingerprint(main, _specs(), (loss.name,),
                                         None, True)        # check_nan
    wide = dict(_specs(), x=((5, 4), 'float32'))
    assert base != cc.launch_fingerprint(main, wide, (loss.name,),
                                         None, False)       # feed shape
    main.set_amp(True)
    assert base != cc.launch_fingerprint(main, _specs(), (loss.name,),
                                         None, False)       # AMP policy


def test_fingerprint_stable_across_processes():
    """The key must be a pure function of program+signature+environment —
    no id()s, no process-local serials — or the disk cache could never
    hit across restarts."""
    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import sys; sys.path.insert(0, %r)\n"
        "import paddle_tpu as fluid\n"
        "from paddle_tpu.core import compile_cache as cc\n"
        "main, startup = fluid.Program(), fluid.Program()\n"
        "main.random_seed = 7\n"
        "with fluid.program_guard(main, startup):\n"
        "    with fluid.unique_name.guard():\n"
        "        x = fluid.layers.data('x', shape=[4], dtype='float32')\n"
        "        y = fluid.layers.fc(x, 3)\n"
        "        loss = fluid.layers.reduce_mean(y)\n"
        "print(cc.launch_fingerprint(main, {'x': ((2, 4), 'float32')},\n"
        "                            (loss.name,), None, False))\n"
    ) % os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fps = set()
    for _ in range(2):
        r = subprocess.run([sys.executable, '-c', code],
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        fps.add(r.stdout.strip().splitlines()[-1])
    assert len(fps) == 1, 'fingerprint differs across processes: %s' % fps


def test_program_fingerprint_tracks_edits():
    main, _, _ = _build()
    fp0 = cc.program_fingerprint(main)
    assert fp0 == cc.program_fingerprint(main)
    with fluid.program_guard(main, fluid.Program()):
        fluid.layers.data('extra', shape=[2], dtype='float32')
    assert cc.program_fingerprint(main) != fp0


# --------------------------------------------------------------- warm start

_WARMSTART_CODE = r"""
import os, sys, time
os.environ['JAX_PLATFORMS'] = 'cpu'
os.environ['PT_CACHE'] = '1'
sys.path.insert(0, sys.argv[1])
os.environ['JAX_COMPILATION_CACHE_DIR'] = sys.argv[2]
import json
import numpy as np
import paddle_tpu as fluid
import paddle_tpu.observability as obs

main, startup = fluid.Program(), fluid.Program()
main.random_seed = 7
with fluid.program_guard(main, startup):
    with fluid.unique_name.guard():
        x = fluid.layers.data('x', shape=[4], dtype='float32')
        lbl = fluid.layers.data('lbl', shape=[1], dtype='int64')
        h = fluid.layers.fc(x, 8, act='relu')
        logits = fluid.layers.fc(h, 3)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, lbl))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
exe, scope = fluid.Executor(), fluid.Scope()
feed = {'x': np.ones((2, 4), 'float32'), 'lbl': np.zeros((2, 1), 'int64')}
t0 = time.perf_counter()
with fluid.scope_guard(scope):
    exe.run(startup)
    l1, = exe.run(main, feed=feed, fetch_list=[loss])
    ls, = exe.run_steps(main, feed_list=[feed] * 3, fetch_list=[loss])
wall = time.perf_counter() - t0
c = obs.counters()
print(json.dumps({
    'loss': float(np.asarray(l1).ravel()[0]),
    'losses': np.asarray(ls).ravel().tolist(),
    'wall_s': wall,
    'hits': c.get('compile_cache.disk_hits') or 0,
    'misses': c.get('compile_cache.disk_misses') or 0,
    'compile_s': c.get('executor.compile_s') or 0.0,
    'load_s': c.get('compile_cache.load_s') or 0.0,
}))
"""


def _run_warmstart_proc(cache_dir):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != 'PT_CACHE'}
    r = subprocess.run(
        [sys.executable, '-c', _WARMSTART_CODE, repo, str(cache_dir)],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_warm_start_across_fresh_processes(tmp_path):
    """The acceptance contract: run the same program twice in FRESH
    processes over one JAX_COMPILATION_CACHE_DIR — the second must report disk hits,
    zero actual compiles, and materially lower compile time."""
    cold = _run_warmstart_proc(tmp_path / 'cache')
    warm = _run_warmstart_proc(tmp_path / 'cache')
    assert cold['misses'] >= 3 and cold['hits'] == 0
    assert cold['compile_s'] > 0
    assert warm['hits'] >= 3, warm
    assert warm['misses'] == 0, warm
    # no trace happened, so no compile seconds were recorded at all
    assert warm['compile_s'] == 0.0, warm
    # the loaded executable computes the same numbers
    assert warm['loss'] == cold['loss']
    assert warm['losses'] == cold['losses']
    # "materially lower": deserialization must beat trace+compile by a
    # wide margin (measured ~10x; assert 2x to stay CI-noise-proof)
    assert warm['load_s'] < cold['compile_s'] / 2, (warm, cold)


def test_corrupt_disk_entries_are_misses(tmp_path, monkeypatch):
    monkeypatch.setenv('PT_CACHE', '1')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    disk = cc.DiskCache(str(tmp_path))
    fp = 'ab' + 'cd' * 31
    # truncated garbage
    path = disk._path(fp)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, 'wb') as f:
        f.write(b'\x80\x04 this is not a pickle')
    assert disk.load(fp) == (None, None)
    assert not os.path.exists(path), 'corrupt entry must be deleted'
    # wrong format version
    with open(path, 'wb') as f:
        pickle.dump({'format': -1, 'fingerprint': fp, 'tier': 'exec',
                     'payload': None}, f)
    assert disk.load(fp) == (None, None)
    assert not os.path.exists(path)


def test_disk_cache_round_trip_in_process(tmp_path, monkeypatch):
    """PT_CACHE on within one process: a second Executor (fresh L1) must
    resolve from disk without tracing."""
    from paddle_tpu.core import executor as em
    monkeypatch.setenv('PT_CACHE', '1')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    main, startup, loss = _build()
    feed = {'x': np.ones((2, 4), 'float32'),
            'lbl': np.zeros((2, 1), 'int64')}
    exe1, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe1.run(startup)
        a, = exe1.run(main, feed=feed, fetch_list=[loss])
    exe2, scope2 = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2.run(startup)
        tc = em._TRACE_COUNT[0]
        b, = exe2.run(main, feed=feed, fetch_list=[loss])
        assert em._TRACE_COUNT[0] == tc, \
            'second executor must load the AOT executable, not retrace'
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    # the explainer recorded the warm start as a disk_load report
    kinds = [r['kind'] for r in obs.explainer().reports]
    assert 'disk_load' in kinds


# ----------------------------------------------------------------- LRU cap

def test_exec_cache_lru_bound_and_eviction_counter(monkeypatch):
    monkeypatch.setenv('PT_EXEC_CACHE_MAX', '2')
    main, startup, loss = _build()
    exe, scope = fluid.Executor(), fluid.Scope()
    before = obs.counters().get('pt_exec_cache_evictions') or 0
    with fluid.scope_guard(scope):
        exe.run(startup)  # entry 1 (startup program)
        for b in (2, 3, 4, 5):  # distinct feed shapes: distinct entries
            exe.run(main, feed={'x': np.ones((b, 4), 'float32'),
                                'lbl': np.zeros((b, 1), 'int64')},
                    fetch_list=[loss])
    assert len(exe._cache) <= 2
    evictions = (obs.counters().get('pt_exec_cache_evictions') or 0) - before
    assert evictions >= 3, 'LRU bound must evict, and count it'


def test_lru_keeps_recently_used():
    lru = cc.ExecutableLRU(capacity=2)
    lru.put('a', 1)
    lru.put('b', 2)
    assert lru.get('a') == 1      # refresh a
    lru.put('c', 3)               # evicts b, not a
    assert lru.get('a') == 1 and lru.get('b') is None
    assert 'c' in lru and len(lru) == 2


# ------------------------------------------------------- predictor warm start

def test_predictor_warm_starts_from_disk(tmp_path, monkeypatch):
    monkeypatch.setenv('PT_CACHE', '1')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path / 'cache'))
    from paddle_tpu import inference
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[4], dtype='float32')
            y = fluid.layers.fc(x, 3, act='softmax')
    exe = fluid.Executor()
    exe.run(startup)
    model_dir = str(tmp_path / 'model')
    fluid.io.save_inference_model(model_dir, ['x'], [y], exe,
                                  main_program=main)
    feed = {'x': np.ones((2, 4), 'float32')}
    r1 = inference.Predictor(model_dir).run(feed)
    hits0 = obs.counters().get('compile_cache.disk_hits') or 0
    r2 = inference.Predictor(model_dir).run(feed)   # fresh L1: disk hit
    hits1 = obs.counters().get('compile_cache.disk_hits') or 0
    assert hits1 == hits0 + 1
    np.testing.assert_array_equal(np.asarray(r1[0]), np.asarray(r2[0]))


# ------------------------------------------------------------ int64 silence

def test_int64_sites_stay_silent():
    """fill_constant / astype / cast asked for int64 route through
    core.dtypes.jax_dtype — no warn-and-truncate from jax may fire.

    Covers both BENCH_r05-tail leak sites: the `jnp.full` inside
    fill_constant (ops/tensor.py) and the in-trace `.astype` path (the
    *_batch_size_like random ops went through convert_dtype, whose
    int64 survives to `.astype` inside the trace).  The np.int64 VALUE
    case pins the _fill_value normalization (a 64-bit numpy scalar from
    program serialization must not reach jnp.full raw).  Runs over the
    full PT_OPT x PT_EMIT matrix so const-fold/fusion replay AND the
    direct-emitter paths are pinned silent too."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[4], dtype='float32')
            c = fluid.layers.fill_constant([2, 2], 'int64', 7)
            c64 = fluid.layers.fill_constant([2], 'int64', np.int64(9))
            c2 = fluid.layers.cast(c, 'int64') + 1  # fold+fuse fodder
            casted = x.astype('int64')
            topv, topi = fluid.layers.topk(x, k=2)
            blk = main.global_block()
            rnd = blk.create_var(dtype='int64', shape=(-1, 4))
            blk.append_op(
                type='uniform_random_batch_size_like',
                inputs={'Input': x}, outputs={'Out': rnd},
                attrs={'shape': [-1, 4], 'dtype': 'int64',
                       'min': 0.0, 'max': 9.0})
    for pt_opt in ('1', '0'):
        for pt_emit in ('1', '0'):
            os.environ['PT_OPT'] = pt_opt
            os.environ['PT_EMIT'] = pt_emit
            try:
                exe, scope = fluid.Executor(), fluid.Scope()
                with warnings.catch_warnings():
                    warnings.simplefilter('error', UserWarning)
                    with fluid.scope_guard(scope):
                        exe.run(startup)
                        cv, c64v, c2v, iv, tv, rv = exe.run(
                            main, feed={'x': np.ones((3, 4), 'float32')},
                            fetch_list=[c, c64, c2, topi, casted, rnd])
            finally:
                os.environ.pop('PT_OPT', None)
                os.environ.pop('PT_EMIT', None)
            assert cv.ravel()[0] == 7 and c2v.ravel()[0] == 8
            assert c64v.ravel()[0] == 9 and c64v.dtype.kind == 'i'
            assert iv.dtype.kind == 'i' and tv.dtype.kind == 'i'
            assert rv.dtype.kind == 'i' and rv.shape == (3, 4)
