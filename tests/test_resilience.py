"""Fault-tolerant training runtime (train/checkpoint.py, train/recovery.py):
async checkpointing, torn-write scanning, SIGKILL/SIGTERM kill-and-resume
with bitwise loss parity, and divergence rollback."""
import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.observability as obs
from paddle_tpu.data_feeder import SampleQuarantine
from paddle_tpu.testing import faults
from paddle_tpu.train import (CheckpointConfig, Checkpointer, LaunchRecord,
                              RecoveryPolicy, DivergenceError)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _build_model(seed=11):
    """Tiny classifier with dropout (RNG-dependent) + AMP + Adam (optimizer
    accumulator state) — the full resume surface."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[4], dtype='float32')
            lbl = fluid.layers.data('lbl', shape=[1], dtype='int64')
            h = fluid.layers.fc(x, 8, act='relu')
            h = fluid.layers.dropout(h, 0.3)
            logits = fluid.layers.fc(h, 3)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, lbl))
            fluid.optimizer.AdamOptimizer(0.01).minimize(loss)
    main.set_amp(True)
    return main, startup, loss


def _feed_at(i):
    rng = np.random.RandomState(100 + i)
    return {'x': rng.rand(4, 4).astype('float32'),
            'lbl': rng.randint(0, 3, (4, 1)).astype('int64')}


# ------------------------------------------------------------ async writer

def test_async_save_restore_roundtrip_with_rng_state(tmp_path):
    main, startup, loss = _build_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    ck = Checkpointer(CheckpointConfig(str(tmp_path), step_interval=1),
                      exe, main, scope=scope)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for i in range(3):
            exe.run(main, feed=_feed_at(i), fetch_list=[loss])
        ck.save(0, 2, extra_meta={'note': 'hello'})
        ck.wait()
        w = np.asarray(scope.get('fc_0.w_0'))
        m1 = np.asarray(scope.get('fc_0.w_0_moment1_0'))

    # fresh executor/scope = fresh process stand-in
    main2, startup2, loss2 = _build_model()
    exe2, scope2 = fluid.Executor(), fluid.Scope()
    ck2 = Checkpointer(CheckpointConfig(str(tmp_path), step_interval=1),
                       exe2, main2, scope=scope2)
    meta = ck2.restore()
    assert meta['epoch_id'] == 0 and meta['step_id'] == 2
    assert meta['note'] == 'hello'
    # params AND optimizer accumulators restored bit-for-bit
    np.testing.assert_array_equal(np.asarray(scope2.get('fc_0.w_0')), w)
    np.testing.assert_array_equal(
        np.asarray(scope2.get('fc_0.w_0_moment1_0')), m1)
    # RNG/run counters restored: the next launch's counter continues
    assert meta['rng_state'] and exe2._pending_counters


def test_async_saves_do_not_block_and_rotate_valid_only(tmp_path):
    main, startup, loss = _build_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    ck = Checkpointer(CheckpointConfig(str(tmp_path), step_interval=1,
                                       max_num_checkpoints=2),
                      exe, main, scope=scope)
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed_at(0), fetch_list=[loss])
        for step in range(5):
            ck.save(0, step)
    ck.wait()
    kept = sorted(d for d in os.listdir(tmp_path)
                  if d.startswith('checkpoint_'))
    assert kept == ['checkpoint_3', 'checkpoint_4']
    assert (obs.counters().get('ckpt.saves') or 0) >= 5


def test_write_failure_is_counted_not_fatal(tmp_path):
    """A torn write (injected ckpt_write fault) must not kill training:
    counted + warned, and the NEXT save succeeds."""
    faults.configure('ckpt_write:at=1')
    main, startup, loss = _build_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    ck = Checkpointer(CheckpointConfig(str(tmp_path), step_interval=1),
                      exe, main, scope=scope)
    with fluid.scope_guard(scope):
        exe.run(startup)
        ck.save(0, 0)          # torn by the fault
        with pytest.warns(UserWarning, match='checkpoint write failed'):
            ck.wait()          # draining surfaces the async failure
        ck.save(0, 1)          # ...and the next save succeeds
        ck.wait()
    meta = Checkpointer(CheckpointConfig(str(tmp_path)), exe, main,
                        scope=scope).restore()
    assert meta['step_id'] == 1
    assert (obs.counters().get('ckpt.write_failures') or 0) >= 1


def test_torn_checkpoint_scan_restores_previous_valid(tmp_path):
    """The satellite contract: an injected mid-write failure leaves a torn
    dir; the restorer deletes it and picks the previous valid serial."""
    main, startup, loss = _build_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    ck = Checkpointer(CheckpointConfig(str(tmp_path), step_interval=1),
                      exe, main, scope=scope)
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed_at(0), fetch_list=[loss])
        ck.save(0, 0)
        ck.wait()
        w0 = np.asarray(scope.get('fc_0.w_0'))
        exe.run(main, feed=_feed_at(1), fetch_list=[loss])
        faults.configure('ckpt_write:at=1')   # tear the SECOND save
        ck.save(0, 1)
        try:
            ck.wait()
        except Exception:
            pass
    # torn leftovers exist before the scan...
    leftovers = [d for d in os.listdir(tmp_path)
                 if d.startswith('.tmp_ckpt_')]
    assert leftovers, 'fault should have left a torn temp dir'
    main2, startup2, loss2 = _build_model()
    exe2, scope2 = fluid.Executor(), fluid.Scope()
    ck2 = Checkpointer(CheckpointConfig(str(tmp_path)), exe2, main2,
                       scope=scope2)
    meta = ck2.restore()
    # ...and are swept by it, with the previous valid serial restored
    assert meta['step_id'] == 0
    np.testing.assert_array_equal(np.asarray(scope2.get('fc_0.w_0')), w0)
    assert not [d for d in os.listdir(tmp_path)
                if d.startswith('.tmp_ckpt_')]
    assert (obs.counters().get('ckpt.torn_deleted') or 0) >= 1


# ------------------------------------------------- retry-routed disk I/O

def test_ckpt_io_transient_blip_absorbed_by_retry(tmp_path):
    """A one-shot ckpt_io OSError is a blip, not a torn write: the
    retried writer absorbs it and the checkpoint still lands."""
    faults.configure('ckpt_io:at=1')
    main, startup, loss = _build_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    ck = Checkpointer(CheckpointConfig(str(tmp_path), step_interval=1),
                      exe, main, scope=scope)
    c0 = obs.counters()
    w0, r0 = c0.get('ckpt.write_failures') or 0, c0.get('retry.attempts') or 0
    with fluid.scope_guard(scope):
        exe.run(startup)
        ck.save(0, 0)
        ck.wait()
    c = obs.counters()
    assert (c.get('ckpt.write_failures') or 0) == w0, 'blip must be absorbed'
    assert (c.get('retry.attempts') or 0) > r0
    assert (c.get('retry.attempts.ckpt.write') or 0) >= 1
    meta = Checkpointer(CheckpointConfig(str(tmp_path)), exe, main,
                        scope=scope).restore()
    assert meta['step_id'] == 0


def test_ckpt_io_exhausted_retry_budget_fails_the_write(tmp_path):
    """A persistent disk failure burns the whole backoff budget, then
    surfaces exactly like any other write failure: counted + warned."""
    faults.configure('ckpt_io:at=1:times=99')
    main, startup, loss = _build_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    ck = Checkpointer(CheckpointConfig(str(tmp_path), step_interval=1),
                      exe, main, scope=scope)
    g0 = obs.counters().get('retry.giveups') or 0
    with fluid.scope_guard(scope):
        exe.run(startup)
        ck.save(0, 0)
        with pytest.warns(UserWarning, match='checkpoint write failed'):
            ck.wait()
    c = obs.counters()
    assert (c.get('retry.giveups') or 0) > g0
    assert (c.get('ckpt.write_failures') or 0) >= 1


# --------------------------------------------- ckpt.lock (two processes)

_LOCK_CHILD = r"""
import fcntl, os, sys
fd = os.open(sys.argv[1], os.O_CREAT | os.O_RDWR, 0o644)
fcntl.flock(fd, fcntl.LOCK_EX)
print('locked', flush=True)
sys.stdin.readline()
fcntl.flock(fd, fcntl.LOCK_UN)
print('released', flush=True)
"""


def test_ckpt_lock_excludes_a_second_process(tmp_path):
    """The satellite contract: two Checkpointers sharing one directory
    cannot interleave rotation sweeps — a second PROCESS holding
    ckpt.lock blocks dir_lock() until it releases."""
    child = subprocess.Popen(
        [sys.executable, '-c', _LOCK_CHILD, str(tmp_path / 'ckpt.lock')],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        assert child.stdout.readline().strip() == 'locked'
        main, startup, loss = _build_model()
        exe, scope = fluid.Executor(), fluid.Scope()
        ck = Checkpointer(CheckpointConfig(str(tmp_path),
                                           lock_timeout_s=0.4),
                          exe, main, scope=scope)
        with pytest.raises(RuntimeError, match='checkpoint lock'):
            with ck.dir_lock():
                pass
        child.stdin.write('\n')
        child.stdin.flush()
        assert child.stdout.readline().strip() == 'released'
        child.wait(timeout=30)
        with ck.dir_lock():
            pass   # free again once the peer released
    finally:
        if child.poll() is None:
            child.kill()


# -------------------------------------------- manifest integrity (sharded)

def test_corrupt_shard_and_manifest_fall_back_to_previous_serial(tmp_path):
    """Flip one byte in a shard payload and one in a MANIFEST.json: both
    serials must be skipped (checksum / parse failure), the previous
    clean serial restored, and every skip counted."""
    main, startup, loss = _build_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    ck = Checkpointer(CheckpointConfig(str(tmp_path), step_interval=1,
                                       sharded=True),
                      exe, main, scope=scope)
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=_feed_at(0), fetch_list=[loss])
        ck.save(0, 0)
        ck.wait()
        w0 = np.asarray(scope.get('fc_0.w_0'))
        for i in (1, 2):
            exe.run(main, feed=_feed_at(i), fetch_list=[loss])
            ck.save(0, i)
            ck.wait()

    def flip(path):
        blob = bytearray(path.read_bytes())
        blob[len(blob) // 2] ^= 0xFF
        path.write_bytes(bytes(blob))

    flip(tmp_path / 'checkpoint_3' / 'arrays_0.npz')      # newest: payload
    flip(tmp_path / 'checkpoint_2' / 'MANIFEST.json')     # next: manifest
    c0 = obs.counters().get('ckpt.corrupt_skipped') or 0
    main2, startup2, loss2 = _build_model()
    exe2, scope2 = fluid.Executor(), fluid.Scope()
    ck2 = Checkpointer(CheckpointConfig(str(tmp_path), sharded=True),
                       exe2, main2, scope=scope2)
    meta = ck2.restore()
    assert meta['step_id'] == 0, 'must land on the last CLEAN serial'
    np.testing.assert_array_equal(np.asarray(scope2.get('fc_0.w_0')), w0)
    assert (obs.counters().get('ckpt.corrupt_skipped') or 0) == c0 + 2


# --------------------------------------------------------- recovery policy

def test_recovery_rolls_back_and_skips_nan_step(tmp_path):
    faults.configure('nan_step:at=2')
    main, startup, loss = _build_model()
    exe = fluid.Executor(check_nan=True)
    scope = fluid.Scope()
    ck = Checkpointer(CheckpointConfig(str(tmp_path), step_interval=1),
                      exe, main, scope=scope)
    pol = RecoveryPolicy(ck, max_retries=2)
    r0 = obs.counters().get('recovery.rollbacks') or 0
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        skipped = []
        for i in range(5):
            out = pol.run(lambda: exe.run(main, feed=_feed_at(i),
                                          fetch_list=[loss]))
            if out is None:
                skipped.append(i)
                continue
            ck.maybe_save(0, i)
            losses.append(float(np.asarray(out[0]).ravel()[0]))
    assert skipped == [2]
    assert all(np.isfinite(losses)) and len(losses) == 4
    c = obs.counters()
    assert c.get('recovery.rollbacks') == r0 + 1
    assert (c.get('faults.injected.nan_step') or 0) >= 1


def test_recovery_gives_up_after_bounded_retries(tmp_path):
    main, startup, loss = _build_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    ck = Checkpointer(CheckpointConfig(str(tmp_path), step_interval=1),
                      exe, main, scope=scope)
    with fluid.scope_guard(scope):
        exe.run(startup)
        ck.save(0, 0)
        ck.wait()
    pol = RecoveryPolicy(ck, max_retries=2)

    def always_nan():
        raise RuntimeError('check_nan: non-finite values everywhere')

    assert pol.run(always_nan) is None
    assert pol.run(always_nan) is None
    with pytest.raises(RuntimeError, match='check_nan'):
        pol.run(always_nan)   # third consecutive divergence: re-raise
    assert (obs.counters().get('recovery.giveups') or 0) >= 1


def test_recovery_requires_a_checkpoint():
    main, startup, loss = _build_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    ck = Checkpointer(CheckpointConfig('/nonexistent/ckpt'), exe, main,
                      scope=scope)
    pol = RecoveryPolicy(ck, max_retries=3)
    with pytest.raises(RuntimeError, match='no valid checkpoint'):
        pol.run(lambda: (_ for _ in ()).throw(
            RuntimeError('check_nan: boom')))


def test_loss_spike_heuristic():
    main, startup, loss = _build_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    ck = Checkpointer(CheckpointConfig('unused_dir'), exe, main,
                      scope=scope)
    pol = RecoveryPolicy(ck, spike_factor=10.0, min_history=3)
    for v in (1.0, 1.1, 0.9, 1.05):
        pol.check_loss(np.float32(v))
    with pytest.raises(DivergenceError, match='loss spike'):
        pol.check_loss(np.float32(50.0))
    with pytest.raises(DivergenceError, match='non-finite'):
        pol.check_loss(np.float32(np.nan))


def test_non_divergence_errors_propagate_untouched(tmp_path):
    main, startup, loss = _build_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    ck = Checkpointer(CheckpointConfig(str(tmp_path)), exe, main,
                      scope=scope)
    pol = RecoveryPolicy(ck)
    with pytest.raises(ValueError, match='a real bug'):
        pol.run(lambda: (_ for _ in ()).throw(ValueError('a real bug')))


# ----------------------------------------------------- prefetcher cursor

def test_prefetcher_skip_steps_fast_forwards():
    from paddle_tpu.data_feeder import FeedPrefetcher
    feeds = [{'x': np.full((2,), i, np.float32)} for i in range(8)]
    pf = FeedPrefetcher(iter(feeds), steps=2, to_device=False, skip_steps=4)
    got = [stacked['x'][:, 0].tolist() for stacked, k in pf]
    pf.close()
    assert got == [[4.0, 5.0], [6.0, 7.0]]
    assert pf.cursor() == {'steps': 8, 'superbatches': 2, 'skipped': 4}


# ------------------------------------------------- kill-and-resume (E2E)

_TRAIN_SCRIPT = r"""
import json, os, signal, sys
os.environ['JAX_PLATFORMS'] = 'cpu'
os.environ.setdefault('PT_CACHE', '0')
sys.path.insert(0, sys.argv[1])
mode, ckpt_dir = sys.argv[2], sys.argv[3]
total, kill_at = int(sys.argv[4]), int(sys.argv[5])
import numpy as np
import paddle_tpu as fluid
from paddle_tpu.train import CheckpointConfig, Checkpointer

main, startup = fluid.Program(), fluid.Program()
main.random_seed = 11
with fluid.program_guard(main, startup):
    with fluid.unique_name.guard():
        x = fluid.layers.data('x', shape=[4], dtype='float32')
        lbl = fluid.layers.data('lbl', shape=[1], dtype='int64')
        h = fluid.layers.fc(x, 8, act='relu')
        h = fluid.layers.dropout(h, 0.3)
        logits = fluid.layers.fc(h, 3)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, lbl))
        fluid.optimizer.AdamOptimizer(0.01).minimize(loss)
main.set_amp(True)

def feed_at(i):
    rng = np.random.RandomState(100 + i)
    return {'x': rng.rand(4, 4).astype('float32'),
            'lbl': rng.randint(0, 3, (4, 1)).astype('int64')}

exe, scope = fluid.Executor(), fluid.Scope()
ck = Checkpointer(CheckpointConfig(ckpt_dir, step_interval=1,
                                   max_num_checkpoints=3),
                  exe, main, scope=scope)
ck.install_signal_handlers()
meta = ck.restore()
start = meta['step_id'] + 1 if meta else 0
K = 2
losses = []
with fluid.scope_guard(scope):
    if meta is None:
        exe.run(startup)
    if mode == 'run':
        for i in range(start, total):
            l, = exe.run(main, feed=feed_at(i), fetch_list=[loss])
            losses.append(float(np.asarray(l).ravel()[0]))
            ck.save(0, i)                        # async, every step
            if i == kill_at - 1:
                # one checkpoint is durable however slowly the writer
                # thread runs (on a loaded host none of five had landed
                # at the kill); step kill_at's write still races the kill
                ck.wait()
            if i == kill_at:
                os.kill(os.getpid(), signal.SIGKILL)   # preemption, hard
    else:
        for s in range(start, total, K):
            feeds = [feed_at(i) for i in range(s, s + K)]
            ls, = exe.run_steps(main, feed_list=feeds, steps=K,
                                fetch_list=[loss])
            losses.extend(float(v) for v in np.asarray(ls).ravel())
            ck.save(0, s + K - 1)
            if s <= kill_at - K < s + K:
                ck.wait()        # as above: the window before the kill
            if s <= kill_at < s + K:
                os.kill(os.getpid(), signal.SIGKILL)
print(json.dumps({'start': start, 'losses': losses}))
"""


def _run_train_proc(mode, ckpt_dir, total=8, kill_at=-1, timeout=240,
                    env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != 'PT_FAULT'}
    env.update(env_extra or {})
    r = subprocess.run(
        [sys.executable, '-c', _TRAIN_SCRIPT, REPO, mode, str(ckpt_dir),
         str(total), str(kill_at)],
        capture_output=True, text=True, timeout=timeout, env=env)
    return r


@pytest.mark.parametrize('mode', ['run', 'run_steps'])
def test_sigkill_and_auto_resume_is_bitwise(tmp_path, mode):
    """The acceptance contract: SIGKILL a training run mid-epoch, restart
    with auto-resume, and the combined loss stream is BITWISE equal to an
    uninterrupted run (CPU, dropout + AMP on) — through both the run and
    run_steps paths."""
    # uninterrupted reference (its own checkpoint dir, same code path)
    full = _run_train_proc(mode, tmp_path / 'full')
    assert full.returncode == 0, full.stderr
    ref = json.loads(full.stdout.strip().splitlines()[-1])
    assert ref['start'] == 0 and len(ref['losses']) == 8

    # killed run: SIGKILL right after step 4's (async) checkpoint submit
    killed = _run_train_proc(mode, tmp_path / 'ck', kill_at=4)
    assert killed.returncode == -signal.SIGKILL, (killed.returncode,
                                                  killed.stderr)

    # resume: picks the newest VALID checkpoint and finishes the epoch
    resumed = _run_train_proc(mode, tmp_path / 'ck')
    assert resumed.returncode == 0, resumed.stderr
    res = json.loads(resumed.stdout.strip().splitlines()[-1])
    assert res['start'] >= 1, 'resume did not find a checkpoint'
    assert res['start'] <= 5, 'resume overshot the kill point'
    # bitwise: the resumed tail equals the uninterrupted run's tail
    assert res['losses'] == ref['losses'][res['start']:], \
        'resumed run diverged from the uninterrupted one'


# ------------------------------------- forensics & sample quarantine (E2E)

def _stack_feeds(i0, k):
    per = [_feed_at(i0 + j) for j in range(k)]
    return {n: np.stack([f[n] for f in per]) for n in per[0]}


def _forensic_reference(qstate, total, k=1):
    """Uninjected run with the quarantine pre-seeded — the bitwise target
    a healed run must match.  Launch shape (single-step vs run_steps
    windows) mirrors the injected run so RNG stream counters line up."""
    faults.configure('')   # disarm: this is the clean-world counterfactual
    main, startup, loss = _build_model()
    exe, scope = fluid.Executor(check_nan=True), fluid.Scope()
    q = SampleQuarantine()
    q.restore(qstate)
    losses = {}
    with fluid.scope_guard(scope):
        exe.run(startup)
        step = 0
        while step < total:
            if k == 1:
                feed, _ = q.apply(_feed_at(step), step)
                out = exe.run(main, feed=feed, fetch_list=[loss])
                losses[step] = float(np.asarray(out[0]).ravel()[0])
            else:
                stacked, _ = q.apply(_stack_feeds(step, k), step, k)
                out = exe.run_steps(main, feed_list=stacked, steps=k,
                                    fetch_list=[loss])
                for j, v in enumerate(np.asarray(out[0]).ravel()):
                    losses[step + j] = float(v)
            step += k
    return losses


def test_forensics_names_injected_op_and_row_sync(tmp_path):
    """The tentpole contract, sync verdicts (nan_poll=1): a row-targeted
    nan_step trip must come back as a ForensicReport naming the exact
    step, consuming op, and batch row; the row's sample lands in the
    quarantine; the healed loss stream is BITWISE equal to an uninjected
    run with the same quarantine pre-seeded."""
    faults.configure('nan_step:at=2:row=1')
    main, startup, loss = _build_model()
    exe = fluid.Executor(check_nan=True, nan_poll=1)
    scope = fluid.Scope()
    q = SampleQuarantine()
    ck = Checkpointer(CheckpointConfig(str(tmp_path), step_interval=1,
                                       max_num_checkpoints=3),
                      exe, main, scope=scope, quarantine=q)
    pol = RecoveryPolicy(ck, max_retries=4)
    losses = {}
    with fluid.scope_guard(scope):
        exe.run(startup)
        ck.save(0, -1)
        ck.wait()
        pol.note_checkpoint(-1)
        for i in range(5):
            out = pol.run(lambda: exe.run(main, feed=_feed_at(i),
                                          fetch_list=[loss]),
                          launch=LaunchRecord(main, _feed_at(i), None,
                                              [loss], i))
            if pol.last_replay is not None:       # rung 1 healed the window
                for s0, _n, o in pol.last_replay:
                    losses[s0] = float(np.asarray(o[0]).ravel()[0])
            else:
                assert out is not None, 'forensic heal must not skip-batch'
                losses[i] = float(np.asarray(out[0]).ravel()[0])
            ck.save(0, i)
            ck.wait()
            pol.note_checkpoint(i)
    rep = pol.last_report
    assert rep is not None and rep.tripped, 'no forensic report'
    assert rep.step == 2 and rep.rows == [1]
    assert rep.row_method == 'feed_scan'
    assert rep.op_type and rep.source_loc, 'report must name the op'
    assert 2 * 4 + 1 in q.state()      # default step*batch_size+row mapping
    assert sorted(losses) == list(range(5))
    assert all(np.isfinite(v) for v in losses.values())
    assert (obs.counters().get('recovery.escalation.quarantine') or 0) >= 1
    assert losses == _forensic_reference(q.state(), 5)


def test_forensics_localizes_inside_deferred_window_async(tmp_path):
    """Same contract under deferred verdicts (nan_poll=4, as_futures):
    the trip lands steps AFTER the poisoned launch, so forensics must
    bisect the whole condemned multi-launch window back to one step and
    one row — and the heal must still be bitwise."""
    faults.configure('nan_step:at=2:row=1')
    main, startup, loss = _build_model()
    exe = fluid.Executor(check_nan=True, nan_poll=4)
    scope = fluid.Scope()
    q = SampleQuarantine()
    ck = Checkpointer(CheckpointConfig(str(tmp_path), step_interval=1,
                                       max_num_checkpoints=3),
                      exe, main, scope=scope, quarantine=q)
    pol = RecoveryPolicy(ck, max_retries=4)
    K, total = 2, 8
    losses = {}
    pending = []   # [(loss_future, step0)] not yet past a clean poll

    def flush():
        for f, s0 in pending:
            for j, v in enumerate(np.asarray(f).ravel()):
                losses[s0 + j] = float(v)
        del pending[:]

    def land_replay():
        del pending[:]   # condemned-launch futures: superseded by the heal
        for s0, _n, o in pol.last_replay:
            for j, v in enumerate(np.asarray(o[0]).ravel()):
                losses[s0 + j] = float(v)

    def saved(step_id):
        ck.save(0, step_id)
        ck.wait()
        pol.note_checkpoint(step_id)

    with fluid.scope_guard(scope):
        exe.run(startup)
        ck.save(0, -1)
        ck.wait()
        pol.note_checkpoint(-1)
        step = 0
        while step < total:
            stacked = _stack_feeds(step, K)
            out = pol.run(
                lambda: exe.run_steps(main, feed_list=stacked, steps=K,
                                      fetch_list=[loss], as_futures=True),
                launch=LaunchRecord(main, stacked, K, [loss], step))
            if pol.last_replay is not None:
                land_replay()
                saved(step + K - 1)
            elif out is not None:
                pending.append((out[0], step))
                if exe.nan_clean():   # deferred verdict read AND clean
                    flush()
                    saved(step + K - 1)
            step += K
        if pending:
            def drain():
                exe.poll_nan()
                return []
            tail = pol.run(drain)
            if pol.last_replay is not None:
                land_replay()
            elif tail is not None:
                flush()
    rep = pol.last_report
    assert rep is not None and rep.tripped, 'no forensic report'
    assert rep.step == 2 and rep.rows == [1]
    assert rep.op_type and rep.source_loc
    assert 2 * 4 + 1 in q.state()
    assert sorted(losses) == list(range(total))
    assert all(np.isfinite(v) for v in losses.values())
    assert losses == _forensic_reference(q.state(), total, k=K)


_FORENSIC_SCRIPT = r"""
import json, os, signal, sys
os.environ['JAX_PLATFORMS'] = 'cpu'
os.environ.setdefault('PT_CACHE', '0')
sys.path.insert(0, sys.argv[1])
ckpt_dir = sys.argv[2]
total, kill_at = int(sys.argv[3]), int(sys.argv[4])
import numpy as np
import paddle_tpu as fluid
import paddle_tpu.observability as obs
from paddle_tpu.data_feeder import SampleQuarantine
from paddle_tpu.train import (CheckpointConfig, Checkpointer, LaunchRecord,
                              RecoveryPolicy)

main, startup = fluid.Program(), fluid.Program()
main.random_seed = 11
with fluid.program_guard(main, startup):
    with fluid.unique_name.guard():
        x = fluid.layers.data('x', shape=[4], dtype='float32')
        lbl = fluid.layers.data('lbl', shape=[1], dtype='int64')
        h = fluid.layers.fc(x, 8, act='relu')
        logits = fluid.layers.fc(h, 3)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, lbl))
        fluid.optimizer.AdamOptimizer(0.01).minimize(loss)

EPOCH, BATCH = 4, 4

def feed_at(i):
    e = i % EPOCH
    rng = np.random.RandomState(100 + e)
    f = {'x': rng.rand(BATCH, 4).astype('float32'),
         'lbl': rng.randint(0, 3, (BATCH, 1)).astype('int64')}
    if e == 1:
        f['x'][2] = np.nan   # a genuinely bad sample, recurs every epoch
    return f

def index_of(step, row, batch):
    # epoch-stable reader index: the same bad sample keeps the same id
    return (int(step) % EPOCH) * batch + int(row)

exe = fluid.Executor(check_nan=True, nan_poll=1)
scope = fluid.Scope()
q = SampleQuarantine(index_of=index_of)
ck = Checkpointer(CheckpointConfig(ckpt_dir, step_interval=1,
                                   max_num_checkpoints=3),
                  exe, main, scope=scope, quarantine=q)
pol = RecoveryPolicy(ck, max_retries=4, sample_index_of=index_of)
meta = ck.restore()
start = meta['step_id'] + 1 if meta else 0
losses = []
with fluid.scope_guard(scope):
    if meta is None:
        exe.run(startup)
        ck.save(0, -1)
        ck.wait()
        pol.note_checkpoint(-1)
    for i in range(start, total):
        feed = q.apply(feed_at(i), i)[0]
        out = pol.run(lambda: exe.run(main, feed=feed, fetch_list=[loss]),
                      launch=LaunchRecord(main, feed, None, [loss], i))
        if pol.last_replay is not None:
            for s0, n, o in pol.last_replay:
                losses.append(float(np.asarray(o[0]).ravel()[0]))
        elif out is not None:
            losses.append(float(np.asarray(out[0]).ravel()[0]))
        ck.save(0, i)
        ck.wait()
        pol.note_checkpoint(i)
        if i == kill_at:
            os.kill(os.getpid(), signal.SIGKILL)
print(json.dumps({'start': start, 'losses': losses,
                  'divergences':
                      obs.counters().get('recovery.divergences') or 0,
                  'quarantine': q.state()}))
"""


def _run_forensic_proc(ckpt_dir, total=12, kill_at=-1, timeout=240):
    env = {k: v for k, v in os.environ.items() if k != 'PT_FAULT'}
    return subprocess.run(
        [sys.executable, '-c', _FORENSIC_SCRIPT, REPO, str(ckpt_dir),
         str(total), str(kill_at)],
        capture_output=True, text=True, timeout=timeout, env=env)


def test_sigkill_resume_restores_quarantine_from_meta(tmp_path):
    """The satellite contract: a genuinely bad sample (NaN row baked into
    the data, recurring every epoch) is quarantined by forensics in epoch
    one; the process is then SIGKILLed.  The resumed process must inherit
    the quarantine from checkpoint META and finish the run WITHOUT ever
    re-tripping on that sample."""
    killed = _run_forensic_proc(tmp_path / 'ck', total=12, kill_at=6)
    assert killed.returncode == -signal.SIGKILL, (killed.returncode,
                                                  killed.stderr)
    resumed = _run_forensic_proc(tmp_path / 'ck', total=12)
    assert resumed.returncode == 0, resumed.stderr
    res = json.loads(resumed.stdout.strip().splitlines()[-1])
    assert res['start'] == 7, res['start']
    # (epoch step 1, row 2) on batch 4 -> stable reader index 6,
    # restored from META — not re-derived by a second forensic run
    assert res['quarantine'] == [6], res['quarantine']
    assert res['divergences'] == 0, \
        'resume re-tripped on an already-quarantined sample'
    assert len(res['losses']) == 5
    assert all(np.isfinite(res['losses']))


def test_sigterm_flushes_final_checkpoint_and_resumes_bitwise(tmp_path):
    """Graceful preemption: the sigterm fault site delivers SIGTERM as
    step 3 is about to launch; the installed handler flushes one final
    checkpoint (scope, RNG counters, and recorded progress all consistent
    at "step 2 complete") before the process dies, and the resumed run
    continues bitwise."""
    full = _run_train_proc('run', tmp_path / 'full')
    ref = json.loads(full.stdout.strip().splitlines()[-1])

    killed = _run_train_proc('run', tmp_path / 'ck',
                             env_extra={'PT_FAULT': 'sigterm:at=3'})
    assert killed.returncode != 0
    resumed = _run_train_proc('run', tmp_path / 'ck')
    assert resumed.returncode == 0, resumed.stderr
    res = json.loads(resumed.stdout.strip().splitlines()[-1])
    # the flush covered steps 0..2, so resume starts exactly at step 3 —
    # no step lost, no step double-trained
    assert res['start'] == 3, res
    assert res['losses'] == ref['losses'][3:]
