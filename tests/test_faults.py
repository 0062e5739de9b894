"""Fault-injection harness (paddle_tpu/testing/faults.py): spec parsing,
deterministic firing, metric accounting, and the runtime sites it drives
(retry_with_backoff, compile-cache I/O, prefetcher stall, nan_step)."""
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.observability as obs
from paddle_tpu.core.retry import retry_with_backoff
from paddle_tpu.testing import faults


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# ------------------------------------------------------------------ parsing

def test_spec_parsing():
    armed = faults.configure('ckpt_write:at=2,nan_step:at=5:times=3:row=1,'
                             'prefetch_stall:at=1:s=0.25')
    assert set(armed) == {'ckpt_write', 'nan_step', 'prefetch_stall'}
    assert armed['nan_step'].at == 5 and armed['nan_step'].times == 3
    assert armed['nan_step'].row == 1
    assert armed['prefetch_stall'].sleep_s == 0.25
    assert faults.active('ckpt_write') and not faults.active('cache_read')
    # spec() is the read-only accessor soak gates compare verdicts against
    assert faults.spec('nan_step').row == 1
    assert faults.spec('cache_read') is None


def test_spec_rejects_unknown_field():
    with pytest.raises(ValueError, match='not understood'):
        faults.configure('ckpt_write:frequency=2')


def test_env_parse_is_lazy_and_resettable(monkeypatch):
    monkeypatch.setenv('PT_FAULT', 'cache_read:at=1')
    faults.reset()
    assert faults.any_active() and faults.active('cache_read')
    monkeypatch.delenv('PT_FAULT')
    faults.reset()
    assert not faults.any_active()


# ------------------------------------------------------------------- firing

def test_hit_indexed_fire_is_deterministic():
    faults.configure('cache_read:at=3:times=2')
    fires = [faults.fire('cache_read') for _ in range(6)]
    assert fires == [False, False, True, True, False, False]


def test_step_indexed_fire_and_budget_cap():
    faults.configure('nan_step:at=4')
    assert not faults.fire('nan_step', step=3)
    assert faults.fire('nan_step', step=4)
    # budget spent: a rollback replaying step 4 must not re-fire forever
    assert not faults.fire('nan_step', step=4)


def test_fire_in_window_overlap():
    faults.configure('sigterm:at=5')
    assert not faults.fire_in('sigterm', 0, 4)    # [0,4) misses 5
    assert faults.fire_in('sigterm', 4, 4)        # [4,8) covers 5
    assert not faults.fire_in('sigterm', 4, 4)    # budget spent


def test_forensic_replay_ignores_and_preserves_spent_budget():
    """Inside forensic_replay() the nan_step site re-fires its armed
    window without consuming budget; outside, the one-shot semantics
    are intact — before AND after the replay."""
    before = obs.counters().get('faults.injected.nan_step') or 0
    faults.configure('nan_step:at=4')
    assert faults.fire_in('nan_step', 4, 2)       # production: consumed
    assert not faults.fire_in('nan_step', 4, 2)   # budget spent
    with faults.forensic_replay():
        assert faults.fire_in('nan_step', 4, 2)   # replay re-fires...
        assert faults.fire('nan_step', step=4)    # ...as often as asked
    assert not faults.fire_in('nan_step', 4, 2)   # budget still spent
    # the replay fires were not re-counted as injections
    assert obs.counters().get('faults.injected.nan_step') == before + 1


def test_forensic_replay_only_covers_nan_step():
    faults.configure('cache_read:at=1')
    assert faults.fire('cache_read')
    with faults.forensic_replay():
        # other sites keep their budget semantics during a replay
        assert not faults.fire('cache_read')


def test_fired_faults_count_into_observability():
    faults.configure('io_write:at=1')
    c0 = obs.counters()
    with pytest.raises(faults.InjectedFault):
        faults.maybe_fail('io_write')
    c = obs.counters()
    assert c.get('faults.injected') == (c0.get('faults.injected') or 0) + 1
    assert c.get('faults.injected.io_write') == \
        (c0.get('faults.injected.io_write') or 0) + 1


# ------------------------------------------------------------------- retry

def test_retry_recovers_from_transient_failure():
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] < 3:
            raise OSError('transient')
        return 'ok'

    assert retry_with_backoff(flaky, attempts=3, base_delay=0.001) == 'ok'
    assert calls[0] == 3


def test_retry_gives_up_and_reraises():
    with pytest.raises(OSError, match='persistent'):
        retry_with_backoff(lambda: (_ for _ in ()).throw(
            OSError('persistent')), attempts=2, base_delay=0.001)
    assert (obs.counters().get('retry.giveups') or 0) >= 1


def test_retry_never_retries_give_up_exceptions():
    calls = [0]

    def missing():
        calls[0] += 1
        raise FileNotFoundError('no entry')

    with pytest.raises(FileNotFoundError):
        retry_with_backoff(missing, attempts=5, base_delay=0.001,
                           give_up_on=(FileNotFoundError,))
    assert calls[0] == 1


# ------------------------------------------------------- compile-cache site

def test_cache_write_fault_recovers_via_retry(tmp_path, monkeypatch):
    """One injected cache_write OSError must NOT lose the disk store:
    the shared retry_with_backoff absorbs it on the second attempt."""
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    from paddle_tpu.core.compile_cache import DiskCache
    faults.configure('cache_write:at=1')

    class _Lowered(object):
        @staticmethod
        def as_text():
            return 'module @jit { }'

    cache = DiskCache(str(tmp_path))
    tier = cache.store('ab' * 32, compiled=None, lowered=_Lowered())
    assert tier == 'stablehlo'
    assert (obs.counters().get('retry.attempts.cache_write') or 0) >= 1
    assert cache.load('ab' * 32) == (None, 'stablehlo')


def test_cache_read_fault_recovers_via_retry(tmp_path):
    from paddle_tpu.core.compile_cache import DiskCache

    class _Lowered(object):
        @staticmethod
        def as_text():
            return 'module @jit { }'

    cache = DiskCache(str(tmp_path))
    assert cache.store('cd' * 32, lowered=_Lowered()) == 'stablehlo'
    faults.configure('cache_read:at=1')
    assert cache.load('cd' * 32) == (None, 'stablehlo')
    assert (obs.counters().get('retry.attempts.cache_read') or 0) >= 1


# ----------------------------------------------------------- io.py sites

def test_io_write_and_read_faults_recover_via_retry(tmp_path):
    """One transient OSError on each side of the io.py tensor store must
    be absorbed by retry_with_backoff — the save/load pair still meets."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[4], dtype='float32')
            fluid.layers.fc(x, 3)
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        w = np.asarray(scope.get('fc_0.w_0'))
        faults.configure('io_write:at=1,io_read:at=1')
        fluid.io.save_persistables(exe, str(tmp_path), main)
        scope.set('fc_0.w_0', w * 0)
        fluid.io.load_persistables(exe, str(tmp_path), main)
        np.testing.assert_array_equal(np.asarray(scope.get('fc_0.w_0')), w)
    c = obs.counters()
    assert (c.get('retry.attempts.io_write') or 0) >= 1
    assert (c.get('retry.attempts.io_read') or 0) >= 1


# ---------------------------------------------------------- prefetcher site

def test_prefetch_stall_site_fires_and_counts():
    from paddle_tpu.data_feeder import FeedPrefetcher
    before = obs.counters().get('faults.injected.prefetch_stall') or 0
    faults.configure('prefetch_stall:at=1:s=0.01')
    feeds = [{'x': np.full((2, 2), i, np.float32)} for i in range(4)]
    pf = FeedPrefetcher(iter(feeds), steps=2, to_device=False)
    got = [k for _, k in pf]
    pf.close()
    assert got == [2, 2]
    assert obs.counters().get('faults.injected.prefetch_stall') == before + 1


# ------------------------------------------------------------ feed_read site

def test_feed_read_fault_absorbed_by_retry():
    """One injected reader OSError must NOT kill the trainer: the worker
    pulls through retry_with_backoff, which absorbs it and re-reads."""
    from paddle_tpu.data_feeder import FeedPrefetcher
    before = obs.counters().get('retry.attempts.feed_read') or 0
    faults.configure('feed_read:at=2')
    feeds = [{'x': np.full((2, 2), i, np.float32)} for i in range(4)]
    pf = FeedPrefetcher(iter(feeds), steps=2, to_device=False)
    got = [(f, k) for f, k in pf]
    pf.close()
    assert [k for _, k in got] == [2, 2]
    # retried, not reordered: every batch arrived exactly once, in order
    vals = [float(f['x'][j, 0, 0]) for f, _ in got for j in range(2)]
    assert vals == [0.0, 1.0, 2.0, 3.0]
    assert (obs.counters().get('retry.attempts.feed_read') or 0) >= before + 1


def test_feed_read_exhaustion_is_not_a_retry():
    """Reader exhaustion (StopIteration) must drain cleanly through the
    retry wrapper — no attempts, no giveups: an empty stream is not a
    fault."""
    from paddle_tpu.data_feeder import FeedPrefetcher
    faults.configure('feed_read:at=99')   # armed but never reached
    c0 = obs.counters()
    feeds = [{'x': np.zeros((2, 2), np.float32)} for _ in range(3)]
    pf = FeedPrefetcher(iter(feeds), steps=2, to_device=False)
    got = [k for _, k in pf]
    pf.close()
    assert got == [2, 1]                  # partial tail flushed
    c = obs.counters()
    for key in ('retry.attempts.feed_read', 'retry.giveups.feed_read'):
        assert (c.get(key) or 0) == (c0.get(key) or 0)


# --------------------------------------------------- poison_nan row targeting

def test_poison_nan_row_targets_single_row():
    faults.configure('nan_step:at=0:row=1')
    feed = {'x': np.ones((4, 3), np.float32),
            'lbl': np.zeros((4, 1), np.int64)}
    out = faults.poison_nan(feed, 0, 1)
    x = out['x']
    assert np.isnan(x[1]).all()                       # armed row poisoned
    assert np.isfinite(np.delete(x, 1, axis=0)).all()  # others untouched
    np.testing.assert_array_equal(out['lbl'], feed['lbl'])  # ints skipped
    assert np.isfinite(feed['x']).all()               # input not mutated


def test_poison_nan_row_in_stacked_launch():
    """count>1 launches stack steps on axis 0, so the batch is axis 1:
    only (armed step, armed row) goes NaN."""
    faults.configure('nan_step:at=2:row=1')
    feed = {'x': np.ones((4, 3, 2), np.float32)}      # [K=4 steps, B=3, 2]
    out = faults.poison_nan(feed, 0, 4)
    x = out['x']
    assert np.isnan(x[2, 1]).all()
    mask = np.ones(x.shape, bool)
    mask[2, 1] = False
    assert np.isfinite(x[mask]).all()


def test_poison_nan_without_row_poisons_whole_step():
    faults.configure('nan_step:at=1')
    feed = {'x': np.ones((3, 2, 2), np.float32)}      # [K=3 steps, B=2, 2]
    out = faults.poison_nan(feed, 0, 3)
    x = out['x']
    assert np.isnan(x[1]).all()                       # entire armed step
    assert np.isfinite(x[0]).all() and np.isfinite(x[2]).all()


def test_poison_nan_outside_window_is_identity():
    faults.configure('nan_step:at=7:row=0')
    feed = {'x': np.ones((2, 2), np.float32)}
    assert faults.poison_nan(feed, 0, 2) is feed      # window miss: no copy


# ------------------------------------------------------------ executor site

def test_nan_step_fault_trips_check_nan(tmp_path):
    """The nan_step site poisons one step's feeds; the executor's fused
    check_nan verdict must trip exactly at that step, with the steps
    before and after healthy."""
    before = obs.counters().get('faults.injected.nan_step') or 0
    faults.configure('nan_step:at=1')
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[4], dtype='float32')
            y = fluid.layers.fc(x, 3)
            loss = fluid.layers.reduce_mean(y)
            fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe, scope = fluid.Executor(check_nan=True), fluid.Scope()
    feed = {'x': np.ones((2, 4), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])          # step 0: fine
        with pytest.raises(RuntimeError, match='check_nan'):
            exe.run(main, feed=feed, fetch_list=[loss])      # step 1: poisoned
    assert obs.counters().get('faults.injected.nan_step') == before + 1
