"""Fully-async executor runtime: never let the host serialize the device.

JAX dispatches launches asynchronously — the device only waits on the
host when the host *reads* (``np.asarray``, ``bool()``, a blocking
fetch).  PERF.md measured the cost of ignoring that: the per-launch
``check_nan`` verdict read alone held a 4x slowdown, because one
``bool(ok)`` per step drains the whole dispatch pipeline.

This module holds the three primitives the executor's async mode is
built from:

  * ``host_block(reason)`` — a context manager that meters every forced
    host<->device sync into the ``executor.host_blocked_s`` counter and
    a ``host_block`` span, so "how much did the host serialize the
    device" is a recorded number, not a vibe.
  * ``FetchFuture`` — the handle ``run``/``run_steps`` return in
    non-blocking mode (``as_futures=True``): the device array plus a
    lazy, cached, metered ``.numpy()``.
  * ``DeferredNanVerdict`` — the fused all-finite verdict stays
    device-resident as a running AND across launches and is only read
    (one host sync) every ``poll_every`` steps.  ``PT_NAN_POLL=1`` — the
    default unless ``PT_ASYNC=1`` opts in — reproduces the synchronous
    per-launch read bit-for-bit.

Env knobs (see docs/async.md):

  ``PT_ASYNC=1``     opt the process into async defaults (deferred
                     verdict polling every ``_ASYNC_DEFAULT_POLL`` steps).
  ``PT_NAN_POLL=N``  explicit verdict poll cadence in steps; overrides
                     the PT_ASYNC default.  N=1 is today's synchronous
                     semantics.
"""
import contextlib
import os
import time

import numpy as np

from .. import observability as _obs

__all__ = ['FetchFuture', 'DeferredNanVerdict', 'host_block',
           'async_enabled', 'default_nan_poll', 'DEFERRED_TRIP_MSG']

# deferred-poll cadence when PT_ASYNC=1 and PT_NAN_POLL is unset: long
# enough to amortize the verdict read over a fused launch window, short
# enough that a rollback replays a bounded number of steps
_ASYNC_DEFAULT_POLL = 8

# a deferred trip cannot always name a single step: the running AND only
# says "some step since the last poll went non-finite".  The message MUST
# keep the 'check_nan' prefix — train/recovery.py classifies divergences
# by it.
DEFERRED_TRIP_MSG = (
    'check_nan: non-finite values (nan/inf) detected by a deferred '
    'verdict poll covering the last %d step(s) — the divergence is '
    'localized to this window, not a single step (set PT_NAN_POLL=1 '
    'for per-step attribution). Roll back to a checkpoint saved before '
    'the window (Executor.nan_clean() aligned saves guarantee one).')


def async_enabled():
    return os.environ.get('PT_ASYNC', '') in ('1', 'true', 'True')


def default_nan_poll():
    """Verdict poll cadence: explicit ``PT_NAN_POLL`` wins; otherwise 1
    (the synchronous per-launch read, bit-for-bit today's semantics)
    unless ``PT_ASYNC=1`` opts the process into deferred polling."""
    env = os.environ.get('PT_NAN_POLL', '')
    if env:
        return max(1, int(env))
    return _ASYNC_DEFAULT_POLL if async_enabled() else 1


@contextlib.contextmanager
def host_block(reason, **args):
    """Meter a forced host<->device sync.

    Every second spent inside lands in ``executor.host_blocked_s`` and a
    ``host_block`` span tagged with the reason — verdict polls, future
    reads, checkpoint snapshots all become visible, attributable time."""
    if not _obs.enabled():
        yield
        return
    t0 = time.perf_counter()
    try:
        yield
    finally:
        t1 = time.perf_counter()
        _obs.metrics.counter('executor.host_blocked_s').inc(t1 - t0)
        _obs.tracing.add_span('host_block', t0, t1, cat='launch',
                              args=dict(args, reason=reason))


class FetchFuture(object):
    """One not-yet-synced fetch from a non-blocking run (``as_futures``).

    Wraps the device array; nothing blocks until the caller asks for host
    data.  ``numpy()`` (and the ``np.asarray(fut)`` protocol) forces the
    sync ONCE, meters it via ``host_block``, and caches the host copy.
    ``__getitem__`` returns a still-lazy future over a device-side slice,
    so a stacked ``[K, ...]`` fetch hands out per-step views for free."""
    __slots__ = ('_device', '_host', '_reason')

    def __init__(self, device_value, reason='fetch_future'):
        self._device = device_value
        self._host = None
        self._reason = reason

    def device(self):
        """The underlying device array — never blocks."""
        return self._device

    @property
    def shape(self):
        return tuple(self._device.shape)

    @property
    def dtype(self):
        return self._device.dtype

    def ready(self):
        """True once the producing computation finished (non-blocking)."""
        if self._host is not None:
            return True
        is_ready = getattr(self._device, 'is_ready', None)
        return bool(is_ready()) if callable(is_ready) else True

    def block(self):
        """Wait for the device value WITHOUT copying it to host."""
        if self._host is None:
            bur = getattr(self._device, 'block_until_ready', None)
            if callable(bur):
                with host_block(self._reason):
                    bur()
        return self

    def numpy(self):
        if self._host is None:
            with host_block(self._reason):
                self._host = np.asarray(self._device)
        return self._host

    def __array__(self, dtype=None):
        a = self.numpy()
        return a if dtype is None else a.astype(dtype, copy=False)

    def __float__(self):
        return float(self.numpy())

    def __getitem__(self, idx):
        return FetchFuture(self._device[idx], reason=self._reason)

    def __len__(self):
        return int(self._device.shape[0])

    def __repr__(self):
        return '<FetchFuture %s %s %s>' % (
            self.shape, self.dtype,
            'synced' if self._host is not None else 'pending')


class DeferredNanVerdict(object):
    """Device-resident running AND of per-launch all-finite verdicts.

    ``push`` accumulates each launch's fused ``ok`` scalar with a device
    ``logical_and`` (async, never blocks); ``poll`` performs the ONE host
    sync per window and resets it.  With ``poll_every=1`` every push is
    immediately due, reproducing the synchronous per-launch read."""
    __slots__ = ('poll_every', '_ok', '_pending', '_start',
                 'last_window_start')

    def __init__(self, poll_every=1):
        self.poll_every = max(1, int(poll_every))
        self._ok = None
        self._pending = 0
        self._start = None           # run counter of the window's first step
        self.last_window_start = None  # ... of the last polled window

    @property
    def pending_steps(self):
        """Steps since the last poll — the rollback window a trip at the
        next poll would condemn (exported as the ``nan_poll.lag_steps``
        gauge)."""
        return self._pending

    def push(self, ok, steps=1, start=None):
        """``start`` is the run counter of the launch's first step — kept
        so a trip can tell forensics exactly which window to replay."""
        if self._ok is None:
            self._ok = ok
            if start is not None:
                self._start = int(start)
        else:
            import jax.numpy as jnp
            self._ok = jnp.logical_and(self._ok, ok)
        self._pending += int(steps)
        if _obs.enabled():
            _obs.metrics.gauge('nan_poll.lag_steps').set(self._pending)

    def due(self):
        return self._pending >= self.poll_every

    def poll(self):
        """Force the host sync on the accumulated verdict.  Returns 0
        when clean (or nothing pending), else the number of steps the
        tripped window covers.  The window resets either way — after a
        rollback the next window starts clean."""
        if self._ok is None:
            return 0
        window = self._pending
        with host_block('nan_poll', steps=window):
            ok = bool(self._ok)
        self.last_window_start = self._start
        self._ok = None
        self._pending = 0
        self._start = None
        if _obs.enabled():
            _obs.metrics.counter('nan_poll.polls').inc()
            _obs.metrics.gauge('nan_poll.lag_steps').set(0)
            if not ok:
                _obs.metrics.counter('nan_poll.trips').inc()
        return 0 if ok else window

    def reset(self):
        """Drop pending verdicts without reading them — the rollback
        path: verdicts computed on the pre-restore stream say nothing
        about the restored state."""
        if self._pending and _obs.enabled():
            _obs.metrics.counter('nan_poll.window_resets').inc()
        self._ok = None
        self._pending = 0
        self._start = None
        if _obs.enabled():
            _obs.metrics.gauge('nan_poll.lag_steps').set(0)
