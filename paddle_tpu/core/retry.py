"""Shared transient-failure retry: bounded exponential backoff.

Disk I/O on shared infrastructure fails transiently — two processes
racing on one cache directory, NFS hiccups, a checkpoint volume briefly
remounting.  Treating every such error as fatal turned BENCH-grade soaks
into dead rounds; swallowing them silently hides real corruption.  This
module gives every disk-touching subsystem (core/compile_cache.py, io.py,
train/checkpoint.py) one policy: retry with deterministic exponential
backoff, count every attempt in observability, and re-raise the last
error once the budget is spent.
"""
import os
import random
import time
import zlib

from .. import observability as _obs

__all__ = ['retry_with_backoff']


def retry_with_backoff(fn, attempts=None, base_delay=0.02, max_delay=0.5,
                       retry_on=(OSError,), give_up_on=(), name=None,
                       sleep=time.sleep, jitter=None, seed=None):
    """Call ``fn()`` up to ``attempts`` times (default ``PT_RETRIES``+1,
    env default 2 retries).

    ``retry_on`` exceptions are retried after ``base_delay * 2**i``
    seconds (capped at ``max_delay``); ``give_up_on`` exceptions
    propagate immediately even when they subclass a retryable type
    (``FileNotFoundError`` under ``OSError`` is the canonical case: a
    missing cache entry is a miss, not a transient fault).  Each retry
    counts into ``retry.attempts`` (and ``retry.attempts.<name>``); an
    exhausted budget counts ``retry.giveups`` and re-raises.

    ``jitter`` (default ``PT_RETRY_JITTER``, env default 0) spreads each
    delay by up to ±``jitter`` fraction so N serving workers retrying a
    shared resource (one compile-cache entry, one checkpoint volume)
    don't retry in lockstep and re-collide forever.  The jitter is
    SEEDED, not wall-clock: ``seed`` (default: a crc32 of ``name`` mixed
    with the pid, so distinct workers de-sync while one process replays
    exactly) drives a private ``random.Random`` — the same seed yields
    the same backoff sequence every run, so failure-path tests stay as
    reproducible as the no-jitter default."""
    if attempts is None:
        attempts = 1 + max(0, int(os.environ.get('PT_RETRIES', '2')))
    attempts = max(1, int(attempts))
    if jitter is None:
        jitter = float(os.environ.get('PT_RETRY_JITTER', '0') or 0.0)
    rng = None
    if jitter:
        if seed is None:
            seed = zlib.crc32(
                ('%s:%d' % (name or '', os.getpid())).encode('utf-8'))
        rng = random.Random(seed)
    for i in range(attempts):
        try:
            return fn()
        except give_up_on:
            raise
        except retry_on as e:
            if i + 1 >= attempts:
                _obs.metrics.counter('retry.giveups').inc()
                if name:
                    _obs.metrics.counter('retry.giveups.%s' % name).inc()
                raise
            _obs.metrics.counter('retry.attempts').inc()
            if name:
                _obs.metrics.counter('retry.attempts.%s' % name).inc()
            _obs.tracing.instant('retry.backoff', cat='fault',
                                 args={'name': name or '?', 'attempt': i + 1,
                                       'error': repr(e)[:200]})
            delay = min(max_delay, base_delay * (2 ** i))
            if rng is not None:
                delay *= 1.0 + jitter * (2.0 * rng.random() - 1.0)
            sleep(delay)
