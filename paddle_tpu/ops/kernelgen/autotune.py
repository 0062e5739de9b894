"""Persistent tile/block autotuner for the kernelgen tier.

On first compile of a (kernel kind, signature) pair the builder asks
``choose()`` for a block config.  The search is bounded — each call site
hands in a pre-filtered candidate list (a handful of block bases or row
counts, deduped by *effective* config) — and runs under real timing:
one warmup + best-of-N wall-clock executions per candidate, with inputs
synthesized FRESH for every run so kernels that donate their buffers
(``input_output_aliases``) never time against an already-consumed arg.

The winner persists in the compile-cache directory
(``compile_cache.cache_dir()/autotune/<sha256>.json``) keyed by the
signature plus ``kernelgen.fingerprint_extra()``, so a fleet tunes once
and every later process starts warm.  Lookup order per signature:

  in-process memo  ->  disk (counts ``kernelgen.autotune_cache_hits``)
  ->  timed search (counts ``kernelgen.autotune_searches``)

Knobs (docs/kernels.md):

``PT_AUTOTUNE``
    ``1`` (default) search on miss; ``cached`` use memo/disk only and
    fall back to the static default on miss (never search — fleet
    followers); ``0`` tier runs entirely on the static
    ``PT_KERNELGEN_BLOCK`` default.
``PT_AUTOTUNE_SIZE_CAP``
    Max flat lane count a segment may have before the *interpret-mode*
    (CPU emulation) search is skipped — the interpreter pays per grid
    step, so timing (and even compiling) a megabyte-scale group costs
    minutes, far more than any block choice could save.  Default
    ``1 << 16``.  Real-TPU searches ignore the cap.

Failures are loud-but-soft: a candidate that raises is warned about and
dropped; if every candidate fails, ``choose()`` warns and returns the
static default (the tier keeps running untuned rather than falling back
to the replay path).
"""
import json
import os
import time

__all__ = ['mode', 'choose', 'clear_memory', 'interpret_size_cap',
           'synth_value', 'time_thunk']

_MEM = {}


def mode():
    v = os.environ.get('PT_AUTOTUNE', '1')
    return v if v in ('0', '1', 'cached') else '1'


def interpret_size_cap():
    return int(os.environ.get('PT_AUTOTUNE_SIZE_CAP', str(1 << 16)))


def clear_memory():
    """Drop the in-process memo (tests: force disk/search re-resolution)."""
    _MEM.clear()


def _warn(msg):
    import warnings
    warnings.warn('kernelgen autotune: %s' % msg, stacklevel=3)


def _counter(name):
    from ...observability import metrics
    return metrics.counter(name)


def _sig_key(kind, signature):
    """Stable digest: the signature plus the tier fingerprint, so a rule
    table / version change invalidates every persisted choice exactly
    like it invalidates the AOT executables."""
    import hashlib
    from . import fingerprint_extra
    blob = repr((kind, signature, fingerprint_extra()))
    return hashlib.sha256(blob.encode('utf-8')).hexdigest()[:32]


def _autotune_dir():
    from ...core import compile_cache
    return os.path.join(compile_cache.cache_dir(), 'autotune')


def _disk_load(path):
    from ...core import compile_cache
    if not compile_cache.disk_enabled():
        return None
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return None
    choice = rec.get('choice')
    return choice if isinstance(choice, dict) else None


def _disk_store(path, kind, signature, choice, timings):
    from ...core import compile_cache
    if not compile_cache.disk_enabled():
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = '%s.tmp.%d' % (path, os.getpid())
        with open(tmp, 'w') as f:
            json.dump({'kind': kind, 'signature': repr(signature),
                       'choice': choice, 'timings_ms': timings}, f,
                      sort_keys=True)
        os.replace(tmp, path)
    except OSError as e:
        _warn('could not persist %s choice (%s)' % (kind, e))


def time_thunk(thunk, warmup=1, runs=2):
    """Best-of-``runs`` wall seconds of ``thunk()`` (blocked to ready).
    The thunk must synthesize its own inputs per call — donated buffers
    are consumed by each execution.

    Searches start while the executor traces a step.  On that thread
    every jax call is staged into the step's trace, and the clock would
    time the staging of a kernel that never runs.  A trace belongs to its
    thread, so the candidate runs on a thread of its own, where calls
    execute."""
    import threading
    import jax
    box = {}

    def work():
        try:
            best = None
            for i in range(warmup + runs):
                t0 = time.perf_counter()
                out = thunk()
                jax.block_until_ready(out)
                dt = time.perf_counter() - t0
                if i >= warmup and (best is None or dt < best):
                    best = dt
            box['best'] = best
        except BaseException as e:  # noqa: BLE001 - re-raised below
            box['error'] = e

    worker = threading.Thread(target=work, name='pt-autotune')
    worker.start()
    worker.join()
    if 'error' in box:
        raise box['error']
    return box['best']


def synth_value(shape, dtype):
    """A benign concrete array for timing runs: mid-range floats (no
    overflow through exp/log chains), ones for int/bool (valid masks and
    lengths)."""
    import numpy as np
    import jax.numpy as jnp
    dt = np.dtype(dtype)
    if dt.kind in 'iub':
        return jnp.asarray(np.ones(shape, dt))
    return jnp.asarray(np.full(shape, 0.5, dt))


def choose(kind, signature, candidates, timer, default, allow_search):
    """Resolve the block config for one (kind, signature) pair.

    ``candidates`` is a non-empty list of JSON-plain dicts; ``timer`` is
    ``cand -> seconds`` (may raise — the candidate is dropped);
    ``default`` is returned whenever no search happens and nothing is
    cached.  ``allow_search=False`` callers (the lint abstract
    interpreter, which reaches plan building under ``eval_shape``) never
    time anything.
    """
    m = mode()
    if m == '0' or not candidates:
        return default
    key = _sig_key(kind, signature)
    hit = _MEM.get(key)
    if hit is not None:
        return hit
    path = os.path.join(_autotune_dir(), key + '.json')
    disk = _disk_load(path)
    if disk is not None:
        _MEM[key] = disk
        _counter('kernelgen.autotune_cache_hits').inc()
        return disk
    if len(candidates) == 1:
        # nothing to search; memoize (skip the disk stat next time) but
        # don't count a search that never ran, don't persist
        _MEM[key] = candidates[0]
        return candidates[0]
    if m == 'cached' or not allow_search:
        return default
    _counter('kernelgen.autotune_searches').inc()
    best, best_t, timings = None, None, {}
    for cand in candidates:
        try:
            t = timer(cand)
        except Exception as e:     # noqa: BLE001 — drop, loudly
            _warn('%s candidate %r failed (%s: %s)'
                  % (kind, cand, type(e).__name__, e))
            continue
        timings[repr(sorted(cand.items()))] = round(t * 1e3, 4)
        if best_t is None or t < best_t:
            best, best_t = cand, t
    if best is None:
        _warn('every %s candidate failed — using the static '
              'PT_KERNELGEN_BLOCK default' % kind)
        return default
    _MEM[key] = best
    _disk_store(path, kind, signature, best, timings)
    return best
