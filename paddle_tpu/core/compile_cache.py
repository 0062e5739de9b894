"""Compilation persistence: fingerprints, the bounded executable LRU, and
the on-disk AOT cache that warm-starts fresh processes.

The Julia->TPU compile-the-loop model (arxiv 1810.09868) treats the whole
program as one ahead-of-time compilation artifact.  This module gives
paddle_tpu the same property: every lowered executable is addressed by a
**canonical fingerprint** — a stable hash over the serialized ProgramDesc,
the launch signature (feed shapes/dtypes, fetch set, steps=K, mesh layout,
param specs, AMP policy, check_nan) and the environment (jax/jaxlib
version, backend platform + chip kind) — and stored in two tiers:

  L1  in-process map, LRU-bounded by ``PT_EXEC_CACHE_MAX`` (default 64).
      Evictions count into the ``pt_exec_cache_evictions`` metric; the
      seed executor grew this map without limit across programs.
  L2  on-disk store under ``cache_dir()`` holding executables serialized
      through JAX's AOT path (``jit(fn).lower(...).compile()`` +
      ``serialize_executable``).  A backend that cannot serialize
      executables falls back to caching the lowered StableHLO text —
      inspectable, and JAX's own persistent cache (same directory) still
      shortcuts the backend compile on the retrace.

``cache_dir()`` is the ONE place the directory is decided:
``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads that variable itself,
so no code here touches ``jax_compilation_cache_dir`` then), else
``<checkout>/.jax_cache``.  JAX's persistent cache and the L2 store
(``v<FMT>/``) both live under it, so whoever runs the program can place
— and keep — every compile artifact by setting one variable.

Corrupt, truncated, or version-mismatched disk entries are MISSES, never
errors: the entry is deleted and the caller recompiles.  Disable the disk
tier with ``PT_CACHE=0`` (the test suite does — cache-hit timing would
make retrace-count assertions order-dependent).
"""
import hashlib
import json
import os
import pickle
import threading
from collections import OrderedDict

from .. import observability as _obs
from ..testing import faults as _faults
from .retry import retry_with_backoff

__all__ = ['launch_fingerprint', 'callable_fingerprint',
           'program_fingerprint', 'ExecutableLRU', 'DiskCache', 'disk_cache',
           'cache_dir', 'disk_enabled', 'ensure_xla_cache_backstop']

# bump when the on-disk payload layout changes: old entries become misses
CACHE_FORMAT = 2

_PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DEFAULT_DIR = os.path.join(os.path.dirname(_PACKAGE_DIR), '.jax_cache')


def disk_enabled():
    return os.environ.get('PT_CACHE', '1') not in ('0', 'false', 'False')


def cache_dir():
    return os.environ.get('JAX_COMPILATION_CACHE_DIR') or _DEFAULT_DIR


# ------------------------------------------------------------ fingerprints

def program_fingerprint(program):
    """Stable hash of the serialized ProgramDesc (+ AMP flag and sharding
    annotations, which change the lowering without touching the desc).
    Cached on the program keyed by its mutation counter, so the desc walk
    runs once per edit, not once per launch."""
    cached = getattr(program, '_pt_fingerprint', None)
    if cached is not None and cached[0] == program._version:
        return cached[1]
    from .. import io as fluid_io
    desc = fluid_io.program_to_desc(program)
    desc['_amp'] = bool(getattr(program, '_amp', False))
    desc['_sharding'] = {n: str(s) for n, s in
                        sorted(getattr(program, '_sharding', {}).items())}
    blob = json.dumps(desc, sort_keys=True, default=str)
    fp = hashlib.sha256(blob.encode()).hexdigest()
    program._pt_fingerprint = (program._version, fp)
    return fp


_SOURCE_DIGEST = []


def source_digest():
    """sha256 over every ``paddle_tpu/**/*.py`` (relative path + bytes).
    The ProgramDesc names ops, not their implementations: without this an
    edited op impl would be served the previous build's executable from a
    cache that outlives the edit, and measure as "unchanged"."""
    if not _SOURCE_DIGEST:
        h = hashlib.sha256()
        for root, dirs, files in os.walk(_PACKAGE_DIR):
            dirs.sort()
            for name in sorted(files):
                if name.endswith('.py'):
                    path = os.path.join(root, name)
                    h.update(os.path.relpath(path, _PACKAGE_DIR).encode())
                    with open(path, 'rb') as f:
                        h.update(f.read())
        _SOURCE_DIGEST.append(h.hexdigest())
    return _SOURCE_DIGEST[0]


def _environment_blob():
    """Everything outside the program that decides executable validity."""
    import jax
    import jaxlib
    try:
        dev0 = jax.devices()[0]
        backend = (dev0.platform, str(dev0.device_kind), jax.device_count())
    except Exception:  # noqa: BLE001 - no backend yet: still fingerprintable
        backend = ('none', 'none', 0)
    return {
        'format': CACHE_FORMAT,
        'jax': jax.__version__,
        'jaxlib': jaxlib.__version__,
        'backend': backend,
        'x64': bool(jax.config.jax_enable_x64),
        'source': source_digest(),
    }


def _mesh_blob(mesh):
    if mesh is None:
        return None
    return {'axes': [str(a) for a in mesh.axis_names],
            'shape': list(mesh.devices.shape)}


def launch_fingerprint(program, feed_specs, fetch_names, steps, check_nan,
                       mesh=None, param_specs=None, extra=None):
    """The canonical cache key: program + launch signature + environment.

    feed_specs / param_specs: {name: (shape_tuple, dtype_str)}.  Param
    specs come from the scope at lowering time — an executable compiled
    for f32 params can never be handed bf16 ones (the AOT artifact has no
    re-specialization path, unlike jit)."""
    blob = {
        'program': program_fingerprint(program),
        'feeds': {n: [list(s), d] for n, (s, d) in sorted(feed_specs.items())},
        'params': {n: [list(s), d] for n, (s, d) in
                   sorted((param_specs or {}).items())},
        'fetch': list(fetch_names),
        'steps': steps,
        'check_nan': bool(check_nan),
        'mesh': _mesh_blob(mesh),
        'env': _environment_blob(),
        'extra': extra,
    }
    canon = json.dumps(blob, sort_keys=True, default=str)
    return hashlib.sha256(canon.encode()).hexdigest()


def callable_fingerprint(kind, spec, param_specs=None):
    """Cache key for AOT executables that are NOT program launches — the
    streaming decode loop, prefill chunks, and similar hand-built jitted
    callables.  ``kind`` namespaces the producer; ``spec`` is any
    JSON-able blob that pins the callable's structure (model config,
    cache geometry, window size, mesh layout); ``param_specs`` follows
    the launch_fingerprint convention {name: (shape_tuple, dtype_str)}."""
    blob = {
        'kind': str(kind),
        'spec': spec,
        'params': {n: [list(s), d] for n, (s, d) in
                   sorted((param_specs or {}).items())},
        'env': _environment_blob(),
    }
    canon = json.dumps(blob, sort_keys=True, default=str)
    return hashlib.sha256(canon.encode()).hexdigest()


# ------------------------------------------------------------ in-process L1

class ExecutableLRU(object):
    """Bounded insertion/access-ordered map for compiled-executable entries.

    The seed executor's ``self._cache`` dict grew one entry per
    (program, feeds, fetches, K, scope) forever; long-running services
    compiling many programs leaked every executable they ever built.
    Capacity comes from ``PT_EXEC_CACHE_MAX`` (default 64); each eviction
    increments ``pt_exec_cache_evictions``."""

    def __init__(self, capacity=None):
        if capacity is None:
            capacity = int(os.environ.get('PT_EXEC_CACHE_MAX', '64'))
        self.capacity = max(1, int(capacity))
        self._map = OrderedDict()
        self._lock = threading.Lock()

    def get(self, key):
        with self._lock:
            entry = self._map.get(key)
            if entry is not None:
                self._map.move_to_end(key)
            return entry

    def put(self, key, entry):
        with self._lock:
            self._map[key] = entry
            self._map.move_to_end(key)
            while len(self._map) > self.capacity:
                self._map.popitem(last=False)
                _obs.metrics.counter('pt_exec_cache_evictions').inc()

    def __len__(self):
        return len(self._map)

    def __contains__(self, key):
        return key in self._map

    def clear(self):
        with self._lock:
            self._map.clear()


# ------------------------------------------------------------ on-disk L2

class DiskCache(object):
    """Content-addressed executable store: ``<dir>/v<FMT>/<fp[:2]>/<fp>.pkl``.

    Payloads are pickled dicts carrying either a serialized executable
    (``tier='exec'``: the (bytes, in_tree, out_tree) triple from
    ``serialize_executable.serialize`` plus the ids of the devices it was
    compiled for, in assignment order) or the lowered StableHLO text
    (``tier='stablehlo'``).  Every load failure — unpickleable, truncated,
    foreign format, deserialize error — deletes the entry and reports a
    miss."""

    def __init__(self, root=None):
        self._root = root

    @property
    def root(self):
        return self._root if self._root is not None else cache_dir()

    def _path(self, fingerprint):
        return os.path.join(self.root, 'v%d' % CACHE_FORMAT,
                            fingerprint[:2], fingerprint + '.pkl')

    def load(self, fingerprint):
        """Returns (compiled_or_None, tier_or_None).  ``('…', 'exec')`` is
        a full hit (trace AND compile skipped); ``(None, 'stablehlo')``
        means only the HLO was cached — the caller retraces, with the XLA
        backstop shortcutting the backend compile; ``(None, None)`` is a
        miss."""
        path = self._path(fingerprint)

        def _read():
            _faults.maybe_fail('cache_read')
            with open(path, 'rb') as f:
                return pickle.load(f)

        try:
            # transient OSErrors (a racing writer's os.replace mid-flight
            # on a shared cache directory, NFS hiccups, injected
            # cache_read faults) retry with backoff; a missing entry is an
            # ordinary miss and never retries
            payload = retry_with_backoff(_read, retry_on=(OSError,),
                                         give_up_on=(FileNotFoundError,),
                                         name='cache_read')
        except FileNotFoundError:
            return None, None
        except Exception:  # noqa: BLE001 - corruption is a miss
            self._drop(path, 'unreadable')
            return None, None
        try:
            if (payload.get('format') != CACHE_FORMAT or
                    payload.get('fingerprint') != fingerprint):
                raise ValueError('format/fingerprint mismatch')
            if payload['tier'] == 'exec':
                import jax
                from jax.experimental import serialize_executable as se
                serialized, in_tree, out_tree = payload['payload']
                # load onto the devices it was compiled for: left to
                # itself deserialize_and_load takes EVERY device of the
                # backend, and a one-device executable then dies at its
                # first call on any host with more than one
                by_id = {d.id: d for d in jax.devices()}
                compiled = se.deserialize_and_load(
                    serialized, in_tree, out_tree,
                    execution_devices=[by_id[i] for i in payload['devices']])
                _obs.metrics.counter('compile_cache.bytes_read').inc(
                    os.path.getsize(path))
                return compiled, 'exec'
            if payload['tier'] == 'stablehlo':
                return None, 'stablehlo'
            raise ValueError('unknown tier %r' % (payload.get('tier'),))
        except Exception:  # noqa: BLE001 - stale entries die quietly
            self._drop(path, 'undeserializable')
            return None, None

    def store(self, fingerprint, compiled=None, lowered=None, meta=None):
        """Serialize ``compiled`` (preferred) or fall back to the lowered
        StableHLO.  Returns the tier written, or None when nothing could
        be persisted.  Failures never propagate: persistence is an
        optimization, not a correctness dependency."""
        payload = None
        if compiled is not None:
            try:
                from jax.experimental import serialize_executable as se
                payload = {
                    'tier': 'exec', 'payload': se.serialize(compiled),
                    'devices': [d.id for d in compiled.runtime_executable()
                                .local_devices()]}
            except Exception:  # noqa: BLE001 - backend can't serialize
                payload = None
        if payload is None and lowered is not None:
            try:
                payload = {'tier': 'stablehlo', 'payload': lowered.as_text()}
            except Exception:  # noqa: BLE001
                return None
        if payload is None:
            return None
        payload['format'] = CACHE_FORMAT
        payload['fingerprint'] = fingerprint
        payload['meta'] = dict(meta or {}, env=_environment_blob())
        path = self._path(fingerprint)
        tmp = path + '.tmp.%d' % os.getpid()

        def _write():
            _faults.maybe_fail('cache_write')
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(tmp, 'wb') as f:
                pickle.dump(payload, f)
            os.replace(tmp, path)  # atomic: concurrent readers never see torn

        try:
            # transient write errors (injected cache_write faults, brief
            # volume pressure) retry with backoff before giving up
            retry_with_backoff(_write, retry_on=(OSError,),
                               name='cache_write')
            _obs.metrics.counter('compile_cache.disk_stores').inc()
            _obs.metrics.counter('compile_cache.bytes_written').inc(
                os.path.getsize(path))
        except Exception:  # noqa: BLE001 - read-only/full disk: skip caching
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
        return payload['tier']

    @staticmethod
    def _drop(path, reason):
        _obs.metrics.counter('compile_cache.corrupt_entries').inc()
        try:
            os.unlink(path)
        except OSError:
            pass


_DISK = DiskCache()


def disk_cache():
    return _DISK


# ------------------------------------------------------- XLA-level backstop

_XLA_WIRED = [False]


def ensure_xla_cache_backstop():
    """Make sure JAX's persistent compilation cache is on, in
    ``cache_dir()``.

    This is the third tier: when only StableHLO could be cached (or a jit
    fallback retraces), the retrace still happens in Python but XLA's
    backend compile — the dominant cost — is served from disk.  With
    ``$JAX_COMPILATION_CACHE_DIR`` set JAX has already configured itself
    from it and nothing is touched here."""
    if _XLA_WIRED[0] or not disk_enabled():
        return
    _XLA_WIRED[0] = True
    if os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        return
    import jax
    jax.config.update('jax_compilation_cache_dir', cache_dir())
