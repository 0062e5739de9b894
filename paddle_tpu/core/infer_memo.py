"""One memo of abstract evaluation, shared by `Block._infer_shapes`
(core/framework.py) and the lint gate's shape pass
(analysis/passes/shapes.py).

Both evaluate an op's impl under `jax.eval_shape` at the two probe
batches; a Program repeats a few dozen signatures hundreds of times, and
the gate repeats every one the build evaluated seconds before.  What an
impl returns under `InferCtx` is a function of what it can observe
there, and the key holds all of it: the impl object, the op type, the
attrs, each input slot with every input's shape, dtype and weak type at
that probe, and `jax_enable_x64`.  `abstract_eval` is the one entry
point; each caller keeps its own handling of what comes back, and of
what is raised: a failure is never stored.

`infer.memo_hits` / `infer.memo_misses` count the look-ups: a miss is
one `jax.eval_shape`, a hit one avoided.  An op that goes past the memo
moves neither.  docs/analysis.md, "The memo of abstract evaluation".
"""
import jax
import numpy as np

from . import registry
from .. import observability as _obs

__all__ = ['PROBE_BATCHES', 'DATA_DEPENDENT', 'abstract_eval', 'counts',
           'span_args']

# every -1 dim is probed with two trial sizes; output dims that differ
# between the probes are batch dims
PROBE_BATCHES = (7, 11)

# registered ops whose output extents are data-dependent (selected boxes,
# decoded paths, ...): build-time inference is skipped for them
# (infer_shape=False call sites), the linter does not re-derive their
# shapes, and one that is evaluated all the same goes past the memo
DATA_DEPENDENT = frozenset({
    'multiclass_nms', 'generate_proposals', 'generate_proposal_labels',
    'generate_mask_labels', 'rpn_target_assign', 'bipartite_match',
    'beam_search', 'beam_search_decode', 'ctc_align', 'edit_distance',
    'detection_map', 'py_func',
})

_MEMO = {}
# a process that builds programs without end starts over rather than grow
_MAX_ENTRIES = 1 << 16

_SCALARS = (bool, int, float, complex, str, bytes, type(None))


class _NoCanonicalForm(Exception):
    pass


def _canon(v):
    """Hashable canonical form of an attr value.  The type goes with the
    value: 1, 1.0 and True hash alike and an impl tells them apart."""
    t = type(v)
    if t in _SCALARS:
        return (t, v)
    if isinstance(v, (list, tuple)):
        return (t, tuple(_canon(x) for x in v))
    if isinstance(v, dict):
        return (t, tuple((_canon(k), _canon(x)) for k, x in v.items()))
    if isinstance(v, (np.ndarray, np.generic)) and not v.dtype.hasobject:
        return (t, v.dtype.str, v.shape, v.tobytes())
    if isinstance(v, np.dtype):
        return (t, v.str)
    raise _NoCanonicalForm(t.__name__)


def _signature(impl, op):
    """The part of the key both probes share, or None for an op that
    goes past the memo: a data-dependent type, a `sub_block`, an attr
    with no canonical form (a callable).  The executor-native types the
    gate also treats apart (control flow, `__backward__`) have no
    registered impl and never come here."""
    if op.type in DATA_DEPENDENT or 'sub_block' in op.attrs:
        return None
    try:
        attrs = _canon(op.attrs)
    except _NoCanonicalForm:
        return None
    return (impl, op.type, attrs, bool(jax.config.jax_enable_x64))


def _struct_key(s):
    # eval_shape hands the impl the canonical dtype (int64 reads int32
    # with x64 off), so the build's np_dtype and the gate's jax_dtype
    # make one entry
    return (s.shape, jax.dtypes.canonicalize_dtype(s.dtype), s.weak_type)


def _inputs_key(ins):
    return tuple(
        (slot, True, tuple(_struct_key(s) for s in v))
        if isinstance(v, (list, tuple)) else (slot, False, _struct_key(v))
        for slot, v in ins.items())


def _evaluate(impl, op, ins):
    ctx = registry.InferCtx(op)
    return jax.eval_shape(lambda kw: impl(ctx, kw, op.attrs), ins)


def abstract_eval(op, probes):
    """The op's impl evaluated abstractly on each of `probes`, its
    inputs `{slot: ShapeDtypeStruct | [ShapeDtypeStruct]}` at one probe
    batch each; returns the impl's outputs as `jax.ShapeDtypeStruct`s,
    one pytree a probe, shared between callers and not to be written.
    Raises what the impl raised, every time."""
    impl = registry.get_op(op.type).impl
    sig = _signature(impl, op)
    if sig is None:
        return [_evaluate(impl, op, ins) for ins in probes]
    results = []
    for ins in probes:
        key = sig + (_inputs_key(ins),)
        out = _MEMO.get(key)
        if out is None:
            _obs.counter('infer.memo_misses').inc()
            out = _evaluate(impl, op, ins)
            if len(_MEMO) >= _MAX_ENTRIES:
                _MEMO.clear()
            _MEMO[key] = out
        else:
            _obs.counter('infer.memo_hits').inc()
        results.append(out)
    return results


def counts():
    """(hits, misses) so far."""
    return (_obs.counter('infer.memo_hits').value,
            _obs.counter('infer.memo_misses').value)


def span_args(before):
    """What a phase that began at `before = counts()` reports of the
    memo: its own delta, as the args of its span."""
    hits, misses = counts()
    return {'infer_hits': int(hits - before[0]),
            'infer_misses': int(misses - before[1])}


def clear():
    _MEMO.clear()
