"""Pallas codegen tier: lower fused_elementwise sub-programs to
generated kernels (docs/kernels.md).

The tier is OFF unless ``PT_KERNELGEN=1`` asks for it, on every backend:
a fused group then takes the inline replay with plain AD, and XLA fuses
it with its neighbours.  On a TPU the one generated kernel Mosaic
compiled, the ``row`` LayerNorm, cost ``tbase.train_1chip`` 3 % of its
``train_rate`` (an opaque custom call between the residual add and the
next matmul; PERF.md §6, PR 41), and ``1`` compiles on no chip (Mosaic
refuses the ``ew`` kind).  What is left is a CPU test vehicle in
interpret mode, ~9x slower than XLA fusion (ops/_pallas.py), until
ROADMAP D4 takes it out.

Entry points:

* ``run_fused(ctx, ins, attrs)`` — kernel path (ops/fused.py tries this
  first when ``PT_KERNELGEN=1``), RNG keys from the executor OpCtx's
  ``sub_ctx`` fold-in.
* the ``register_emit('fused_elementwise')`` rule — emit path: the
  PR-12 memoized emitter dispatches fused groups here so generated
  kernels key into the same per-signature memo, RNG keys from the
  traced ``(base_key, stream)`` pair.

There is no reroute: a group the tier takes on lowers or the launch
raises (``KernelgenUnsupported`` names the sub-op; a Mosaic refusal
surfaces when the whole step compiles).  Which *kinds* of generated
kernel the tier takes on is ``pallas_kinds()``; sub-ops of the other
kinds run their registered impl as plain XLA steps inside the plan
(``builder._build_plan(..., kinds=...)``: chip_smoke.py builds the
``row`` plans that way to hold them to their replay through Mosaic).

Env vars (docs/kernels.md has the full table): ``PT_KERNELGEN`` (``1``:
every kind; unset or ``0``: off), ``PT_KERNELGEN_BLOCK`` (static base
block size, default 1024), ``PT_AUTOTUNE`` (0/1/cached —
kernelgen/autotune.py block-size search + persistence).
"""
import os

from .rules import ALL_KINDS, KERNEL_RULES, rule_names
from .builder import (KernelgenUnsupported, clear_plans, plan_for, plans,
                      rng_rule_types)
from ...core.registry import register_emit
from .._pallas import single_device

__all__ = ['KERNEL_RULES', 'KernelgenUnsupported', 'KERNELGEN_VERSION',
           'ALL_KINDS', 'pallas_kinds', 'enabled',
           'config_token', 'fingerprint_extra', 'rule_names',
           'run_fused', 'run_fused_emit', 'plan_for', 'plans',
           'clear_plan_cache', 'unsupported_sub_ops']

# bump on any change to plan building / kernel emission semantics: it
# feeds the compile-cache fingerprint and the emitter memo key
KERNELGEN_VERSION = 3


def pallas_kinds():
    """The kinds that lower to generated Pallas kernels right now
    (sorted tuple): all of them under ``PT_KERNELGEN=1``, none otherwise,
    whatever the backend."""
    v = os.environ.get('PT_KERNELGEN')
    return ALL_KINDS if v in ('1', 'true', 'True') else ()


def enabled():
    return bool(pallas_kinds())


def config_token():
    """Launch-signature / emitter-memo component: which kinds are on,
    which codegen generation, and the autotune mode (a mode flip can
    change every kernel's block shapes, so memoized traces must not
    survive it)."""
    from . import autotune
    return ('kernelgen', pallas_kinds(), KERNELGEN_VERSION,
            autotune.mode())


def fingerprint_extra():
    """AOT disk-cache fingerprint component: version + kinds on + rule
    coverage (a new rule changes which sub-programs lower, so cached
    executables from an older table must not be reused) + autotune mode
    (tuned and untuned builds compile different block shapes)."""
    return ('kernelgen', KERNELGEN_VERSION, pallas_kinds(), rule_names(),
            _autotune_mode())


def _autotune_mode():
    from . import autotune
    return autotune.mode()


def unsupported_sub_ops(attrs):
    """Sub-op types of one fused_elementwise op with no KERNEL_RULES
    entry (deduped, first-seen order) — the D016 lint surface."""
    out, seen = [], set()
    for sub in attrs.get('sub_ops') or ():
        t = sub['type']
        if t not in KERNEL_RULES and t not in seen:
            seen.add(t)
            out.append(t)
    return out


def clear_plan_cache():
    clear_plans()


def _in_avals(xs):
    import numpy as np
    import jax.numpy as jnp
    return tuple((tuple(np.shape(x)), str(jnp.result_type(x)))
                 for x in xs)


def _keys_for(attrs, keyfn):
    """One key per rng-kind sub-op, in sub-op order.  A pinned seed attr
    overrides the stream key exactly as the impls themselves do."""
    import jax
    keys, si = [], 0
    for sub in attrs['sub_ops']:
        if sub['type'] in rng_rule_types():
            seed = sub['attrs'].get('seed', 0)
            keys.append(jax.random.key(seed) if seed
                        else keyfn(si, sub))
            si += 1
    return tuple(keys)


def _note_ok(plan):
    from ...observability import metrics
    metrics.counter('kernelgen.ops').inc()
    metrics.counter('kernelgen.kernels').inc(
        plan.n_kernels + plan.n_dsteps)


def _xs_of(ins):
    xs = ins.get('X', [])
    return list(xs) if isinstance(xs, (list, tuple)) else [xs]


def run_fused(ctx, ins, attrs):
    """Kernel-path entry: executor OpCtx RNG discipline
    (ctx.sub_ctx(sub).rng() — the replay path's exact keys).  Ctxs
    without sub-op streams (the lint abstract interpreter's InferCtx)
    draw from ctx.rng() directly, exactly like the replay path's
    hasattr guard — shapes are all that survive eval_shape anyway, so
    they also must never trigger a timed autotune search."""
    xs = _xs_of(ins)
    amp = bool(getattr(ctx, 'amp', False))
    plan = plan_for(attrs, _in_avals(xs), amp,
                    allow_search=hasattr(ctx, 'sub_ctx'))
    keys = _keys_for(
        attrs,
        lambda si, sub: (ctx.sub_ctx(sub) if hasattr(ctx, 'sub_ctx')
                         else ctx).rng())
    outs = plan.fn(tuple(xs), keys)
    _note_ok(plan)
    return {'Out': list(outs)}


def run_fused_emit(key, streams, amp, ins, attrs):
    """Emit-path entry: EmitCtx RNG discipline (fold_in of the traced
    base key with each sub-op's pinned stream — core/emit/emitter's
    _op_streams order)."""
    import jax
    xs = _xs_of(ins)
    plan = plan_for(attrs, _in_avals(xs), bool(amp))
    streams = list(streams or ())
    keys = _keys_for(
        attrs, lambda si, sub: jax.random.fold_in(key, streams[si]))
    outs = plan.fn(tuple(xs), keys)
    _note_ok(plan)
    return {'Out': list(outs)}


def _fctx_parts(fctx):
    """(key, streams, amp, mesh) from either the emitter's _FusedEmitCtx
    (key/streams attrs) or a plain EmitCtx (_key/_stream slots)."""
    key = getattr(fctx, 'key', None)
    if key is None:
        key = getattr(fctx, '_key', None)
    streams = getattr(fctx, 'streams', None)
    if streams is None:
        st = getattr(fctx, '_stream', None)
        streams = () if st is None else (st,)
    return (key, tuple(streams), bool(getattr(fctx, 'amp', False)),
            getattr(fctx, 'mesh', None))


@register_emit('fused_elementwise')
def _emit_fused(fctx, ins, attrs):
    """Emitter dispatch: generated kernels when the tier is on, else the
    inline reference replay."""
    key, streams, amp, mesh = _fctx_parts(fctx)
    if enabled() and single_device(mesh):
        return run_fused_emit(key, streams, amp, ins, attrs)
    from ...core.emit.emitter import _replay_fused
    return _replay_fused(ins, attrs, amp, mesh, key, streams)
