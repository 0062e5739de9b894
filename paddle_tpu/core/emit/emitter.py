"""EmitEngine: memoized per-signature op lowering (see package docstring).

The engine is built once per ``_resolve_entry`` miss, on the OPTIMIZED
program twin (post core/passes — emission must see the same
``fused_elementwise``/``rng_stream`` shape the tracer would).  Its three
jobs:

1. **Static coverage walk** at construction: every op in every block
   must be emit-capable or the whole program falls back to traced
   lowering (EmitFallback — per-program, loud, strict-gateable).
2. **Demanded-output analysis**: a per-op-instance mask of which output
   slots anything downstream can observe (readers anywhere, writeback,
   fetches, the loss, the slim vjp keep-set).  Undemanded outputs are
   pruned from the memoized function's return — this is what restores
   bitwise parity with the traced path, where jax's global DCE removes
   dead chains that a naively-memoized op boundary would pin alive
   (a dead ``log_softmax`` auxiliary output, left as a vjp primal,
   otherwise splits the jvp and changes float association).  Ops with
   NO demanded outputs are skipped entirely — except effectful ops
   ('print'), which always dispatch.
3. **Per-op dispatch** (``run_op``, called from the executor's
   ``_exec_ops_plain`` under the outer trace): canonicalize the op to a
   signature key, build-or-reuse the jitted pure function, apply it.
   RNG fold-in stream bases travel as traced arguments so ops differing
   only in ``rng_stream`` share one signature bitwise.

The memo is PROCESS-WIDE, not per-engine: the second lowering of the
same workload (run_steps after run, a ParallelExecutor twin) hits every
memoized function, and stable function identity keeps jax's own pjit
trace cache warm underneath.
"""
import time

import numpy as np

from . import EMITTER_VERSION, EmitError, EmitFallback
from .. import registry
from ..control_flow_exec import NATIVE_OPS as _CONTROL_FLOW
from ..passes.cse import RNG_OPS as _RNG_BASE

# hand raw-lax rules self-register against the op registry on import
from . import rules as _rules  # noqa: F401,E402

__all__ = ['EmitEngine', 'unsupported_ops', 'op_capability', 'clear_memo']

# ops whose kernels may draw from ctx.rng (core/passes/cse.py owns the
# base set — the CSE pass must refuse to merge these for the same
# reason the emitter must thread streams to them); sample_tokens is the
# serving-path addition that postdates that list
RNG_OPS = set(_RNG_BASE) | {'sample_tokens'}

# effectful kernels (host side effects under jax.debug.*): never skipped
# by dead-output pruning — the effect IS the point
EFFECTFUL_OPS = {'print'}

# static deny-list: op types the emitter must not attempt (empty today;
# tests monkeypatch it to exercise the fallback path, and a future op
# whose kernel resists memoized emission gets parked here loudly
# instead of producing wrong numbers)
DENY_OPS = set()

# executor-native op types handled outside the registry dispatch
_NATIVE = {'__backward__'} | set(_CONTROL_FLOW)


def op_capability(op_type):
    """(capable, why) — the single capability test shared by the engine's
    coverage walk and the pt_lint D015 pass."""
    if op_type in _NATIVE:
        return True, 'executor-native'
    if op_type in DENY_OPS:
        return False, 'deny-listed for direct emission'
    if not registry.has_op(op_type):
        return False, 'no registered kernel'
    return True, 'kernel' if registry.get_op(op_type).emit is None \
        else 'rule'


def unsupported_ops(program):
    """[(op_type, why)] across all blocks, deduped by type."""
    out, seen = [], set()
    for block in program.blocks:
        for op in block.ops:
            if op.type in seen:
                continue
            seen.add(op.type)
            ok, why = op_capability(op.type)
            if not ok:
                out.append((op.type, why))
    return out


# ------------------------------------------------------ canonical keys
_SKIP_ATTRS = {'op_role', 'rng_stream', 'recompute_id'}


def _canonv(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _canonv(x)) for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        return tuple(_canonv(x) for x in v)
    if isinstance(v, (str, int, float, bool, bytes, type(None))):
        return v
    return repr(v)


def _canon_attrs(op_type, attrs):
    """Attrs with identity-irrelevant keys dropped; fused sub-programs
    alpha-renamed (var names -> positional ids) so e.g. every layer's
    structurally-identical Adam group shares one signature."""
    if op_type == 'fused_elementwise':
        names = {}

        def nid(n):
            if n not in names:
                names[n] = 'v%d' % len(names)
            return names[n]

        for n in attrs['arg_names']:
            nid(n)
        sub = []
        for so in attrs['sub_ops']:
            sub.append((
                so['type'],
                tuple(sorted((s, tuple(nid(n) for n in ns))
                             for s, ns in so['inputs'].items())),
                tuple(sorted((s, tuple(nid(n) for n in ns))
                             for s, ns in so['outputs'].items())),
                tuple(sorted((k, repr(v))
                             for k, v in so.get('attrs', {}).items()
                             if k not in _SKIP_ATTRS)),
                tuple(sorted(so.get('stop_grad') or ())),
            ))
        return ('fused', tuple(sub), tuple(nid(n)
                                           for n in attrs['out_names']))
    return tuple(sorted((k, _canonv(v)) for k, v in attrs.items()
                        if k not in _SKIP_ATTRS))


def _mesh_key(mesh):
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(d.id for d in mesh.devices.flat))


# --------------------------------------------------------- emit context
class EmitCtx(object):
    """Kernel-facing ctx shim inside a memoized function.  Mirrors the
    OpCtx surface kernels actually use (rng / amp / mesh / is_infer /
    sub_ctx) but derives RNG keys from a TRACED (base_key, stream)
    pair: ``fold_in`` of equal uint32 values is bitwise equal whether
    the operand was a literal or an argument, so this matches OpCtx.rng
    exactly while keeping ``rng_stream`` out of the signature key."""

    is_infer = False
    __slots__ = ('_key', '_stream', '_op_type', 'amp', 'mesh')

    def __init__(self, key, stream, amp, mesh, op_type):
        self._key = key
        self._stream = stream
        self._op_type = op_type
        self.amp = amp
        self.mesh = mesh

    def rng(self, n=0):
        import jax
        if self._stream is None:
            raise EmitError(
                self._op_type,
                'kernel drew ctx.rng but the op type is not in the '
                'emitter RNG set (core/emit/emitter.RNG_OPS) — add it '
                'there so its stream base can be threaded')
        return jax.random.fold_in(self._key, self._stream + n)


def _op_streams(op, op_index):
    """Concrete uint32 fold-in bases for every RNG site of this op
    instance, in kernel draw order — (rng_stream attr, else the op's
    position), exactly OpCtx.rng's derivation.  Fused sub-ops inherit
    the FUSED op's op_index when unpinned, matching OpCtx.sub_ctx."""
    out = []
    if op.type in RNG_OPS:
        idx = op.attrs.get('rng_stream')
        if idx is None:
            idx = op_index
        out.append(np.uint32((idx + 1) * 1009))
    elif op.type == 'fused_elementwise':
        for sub in op.attrs['sub_ops']:
            if sub['type'] in RNG_OPS:
                idx = sub['attrs'].get('rng_stream')
                if idx is None:
                    idx = op_index
                out.append(np.uint32((idx + 1) * 1009))
    return tuple(out)


def _replay_fused(ins, attrs, amp, mesh, key, streams):
    """Inline replay of a fused_elementwise sub-program (ops/fused.py
    semantics), dispatching each sub-op to its emit rule when one
    exists, else its kernel — no nested jit: per-sub pjit call overhead
    was measured to cancel the savings at Adam-group size."""
    import jax.numpy as jnp
    import jax.lax as lax
    from .. import executor as _ex
    xs = ins.get('X', [])
    xs = xs if isinstance(xs, (list, tuple)) else [xs]
    env = dict(zip(attrs['arg_names'], xs))
    si = 0
    for sub in attrs['sub_ops']:
        od = registry.get_op(sub['type'])
        fn = od.emit or od.impl
        ins2 = {}
        for slot, names in sub['inputs'].items():
            vals = [env[n] for n in names]
            ins2[slot] = vals if sub['input_is_list'].get(slot) else vals[0]
        if amp:
            ins2 = _ex._amp_sub_ins(sub['type'], ins2, amp)
        if sub['type'] in RNG_OPS:
            sctx = EmitCtx(key, streams[si], amp, mesh, sub['type'])
            si += 1
        else:
            sctx = EmitCtx(key, None, amp, mesh, sub['type'])
        outs = fn(sctx, ins2, sub['attrs']) or {}
        if amp:
            outs = _ex._amp_sub_outs(sub['type'], sub['attrs'], outs,
                                     amp)
        stop = set(sub.get('stop_grad') or ())
        for slot, names in sub['outputs'].items():
            if slot not in outs:
                continue
            vals = outs[slot]
            vals = vals if isinstance(vals, (list, tuple)) else [vals]
            for name, val in zip(names, vals):
                if val is None:
                    continue
                if name in stop and hasattr(val, 'dtype') and \
                        jnp.issubdtype(val.dtype, jnp.floating):
                    val = lax.stop_gradient(val)
                env[name] = val
    return {'Out': [env[n] for n in attrs['out_names']]}


# ------------------------------------------------------- the fn memo
_MEMO = {}


def clear_memo():
    _MEMO.clear()


def _memo_fn(op, ins, amp, dmask, mesh):
    """Signature-keyed jitted pure function for one op shape.  The key
    deliberately EXCLUDES rng_stream (traced arg), stop-gradient var
    flags (applied outside, at the env write, like the traced path) and
    op position — the bench transformer's 232 ops land on ~30 keys."""
    import jax
    import jax.numpy as jnp
    from jax import tree_util as jtu
    from .. import executor as _ex
    use_amp = amp and op.type in _ex._AMP_OPS
    avals = jtu.tree_map(
        lambda x: (np.shape(x), str(jnp.result_type(x))), ins)
    dkey = tuple(sorted(dmask.items()))
    key = (op.type, _canon_attrs(op.type, op.attrs), _canonv(avals),
           use_amp, amp, dkey, _mesh_key(mesh))
    fn = _MEMO.get(key)
    if fn is None:
        attrs = op.attrs
        otype = op.type
        fused = otype == 'fused_elementwise'
        od = registry.get_op(otype)
        rule = None if fused else (od.emit or od.impl)

        def pure_op(kw, bkey, streams):
            kw2 = {}
            for slot, vals in kw.items():
                if isinstance(vals, (list, tuple)):
                    kw2[slot] = [(_ex._amp_cast(v, jnp.bfloat16)
                                  if use_amp else v) for v in vals]
                else:
                    kw2[slot] = _ex._amp_cast(vals, jnp.bfloat16) \
                        if use_amp else vals
            if amp:
                kw2 = _ex._amp_match_ins(otype, kw2)
            if fused:
                outs = _replay_fused(kw2, attrs, amp, mesh, bkey, streams)
            else:
                ctx = EmitCtx(bkey, streams[0] if streams else None,
                              amp, mesh, otype)
                outs = rule(ctx, kw2, attrs) or {}
            if use_amp and otype in _ex._AMP_CAST_OPS and outs and \
                    not attrs.get('amp_keep_bf16'):
                outs = {s: ([_ex._amp_cast(v, jnp.float32) for v in vs]
                            if isinstance(vs, (list, tuple))
                            else _ex._amp_cast(vs, jnp.float32))
                        for s, vs in outs.items()}
            pruned = {}
            for s, vs in outs.items():
                mm = dmask.get(s)
                if mm is None or not any(mm):
                    continue
                if isinstance(vs, (list, tuple)):
                    pruned[s] = [v if (i < len(mm) and mm[i]) else None
                                 for i, v in enumerate(vs)]
                else:
                    pruned[s] = vs if mm[0] else None
            return pruned

        fn = jax.jit(pure_op)
        _MEMO[key] = fn
    return fn


# ------------------------------------------------------------- engine
class EmitEngine(object):
    """Per-(program, feeds, fetches) emission state; see module doc."""

    def __init__(self, program, feed_names, fetch_names):
        from .. import executor as _ex
        self.program = program
        self.version = EMITTER_VERSION
        self._build_s = 0.0

        # 1. static coverage walk (all blocks) — first gap aborts
        coverage = {}
        for block in program.blocks:
            for op in block.ops:
                if op.type in coverage or op.type in _NATIVE:
                    continue
                ok, why = op_capability(op.type)
                if not ok:
                    raise EmitFallback(op.type, why)
                coverage[op.type] = why
                if op.type == 'fused_elementwise':
                    for sub in op.attrs['sub_ops']:
                        sok, swhy = op_capability(sub['type'])
                        if not sok:
                            raise EmitFallback(sub['type'],
                                               swhy + ' (fused sub-op)')
        self.coverage = tuple(sorted(coverage.items()))

        # 2. demanded-output analysis
        block = program.global_block()
        ops = block.ops
        required, written = _ex._analyze(block, feed_names, fetch_names)
        writeback = set(required | written)
        bw_idx = next((i for i, op in enumerate(ops)
                       if op.type == _ex._BACKWARD_OP), None)
        self.slim_fw_keep = None
        loss_name = None
        if bw_idx is not None:
            loss_name = ops[bw_idx].inputs['Loss'][0]
            fw_computed = set()
            for op in ops[:bw_idx]:
                fw_computed.update(op.output_names())
            post_needs, seen_w = set(), set()

            def _scan_reads(op_list):
                for op in op_list:
                    for n in op.input_names():
                        if n not in seen_w:
                            post_needs.add(n)
                    sb = op.attrs.get('sub_block')
                    if sb is not None:
                        _scan_reads(program.block(sb).ops)
                    for n in op.output_names():
                        seen_w.add(n)

            _scan_reads(ops[bw_idx + 1:])
            # writeback ∩ fw_computed matters: a persistable BOTH updated
            # pre-backward and written back (the LR decay counter) must
            # surface from the vjp'd forward or the step returns a stale
            # value (observed as an off-by-one in the decay schedule)
            self.slim_fw_keep = frozenset(
                ((post_needs | set(fetch_names) | writeback)
                 & fw_computed) | {loss_name})

        demanded = set(writeback) | set(fetch_names)
        demanded.update(n for n in (loss_name,) if n)
        if self.slim_fw_keep:
            demanded |= self.slim_fw_keep
        for b in program.blocks:
            for op in b.ops:
                demanded.update(op.input_names())
                if op.type not in _CONTROL_FLOW:
                    continue
                # native control-flow executors read env entries by
                # names carried in ATTRS (recurrent seq/init/update/out
                # vars, length_var, ...) and read back EVERY var their
                # sub-block writes (the while/cond carry machinery) —
                # none of which surfaces through input_names()
                for v in op.attrs.values():
                    if isinstance(v, str):
                        demanded.add(v)
                    elif isinstance(v, (list, tuple)):
                        demanded.update(
                            x for x in v if isinstance(x, str))
                stack = [op.attrs.get('sub_block')]
                seen_sb = set()
                while stack:
                    sb = stack.pop()
                    if sb is None or sb in seen_sb:
                        continue
                    seen_sb.add(sb)
                    for sop in program.block(sb).ops:
                        demanded.update(sop.output_names())
                        stack.append(sop.attrs.get('sub_block'))
        self._dmasks = {}
        for b in program.blocks:
            for op in b.ops:
                self._dmasks[id(op)] = {
                    s: tuple(n in demanded for n in names)
                    for s, names in op.outputs.items()}

    def fingerprint_extra(self):
        """Joins the AOT disk fingerprint: emitter version + the
        program's coverage set with each op's emission mode."""
        return ('emitter', self.version, self.coverage)

    def take_build_seconds(self):
        """Accumulated memo-build + dispatch wall time (the `emit_s`
        half of the old trace_s) since construction/last take."""
        s, self._build_s = self._build_s, 0.0
        return s

    def run_op(self, op, op_index, env, ectx):
        """Emit one op into `env` under the outer trace (called from
        executor._exec_ops_plain in place of kernel tracing)."""
        import jax
        import jax.numpy as jnp
        import jax.lax as lax
        dmask = self._dmasks.get(id(op))
        if dmask is None or getattr(ectx, 'forensic', None) is not None:
            # op object outside the analyzed program — or a forensic
            # probe lowering, where every output must materialize so the
            # per-op finite probes have something to look at (dead-op
            # elision would hide exactly the op being hunted)
            dmask = {s: tuple(True for _ in names)
                     for s, names in op.outputs.items()}
        if op.type not in EFFECTFUL_OPS and \
                not any(any(mm) for mm in dmask.values()):
            return   # dead op instance: nothing downstream can see it
        ins = {}
        for slot, names in op.inputs.items():
            vals = [env[n] for n in names]
            ins[slot] = vals if op.input_is_list[slot] else vals[0]
        streams = _op_streams(op, op_index)
        t0 = time.perf_counter()
        fn = _memo_fn(op, ins, getattr(ectx, 'amp', False), dmask,
                      ectx.mesh)
        # one scope per Fluid op type: an operation's op_name in a
        # device trace says which op of the Program it came from
        with jax.named_scope(op.type):
            outs = fn(ins, ectx.base_key, streams)
        self._build_s += time.perf_counter() - t0
        for slot, names in op.outputs.items():
            if slot not in outs:
                continue
            vals = outs[slot]
            vals = vals if isinstance(vals, (list, tuple)) else [vals]
            for name, val in zip(names, vals):
                if val is None:
                    continue
                var = op.block._find_var_recursive(name)
                if var is not None and var.stop_gradient and \
                        hasattr(val, 'dtype') and \
                        jnp.issubdtype(val.dtype, jnp.floating):
                    val = lax.stop_gradient(val)
                env[name] = val
