"""D002/D003/D004: shape/dtype abstract interpretation.

Propagates jax.ShapeDtypeStruct through every registered op with
`core.infer_memo.abstract_eval` (the entry point
framework.Block._infer_shapes uses at build time: a signature the build
evaluated is answered from the memo), but over the WHOLE program at
once — so it also covers ops appended with infer_shape=False (optimizer
updates, detection heads), programs loaded from disk via
io.desc_to_program (which never ran build-time inference), and
hand-edited descs.

Like build-time inference, the batch dim stays symbolic: every -1 dim is
probed with two trial sizes (7 and 11) and output dims that differ
between the probes are batch dims.  An op whose inputs aren't fully
known is skipped (its outputs become unknown) — the pass is conservative
by construction and can only flag ops it could genuinely evaluate, which
is exactly the set that would fail identically mid-trace.

  D002 warning  op type has no registered JAX impl (would fail to lower)
  D003 error    eval_shape raised, or inferred shape/dtype contradicts
                the declared output var
  D004 info     attrs request a 64-bit dtype that jax_dtype narrows to
                32-bit under x64-disabled (core/dtypes.py semantics)
"""
import numpy as np

from ...core import infer_memo, registry
from ...core.dtypes import convert_dtype, jax_dtype
from ..engine import register_pass

__all__ = ['run']

_PROBE_B1, _PROBE_B2 = infer_memo.PROBE_BATCHES

# executor-native op types: lowered by core/control_flow_exec.py /
# the __backward__ vjp path, not through the registry
_BACKWARD_OP = '__backward__'

_UNKNOWN = object()

_DTYPE_ATTRS = ('dtype', 'out_dtype')
_64BIT = {'int64', 'uint64', 'float64', 'complex128'}


def _native_ops():
    from ...core.control_flow_exec import NATIVE_OPS
    return NATIVE_OPS


def _struct_from_var(v, B):
    """Declared var -> probe ShapeDtypeStruct, or _UNKNOWN."""
    import jax
    if v is None or v.shape is None or v.dtype is None:
        return _UNKNOWN
    try:
        shape = tuple(B if d in (-1, None) else int(d) for d in v.shape)
        return jax.ShapeDtypeStruct(shape, jax_dtype(v.dtype))
    except Exception:
        return _UNKNOWN


def _merge_probe_shapes(s1, s2):
    """Two probe results -> declared-style shape (-1 where they differ)."""
    return tuple(int(a) if a == b else -1
                 for a, b in zip(s1.shape, s2.shape))


def _shapes_conflict(declared, inferred):
    """True when two declared-style shapes cannot describe one tensor:
    different rank, or a static dim disagreeing with a static dim."""
    if len(declared) != len(inferred):
        return True
    for d, i in zip(declared, inferred):
        if d in (-1, None) or i in (-1, None):
            continue
        if int(d) != int(i):
            return True
    return False


class _AbstractInterp(object):
    def __init__(self, ctx):
        self.ctx = ctx
        self.diags = []
        self.native = _native_ops()

    # -------------------------------------------------- per-op handlers
    def _inputs_for(self, op, env, B, block):
        idx = 0 if B == _PROBE_B1 else 1
        ins = {}
        for slot, names in op.inputs.items():
            structs = []
            for n in names:
                s = env.get(n, _UNKNOWN)
                if s is _UNKNOWN:
                    # not propagated (skipped producer / outer var):
                    # the declared shape from build-time inference is
                    # still the best — and a sound — estimate
                    s = _struct_from_var(block._find_var_recursive(n), B)
                else:
                    s = s[idx]
                if s is _UNKNOWN:
                    return None
                structs.append(s)
            ins[slot] = (structs if op.input_is_list.get(slot, False)
                         else structs[0])
        return ins

    def _mark_outputs_unknown(self, op, env):
        for n in op.output_names():
            env[n] = _UNKNOWN

    def _set_outputs_declared(self, op, env, block):
        """Seed outputs from declared shapes (native / skipped ops)."""
        for n in op.output_names():
            s1 = _struct_from_var(block._find_var_recursive(n), _PROBE_B1)
            s2 = _struct_from_var(block._find_var_recursive(n), _PROBE_B2)
            env[n] = (_UNKNOWN if s1 is _UNKNOWN or s2 is _UNKNOWN
                      else (s1, s2))

    def _backward_outputs(self, op, env, block):
        """jax.vjp semantics: each grad matches its parameter's
        shape/dtype AT THAT POINT (a later in-place clip may rebind the
        @GRAD var's declared dtype — the actual cotangent doesn't care);
        LossGrad matches the loss."""
        pnames = op.attrs.get('params', ())
        for slot, names in op.outputs.items():
            if slot == 'Grads':
                for p, gname in zip(pnames, names):
                    s = env.get(p, _UNKNOWN)
                    if s is _UNKNOWN:
                        s1 = _struct_from_var(
                            block._find_var_recursive(p), _PROBE_B1)
                        s2 = _struct_from_var(
                            block._find_var_recursive(p), _PROBE_B2)
                        s = (_UNKNOWN if s1 is _UNKNOWN or
                             s2 is _UNKNOWN else (s1, s2))
                    env[gname] = s
            elif slot == 'LossGrad' and names:
                loss = op.inputs.get('Loss', [None])[0]
                env[names[0]] = env.get(loss, _UNKNOWN) \
                    if loss is not None else _UNKNOWN
            else:
                for n in names:
                    env[n] = _UNKNOWN

    def _check_64bit_attrs(self, op, i, block):
        import jax
        if jax.config.jax_enable_x64:
            return
        for a in _DTYPE_ATTRS:
            val = op.attrs.get(a)
            if isinstance(val, str) and val in _64BIT:
                self.diags.append(self.ctx.diag(
                    'D004', 'info',
                    "attr %s='%s' narrows to %s inside the computation "
                    '(x64 is disabled; core/dtypes.jax_dtype semantics)'
                    % (a, val, jax_dtype(val).name),
                    block=block, op=op, op_index=i,
                    fixit="declare the 32-bit dtype explicitly",
                    pass_name='shape_dtype'))
                return

    # -------------------------------------------------- the block walk
    def walk_block(self, block, env):
        program = self.ctx.program
        for i, op in enumerate(block.ops):
            sub = op.attrs.get('sub_block')
            if sub is not None:
                inner = dict(env)
                self.walk_block(program.block(sub), inner)
                self._set_outputs_declared(op, env, block)
                continue
            if op.type == _BACKWARD_OP:
                self._backward_outputs(op, env, block)
                continue
            if op.type in self.native:
                # tensor-array / control-flow results: declared shapes
                # are the only ground truth available
                self._set_outputs_declared(op, env, block)
                continue
            if not registry.has_op(op.type):
                guess = self.ctx.suggest(op.type, registry.op_names())
                self.diags.append(self.ctx.diag(
                    'D002', 'warning',
                    'op "%s" has no registered JAX impl — the program '
                    'cannot lower' % op.type,
                    block=block, op=op, op_index=i,
                    fixit=('did you mean "%s"?' % guess) if guess else
                    'register an impl via core.registry.register',
                    pass_name='shape_dtype'))
                self._mark_outputs_unknown(op, env)
                continue
            self._check_64bit_attrs(op, i, block)
            if op.type in infer_memo.DATA_DEPENDENT:
                # output extents depend on the data: nothing to re-derive
                self._mark_outputs_unknown(op, env)
                continue
            probes = [self._inputs_for(op, env, B, block)
                      for B in (_PROBE_B1, _PROBE_B2)]
            results = err = None
            if None not in probes:
                try:
                    results = infer_memo.abstract_eval(op, probes)
                except Exception as e:  # noqa: BLE001 - reported as D003
                    err = e
            if err is not None:
                in_vars = ', '.join(op.input_names()) or '<none>'
                self.diags.append(self.ctx.diag(
                    'D003', 'error',
                    'op "%s" fails shape/dtype inference on inputs [%s]: '
                    '%s' % (op.type, in_vars, err),
                    block=block, op=op, op_index=i,
                    fixit='check the input shapes/dtypes feeding this op',
                    pass_name='shape_dtype'))
                self._mark_outputs_unknown(op, env)
                continue
            if results is None:
                # some input unknown: cannot evaluate — stay conservative
                self._mark_outputs_unknown(op, env)
                continue
            self._record_outputs(op, i, block, env, results)
        return env

    def _record_outputs(self, op, i, block, env, results):
        r1, r2 = results
        for slot, names in op.outputs.items():
            o1 = r1.get(slot) if isinstance(r1, dict) else None
            o2 = r2.get(slot) if isinstance(r2, dict) else None
            if o1 is None:
                for n in names:
                    env[n] = _UNKNOWN
                continue
            l1 = o1 if isinstance(o1, (list, tuple)) else [o1]
            l2 = o2 if isinstance(o2, (list, tuple)) else [o2]
            for n, s1, s2 in zip(names, l1, l2):
                env[n] = (s1, s2)
                v = block._find_var_recursive(n)
                if v is None or v.shape is None:
                    continue
                if self.ctx.write_counts.get(n, 0) > 1:
                    # rebound var (e.g. in-place grad clip): declared
                    # metadata reflects only the LAST write — comparing
                    # an earlier write against it is meaningless.  The
                    # propagated env struct stays point-in-time correct.
                    continue
                inferred = _merge_probe_shapes(s1, s2)
                if _shapes_conflict(tuple(v.shape), inferred):
                    self.diags.append(self.ctx.diag(
                        'D003', 'error',
                        'op "%s" produces var "%s" with shape %s but the '
                        'program declares %s'
                        % (op.type, n, list(inferred), list(v.shape)),
                        block=block, op=op, op_index=i, var=n,
                        fixit='fix the producing op or the declared shape',
                        pass_name='shape_dtype'))
                    continue
                try:
                    declared_dt = jax_dtype(v.dtype)
                except Exception:
                    continue
                inferred_dt = np.dtype(s1.dtype)
                if jax_dtype(inferred_dt) != declared_dt:
                    # warning, not error: impls lean on JAX promotion, so
                    # a drifted dtype usually still RUNS — it just runs
                    # at a different precision than declared (e.g. bf16
                    # params silently updating in f32 after an f32 clip
                    # scale).  That's worth surfacing, not blocking.
                    self.diags.append(self.ctx.diag(
                        'D003', 'warning',
                        'op "%s" produces var "%s" as %s but the program '
                        'declares %s — the computation silently runs at '
                        'the promoted dtype'
                        % (op.type, n, inferred_dt.name,
                           convert_dtype(v.dtype).name),
                        block=block, op=op, op_index=i, var=n,
                        fixit='insert a cast or fix the declared dtype',
                        pass_name='shape_dtype'))


@register_pass('shape_dtype')
def run(ctx):
    interp = _AbstractInterp(ctx)
    program = ctx.program
    root = program.global_block()
    env = {}
    # seed: feeds, data vars (+@LENGTH companions), params, persistables
    from ...core.framework import Parameter
    for name, v in root.vars.items():
        if isinstance(v, Parameter) or v.persistable or \
                getattr(v, 'is_data', False) or name in ctx.feed_names:
            s1 = _struct_from_var(v, _PROBE_B1)
            s2 = _struct_from_var(v, _PROBE_B2)
            env[name] = (_UNKNOWN if s1 is _UNKNOWN or s2 is _UNKNOWN
                         else (s1, s2))
    interp.walk_block(root, env)
    return interp.diags
