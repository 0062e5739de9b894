"""Scope + Executor: lower a whole Block to ONE jitted XLA executable.

Capability parity with reference python/paddle/fluid/executor.py and the C++
paddle/fluid/framework/executor.cc — redesigned TPU-first.  The reference
interprets a ProgramDesc op-by-op, dispatching a CUDA kernel per OpDesc; here
the entire block (forward, vjp backward, optimizer updates) is traced into a
single jitted function, so one `exe.run()` is one device launch.  Parameters
live on device in a Scope and are donated to the executable, so updates are
in-place (input/output buffer aliasing) with zero copies.
"""
import os
import time

import numpy as np

from . import registry
from . import async_runtime as _async
from . import compile_cache as _cc
from . import emit as _emit
from . import infer_memo as _infer_memo
from . import passes as _passes
from .framework import Variable, default_main_program, TPUPlace
from .. import observability as _obs
from ..testing import faults as _faults

__all__ = ['Executor', 'Scope', 'scope_guard', 'global_scope']

# ops the executor handles natively (no registry impl)
_BACKWARD_OP = '__backward__'
from .control_flow_exec import NATIVE_OPS as _CONTROL_FLOW

import itertools

_scope_serial = itertools.count()


class Scope(object):
    """name -> on-device jax.Array holder for persistable variables.

    Parity: paddle/fluid/framework/scope.{h,cc}.  Flat (the reference's
    scope hierarchy existed for per-thread local scopes in the parallel
    executor; with a single XLA executable temporaries never materialize).
    `_serial` is a process-unique id used in the executor's lowering-cache
    key — unlike id(), it can never be recycled by a later Scope."""

    def __init__(self):
        self.vars = {}
        self._serial = next(_scope_serial)

    def var(self, name):
        return self

    def find_var(self, name):
        return _VarHandle(self, name) if name in self.vars else None

    def set(self, name, value):
        self.vars[name] = value

    def get(self, name):
        return self.vars[name]

    def keys(self):
        return self.vars.keys()

    def __contains__(self, name):
        return name in self.vars


class _VarHandle(object):
    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def get_tensor(self):
        return self._scope.vars[self._name]

    def set(self, value, place=None):
        self._scope.vars[self._name] = np.asarray(value)


_global_scope = Scope()


def global_scope():
    return _global_scope


import contextlib


@contextlib.contextmanager
def scope_guard(scope):
    global _global_scope
    old = _global_scope
    _global_scope = scope
    try:
        yield
    finally:
        _global_scope = old


def _as_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _stack_feeds(per_step):
    """Stack K per-step feed dicts on a new leading [K] axis.  Host arrays
    stack on host (one device_put per superbatch, not per step); if any
    step's value is already a device array the stack happens on device."""
    stacked = {}
    for k in per_step[0]:
        vals = [f[k] for f in per_step]
        if any(hasattr(v, 'devices') for v in vals):
            import jax.numpy as jnp
            stacked[k] = jnp.stack(vals)
        else:
            stacked[k] = np.stack(vals)
    return stacked


def _zero_cotangent(v):
    import jax
    import jax.numpy as jnp
    if jnp.issubdtype(v.dtype, jnp.floating) or jnp.issubdtype(
            v.dtype, jnp.complexfloating):
        return jnp.zeros_like(v)
    return np.zeros(v.shape, dtype=jax.dtypes.float0)


# MXU-bound ops worth running in bfloat16 under AMP (matmul/conv class):
# their f32 inputs cast down to bf16.  What happens to the OUTPUT is
# per-class:
#   - conv class: outputs STAY bf16 ("flow-through") — activations keep
#     half-width through the BN/relu/residual chains, halving HBM traffic.
#   - matmul/attention class: outputs cast back to f32 (the cast fuses
#     into the GEMM epilogue): its hot f32 consumers (layer_norm stats,
#     the CE logsumexp) upcast anyway, so bf16 outputs only add VPU cast
#     work.
# Numerics-sensitive ops (norm statistics, softmax, cross-entropy)
# upcast internally to f32 in their impls, so precision-critical
# reductions never run in bf16 either way.
_AMP_CAST_OPS = {'mul', 'matmul', 'flash_attention', 'ring_attention',
                 'attn_out_proj', 'bilinear_tensor_product'}
_AMP_FLOW_OPS = {'conv2d', 'conv3d', 'conv2d_transpose',
                 'conv3d_transpose', 'sequence_conv'}
_AMP_OPS = _AMP_CAST_OPS | _AMP_FLOW_OPS

# Elementwise glue: under AMP, if any float input is already bf16, cast
# the f32 ones down instead of letting numpy promotion drag the chain
# back to f32 (conv bias adds, CNN residual adds).  Scalar-only f32
# chains (LR schedules, loss reductions) have no bf16 input and are
# untouched.
_AMP_MATCH = {'elementwise_add', 'elementwise_sub', 'elementwise_mul',
              'elementwise_div', 'elementwise_max', 'elementwise_min'}

def _amp_cast(x, to):
    import jax.numpy as jnp
    if hasattr(x, 'dtype') and x.dtype == (
            jnp.float32 if to == jnp.bfloat16 else jnp.bfloat16):
        return x.astype(to)
    return x


def _amp_match_ins(op_type, ins):
    """The elementwise-glue half of the AMP policy (see _AMP_MATCH): if
    any float input is already bf16, cast the f32 ones down.  Shared by
    the trace loop below and the fused_elementwise replay (ops/fused.py),
    which must apply the identical policy per sub-op."""
    import jax.numpy as jnp
    if op_type not in _AMP_MATCH:
        return ins
    if not any(getattr(v, 'dtype', None) == jnp.bfloat16
               for v in ins.values() if not isinstance(v, (list, tuple))):
        return ins
    return {s: (v if isinstance(v, (list, tuple))
                else _amp_cast(v, jnp.bfloat16))
            for s, v in ins.items()}


def _amp_sub_ins(op_type, ins, amp):
    """The FULL per-op AMP input policy the trace loop below applies,
    for replayed sub-ops (ops/fused.py, the emitter's _replay_fused):
    _AMP_OPS get every input cast to bf16 before dispatch, then the
    elementwise-match glue runs.  A fused group containing e.g.
    flash_attention must see the same activations it would have
    unfused."""
    import jax.numpy as jnp
    if not amp:
        return ins
    if op_type in _AMP_OPS:
        ins = {s: ([_amp_cast(v, jnp.bfloat16) for v in vs]
                   if isinstance(vs, (list, tuple))
                   else _amp_cast(vs, jnp.bfloat16))
               for s, vs in ins.items()}
    return _amp_match_ins(op_type, ins)


def _amp_sub_outs(op_type, attrs, outs, amp):
    """The cast-back half: _AMP_CAST_OPS outputs return to f32 unless
    the op carries the amp_keep_bf16 opt-out — exactly the trace loop's
    policy, applied at the sub-op granularity of a fused replay."""
    import jax.numpy as jnp
    if not (amp and op_type in _AMP_CAST_OPS and outs) \
            or attrs.get('amp_keep_bf16'):
        return outs
    return {s: ([_amp_cast(v, jnp.float32) for v in vs]
                if isinstance(vs, (list, tuple))
                else _amp_cast(vs, jnp.float32))
            for s, vs in outs.items()}


class ForensicProbes(object):
    """Trace-time collector for the per-op finite-probe lowering
    (train/forensics.py, PT_FORENSIC).

    While a forensic lowering traces, every op's inexact outputs get a
    3-vector probe [all_finite, nonfinite_count, max_abs_finite] written
    into the active environment under a reserved ``__fprobe_K__`` name.
    Riding the environment is what lets forward-op probes cross the vjp
    boundary as ordinary primal outputs (stop_gradient'd, zero
    cotangent) instead of leaking tracers.  ``meta`` records, in
    allocation order, which (op position, op type, output var,
    source_loc) each probe slot describes — the python-side key that
    turns the fetched [N, 3] stack back into a named verdict."""

    PREFIX = '__fprobe_'

    def __init__(self):
        self.meta = []
        self.env = None    # the environment dict currently being traced

    def begin(self):
        self.meta = []
        self.env = None

    def names(self):
        return ['%s%d__' % (self.PREFIX, i) for i in range(len(self.meta))]

    def note(self, pos, op_type, var_name, source_loc, val):
        import jax
        import jax.numpy as jnp
        if self.env is None or not (
                hasattr(val, 'dtype') and
                jnp.issubdtype(val.dtype, jnp.inexact)):
            return
        name = '%s%d__' % (self.PREFIX, len(self.meta))
        try:
            loc = '%s:%s' % tuple(source_loc) if source_loc else ''
        except TypeError:
            loc = str(source_loc)
        self.meta.append({'pos': int(pos), 'op_type': op_type,
                          'var': var_name, 'source_loc': loc})
        fin = jnp.isfinite(val)
        mag = jnp.abs(val).astype(jnp.float32)
        probe = jnp.stack([
            jnp.all(fin).astype(jnp.float32),
            jnp.sum(jnp.logical_not(fin)).astype(jnp.float32),
            jnp.max(jnp.where(fin, mag, jnp.zeros_like(mag)), initial=0.0),
        ])
        self.env[name] = jax.lax.stop_gradient(probe)

    def note_op(self, env, pos, op):
        """Probe every inexact output `op` just wrote into `env`."""
        self.env = env
        loc = getattr(op, 'source_loc', None)
        for nm in op.output_names():
            v = env.get(nm)
            if v is not None:
                self.note(pos, op.type, nm, loc, v)


def _exec_ops(ops, op_offset, env, ectx, program):
    """Trace a run of registered ops into `env` (the heart of lowering).
    Contiguous runs of ops sharing a recompute_id execute under
    jax.checkpoint: their activations are rematerialized in the backward
    pass instead of saved (see framework.recompute_scope)."""
    import jax
    if getattr(ectx, 'forensic', None) is not None:
        # forensic probe mode: no jax.checkpoint recompute grouping —
        # probe values written inside a checkpointed group could never
        # escape it to the step function's outputs
        _exec_ops_plain(ops, op_offset, env, ectx, program)
        return
    i = 0
    n = len(ops)
    while i < n:
        rid = ops[i].attrs.get('recompute_id')
        if rid is None or ops[i].type in _CONTROL_FLOW:
            _exec_ops_plain(ops[i:i + 1], op_offset + i, env, ectx, program)
            i += 1
            continue
        j = i
        while j < n and ops[j].attrs.get('recompute_id') == rid and \
                ops[j].type not in _CONTROL_FLOW:
            j += 1
        group = ops[i:j]
        reads = set()
        writes = []
        produced = set()
        for op in group:
            for nm in op.input_names():
                if nm not in produced:
                    reads.add(nm)
            for nm in op.output_names():
                produced.add(nm)
                writes.append(nm)
        ext_in = {nm: env[nm] for nm in reads if nm in env}

        def grp_fn(ins, _group=group, _off=op_offset + i, _w=writes):
            env2 = dict(ins)
            _exec_ops_plain(_group, _off, env2, ectx, program)
            return {nm: env2[nm] for nm in _w if nm in env2}

        env.update(jax.checkpoint(grp_fn)(ext_in))
        i = j


def _exec_ops_plain(ops, op_offset, env, ectx, program):
    import jax
    import jax.lax as lax
    import jax.numpy as jnp
    amp = getattr(program, '_amp', False)
    # direct-emit mode (core/emit): _lower attached an EmitEngine to the
    # ExecCtx — ops lower through memoized per-signature functions
    # instead of per-op kernel tracing.  Control flow stays native (its
    # bodies re-enter here, engine in tow).
    engine = getattr(ectx, 'emit_engine', None)
    fx = getattr(ectx, 'forensic', None)
    for i, op in enumerate(ops):
        if fx is not None:
            # point the collector at the live env BEFORE dispatch so
            # impls that probe internally (fused_elementwise sub-ops)
            # write their probes where the step outputs can see them
            fx.env = env
        if op.type in _CONTROL_FLOW:
            from . import control_flow_exec
            control_flow_exec.exec_control_flow_op(
                op, env, ectx, op_offset + i, program)
            if fx is not None:
                fx.note_op(env, op_offset + i, op)
            continue
        if engine is not None:
            engine.run_op(op, op_offset + i, env, ectx)
            if fx is not None:
                # emit mode probes at op granularity (the memoized fns
                # never see the collector); sub-program granularity for
                # fused groups comes from the plain-trace forensic
                # runner, which is what train/forensics.py lowers
                fx.note_op(env, op_offset + i, op)
            continue
        impl = registry.get_op(op.type).impl
        use_amp = amp and op.type in _AMP_OPS
        ins = {}
        for slot, names in op.inputs.items():
            vals = [env[n] for n in names]
            if use_amp:
                vals = [_amp_cast(v, jnp.bfloat16) for v in vals]
            ins[slot] = vals if op.input_is_list[slot] else vals[0]
        if amp:
            ins = _amp_match_ins(op.type, ins)
        ctx = ectx.for_op(op_offset + i, op)
        outs = impl(ctx, ins, op.attrs)
        # amp_keep_bf16: per-op opt-out of the cast-back policy for a
        # GEMM whose consumers are bf16-tolerant (e.g. the logit
        # projection feeding softmax_with_cross_entropy, which upcasts
        # its reductions internally) — halves that [B, T, V] buffer
        if use_amp and op.type in _AMP_CAST_OPS and outs and \
                not op.attrs.get('amp_keep_bf16'):
            outs = {s: ([_amp_cast(v, jnp.float32) for v in vs]
                        if isinstance(vs, (list, tuple))
                        else _amp_cast(vs, jnp.float32))
                    for s, vs in outs.items()}
        if outs is None:
            outs = {}
        for slot, names in op.outputs.items():
            if slot not in outs:
                continue
            vals = outs[slot]
            vals = vals if isinstance(vals, (list, tuple)) else [vals]
            for name, val in zip(names, vals):
                if val is None:
                    continue
                var = op.block._find_var_recursive(name)
                if var is not None and var.stop_gradient and hasattr(
                        val, 'dtype') and jnp.issubdtype(
                            val.dtype, jnp.floating):
                    val = lax.stop_gradient(val)
                env[name] = val
        if fx is not None and op.type != 'fused_elementwise':
            # fused groups probe themselves at sub-program granularity
            # (ops/fused.py) — an outer probe would double-count
            fx.note_op(env, op_offset + i, op)


def _analyze(block, feed_names, fetch_names):
    """Static analysis: which persistables must come from scope, which get
    written back.  Recurses into control-flow sub-blocks: a persistable
    referenced anywhere inside a while/conditional body (even write-only —
    it's a loop carry needing an initial value) counts as required."""
    program = block.program
    persistable = set()
    for b in program.blocks:
        persistable |= {n for n, v in b.vars.items() if v.persistable}
    written = set()
    required = set()
    feed = set(feed_names)

    def visit_read(n):
        if n in persistable and n not in written and n not in feed:
            required.add(n)

    def visit_block(b, is_sub):
        for op in b.ops:
            for n in op.input_names():
                visit_read(n)
            if op.type == _BACKWARD_OP:
                for p in op.attrs['params']:
                    visit_read(p)
            sb = op.attrs.get('sub_block')
            if sb is not None:
                visit_block(program.block(sb), True)
            for n in op.output_names():
                if is_sub:
                    visit_read(n)
                if n in persistable:
                    written.add(n)

    visit_block(block, False)
    for n in fetch_names:
        visit_read(n)
    return required, written


# traces completed by _lower-built functions — a python-side effect that
# runs once per jit trace, so tests can assert "retraced exactly once per
# cache key" directly instead of inferring it from cache sizes
_TRACE_COUNT = [0]

_program_serial_counter = itertools.count()


def _program_serial(program):
    """Process-unique program id for telemetry: unlike id(), never recycled,
    and paired with _version so an in-place program edit reads as a change."""
    serial = getattr(program, '_obs_serial', None)
    if serial is None:
        serial = next(_program_serial_counter)
        program._obs_serial = serial
    return (serial, program._version)


def _launch_signature(program, feed_vals, feed_names, fetch_names, steps,
                      check_nan, scope):
    """Every component the lowering cache (and jax.jit under it) keys on,
    structured so the retrace explainer can name what changed."""
    return _obs.LaunchSignature(
        program=_program_serial(program),
        feed_shapes={n: tuple(np.shape(feed_vals[n])) for n in feed_names},
        feed_dtypes={n: str(getattr(feed_vals[n], 'dtype',
                                    type(feed_vals[n]).__name__))
                     for n in feed_names},
        fetch_set=fetch_names, steps=steps, check_nan=check_nan,
        scope=scope._serial, opt=_passes.config_token(),
        emit=_emit.config_token())


def _lower(program, feed_names, fetch_names, donate=True, mesh=None,
           out_shardings_for=None, check_nan=False, steps=None,
           emit_engine=None, forensic=None):
    """Build the jitted step function for (program, feeds, fetches).
    check_nan compiles a fused all-finite flag over fetches+updates INTO
    the executable (one host sync per launch instead of one per array);
    run_fn then returns a third output, one bool scalar.

    steps=None lowers the classic one-step executable.  steps=K lowers K
    training iterations into ONE executable: a lax.scan over feeds
    stacked on a leading [K] axis, parameter/optimizer state threaded as
    the (donated) carry, per-step RNG derived by folding `counter + i`
    into the program seed (bitwise-identical to K sequential runs, which
    consume counters counter..counter+K-1), fetches stacked per step,
    and the check_nan flag AND-reduced across the scan.

    forensic=ForensicProbes() builds the PT_FORENSIC probe variant: the
    step function additionally returns a stacked [N, 3] array of per-op
    finite probes (see ForensicProbes) whose rows line up with
    ``forensic.meta`` after the first trace.  One-step lowerings only —
    forensic replay walks the window a step at a time by design."""
    import jax
    import jax.numpy as jnp

    if forensic is not None and steps is not None:
        raise ValueError('forensic lowering is single-step only '
                         '(steps must be None)')

    # Static analysis at the lowering-cache miss (SSA-graph race
    # detection analog, SURVEY §2.8, grown into the full pt-lint pass
    # suite): def-use ordering bugs, shape/dtype mismatches, donation
    # conflicts etc. fail at build with the op+var named, not mid-trace.
    # PT_LINT=strict (default) raises on error findings; =warn demotes
    # them to one LintWarning; =0 restores the raw mid-trace failures.
    # An optimizer-produced twin (core/passes) skips the hook: its RAW
    # original was already linted — gating on the rewritten program
    # would let DCE delete a user's bug before strict mode could name it.
    if not getattr(program, '_opt_of', False):
        from ..analysis import apply_lint_policy, lint_mode
        apply_lint_policy(program, feed_names=feed_names,
                          fetch_names=fetch_names, mode=lint_mode(),
                          header='program lint failed before lowering')

    block = program.global_block()
    ops = block.ops
    required, written = _analyze(block, feed_names, fetch_names)
    params_in = sorted(required)
    writeback = sorted((required | written))
    bw_idx = next((i for i, op in enumerate(ops)
                   if op.type == _BACKWARD_OP), None)

    def step_fn(params, feeds, counter):
        _TRACE_COUNT[0] += 1
        # the run counter is FOLDED into the program key rather than mixed
        # arithmetically into the seed: inside a K-step scan the per-step
        # key is fold_in(key, counter + i), which is exactly what the i-th
        # sequential run would derive — multi-step and single-step paths
        # share one RNG stream by construction
        base_key = jax.random.fold_in(
            jax.random.key(program.random_seed), counter)
        ectx = registry.ExecCtx(base_key, mesh=mesh,
                                amp=getattr(program, '_amp', False))
        if emit_engine is not None:
            ectx.emit_engine = emit_engine
        if forensic is not None:
            forensic.begin()   # a retrace must not duplicate probe meta
            ectx.forensic = forensic
        env0 = {}
        env0.update(feeds)
        env0.update(params)

        if bw_idx is None:
            env = dict(env0)
            _exec_ops(ops, 0, env, ectx, program)
        else:
            bw_op = ops[bw_idx]
            pnames = bw_op.attrs['params']
            loss_name = bw_op.inputs['Loss'][0]
            missing = [p for p in pnames if p not in env0]
            if missing:
                raise ValueError(
                    '__backward__ wrt non-leaf vars %s not supported yet; '
                    'differentiate wrt parameters or feed vars' % missing)
            diff = {p: env0[p] for p in pnames}
            rest = {k: v for k, v in env0.items() if k not in diff}
            # Prune fw's outputs to what the rest of the step actually
            # reads.  Returning the whole env would make EVERY
            # intermediate a vjp primal output carrying a dense zero
            # cotangent through the transpose — measured on the per-HLO
            # ledger (PERF.md r5): unused auxiliary outputs (op Softmax
            # slots, norm statistics) kept whole [B, T, V]-scale
            # forward+backward chains alive.
            if emit_engine is not None and \
                    emit_engine.slim_fw_keep is not None:
                # emit mode: the engine's keep-set additionally excludes
                # post-backward reads that are (re)written before the
                # read and names the forward never computes — fewer vjp
                # primal outputs means fewer dense zero cotangents
                fw_keep = set(emit_engine.slim_fw_keep)
            else:
                fw_keep = set(fetch_names) | set(writeback) | {loss_name}

                def _collect_reads(op_list):
                    for op_after in op_list:
                        fw_keep.update(op_after.input_names())
                        # control-flow bodies read outer vars directly
                        # from env (not through input slots) — recurse
                        # like _analyze does
                        sb = op_after.attrs.get('sub_block')
                        if sb is not None:
                            _collect_reads(program.block(sb).ops)

                _collect_reads(ops[bw_idx + 1:])

            def fw(d):
                env2 = dict(rest)
                env2.update(d)
                _exec_ops(ops[:bw_idx], 0, env2, ectx, program)
                # probe entries must cross the vjp boundary as primal
                # outputs — they are not in any static keep-set (their
                # names are allocated during this very trace)
                return {k: v for k, v in env2.items()
                        if k in fw_keep or (
                            forensic is not None and
                            k.startswith(ForensicProbes.PREFIX))}

            env_out, pullback = jax.vjp(fw, diff)
            if loss_name not in env_out:
                raise ValueError('loss var %s not produced before backward'
                                 % loss_name)
            ct = {k: (jnp.ones_like(v) if k == loss_name
                      else _zero_cotangent(v))
                  for k, v in env_out.items()}
            grads, = pullback(ct)
            if mesh is None:
                # On one device XLA fuses a weight's optimizer update
                # (parameter and both moments, f32) into the EPILOGUE of
                # the product that computes its gradient, and the product
                # then runs at 59-73 % of the MXU's peak where alone it
                # runs at 86-91 % (PERF.md section 6, PR 54).  So the
                # gradient of a rank-2 parameter (the weight of a mul /
                # matmul) is fenced from the ops behind the backward, each
                # behind its own barrier: one tuple over all of them would
                # hold every gradient alive until the last is computed.
                # A filter (rank 4) and a vector keep their fusion, and
                # under a mesh the gradient already leaves its product
                # for a collective.
                fenced = [p for p in pnames if jnp.ndim(grads[p]) == 2]
                for p in fenced:
                    grads[p] = jax.lax.optimization_barrier(grads[p])
                if _obs.enabled():
                    _obs.metrics.counter('executor.grad_fences').inc(
                        len(fenced))
            if emit_engine is not None and \
                    emit_engine.slim_fw_keep is not None:
                # the slim keep-set drops pass-through names (params the
                # optimizer reads but the forward never writes) from the
                # vjp primal outputs; post-backward ops read them from
                # the original environment instead
                env = dict(env0)
                env.update(env_out)
            else:
                env = dict(env_out)
            for slot, names in bw_op.outputs.items():
                if slot == 'Grads':
                    for p, gname in zip(pnames, names):
                        env[gname] = grads[p]
                        if forensic is not None:
                            forensic.env = env
                            forensic.note(
                                bw_idx, _BACKWARD_OP, gname,
                                getattr(bw_op, 'source_loc', None),
                                env[gname])
                elif slot == 'LossGrad':
                    env[names[0]] = jnp.ones_like(env[loss_name])
            _exec_ops(ops[bw_idx + 1:], bw_idx + 1, env, ectx, program)

        fetches = []
        for n in fetch_names:
            if n not in env:
                raise ValueError('fetch var %s was never computed' % n)
            fetches.append(env[n])
        updates = {n: env[n] for n in writeback if n in env}
        if mesh is not None:
            # pin every annotated writeback layout (the shard pass's
            # ZeRO specs included) so donated state comes back in the
            # layout _gather_params expects — steady state skips the
            # re-shard device_put entirely
            from jax.sharding import NamedSharding
            sh = program._sharding
            for n in updates:
                ps = sh.get(n)
                if ps is not None:
                    updates[n] = jax.lax.with_sharding_constraint(
                        updates[n], NamedSharding(mesh, ps))
        probes = None
        if forensic is not None:
            vals = [env[n] for n in forensic.names() if n in env]
            probes = (jnp.stack(vals) if vals
                      else jnp.zeros((0, 3), jnp.float32))
        if not check_nan:
            if forensic is not None:
                return fetches, updates, probes
            return fetches, updates
        ok = jnp.asarray(True)
        for v in itertools.chain(fetches, updates.values()):
            if hasattr(v, 'dtype') and jnp.issubdtype(v.dtype,
                                                      jnp.inexact):
                ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(v)))
        if forensic is not None:
            return fetches, updates, ok, probes
        return fetches, updates, ok

    if steps is None:
        run_fn = step_fn
    else:
        def run_fn(params, feeds, counter):
            # feeds arrive stacked [steps, ...]; params thread as carry.
            # The carry only needs `required` names: a persistable that is
            # write-only within one step is overwritten before any read,
            # so its start-of-step value never matters — its LAST value is
            # recovered from the stacked per-step outputs below.
            import jax.lax as lax
            step_ids = jnp.arange(steps, dtype=jnp.uint32)

            def body(carry, xs):
                feeds_i, i = xs
                if check_nan:
                    p, ok_all = carry
                else:
                    p = carry
                res = step_fn(p, feeds_i, counter + i)
                fetches_i, updates_i = res[0], res[1]
                new_p = {n: updates_i[n] for n in p}
                extra_i = {n: v for n, v in updates_i.items() if n not in p}
                if check_nan:
                    return ((new_p, jnp.logical_and(ok_all, res[2])),
                            (fetches_i, extra_i))
                return new_p, (fetches_i, extra_i)

            init = (params, jnp.asarray(True)) if check_nan else params
            carry_out, (fetches, extras) = lax.scan(
                body, init, (feeds, step_ids))
            final_p = carry_out[0] if check_nan else carry_out
            updates = dict(final_p)
            updates.update({n: v[-1] for n, v in extras.items()})
            if check_nan:
                return fetches, updates, carry_out[1]
            return fetches, updates

    jit_kwargs = {}
    if donate and writeback:
        jit_kwargs['donate_argnums'] = (0,)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = program._sharding

        def shard_of(name, default=P()):
            return NamedSharding(mesh, spec.get(name, default))
        # feeds default to batch-sharding over the 'data' axis if present
        feed_default = P('data') if 'data' in mesh.axis_names else P()
        if steps is None:
            feed_shardings = {n: shard_of(n, feed_default)
                              for n in feed_names}
        else:
            # stacked feeds put the step axis first: prepend an
            # unsharded dim so the in-scan batch sharding matches the
            # single-step mesh path exactly
            def stacked_shard(name):
                s = spec.get(name, feed_default)
                return NamedSharding(mesh, P(*((None,) + tuple(s))))
            feed_shardings = {n: stacked_shard(n) for n in feed_names}
        jit_kwargs['in_shardings'] = (
            {n: shard_of(n) for n in params_in},
            feed_shardings,
            NamedSharding(mesh, P()),
        )
    return jax.jit(run_fn, **jit_kwargs), params_in, writeback


def _feed_spec(v):
    """(shape, dtype-string) of one feed/param value — the unit both the
    in-process hot key and the disk fingerprint are built from."""
    return (tuple(np.shape(v)),
            str(getattr(v, 'dtype', type(v).__name__)))


class _ExecEntry(object):
    """One resolved executable: `call` is the AOT-compiled artifact (from
    an eager lower().compile() or deserialized from disk); `jit_fn` is the
    lazily-specializing fallback kept for the rare input-spec drift an AOT
    executable cannot absorb (e.g. a scope param swapped to a new dtype).
    The strong `program` ref pins id(program) against recycling while the
    entry lives.  `shard_targets` (mesh launches only) maps each param to
    the NamedSharding of the OPTIMIZED program — the shard pass rewrites
    specs (ZeRO state sharding) on the optimizer twin, and gathering
    against the raw program's specs would re-replicate every launch."""
    __slots__ = ('call', 'jit_fn', 'params_in', 'writeback', 'program',
                 'fingerprint', 'shard_targets')

    def __init__(self, call, jit_fn, params_in, writeback, program,
                 fingerprint, shard_targets=None):
        self.call = call
        self.jit_fn = jit_fn
        self.params_in = params_in
        self.writeback = writeback
        self.program = program
        self.fingerprint = fingerprint
        self.shard_targets = shard_targets


def _tail_split_enabled():
    return os.environ.get('PT_TAIL_SPLIT', '1') not in ('0', 'false',
                                                        'False')


class Executor(object):
    """Parity: reference executor.py Executor (run/close/feed/fetch API)."""

    def __init__(self, place=None, mesh=None, check_nan=None,
                 nan_poll=None):
        self.place = place if place is not None else TPUPlace(0)
        self.mesh = mesh
        # nan/inf debug guard (SURVEY §2.8; parity: the reference's global
        # FLAGS_check_nan_inf, which makes every op kernel assert finite
        # outputs).  Whole-block lowering has no per-op boundary, so the
        # check covers everything that leaves the executable — fetches and
        # written-back persistables — as ONE fused all-finite scalar
        # compiled into the step; the per-array naming pass runs only
        # when that flag trips.
        if check_nan is None:
            check_nan = os.environ.get('FLAGS_check_nan_inf', '') in (
                '1', 'true', 'True')
        self.check_nan = bool(check_nan)
        # verdict poll cadence: the fused ok scalar accumulates on device
        # (running AND) and is only READ every nan_poll steps — the read
        # is the host sync that made check_nan cost 4x (PERF.md).  1 (the
        # default without PT_ASYNC/PT_NAN_POLL) is the synchronous
        # per-launch read, bit-for-bit.  Not part of the compile key: the
        # executable computes the same verdict either way.
        self.nan_poll = _async.default_nan_poll() if nan_poll is None \
            else max(1, int(nan_poll))
        self._nan = _async.DeferredNanVerdict(self.nan_poll)
        # L1 of the two-tier compilation cache (core/compile_cache.py):
        # fingerprinted executables, LRU-bounded by PT_EXEC_CACHE_MAX —
        # the seed's dict grew one executable per signature forever
        self._cache = _cc.ExecutableLRU()
        self._run_counter = {}
        # RNG counters restored from a checkpoint before their base_key
        # exists (fresh process): consumed on the first run of a matching
        # (feed names, fetch names) signature — see set_rng_state
        self._pending_counters = {}
        self._shard_targets = {}
        # largest K ever launched per (program, fetch set): a smaller K
        # against the same program is a ragged tail, and run_steps routes
        # it through the single-step executable instead of lowering a
        # whole new scan (PT_TAIL_SPLIT=0 restores per-tail lowering)
        self._steps_seen = {}
        # telemetry span tags (ParallelExecutor sets mesh/shard info here)
        self._obs_tags = {}

    def close(self):
        self._cache.clear()
        self._shard_targets.clear()
        self._steps_seen.clear()
        self._nan.reset()

    # ---------------------------------------------- deferred nan verdict
    def nan_clean(self):
        """True when no launch verdicts are pending an unread deferred
        poll — i.e. checkpointing NOW cannot capture state a later poll
        will condemn.  Always True with check_nan off or nan_poll=1
        (every launch polls before returning)."""
        return not self.check_nan or self._nan.pending_steps == 0

    def poll_nan(self):
        """Force the deferred verdict poll NOW (end of epoch/stream, or
        before an aligned checkpoint).  Raises the standard check_nan
        RuntimeError — with ``nan_window_steps`` attached — if any launch
        since the last poll produced non-finite values.  No-op when
        check_nan is off or nothing is pending."""
        if not self.check_nan:
            return
        window = self._nan.poll()
        if window:
            e = RuntimeError(_async.DEFERRED_TRIP_MSG % window)
            e.nan_window_steps = window
            e.nan_window_start = self._nan.last_window_start
            raise e

    def reset_nan_window(self):
        """Drop pending verdicts without reading them.  Recovery calls
        this after a rollback: verdicts accumulated over the poisoned
        stream say nothing about the restored state."""
        self._nan.reset()

    # ------------------------------------------------------- rng/run state
    @staticmethod
    def _stream_key(feed_names, fetch_names):
        return '|'.join(sorted(feed_names)) + '=>' + '|'.join(fetch_names)

    def rng_state(self):
        """JSON-able RNG/run-counter state, keyed program-agnostically by
        (feed names, fetch names) — id(program) and scope serials don't
        survive a process restart, the launch *signature* does.  The
        checkpointer saves this so a resumed run derives the exact
        per-step RNG keys (dropout masks included) the uninterrupted run
        would have: the counter fold-in makes the stream a pure function
        of (program seed, counter)."""
        out = {}
        for (pid, ver, feeds, fetch, sserial), v in \
                self._run_counter.items():
            k = self._stream_key(feeds, fetch)
            out[k] = max(int(v), out.get(k, 0))
        # carry still-unconsumed restored counters through re-checkpoints
        for k, v in self._pending_counters.items():
            out.setdefault(k, int(v))
        return out

    def set_rng_state(self, state):
        """Restore counters captured by `rng_state`.  Live base_keys with
        a matching signature are overwritten in place (in-process
        rollback); unseen signatures are parked and consumed on their
        first run (fresh-process resume).  A live stream ABSENT from the
        snapshot had not run when the checkpoint was taken — it rewinds
        to 0, so a rollback to a pre-stream checkpoint replays the exact
        counters (dropout masks, fault windows) the original run drew."""
        state = {k: int(v) for k, v in (state or {}).items()}
        consumed = set()
        for key in list(self._run_counter):
            k = self._stream_key(key[2], key[3])
            if k in state:
                self._run_counter[key] = state[k]
                consumed.add(k)
            else:
                self._run_counter[key] = 0
        self._pending_counters = {k: v for k, v in state.items()
                                  if k not in consumed}

    def stream_counter(self, feed_names, fetch_names):
        """The NEXT run counter a launch with this (feed names, fetch
        names) signature would consume.  Forensic replay (train/
        forensics.py) uses this right after a checkpoint restore to
        re-derive the exact per-step RNG keys the condemned window used."""
        k = self._stream_key(tuple(feed_names), tuple(fetch_names))
        best = None
        for key, v in self._run_counter.items():
            if self._stream_key(key[2], key[3]) == k:
                best = int(v) if best is None else max(best, int(v))
        if best is None:
            best = int(self._pending_counters.get(k, 0))
        return best

    def _resolve_fetch(self, fetch_list):
        names = []
        for f in _as_list(fetch_list):
            if isinstance(f, Variable):
                names.append(f.name)
            elif isinstance(f, str):
                names.append(f)
            else:
                raise TypeError('bad fetch entry: %r' % (f,))
        return names

    def _normalize_feed(self, block, feed):
        """One per-step feed dict -> {name: array}, with LoDTensor feeds
        expanded to padded+lengths and lod lengths synthesized for dense
        arrays fed into lod vars."""
        feed_vals = {}
        for k, v in (feed or {}).items():
            if not block.has_var(k):
                raise KeyError(
                    'feed var "%s" is not a variable of this program; '
                    'data vars: %s' % (k, sorted(
                        n for n, var in block.vars.items() if var.is_data)))
            from .lod import LoDTensor
            if isinstance(v, LoDTensor):
                feed_vals[k] = v.padded
                feed_vals[k + '@LENGTH'] = v.lengths
                if v.outer_lengths is not None and \
                        block.has_var(k + '@OUTERLEN'):
                    feed_vals[k + '@OUTERLEN'] = v.outer_lengths
            elif hasattr(v, 'devices'):
                # already a device array: pass through zero-copy (a feed
                # uploaded once with jax.device_put is NOT round-tripped
                # through the host every step)
                feed_vals[k] = v
            else:
                feed_vals[k] = np.asarray(v)
        # lod vars fed as plain dense arrays: synthesize full lengths
        for k in list(feed_vals.keys()):
            lname = k + '@LENGTH'
            if block.has_var(k) and block.var(k).lod_level > 0 and \
                    lname not in feed_vals and block.has_var(lname):
                arr = feed_vals[k]
                feed_vals[lname] = np.full((arr.shape[0],), arr.shape[1],
                                           dtype=np.int32)
        return feed_vals

    def run(self, program=None, feed=None, fetch_list=None,
            feed_var_name='feed', fetch_var_name='fetch', scope=None,
            return_numpy=True, use_program_cache=True, as_futures=False):
        """``as_futures=True`` is the non-blocking fetch mode: the call
        returns ``async_runtime.FetchFuture`` handles instead of arrays,
        so the host never waits on the device — sync happens lazily at
        ``.numpy()`` (metered in ``executor.host_blocked_s``).  The
        launch itself is identical; ``return_numpy`` is ignored."""
        if program is None:
            program = default_main_program()
        if isinstance(program, _CompiledProgramBase):
            return program._run(self, feed, fetch_list, scope, return_numpy,
                                as_futures=as_futures)
        scope = scope if scope is not None else global_scope()
        feed_vals = self._normalize_feed(program.global_block(), feed)
        return self._run_impl(program, feed_vals, fetch_list, scope,
                              return_numpy, use_program_cache, steps=None,
                              as_futures=as_futures)

    def run_steps(self, program=None, feed_list=None, fetch_list=None,
                  steps=None, scope=None, return_numpy=True,
                  use_program_cache=True, as_futures=False):
        """Run `steps` training iterations in ONE device launch.

        The K iterations lower to a single jitted lax.scan (see _lower):
        one dispatch instead of K, donated
        state threaded through the scan carry, per-step RNG folded from
        the shared run counter — bitwise-identical on CPU to K
        sequential `run` calls with the same feeds.

        feed_list: a list of K per-step feed dicts, or ONE dict whose
        arrays are already stacked on a leading [K] axis (pass `steps`
        explicitly in that case — e.g. a superbatch from
        data_feeder.FeedPrefetcher).
        Returns the fetches stacked per step: each entry is [K, ...]
        (FetchFuture handles over the stacked device arrays when
        ``as_futures=True`` — consecutive launches then chain on-device
        with zero host round-trips between them).
        """
        if program is None:
            program = default_main_program()
        if isinstance(program, _CompiledProgramBase):
            return program._run_steps(self, feed_list, fetch_list, steps,
                                      scope, return_numpy,
                                      as_futures=as_futures)
        scope = scope if scope is not None else global_scope()
        block = program.global_block()
        if isinstance(feed_list, dict):
            if steps is None:
                raise ValueError(
                    'run_steps with a pre-stacked feed dict needs steps=K')
            feed_vals = {k: (v if hasattr(v, 'devices') else np.asarray(v))
                         for k, v in feed_list.items()}
            for k, v in feed_vals.items():
                if v.shape[0] != steps:
                    raise ValueError(
                        'stacked feed "%s" has leading dim %d, expected '
                        'steps=%d' % (k, v.shape[0], steps))
        else:
            per_step = [self._normalize_feed(block, f)
                        for f in (feed_list or [])]
            if not per_step:
                raise ValueError('run_steps needs a non-empty feed_list')
            if steps is None:
                steps = len(per_step)
            elif steps != len(per_step):
                raise ValueError('steps=%d but feed_list has %d entries'
                                 % (steps, len(per_step)))
            names = set(per_step[0])
            for f in per_step[1:]:
                if set(f) != names:
                    raise ValueError('per-step feeds disagree on keys: '
                                     '%s vs %s' % (sorted(names), sorted(f)))
            feed_vals = _stack_feeds(per_step)
        steps = int(steps)
        fetch_names = tuple(self._resolve_fetch(fetch_list))
        seen_key = (id(program), program._version, fetch_names)
        kmax = self._steps_seen.get(seen_key, 0)
        if (use_program_cache and _tail_split_enabled() and steps < kmax
                and self._hot_key(program, feed_vals, fetch_names, steps)
                not in self._cache):
            # ragged tail: a K smaller than this program has already
            # launched, with no executable for it.  Lowering a steps=K'
            # scan per distinct tail length is one full compile each;
            # K' launches of the (reused-forever) single-step executable
            # consume the same RNG counters and are bitwise identical.
            return self._run_tail_split(program, feed_vals, fetch_list,
                                        steps, scope, return_numpy,
                                        as_futures)
        self._steps_seen[seen_key] = max(kmax, steps)
        return self._run_impl(program, feed_vals, fetch_list, scope,
                              return_numpy, use_program_cache,
                              steps=steps, as_futures=as_futures)

    def _run_tail_split(self, program, feed_vals, fetch_list, steps, scope,
                        return_numpy, as_futures=False):
        """Run a ragged-tail superbatch as `steps` single-step launches.
        Output shape contract matches the fused path: fetches stacked on a
        leading [steps] axis.  The stack happens ON DEVICE — the per-step
        launches pipeline asynchronously and the host only syncs once at
        the end (return_numpy), or never (as_futures)."""
        if _obs.enabled():
            _obs.metrics.counter('executor.tail_splits').inc()
            _obs.instant('executor.tail_split', cat='compile',
                         args={'steps': steps})
        outs = [self._run_impl(program,
                               {k: v[i] for k, v in feed_vals.items()},
                               fetch_list, scope, False, True, steps=None)
                for i in range(steps)]
        import jax.numpy as jnp
        stacked = [jnp.stack([o[j] for o in outs])
                   for j in range(len(outs[0]))]
        if as_futures:
            return [_async.FetchFuture(s) for s in stacked]
        if return_numpy:
            with _async.host_block('tail_split_sync', steps=steps):
                return [np.asarray(s) for s in stacked]
        return stacked

    def _hot_key(self, program, feed_vals, fetch_names, steps):
        """In-process (L1) cache key.  Unlike the seed's key it includes
        feed shapes/dtypes — an entry holds one AOT-compiled executable,
        which (by design) has no lazy re-specialization to hide behind —
        and excludes the scope: the executable is scope-agnostic, state
        flows through its arguments."""
        return (id(program), program._version,
                tuple((n,) + _feed_spec(feed_vals[n])
                      for n in sorted(feed_vals)),
                fetch_names, self.check_nan, steps,
                _passes.config_token(), _emit.config_token())

    def _shard_targets_for(self, program, params_in):
        """Param -> NamedSharding targets from `program._sharding`.
        Called with the OPTIMIZED program at entry-resolution time so the
        shard pass's rewritten specs (ZeRO accumulator/param sharding)
        are what the scope arrays get device_put to."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = program._sharding
        return {n: NamedSharding(self.mesh, spec.get(n, P()))
                for n in params_in}

    def _gather_params(self, program, params_in, scope, base_key,
                       targets=None):
        import jax
        import jax.numpy as jnp
        params = {}
        for n in params_in:
            if n not in scope:
                raise RuntimeError(
                    'persistable var "%s" not initialized in scope — run the '
                    'startup program first (exe.run(startup_program))' % n)
            v = scope.vars[n]
            if not hasattr(v, 'devices'):
                # host (numpy) array in scope — a checkpoint restore,
                # load_persistables, or manual scope.set.  It must be
                # uploaded into an XLA-OWNED buffer before it meets a
                # donating executable: on the CPU backend device_put can
                # zero-copy ALIAS the numpy memory, and donating that
                # buffer frees memory numpy still owns — observed as
                # glibc heap corruption in resume-after-restore training.
                # jnp.array forces the copy; written back so the upload
                # happens once per restore, not once per launch.
                v = jnp.array(np.asarray(v))
                scope.vars[n] = v
            params[n] = v
        if self.mesh is not None:
            # arrays in scope may carry a different (e.g. replicated)
            # committed sharding from the startup run; reshard to the
            # program's annotated layout.  Target shardings are cached per
            # lowering entry, and device_put is skipped once the written-
            # back arrays already carry the right sharding (steady state).
            if targets is None:
                targets = self._shard_targets.get(base_key)
            if targets is None:
                targets = self._shard_targets_for(program, params_in)
                self._shard_targets[base_key] = targets
            params = {n: (v if getattr(v, 'sharding', None) == targets[n]
                          else jax.device_put(v, targets[n]))
                      for n, v in params.items()}
        return params

    def _resolve_entry(self, program, feed_vals, feed_names, fetch_names,
                       scope, steps, base_key, counter, use_cache, obs_on):
        """Two-tier executable resolution (see core/compile_cache.py):
        L1 in-process LRU by hot key; on miss, the cold path
        (`_prepare_entry`), which is the phase ``executor.prepare``."""
        hot_key = (self._hot_key(program, feed_vals, fetch_names, steps)
                   if use_cache else None)
        if use_cache:
            entry = self._cache.get(hot_key)
            if entry is not None:
                return entry, self._gather_params(
                    program, entry.params_in, scope, base_key,
                    targets=entry.shard_targets)
        with _obs.span('executor.prepare', cat='compile',
                       counter='executor.prepare_s') as prep:
            if obs_on:
                prep.args.update(self._obs_tags, steps=steps)
            return self._prepare_entry(
                program, feed_vals, feed_names, fetch_names, scope, steps,
                base_key, counter, hot_key, obs_on, prep)

    def _prepare_entry(self, program, feed_vals, feed_names, fetch_names,
                       scope, steps, base_key, counter, hot_key, obs_on,
                       prep):
        """The cold path, from the hot-key miss to `_cache.put`: the
        canonical fingerprint is tried against the disk store (a hit
        skips trace AND compile); on a disk miss the program is traced
        and AOT-compiled eagerly (`jit(fn).lower(...).compile()`) and the
        executable serialized back to disk for the next process.  Every
        step is a child span of ``executor.prepare`` with its own seconds
        counter (docs/observability.md, "Set-up from inside"); a warm
        start runs all of them but trace/compile/store.  ``hot_key`` is
        None on the cache-bypass path."""
        use_cache = hot_key is not None
        tags = dict(self._obs_tags, steps=steps) if obs_on else {}
        # PT_LINT gate on the RAW program, BEFORE the rewriter: a user's
        # def-use/shape bug must be named here, not DCE'd out of sight
        with _obs.span('executor.lint', cat='compile',
                       counter='executor.lint_s') as sp:
            from ..analysis import apply_lint_policy, lint_mode
            memo0 = _infer_memo.counts()
            apply_lint_policy(program, feed_names=feed_names,
                              fetch_names=fetch_names, mode=lint_mode(),
                              header='program lint failed before lowering')
            if obs_on:
                sp.args.update(_infer_memo.span_args(memo0))
        # Program->Program rewriter (core/passes): the tracer sees the
        # optimized twin; every cache key/RNG stream stays keyed on the
        # RAW program (PT_OPT toggling is part of the hot key + launch
        # signature via config_token, so it reads as a named change)
        with _obs.span('executor.optimize', cat='compile',
                       counter='executor.optimize_s') as sp:
            opt_program, opt_stats = _passes.maybe_optimize(program,
                                                            fetch_names)
            if obs_on and opt_stats is not None:
                sp.args.update(self._obs_tags,
                               raw=opt_stats['op_count_raw'],
                               opt=opt_stats['op_count_opt'],
                               pass_ms=opt_stats['pass_ms'])
        # Direct Program->jaxpr emitter (core/emit): built on the
        # optimized twin so emission sees the fused/rng_stream-stamped
        # shape.  A static coverage gap falls back PER PROGRAM to the
        # traced path — loudly (emitter.fallbacks counters, warn-once,
        # PT_STRICT_EMIT=1 raises naming the op).  The cache-bypass path
        # (use_cache=False) keeps seed semantics and never emits.
        engine, emit_verdict = None, 'trace'
        if use_cache and _emit.enabled():
            with _obs.span('executor.emit_build', cat='compile',
                           counter='executor.emit_build_s'):
                try:
                    engine = _emit.build_engine(opt_program, feed_names,
                                                fetch_names)
                    emit_verdict = 'emit'
                except _emit.EmitFallback as e:
                    if _emit.strict():
                        raise
                    _emit.note_fallback(e.op, e.why)
                    emit_verdict = 'emit_fallback:%s' % e.op
        with _obs.span('executor.lower', cat='compile',
                       counter='executor.lower_s', **tags):
            jit_fn, params_in, writeback = _lower(
                opt_program, feed_names, fetch_names, donate=True,
                mesh=self.mesh, check_nan=self.check_nan, steps=steps,
                emit_engine=engine)
            if obs_on:
                _obs.metrics.counter('executor.lowerings').inc()
        # with a mesh this is where ParallelExecutor's shards are placed
        with _obs.span('executor.gather_params', cat='compile',
                       counter='executor.gather_params_s'):
            shard_targets = self._shard_targets_for(opt_program, params_in)
            params = self._gather_params(program, params_in, scope,
                                         base_key, targets=shard_targets)
        if not use_cache:
            # cache bypass keeps the seed semantics: a lazily-retracing
            # jit call per run, observed by the explainer at call time
            if obs_on:
                prep.args['verdict'] = 'uncached'
            return (_ExecEntry(jit_fn, jit_fn, params_in, writeback,
                               program, None, shard_targets), params)

        def fingerprint(engine):
            # fingerprint the OPTIMIZED desc: it is what actually lowers,
            # and it folds the PT_OPT config in for free (PT_OPT=0 hashes
            # the raw desc, a skipped pass changes the rewrite output)
            # emit-mode entries carry the emitter version + coverage set
            # in the key; fallback (and PT_EMIT=0) entries use extra=None
            # so traced artifacts are SHARED across modes on disk.
            return _cc.launch_fingerprint(
                opt_program,
                {n: _feed_spec(feed_vals[n]) for n in feed_names},
                fetch_names, steps, self.check_nan, mesh=self.mesh,
                param_specs={n: _feed_spec(v) for n, v in params.items()},
                extra=engine.fingerprint_extra() if engine is not None
                else None)

        call, fp, disk_tier = None, None, None
        if _cc.disk_enabled():
            with _obs.span('compile_cache.fingerprint', cat='compile',
                           counter='compile_cache.fingerprint_s'):
                _cc.ensure_xla_cache_backstop()
                fp = fingerprint(engine)
            with _obs.span('executor.aot_load', cat='compile',
                           **tags) as load:
                call, disk_tier = _cc.disk_cache().load(fp)
            if obs_on:
                load.args['hit'] = call is not None
                if call is not None:
                    _obs.metrics.counter('compile_cache.disk_hits').inc()
                    _obs.metrics.counter('compile_cache.load_s').inc(
                        load.seconds)
                    sig = _launch_signature(program, feed_vals, feed_names,
                                            fetch_names, steps,
                                            self.check_nan, scope)
                    _obs.explainer().observe_disk_load(
                        sig, load_s=load.seconds)
                else:
                    _obs.metrics.counter('compile_cache.disk_misses').inc()
        if call is None:
            with _obs.span('executor.trace_compile', cat='compile',
                           **tags) as sp:
                tc0 = _TRACE_COUNT[0]
                args = (params, {n: feed_vals[n] for n in feed_names},
                        np.uint32(counter & 0xffffffff))
                t_c0 = time.perf_counter() if obs_on else None
                try:
                    traced = jit_fn.trace(*args)
                except _emit.EmitError as e:
                    # runtime emission gap (e.g. an op outside the known
                    # RNG set drew ctx.rng): rebuild this program on the
                    # traced path.  The fingerprint is recomputed with
                    # extra=None so the stored artifact is the shared
                    # traced one.
                    if engine is None or _emit.strict():
                        raise
                    _emit.note_fallback(e.op, e.why)
                    emit_verdict = 'emit_fallback:%s' % e.op
                    engine = None
                    jit_fn, params_in, writeback = _lower(
                        opt_program, feed_names, fetch_names, donate=True,
                        mesh=self.mesh, check_nan=self.check_nan,
                        steps=steps)
                    if fp is not None:
                        fp = fingerprint(None)
                    traced = jit_fn.trace(*args)
                t_cmid = time.perf_counter() if obs_on else None
                lowered = traced.lower()
                call = lowered.compile()
                t_c1 = time.perf_counter() if obs_on else None
                # emit_s: wall time inside the emitter (memo build +
                # dispatch); trace_s: the residual jaxpr-staging time.
                # With the staged AOT API the StableHLO lowering now
                # lands in backend_compile_s for BOTH modes (accounting
                # change vs PR-5, documented in PERF.md).
                emit_s = engine.take_build_seconds() \
                    if engine is not None else 0.0
                if obs_on:
                    sp.args['lowering'] = emit_verdict
                    _obs.metrics.counter('executor.emit_s').inc(emit_s)
                    _obs.metrics.counter('executor.trace_s').inc(
                        max(0.0, (t_cmid - t_c0) - emit_s))
                    _obs.metrics.counter('executor.backend_compile_s').inc(
                        t_c1 - t_cmid)
                if obs_on and _TRACE_COUNT[0] > tc0:
                    sig = _launch_signature(program, feed_vals, feed_names,
                                            fetch_names, steps,
                                            self.check_nan, scope)
                    cache_status = ('disabled' if fp is None else
                                    'stablehlo_hit'
                                    if disk_tier == 'stablehlo' else 'miss')
                    report = _obs.explainer().observe(
                        sig, compile_s=t_c1 - t_c0, cache=cache_status,
                        lowering=emit_verdict)
                    sp.args.update(
                        kind=report['kind'],
                        cause='; '.join(report['details'])[:512] or None)
            if fp is not None:
                with _obs.span('compile_cache.store', cat='compile',
                               counter='compile_cache.store_s'):
                    _cc.disk_cache().store(
                        fp, compiled=call, lowered=lowered,
                        meta={'steps': steps, 'fetch': list(fetch_names),
                              'program': _cc.program_fingerprint(
                                  opt_program)})
        if obs_on:
            prep.args['verdict'] = 'disk_hit' if disk_tier == 'exec' \
                else 'compiled'
        entry = _ExecEntry(call, jit_fn, params_in, writeback, program, fp,
                           shard_targets)
        self._cache.put(hot_key, entry)
        return entry, params

    def prepare(self, program=None, feed=None, fetch_list=None, scope=None,
                steps=None):
        """AOT pre-warm: resolve — load from disk, or trace+compile and
        persist — the executable for the given feed signature WITHOUT
        running a step.  `feed` maps name -> example array or a
        ``(shape, dtype)`` spec (zeros are synthesized); ``steps=K``
        pre-warms the fused K-step scan (the example feeds are stacked
        internally).  The scope must already hold initialized persistables
        (run the startup program first).  Returns the entry's disk
        fingerprint, or None when the disk tier is disabled."""
        if program is None:
            program = default_main_program()
        scope = scope if scope is not None else global_scope()
        example = {}
        for k, v in (feed or {}).items():
            if isinstance(v, tuple) and len(v) == 2 and \
                    not hasattr(v, 'dtype'):
                from .dtypes import convert_dtype
                shape, dtype = v
                v = np.zeros(tuple(int(d) for d in shape),
                             convert_dtype(dtype))
            example[k] = v
        feed_vals = self._normalize_feed(program.global_block(), example)
        if steps is not None:
            steps = int(steps)
            feed_vals = _stack_feeds([feed_vals] * steps)
        feed_names = tuple(sorted(feed_vals.keys()))
        fetch_names = tuple(self._resolve_fetch(fetch_list))
        base_key = (id(program), program._version, feed_names, fetch_names,
                    scope._serial)
        entry, _ = self._resolve_entry(
            program, feed_vals, feed_names, fetch_names, scope, steps,
            base_key, 0, True, _obs.enabled())
        if steps is not None:
            seen_key = (id(program), program._version, fetch_names)
            self._steps_seen[seen_key] = max(
                self._steps_seen.get(seen_key, 0), steps)
        return entry.fingerprint

    def _run_impl(self, program, feed_vals, fetch_list, scope,
                  return_numpy, use_program_cache, steps,
                  as_futures=False):
        feed_names = tuple(sorted(feed_vals.keys()))
        fetch_names = tuple(self._resolve_fetch(fetch_list))

        # telemetry: ONE flag check per launch; when off, the hot path
        # below does no telemetry work (its span() handles do nothing:
        # no annotation, no event, no counters, no dicts)
        obs_on = _obs.enabled()
        if obs_on:
            t_run0 = time.perf_counter()
            _obs.on_launch_start(self, t_run0)

        # rng/shard-layout bookkeeping stays scope-local (unlike the
        # executable): parallel scopes keep independent RNG streams.
        # The stream is keyed WITHOUT check_nan or steps: toggling the
        # debug flag mid-training does not restart dropout masks, and a
        # K-step launch consumes the same K counters that K sequential
        # runs would — mixed run/run_steps usage shares one stream
        base_key = (id(program), program._version, feed_names, fetch_names,
                    scope._serial)
        counter = self._run_counter.get(base_key)
        if counter is None:
            # first launch of this signature: a checkpoint-restored
            # counter (set_rng_state) resumes the stream mid-sequence
            counter = int(self._pending_counters.pop(
                self._stream_key(feed_names, fetch_names), 0)) \
                if self._pending_counters else 0
        if _faults.any_active():
            # preemption rehearsal: SIGTERM delivered as step `at` is
            # ABOUT TO launch — before the counter bump and writeback, so
            # the signal handler's flushed checkpoint sees scope, RNG
            # counters, and caller-recorded progress all consistent at
            # "step at-1 complete"
            _faults.maybe_kill('sigterm', step=counter, count=steps or 1)
        self._run_counter[base_key] = counter + (steps or 1)

        if _faults.any_active():
            # nan_step fault site: poison this launch's float feeds so
            # the fused check_nan verdict trips like a real divergence
            feed_vals = _faults.poison_nan(feed_vals, counter, steps or 1)

        entry, params = self._resolve_entry(
            program, feed_vals, feed_names, fetch_names, scope, steps,
            base_key, counter, use_program_cache, obs_on)

        if obs_on:
            tc0 = _TRACE_COUNT[0]
        feeds = {n: feed_vals[n] for n in feed_names}
        ctr = np.uint32(counter & 0xffffffff)
        with _obs.span('executor.dispatch', cat='launch') as dispatch:
            try:
                result = entry.call(params, feeds, ctr)
            except TypeError:
                # an input spec drifted under an AOT executable (scope
                # param swapped to a new dtype/sharding): the artifact
                # cannot re-specialize, so drop this entry to the lazily-
                # retracing jit fallback — the explainer names the
                # retrace below
                if entry.call is entry.jit_fn:
                    raise
                entry.call = entry.jit_fn
                result = entry.call(params, feeds, ctr)
            if obs_on:
                dispatch.args.update(self._obs_tags, steps=steps)
            if obs_on and _TRACE_COUNT[0] > tc0:
                # only the jit-fallback / cache-bypass paths trace at call
                # time; cached-path traces happen inside _resolve_entry.
                # The span is recorded under the name of what it turned
                # out to be (its profiler annotation stays `dispatch`)
                sig = _launch_signature(program, feed_vals, feed_names,
                                        fetch_names, steps, self.check_nan,
                                        scope)
                report = _obs.explainer().observe(
                    sig, compile_s=time.perf_counter() - dispatch.t0)
                dispatch.name, dispatch.cat = ('executor.trace_compile',
                                               'compile')
                dispatch.args.update(
                    kind=report['kind'],
                    cause='; '.join(report['details'])[:512] or None)
        if obs_on:
            _obs.metrics.counter('executor.launches').inc()
            _obs.metrics.counter('executor.steps').inc(steps or 1)
        fetches, updates = result[0], result[1]
        # write back BEFORE the nan check: params were donated, so the old
        # scope arrays are dead — raising first would leave the scope
        # holding deleted buffers right when the user wants to inspect it
        for n, v in updates.items():
            scope.vars[n] = v
        if self.check_nan:
            # the fused verdict stays device-resident: push accumulates
            # it into a running AND (async, no host read) and only a DUE
            # window forces the one host sync.  nan_poll=1 makes every
            # launch due — bit-for-bit the old per-launch bool(ok) read.
            self._nan.push(result[2], steps or 1, start=counter)
            if self._nan.due():
                window = self._nan.poll()
                if window:
                    # tripped: per-array pass to NAME the culprits (slow,
                    # but only runs on actual failure).  For a K-step
                    # launch the fetches are stacked [K, ...] and the
                    # updates are end-of-scan state — both still name the
                    # vars; a deferred window's culprit usually persists
                    # into them (NaN propagates through params).  The
                    # launch window must CLOSE before the raise: otherwise
                    # the next launch (after a divergence rollback)
                    # measures its gap from the launch before this one and
                    # reads the whole failed step + recovery as a phantom
                    # pipeline stall.
                    try:
                        self._raise_non_finite(fetch_names, fetches,
                                               updates, window)
                    finally:
                        if obs_on:
                            _obs.on_launch_end(self, time.perf_counter())
        if as_futures:
            # non-blocking fetch mode: hand back device handles; the sync
            # (if any) happens at FetchFuture.numpy(), where it is metered
            fetches = [_async.FetchFuture(f) for f in fetches]
        elif return_numpy:
            # the host-sync point of the launch: converting fetches blocks
            # on the device — its duration is how long the async pipeline
            # made the host wait (near-zero in steady state)
            with _obs.span('executor.fetch_sync', cat='launch') as sync:
                fetches = [np.asarray(f) for f in fetches]
            if obs_on:
                _obs.metrics.counter('executor.host_blocked_s').inc(
                    sync.seconds)
                _obs.metrics.histogram('executor.fetch_sync_ms').observe(
                    sync.seconds * 1000.0)
        if obs_on:
            # drop the donated input refs NOW, inside the launch window: on
            # the CPU backend freeing a donated buffer blocks until its
            # consuming execution completes, and at frame teardown that
            # wait would land AFTER the end mark — misread as inter-launch
            # host gap (phantom pipeline stalls).  On TPU the free is async
            # and this is instant.
            t_w0 = time.perf_counter()
            params = None  # noqa: F841 - the free IS the point
            t_w1 = time.perf_counter()
            if t_w1 - t_w0 > 1e-4:
                _obs.tracing.add_span('executor.donate_wait', t_w0, t_w1,
                                      cat='launch')
            _obs.memory.on_launch()
            _obs.on_launch_end(self, t_w1)
            _obs.metrics.counter('executor.run_s').inc(t_w1 - t_run0)
        return fetches

    def _raise_non_finite(self, fetch_names, fetches, updates, window):
        """A (possibly deferred) verdict poll tripped: name the culprits
        still visible in the latest launch's arrays, annotating the raise
        with the window size; if the non-finite values no longer show
        there (possible when the window spans launches), raise the
        deferred-window message instead.  nan_poll=1 keeps today's exact
        behavior: the naming pass over this launch's own arrays."""
        try:
            self._assert_finite(itertools.chain(
                zip(fetch_names, fetches), updates.items()))
        except RuntimeError as e:
            e.nan_window_steps = window
            e.nan_window_start = self._nan.last_window_start
            raise
        if window > 1:
            e = RuntimeError(_async.DEFERRED_TRIP_MSG % window)
            e.nan_window_steps = window
            e.nan_window_start = self._nan.last_window_start
            raise e

    @staticmethod
    def _assert_finite(named_arrays):
        import jax.numpy as jnp
        named = []
        flags = []
        for n, v in named_arrays:
            try:
                flags.append(jnp.all(jnp.isfinite(v)))   # async dispatch
                named.append(n)
            except TypeError:
                continue  # non-numeric (e.g. tensor arrays) — skip
        if not flags:
            return
        # ONE host sync for the fused verdict, not one per array; the
        # naming pass below only runs on failure
        ok = flags[0]
        for f in flags[1:]:
            ok = jnp.logical_and(ok, f)
        if bool(ok):
            return
        bad = [n for n, f in zip(named, flags) if not bool(f)]
        raise RuntimeError(
            'check_nan: non-finite values (nan/inf) detected after this '
            'step in: %s. Typical causes: exploding gradients (try '
            'gradient clipping or a lower LR), log/div of zero, or '
            'uninitialized feeds.' % ', '.join(sorted(bad)))


class _CompiledProgramBase(object):
    """Marker base so Executor.run can dispatch CompiledProgram wrappers
    (see compiler.py / parallel/parallel_executor.py)."""

    def _run(self, exe, feed, fetch_list, scope, return_numpy,
             as_futures=False):
        raise NotImplementedError

    def _run_steps(self, exe, feed_list, fetch_list, steps, scope,
                   return_numpy, as_futures=False):
        raise NotImplementedError
