"""fused_elementwise: replay a serialized elementwise sub-program as ONE
registered op (built by core/passes/fuse.py).

The op's attrs carry the fused run:
  sub_ops    [{type, inputs, outputs, input_is_list, output_is_list,
               attrs, stop_grad}]  — original ops, original order
  arg_names  ordered external input names (bound from the 'X' slot)
  out_names  ordered escaping output names (returned in the 'Out' slot)

Replaying through each sub-op's own registered kernel, in order, emits
the IDENTICAL jaxpr the unfused executor loop would have — bitwise
parity is by construction.  The three pieces of executor-loop policy
that apply per op are replicated here: the full per-op AMP cast policy
(core/executor._amp_sub_ins/_amp_sub_outs — the _AMP_OPS bf16 in-cast,
elementwise-match glue, and _AMP_CAST_OPS f32 cast-back, so a fused
flash_attention sees exactly the unfused dtypes), per-output
stop_gradient, and RNG streams (ctx.sub_ctx derives each sub-op's
stream from its pinned ``rng_stream`` attr).
"""
import jax.numpy as jnp
from jax import lax

from ..core.registry import register, get_op


def _run_sub_op(ctx, sub, env, amp):
    impl = get_op(sub['type']).impl
    ins = {}
    for slot, names in sub['inputs'].items():
        vals = [env[n] for n in names]
        ins[slot] = vals if sub['input_is_list'].get(slot) else vals[0]
    if amp:
        from ..core.executor import _amp_sub_ins
        ins = _amp_sub_ins(sub['type'], ins, amp)
    sctx = ctx.sub_ctx(sub) if hasattr(ctx, 'sub_ctx') else ctx
    outs = impl(sctx, ins, sub['attrs']) or {}
    if amp:
        from ..core.executor import _amp_sub_outs
        outs = _amp_sub_outs(sub['type'], sub['attrs'], outs, amp)
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


@register('fused_elementwise')
def fused_elementwise(ctx, ins, attrs):
    fx = getattr(ctx, 'forensic', None)
    xs = ins.get('X', [])
    xs = xs if isinstance(xs, (list, tuple)) else [xs]
    env = dict(zip(attrs['arg_names'], xs))
    amp = bool(getattr(ctx, 'amp', False))
    pos = getattr(ctx, 'op_index', 0)
    loc = getattr(getattr(ctx, 'op', None), 'source_loc', None)
    for sub in attrs['sub_ops']:
        _run_sub_op(ctx, sub, env, amp)
        if fx is not None:
            # sub-program granularity: each replayed sub-op's outputs
            # get their own probe, named against the FUSED op's position
            # (the probe writes into fx.env — the executor's outer env —
            # so it escapes this impl's local sub-environment)
            sloc = sub['attrs'].get('source_loc') or loc
            for names in sub['outputs'].values():
                for nm in names:
                    if nm in env:
                        fx.note(pos, 'fused:%s' % sub['type'], nm, sloc,
                                env[nm])
    return {'Out': [env[n] for n in attrs['out_names']]}
