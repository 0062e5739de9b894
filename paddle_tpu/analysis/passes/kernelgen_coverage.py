"""D016: ops the Pallas codegen tier cannot lower — or never saw.

The kernelgen tier (ops/kernelgen) compiles each ``fused_elementwise``
sub-program into generated Pallas kernels; a sub-op with no
``KERNEL_RULES`` entry makes the launch raise ``KernelgenUnsupported`` at
run time (there is no reroute to the replay).  This pass reports the same
gap statically, per fused op, with sub-op names — the static face of
``kernelgen.unsupported_sub_ops``.

It also flags the dual failure: a KERNEL_TIER op (softmax / layer_norm /
flash_attention — ops with dedicated generated kernels) that the
rewriter's fuse pass FAILED to present as a fused group.  Since the fuse
pass wraps tier ops even as singleton runs, a bare tier op in an
otherwise-fused program means something blocked the escape — the fixit
names the blocking reason (sub_block, non-serializable attrs, or a
control-flow-pinned output).  Raw never-optimized programs (no
fused_elementwise anywhere) are skipped: there is no evidence the
rewriter ran at all.

Severity is info: the fuse pass only groups ops that have a rule, so a
gap can only come from a hand-built or disk-loaded program — and only
matters where the kernel tier is on.
"""
from ..engine import register_pass

__all__ = ['run']


def _bare_tier_reason(op):
    """(why, fixit) for a KERNEL_TIER op the fuse pass left bare, by
    re-checking the pass's own escape conditions."""
    from ...core.passes import fuse as _fuse
    if op.attrs.get('sub_block') is not None:
        return ('it carries a sub_block (control-flow ops never fuse)',
                'hoist the op out of the control-flow body so '
                'core/passes/fuse.py can wrap it')
    if _fuse._plain_attrs(op.attrs) is None:
        return ('its attrs are not JSON-serializable, so '
                'core/passes/fuse.py could not record the sub-program',
                'make the op attrs plain str/int/float/bool/list values')
    return ('its output is control-flow-pinned (or the fuse pass was '
            'skipped via PT_OPT_SKIP)',
            'check walker.control_flow_pinned consumers of its outputs '
            'and the PT_OPT_SKIP setting')


@register_pass('kernelgen_coverage')
def run(ctx):
    from ...core.passes import fuse as _fuse
    from ...ops import kernelgen
    diags = []
    seen = set()
    seen_bare = set()
    fused_present = any(op.type == 'fused_elementwise'
                        for block in ctx.program.blocks
                        for op in block.ops)
    for block in ctx.program.blocks:
        for i, op in enumerate(block.ops):
            if op.type in _fuse.KERNEL_TIER_OPS and fused_present:
                if op.type in seen_bare:
                    continue
                seen_bare.add(op.type)
                why, fixit = _bare_tier_reason(op)
                diags.append(ctx.diag(
                    'D016', 'info',
                    'kernel-tier op "%s" was not presented to the '
                    'kernelgen tier as a fused group: %s — it runs '
                    'through its plain registered impl instead of a '
                    'generated kernel' % (op.type, why),
                    block=block, op=op, op_index=i, fixit=fixit,
                    pass_name='kernelgen_coverage'))
                continue
            if op.type != 'fused_elementwise':
                continue
            for sub_type in kernelgen.unsupported_sub_ops(op.attrs):
                if sub_type in seen:
                    continue
                seen.add(sub_type)
                diags.append(ctx.diag(
                    'D016', 'info',
                    'fused sub-op "%s" has no KERNEL_RULES entry: with '
                    'the kernel tier on, launching this '
                    'fused_elementwise group raises '
                    'KernelgenUnsupported' % sub_type,
                    block=block, op=op, op_index=i,
                    fixit='add a KERNEL_RULES entry '
                          '(ops/kernelgen/rules.py), or run with '
                          'PT_KERNELGEN=0',
                    pass_name='kernelgen_coverage'))
    return diags
