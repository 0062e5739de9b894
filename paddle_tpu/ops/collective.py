"""Explicit collective ops inserted by the shard pass (core/passes/shard).

All three lower through `jax.lax.with_sharding_constraint`: the op's
`dst_spec` attr pins the GSPMD layout at that point of the program, and
XLA's SPMD partitioner emits the matching collective — an all-gather
when the constraint removes sharded axes, a dynamic-slice/all-to-all
when it moves them, and (for a constrained vjp cotangent) a
reduce-scatter.  The three TYPES are semantically distinct IR nodes so
the analyzer, pt_lint and a human reading the optimized
program can see WHAT moves where:

  reshard        layout change of a live value (the materialized D018)
  all_gather     shard -> full layout rejoin (ZeRO param gathering)
  grad_allreduce the once-per-parameter gradient reduction point; its
                 dst_spec is the parameter's (possibly ZeRO-sharded)
                 spec, so a replicated dst is a plain all-reduce and a
                 sharded dst collapses all-reduce+scatter into one
                 reduce-scatter

Off-mesh (ctx.mesh is None — single-device executors, build-time shape
inference, const-fold evaluation) every kernel is the identity on the
GLOBAL value, which is exactly what makes sharded-vs-single-device runs
of the SAME optimized program bitwise comparable.

Attrs (all JSON-stable, round-tripping through program_to_desc):
  src_spec / dst_spec  spec_to_jsonable layout (nested lists)
  bytes                estimated per-device bytes moved, computed with
                       the SAME cost model as the D018 lint (arxiv
                       2112.01075) — tests pin the two equal
  param                (grad_allreduce) the parameter this reduction
                       belongs to
"""
from ..core.registry import register
from ..core.sharding import spec_from_jsonable, normalize_spec

__all__ = ['COLLECTIVE_OPS']

COLLECTIVE_OPS = ('reshard', 'all_gather', 'grad_allreduce')


def _constrain(ctx, x, dst_jsonable):
    mesh = getattr(ctx, 'mesh', None)
    if mesh is None:
        return x
    spec = normalize_spec(spec_from_jsonable(dst_jsonable)) or ()
    axes = set(mesh.axis_names)
    rank = len(getattr(x, 'shape', ()) or ())
    # degrade to identity rather than crash on a spec the mesh cannot
    # express (D019 names the bad axis statically; rank overflow is D017)
    entries = []
    for e in spec[:rank]:
        if e is None:
            entries.append(None)
        elif isinstance(e, str):
            entries.append(e if e in axes else None)
        else:
            sub = tuple(a for a in e if a in axes)
            entries.append(sub if len(sub) == len(e) else None)
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*entries)))


@register('reshard')
def reshard(ctx, ins, attrs):
    return {'Out': _constrain(ctx, ins['X'], attrs.get('dst_spec'))}


@register('all_gather')
def all_gather(ctx, ins, attrs):
    return {'Out': _constrain(ctx, ins['X'], attrs.get('dst_spec'))}


@register('grad_allreduce')
def grad_allreduce(ctx, ins, attrs):
    return {'Out': _constrain(ctx, ins['X'], attrs.get('dst_spec'))}
