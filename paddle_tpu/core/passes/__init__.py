"""paddle_tpu.core.passes — the Program->Program optimizing rewriter.

The reference framework rewrites ProgramDesc before execution
(paddle/fluid/framework/ir/ graph passes + the memory_optimize
transpiler); this package is the TPU-native analog, run by the executor
on the lowering-cache-miss path so the tracer sees fewer, larger ops
(Tensor Processing Primitives, arxiv 2104.05755; whole-program rewriting
ahead of XLA, arxiv 1810.09868).

Passes, in order (each ``run(program, ctx) -> stats`` mutates a private
clone in place):

  dce               dead-op/dead-var elimination (shared walker with the
                    analysis D005/D006 pass, kill-on-overwrite rule)
  const_fold        compile-time-constant chains -> one fill_constant,
                    evaluated through the op's own kernel (dtype-exact)
  cse               duplicate (type, inputs, attrs) ops rebind to one
  shard             GSPMD-style partitioner (mesh-declared programs
                    only): completes sharding specs, materializes D018
                    edges as explicit reshard/grad_allreduce/all_gather
                    collectives, ZeRO-shards optimizer state
  attn_layout       split-heads chains around a `flash_attention`: the op
                    and the projection behind it (its `mul` retyped
                    `attn_out_proj`) learn where their operands come
                    from (input slots; nothing is removed), so the tiled
                    route can write and read the tile loop's own layout
  fuse_elementwise  consecutive elementwise/glue runs -> one
                    fused_elementwise op replaying the sub-program
  canon             64-bit attr narrowing + cross-block initializer dedup

Environment:
  PT_OPT=1 (default) enables the pipeline; PT_OPT=0 is the kill switch.
  PT_OPT_SKIP=pass,pass disables individual passes by name.
  PT_SHARD=1 (default) arms the shard pass (inert without a declared
  mesh); PT_SHARD_ZERO=1 arms its optimizer-state sharding tier.

Invariants: deterministic (same program -> same rewrite), idempotent
(optimizing an optimized program is a no-op), `source_loc` preserved on
surviving/folded/fused ops (fused ops carry their first sub-op's), and
bitwise-parity with the unfused lowering — RNG streams are pinned by
stamping every op's original trace position into an ``rng_stream`` attr
that ``registry.OpCtx.rng`` prefers over the live op index.
"""
import os
import time

from . import walker  # noqa: F401  (re-exported for analysis/)
from . import dce, const_fold, cse, fuse, canon, shard, attn_layout

__all__ = ['enabled', 'skip_set', 'config_token', 'optimize_program',
           'maybe_optimize', 'pass_names', 'PASSES', 'walker']

PASSES = (
    ('dce', dce.run),
    ('const_fold', const_fold.run),
    ('cse', cse.run),
    ('shard', shard.run),
    ('attn_layout', attn_layout.run),
    ('fuse_elementwise', fuse.run),
    ('canon', canon.run),
)


def pass_names():
    return [n for n, _ in PASSES]


def enabled():
    return os.environ.get('PT_OPT', '1') not in ('0', 'false', 'False')


def skip_set():
    raw = os.environ.get('PT_OPT_SKIP', '')
    return frozenset(p.strip() for p in raw.split(',') if p.strip())


def config_token():
    """Everything PT_OPT-shaped that changes what the tracer sees — part
    of the executor's hot cache key and the retrace explainer's launch
    signature, so toggling the pipeline mid-process reads as a named
    change instead of a mystery retrace."""
    if not enabled():
        return ('off',)
    return (('on',) + tuple(sorted(skip_set() & set(pass_names())))
            + shard.config_token())


class PassCtx(object):
    """Per-pass view of the program: the liveness roots plus the two
    name sets every pass guards on (recomputed between passes — each
    rewrite changes them)."""

    def __init__(self, program, fetch_names):
        self.program = program
        self.fetch_names = tuple(fetch_names)
        self.persistable = walker.persistable_names(program)
        self.cf_pinned = walker.control_flow_pinned(program)
        counts = {}
        for b in program.blocks:
            for op in b.ops:
                for n in op.output_names():
                    counts[n] = counts.get(n, 0) + 1
        self.multi_written = {n for n, c in counts.items() if c > 1}


def _op_count(program):
    return sum(len(b.ops) for b in program.blocks)


def _stamp_rng_streams(program):
    """Pin every op's RNG stream to its ORIGINAL trace position (the
    executor derives op streams from the live op index; rewrites shift
    indices).  setdefault keeps re-optimization idempotent.  Sub-blocks
    use the control_flow_exec offset convention (idx * 4096)."""
    for b in program.blocks:
        offset = 0 if b.idx == 0 else b.idx * 4096
        for i, op in enumerate(b.ops):
            op.attrs.setdefault('rng_stream', offset + i)


def _clone(program):
    p = program.clone(for_test=False)
    # clone() covers blocks/ops/random_seed; lowering also keys on these
    p._amp = getattr(program, '_amp', False)
    p._sharding = dict(getattr(program, '_sharding', {}))
    p._is_test = getattr(program, '_is_test', False)
    # clone() never rebuilds producer links, and control_flow_exec's
    # static-bound derivation walks var.op — restore them (last writer
    # wins, matching append_op)
    for b in p.blocks:
        for op in b.ops:
            for n in op.output_names():
                v = b._find_var_recursive(n)
                if v is not None:
                    v.op = op
    return p


def optimize_program(program, fetch_names=(), skip=None):
    """Run the pipeline on a CLONE of `program`; returns (program', stats).

    The input program is never mutated — the executor keys its caches on
    the raw program and hands the optimized twin to the tracer.
    """
    skip = skip_set() if skip is None else frozenset(skip)
    opt = _clone(program)
    # the executor's PT_LINT hook runs on the RAW program (user bugs must
    # not be DCE'd away before the gate); mark the twin so _lower skips
    # re-linting it
    opt._opt_of = True
    _stamp_rng_streams(opt)
    stats = {'op_count_raw': _op_count(program), 'passes': {},
             'pass_ms': 0.0}
    for name, fn in PASSES:
        if name in skip:
            continue
        t0 = time.perf_counter()
        pass_stats = fn(opt, PassCtx(opt, fetch_names)) or {}
        ms = (time.perf_counter() - t0) * 1000.0
        pass_stats['ms'] = round(ms, 3)
        stats['passes'][name] = pass_stats
        stats['pass_ms'] += ms
    stats['pass_ms'] = round(stats['pass_ms'], 3)
    stats['op_count_opt'] = _op_count(opt)
    stats['ops_removed'] = sum(
        p.get('ops_removed', 0) for p in stats['passes'].values())
    stats['ops_fused'] = stats['passes'].get(
        'fuse_elementwise', {}).get('ops_fused', 0)
    opt._bump()
    return opt, stats


_MEMO_MAX = 8


def maybe_optimize(program, fetch_names=()):
    """PT_OPT-gated, memoized entry used by the executor.  Returns
    (program', stats) — or (program, None) untouched when disabled."""
    if not enabled():
        return program, None
    token = config_token()
    key = (program._version, tuple(fetch_names), token)
    memo = getattr(program, '_opt_memo', None)
    if memo is None:
        memo = program._opt_memo = {}
    hit = memo.get(key)
    if hit is not None:
        return hit
    opt, stats = optimize_program(program, fetch_names)
    from ... import observability as _obs
    if _obs.enabled():
        shard_stats = stats['passes'].get('shard') or {}
        if shard_stats.get('reshards_inserted') or \
                shard_stats.get('grad_allreduce') or \
                shard_stats.get('all_gathers'):
            _obs.metrics.counter('opt.reshards_inserted').inc(
                shard_stats['reshards_inserted'])
            _obs.metrics.counter('opt.collective_bytes').inc(
                shard_stats.get('collective_bytes', 0))
        _obs.metrics.counter('opt.ops_removed').inc(stats['ops_removed'])
        _obs.metrics.counter('opt.ops_fused').inc(stats['ops_fused'])
        _obs.metrics.counter('opt.pass_ms').inc(stats['pass_ms'])
        _obs.metrics.counter('opt.runs').inc()
    while len(memo) >= _MEMO_MAX:
        memo.pop(next(iter(memo)))
    memo[key] = (opt, stats)
    return opt, stats
