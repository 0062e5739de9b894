"""Compile a fused_elementwise sub-program into generated Pallas kernels.

The plan builder walks the serialized sub-ops once (per canonical
sub-program signature — the same alpha-renamed key the emitter memo
uses, so every transformer layer's identical fused group shares one
plan) and partitions them into three step kinds:

``draw``
    RNG sub-ops' draws, computed OUTSIDE the kernel with exactly the
    replay path's key (impl seed attr / ctx stream fold-in, threaded in
    by the caller), so RNG parity is bitwise by construction.
``glue``
    Order-changing layout (a real transpose) and non-suffix broadcasts.
    These are zero-flop data-movement XLA ops; elementwise math commutes
    with them lane-for-lane, so hoisting them BETWEEN kernels preserves
    bitwise parity while keeping every compute op inside a kernel.
``dstep``
    A dedicated whole-op step.  For rule kinds ``row``/``attention``
    (softmax, layer_norm, flash_attention) the op's logical inputs are
    materialized, its ``rule.step`` runs one generated kernel (a row
    reduction or the flash-attention call), and its outputs re-enter the
    plan as materialized values — with the executor's per-sub-op AMP
    cast policy (core/executor._amp_sub_ins/_amp_sub_outs) applied
    around the step exactly as the replay path applies it.  Block
    shapes come from kernelgen/autotune.py (searched + persisted per
    signature; ``rule.tune`` declares the candidates).  A sub-op whose
    kind is not in ``pallas_kinds()`` takes the same slot with its
    registered impl as the step: plain XLA, the replay path's exact
    computation, so a plan can hold Pallas row kernels while the
    elementwise kind is off.
``kernel``
    A maximal run of elementwise/optimizer/rng-body sub-ops lowered into
    ONE ``pl.pallas_call``.  Every tensor is flattened to 1-D and tiled
    over a single grid axis (the base block size is autotuned per
    segment signature, static ``PT_KERNELGEN_BLOCK`` under
    ``PT_AUTOTUNE=0``):

    * values are grouped by flat element count; each group g gets block
      ``b_g = min(BLOCK, N_g)`` (lcm-lifted over any broadcast divisors)
      and ``tiles_g = ceil(N_g / b_g)``; the grid is ``max_g tiles_g``;
    * a group that exhausts its tiles early keeps a CLAMPED index map
      (``min(i, tiles_g - 1)``) — the fetch degenerates to a re-read of
      the last block and every store is guarded by
      ``pl.when(pid < tiles_g)``, so short groups neither read out of
      bounds nor double-apply updates even with donated (aliased) refs;
    * size-1 values ride as whole ``(1,)`` refs (stored once at pid 0);
      suffix-broadcast operands (the MLP bias-add shape) ride as whole
      ``(D,)`` refs tiled in-kernel, so the chain stays ONE kernel;
    * flat-order-preserving glue (reshape/squeeze/unsqueeze/flatten and
      unit-dim transposes) is a symbolic alias inside the kernel — zero
      data movement, zero flushes.

Optimizer sub-ops donate Param/Moment refs through
``input_output_aliases`` (rule-declared, single-reader checked): the
fused Adam update runs as ONE generated kernel updating its params,
moments and beta pows in place.

Differentiation: ``pallas_call`` has no general VJP, so each plan is a
``jax.custom_vjp`` whose backward replays the sub-program through the
registered kernels (ops/fused.py's ``_run_sub_op`` — the exact function
the forward is bitwise-equal to) with the drawn keys as residuals;
per-output stop_gradient therefore applies exactly as on the replay
path.

On the CPU backend, and only there, the generated calls run under
``interpret=True`` (ops/_pallas.py); there is no fallback between the
test and the kernel.
"""
import os

from .._pallas import interpret as _interpret

__all__ = ['KernelgenUnsupported', 'plan_for', 'plans', 'clear_plans',
           'rng_rule_types']


class KernelgenUnsupported(Exception):
    """A sub-op (or shape pattern) the rule table can't lower; carries
    the sub-op name for the raise and D016."""

    def __init__(self, sub_op, why):
        self.sub_op = sub_op
        self.why = why
        super(KernelgenUnsupported, self).__init__(
            "sub-op '%s': %s" % (sub_op, why))


_FULL_CAP = 8192      # max flat size for a whole-array broadcast ref
_BLOCK_CAP = 65536    # refuse lcm-lifted block sizes past this (VMEM)


def _block_base():
    return int(os.environ.get('PT_KERNELGEN_BLOCK', '1024'))


_RNG_TYPES = None


def rng_rule_types():
    global _RNG_TYPES
    if _RNG_TYPES is None:
        from .rules import KERNEL_RULES
        _RNG_TYPES = frozenset(
            n for n, r in KERNEL_RULES.items() if r.kind == 'rng')
    return _RNG_TYPES


def _size(shape):
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _lcm(a, b):
    x, y = a, b
    while y:
        x, y = y, x % y
    return a // x * b


def _bcast_y_shape(xs, ys, axis):
    """ops/math.py _bcast_y, on shapes only."""
    xs, ys = tuple(xs), tuple(ys)
    if xs == ys or len(ys) == 0:
        return ys
    ax = axis if axis >= 0 else len(xs) - len(ys)
    yshape = list(ys)
    while len(yshape) > 1 and yshape[-1] == 1 and ax + len(yshape) > len(xs):
        yshape = yshape[:-1]
    return tuple([1] * ax + yshape + [1] * (len(xs) - ax - len(yshape)))


def _flat_compatible(eff, out):
    """True when broadcasting eff -> out is pure leading-dim expansion,
    i.e. flat(broadcast(v)) == tile(flat(v)) — the only pattern a kernel
    can serve from a whole-array ref without a gather."""
    e = list(eff)
    while e and e[0] == 1:
        e.pop(0)
    if len(e) > len(out):
        return False
    return list(out[len(out) - len(e):]) == e


class _AbstractCtx(object):
    """eval_shape ctx: constant key (output shapes don't depend on it).
    No sub_ctx attr — _run_sub_op then uses the ctx for every sub-op."""
    amp = False
    mesh = None
    is_infer = False

    def rng(self, n=0):
        import jax
        return jax.random.key(0)


class _OneKeyCtx(object):
    """Replay ctx handing one fixed key: .rng() returns the key this rng
    sub-op drew with in the forward (impls with a seed attr ignore it,
    exactly as they did on the kernel path)."""
    amp = False
    mesh = None
    is_infer = False

    def __init__(self, key):
        self._key = key

    def rng(self, n=0):
        return self._key


def _abstract_replay(attrs, in_avals, amp):
    """Per-step {name: ShapeDtypeStruct} of every env write, via the
    REAL replay (ops/fused._run_sub_op) under jax.eval_shape — amp
    matching, _bcast_y, dtype promotion all come from the one true
    implementation instead of a transcription."""
    import jax
    from .. import fused as _fused
    sds = [jax.ShapeDtypeStruct(tuple(s), d) for s, d in in_avals]

    def run(*xs):
        env = dict(zip(attrs['arg_names'], list(xs)))
        ctx = _AbstractCtx()
        recs = []
        missing = object()
        for sub in attrs['sub_ops']:
            onames = [n for ns in sub['outputs'].values() for n in ns]
            before = {n: env.get(n, missing) for n in onames}
            _fused._run_sub_op(ctx, sub, env, amp)
            recs.append({n: env[n] for n in onames
                         if env.get(n, missing) is not before[n]})
        return recs

    return jax.eval_shape(run, *sds)


class _OpInfo(object):
    """Rule-body metadata: the logical shapes the flat block values
    lost, plus this op's in-kernel lane count."""

    def __init__(self, lanes, in_avals):
        self.lanes = lanes
        self._in = in_avals

    def in_shape(self, slot):
        return self._in[slot][0]

    def in_aval(self, slot):
        return self._in[slot]


class _AvalsView(object):
    def __init__(self, avals):
        self._a = avals or {}

    def in_aval(self, slot):
        return self._a[slot]

    def in_shape(self, slot):
        return self._a[slot][0]


class _Seg(object):
    """One open kernel segment under construction."""

    def __init__(self):
        self.ops = []          # (sub, rule, in_bind, out_bind, g, info,
                               #  draw_bind)
        self.entries = []      # kernel input refs: (mid, kind, size)
        self.entry_ix = {}     # (mid, kind) -> index
        self.entry_key = {}    # index -> (name, ver) | None
        self.entry_dt = {}     # index -> dtype str
        self.keys = {}         # value key -> root key (layout aliasing)
        self.key_aval = {}     # value key -> (shape, dtype str)
        self.groups = {}       # flat size -> set of bcast divisors

    def entry(self, mid, kind, size, key, dt):
        ek = (mid, kind)
        ix = self.entry_ix.get(ek)
        if ix is None:
            ix = len(self.entries)
            self.entry_ix[ek] = ix
            self.entries.append((mid, kind, size))
            self.entry_key[ix] = key
            self.entry_dt[ix] = dt
        return ix


class _Plan(object):
    """``fn(xs, keys)`` is the plan; ``ref(xs, keys)`` the replay it must
    equal; ``attrs`` / ``in_avals`` / ``amp`` say what it was built for
    (chip_smoke.py re-runs every plan against ``ref`` on the device)."""
    __slots__ = ('fn', 'ref', 'attrs', 'in_avals', 'amp', 'n_rng',
                 'n_kernels', 'n_glue', 'kernel_ops', 'groups',
                 'n_donated', 'n_dsteps', 'n_xla', 'tuned')


_PLANS = {}


def plans():
    """Every plan built so far in this process."""
    return list(_PLANS.values())


def clear_plans():
    _PLANS.clear()


def plan_for(attrs, in_avals, amp, allow_search=True):
    """Build-or-fetch the plan for one canonical fused signature.

    ``allow_search=False`` callers (the lint abstract interpreter, which
    reaches here under eval_shape) get a plan built on cached/default
    autotune choices — never a timed search."""
    from ...core.emit.emitter import _canon_attrs
    from . import autotune, pallas_kinds
    kinds = pallas_kinds()
    key = (_canon_attrs('fused_elementwise', attrs), tuple(in_avals),
           bool(amp), _interpret(), _block_base(), autotune.mode(),
           bool(allow_search), kinds)
    plan = _PLANS.get(key)
    if plan is None:
        plan = _build_plan(attrs, tuple(in_avals), bool(amp),
                           bool(allow_search), kinds)
        _PLANS[key] = plan
    return plan


def _blocks_for(base, groups):
    """Effective per-group block map for a candidate base block size
    (None when some lcm lift would exceed the VMEM cap)."""
    blocks = {}
    for g, ds in sorted(groups.items()):
        b = base
        for D in sorted(ds):
            b = _lcm(b, D)
            if b > _BLOCK_CAP:
                return None
        if g <= b:
            b = g              # g is a multiple of every D by compat
        blocks[g] = b
    return blocks


def _tuned_base(s, esc, amp, reads, final_keys, allow_search):
    """Autotuned base block size for one elementwise segment: candidate
    bases are deduped by the *effective* per-group block map, each is
    compiled + timed on synthesized inputs, the winner persists per
    segment signature (kernelgen/autotune.py).  Degenerate segments
    (scalar-only, one effective config, or giant interpret-mode groups)
    keep the static default with zero overhead."""
    from . import autotune
    static = _block_base()
    sizes = [g for g in s.groups if g > 1]
    if autotune.mode() == '0' or not sizes:
        return static
    if _interpret() and max(sizes) > autotune.interpret_size_cap():
        return static
    cands, seen = [], set()
    for b in dict.fromkeys((static, 256, 1024, 4096)):
        eff = _blocks_for(b, s.groups)
        if eff is None:
            continue
        ek = tuple(sorted(eff.items()))
        if ek in seen:
            continue
        seen.add(ek)
        cands.append({'base': b})
    if len(cands) <= 1:
        return static
    sig = ('ew',
           tuple(op[0]['type'] for op in s.ops),
           tuple((kind, size, s.entry_dt[ix])
                 for ix, (mid, kind, size) in enumerate(s.entries)),
           tuple((g, tuple(sorted(ds)))
                 for g, ds in sorted(s.groups.items())),
           tuple((_size(s.key_aval[k][0]), s.key_aval[k][1])
                 for k in esc),
           bool(amp), _interpret())

    def timer(cand):
        scratch = {'donated': 0}
        kspec = _compile_segment(s, esc, amp, reads, final_keys,
                                 scratch, cand['base'])

        def thunk():
            args = [autotune.synth_value((size,), s.entry_dt[ix])
                    for ix, (mid, kind, size)
                    in enumerate(kspec['entries'])]
            return kspec['call'](*args)

        return autotune.time_thunk(thunk)

    choice = autotune.choose('ew', sig, cands, timer, {'base': static},
                             allow_search)
    return int(choice['base'])


def _tune_step(stype, rule, sattrs, avals_d, allow_search):
    """Resolve one dedicated step's autotune choice (None = rule has no
    tuner / nothing viable: step uses its own defaults)."""
    from . import autotune
    if rule.tune is None or autotune.mode() == '0':
        return None
    interp = _interpret()
    spec = rule.tune(sattrs, _AvalsView(avals_d), interp)
    if not spec:
        return None

    def timer(cand):
        def thunk():
            return rule.step(spec['make_ins'](), sattrs,
                             _AvalsView(avals_d), cand, interp)
        return autotune.time_thunk(thunk)

    return autotune.choose(stype, spec['signature'], spec['candidates'],
                           timer, spec.get('default'), allow_search)


def _build_plan(attrs, in_avals, amp, allow_search=True, kinds=None):
    import jax
    import jax.numpy as jnp
    from ...core.registry import get_op
    from .rules import ALL_KINDS, KERNEL_RULES
    if kinds is None:
        kinds = ALL_KINDS

    sub_ops = attrs['sub_ops']
    arg_names = list(attrs['arg_names'])
    out_names = list(attrs['out_names'])
    rng_types = rng_rule_types()

    for sub in sub_ops:
        if sub['type'] not in KERNEL_RULES:
            raise KernelgenUnsupported(sub['type'],
                                       'no KERNEL_RULES entry')

    recs = _abstract_replay(attrs, in_avals, amp)

    # ---- usage pre-pass: versions, read counts, last consumers
    cur = {n: 0 for n in arg_names}
    reads, lastuse = {}, {}
    for i, sub in enumerate(sub_ops):
        for slot, names in sub['inputs'].items():
            for n in names:
                k = (n, cur.get(n, 0))
                reads[k] = reads.get(k, 0) + 1
                lastuse[k] = i
        for n in recs[i]:
            cur[n] = cur.get(n, 0) + 1
    final_keys = set()
    for n in out_names:
        k = (n, cur.get(n, 0))
        final_keys.add(k)
        reads[k] = reads.get(k, 0) + 1

    # ---- walk state
    cur = {n: 0 for n in arg_names}
    loc = {}                   # key -> ('mat', mid) | ('sym', seg)
    aval = {}                  # key -> (shape, dtype str)
    for i, n in enumerate(arg_names):
        loc[(n, 0)] = ('mat', i)
        aval[(n, 0)] = (tuple(in_avals[i][0]), str(in_avals[i][1]))
    mid_next = [len(arg_names)]
    steps = []
    seg = [None]
    stats = {'kernels': 0, 'kernel_ops': 0, 'glue': 0, 'donated': 0,
             'dsteps': 0, 'xla': 0}
    all_groups = []
    tuned = []

    def new_mid():
        mid_next[0] += 1
        return mid_next[0] - 1

    def key_of(n):
        return (n, cur.get(n, 0))

    def _flush(upto):
        s = seg[0]
        seg[0] = None
        if s is None or not s.ops:
            return
        esc = [k for k in s.keys
               if lastuse.get(k, -1) >= upto or k in final_keys]
        if not esc:
            return             # fully dead segment: drop it
        sbase = _tuned_base(s, esc, amp, reads, final_keys,
                            allow_search)
        tuned.append(sbase)
        kspec = _compile_segment(s, esc, amp, reads, final_keys, stats,
                                 sbase)
        for k in esc:
            mid = new_mid()
            loc[k] = ('mat', mid)
            kspec['out_mids'].append(mid)
        steps.append(('kernel', kspec))
        stats['kernels'] += 1
        stats['kernel_ops'] += len(s.ops)
        all_groups.append(sorted(s.groups))

    def _as_mat(k):
        where = loc[k]
        if where[0] != 'mat':
            raise KernelgenUnsupported(
                '?', 'internal: %r not materialized' % (k,))
        return where[1]

    base = _block_base()
    rng_si = 0
    for i, sub in enumerate(sub_ops):
        stype = sub['type']
        rule = KERNEL_RULES[stype]
        written = recs[i]
        this_si = None
        if stype in rng_types:
            this_si = rng_si
            rng_si += 1

        # ---------------------------------------------- layout glue
        if rule.kind == 'layout':
            ik = key_of(sub['inputs']['X'][0])
            out_name = sub['outputs']['Out'][0]
            if out_name not in written:
                continue
            v = written[out_name]
            o_shape, o_dt = tuple(v.shape), str(v.dtype)
            identity = True
            if stype == 'transpose':
                perm = [int(a) for a in sub['attrs']['axis']]
                dims = aval[ik][0]
                nz = [p for p in perm if dims[p] != 1]
                identity = nz == sorted(nz)
            ok = (out_name, cur.get(out_name, 0) + 1)
            cur[out_name] = ok[1]
            if identity and loc[ik][0] == 'sym':
                s = seg[0]
                s.keys[ok] = s.keys[ik]        # flat alias, zero cost
                s.key_aval[ok] = (o_shape, o_dt)
                loc[ok] = ('sym', s)
            else:
                if loc[ik][0] == 'sym':
                    _flush(i)
                mid_in = _as_mat(ik)
                mid = new_mid()
                if identity:
                    steps.append(('glue', mid,
                                  (lambda x, sh=o_shape:
                                   jnp.reshape(x, sh)), [mid_in]))
                else:
                    steps.append(('glue', mid,
                                  (lambda x, p=tuple(perm):
                                   jnp.transpose(x, p)), [mid_in]))
                stats['glue'] += 1
                loc[ok] = ('mat', mid)
            aval[ok] = (o_shape, o_dt)
            continue

        # ------------------------------ rng whole-op draws (no body)
        if rule.kind == 'rng' and rule.body is None:
            out_name = sub['outputs']['Out'][0]
            v = written[out_name]
            mid = new_mid()
            steps.append(('draw', mid, this_si, rule, sub['attrs'],
                          None))
            ok = (out_name, cur.get(out_name, 0) + 1)
            cur[out_name] = ok[1]
            loc[ok] = ('mat', mid)
            aval[ok] = (tuple(v.shape), str(v.dtype))
            continue

        # ------------- dedicated whole-op steps: a row/attention kernel,
        # or the registered impl as plain XLA when the op's kind is off
        # (compute and rng-body rules all lower into the 'ew' kernel)
        dedicated = rule.kind in ('row', 'attention')
        pallas = (rule.kind if dedicated else 'ew') in kinds
        if dedicated or not pallas:
            if any(loc[key_of(n)][0] == 'sym'
                   for names in sub['inputs'].values() for n in names):
                _flush(i)
            in_mids, in_avals_d = {}, {}
            for slot, names in sub['inputs'].items():
                in_mids[slot] = [_as_mat(key_of(n)) for n in names]
                if names:
                    in_avals_d[slot] = aval[key_of(names[0])]
            if pallas:
                tune = _tune_step(stype, rule, sub['attrs'], in_avals_d,
                                  allow_search)
                if tune is not None:
                    tuned.append(tune)

                def run(ins_, keys, rule=rule, sattrs=sub['attrs'],
                        av=_AvalsView(dict(in_avals_d)), tune=tune):
                    return rule.step(ins_, sattrs, av, tune, interp)
            else:
                def run(ins_, keys, impl=get_op(stype).impl,
                        sattrs=sub['attrs'], si=this_si):
                    return impl(_OneKeyCtx(None if si is None
                                           else keys[si]), ins_, sattrs)
            out_bind = {}
            for slot, names in sub['outputs'].items():
                binds = []
                for n in names:
                    if n not in written:
                        binds.append(None)
                        continue
                    v = written[n]
                    mid = new_mid()
                    ok = (n, cur.get(n, 0) + 1)
                    cur[n] = ok[1]
                    loc[ok] = ('mat', mid)
                    aval[ok] = (tuple(v.shape), str(v.dtype))
                    binds.append(mid)
                out_bind[slot] = binds
            steps.append(('dstep', sub, run, in_mids, out_bind))
            stats['dsteps' if pallas else 'xla'] += 1
            continue

        # --------------------------------------- in-kernel compute op
        out_sizes = {n: _size(v.shape) for n, v in written.items()}
        if not out_sizes:
            continue
        g = max(out_sizes.values())
        if g == 0:
            raise KernelgenUnsupported(stype, 'zero-size tensor')
        O = ()
        for n, v in written.items():
            if _size(v.shape) == g:
                O = tuple(v.shape)
                break
        for n, sz in out_sizes.items():
            if sz not in (g, 1):
                raise KernelgenUnsupported(
                    stype, 'output %s size %d vs group size %d'
                    % (n, sz, g))

        x_shape = None
        if sub['inputs'].get('X'):
            x_shape = aval[key_of(sub['inputs']['X'][0])][0]

        # classify operands first (size-based, loc-independent), so a
        # needed flush happens BEFORE any sym operand is resolved
        classified = []        # (slot, first, key, cls, eff, size, dt)
        for slot, names in sub['inputs'].items():
            if slot in rule.shape_only:
                continue
            for nidx, n in enumerate(names):
                k = key_of(n)
                s_in, dt_in = aval[k]
                size = _size(s_in)
                eff = s_in
                if rule.bcast_y and slot == 'Y' and x_shape is not None:
                    eff = _bcast_y_shape(x_shape, s_in,
                                         sub['attrs'].get('axis', -1))
                compat = _flat_compatible(eff, O)
                if size == g and compat and g > 1:
                    cls = 'direct'
                elif size == 1:
                    cls = 'scalar'
                elif compat and size <= _FULL_CAP and g > 1 \
                        and g % size == 0 \
                        and _lcm(base, size) <= _BLOCK_CAP:
                    cls = 'bcast'
                elif g == 1:
                    raise KernelgenUnsupported(
                        stype, 'tensor input into a scalar group')
                else:
                    cls = 'glue'
                classified.append((slot, nidx == 0, k, cls, eff, size,
                                   dt_in))
        if any(cls in ('bcast', 'glue') and loc[k][0] == 'sym'
               for _, _, k, cls, _, _, _ in classified):
            _flush(i)

        s = seg[0]
        if s is None:
            s = _Seg()
            seg[0] = s

        in_bind = {}
        in_avals_by_slot = {}
        for slot, first, k, cls, eff, size, dt_in in classified:
            if first:
                in_avals_by_slot[slot] = (aval[k][0], dt_in)
            where = loc[k]
            if cls in ('direct', 'scalar') and where[0] == 'sym':
                od = ('sym', s.keys[k])
            elif cls == 'direct':
                ix = s.entry(where[1], 'tile', size, k, dt_in)
                s.groups.setdefault(size, set())
                od = ('ref', ix, 'tile', 0)
            elif cls == 'scalar':
                ix = s.entry(where[1], 'scalar', 1, k, dt_in)
                od = ('ref', ix, 'scalar', 0)
            elif cls == 'bcast':
                ix = s.entry(where[1], 'bcast', size, k, dt_in)
                s.groups.setdefault(g, set()).add(size)
                od = ('ref', ix, 'bcast', size)
            else:              # glue: materialize the broadcast via XLA
                nm = new_mid()
                steps.append(('glue', nm,
                              (lambda x, es=tuple(eff), Os=O:
                               jnp.broadcast_to(jnp.reshape(x, es),
                                                Os)), [_as_mat(k)]))
                stats['glue'] += 1
                ix = s.entry(nm, 'tile', g, None, dt_in)
                s.groups.setdefault(g, set())
                od = ('ref', ix, 'tile', 0)
            in_bind.setdefault(slot, []).append(od)

        # dropout's mask rides in as one more tiled ref
        draw_bind = None
        if rule.kind == 'rng':
            xa = aval[key_of(sub['inputs']['X'][0])]
            if not sub['attrs'].get('is_test', False):
                mid = new_mid()
                steps.append(('draw', mid, this_si, rule, sub['attrs'],
                              {'X': (tuple(xa[0]), str(xa[1]))}))
                dsize = _size(xa[0])
                if dsize > 1:
                    ix = s.entry(mid, 'tile', dsize, None, str(xa[1]))
                    s.groups.setdefault(dsize, set())
                    draw_bind = ('ref', ix, 'tile', 0)
                else:
                    ix = s.entry(mid, 'scalar', 1, None, str(xa[1]))
                    draw_bind = ('ref', ix, 'scalar', 0)
        if g > 1:
            s.groups.setdefault(g, set())

        out_bind = {}
        for slot, names in sub['outputs'].items():
            binds = []
            for n in names:
                if n not in written:
                    binds.append(None)
                    continue
                v = written[n]
                ok = (n, cur.get(n, 0) + 1)
                cur[n] = ok[1]
                s.keys[ok] = ok
                s.key_aval[ok] = (tuple(v.shape), str(v.dtype))
                loc[ok] = ('sym', s)
                aval[ok] = s.key_aval[ok]
                binds.append(ok)
            out_bind[slot] = (names, binds)

        info = _OpInfo(1, in_avals_by_slot)
        s.ops.append((sub, rule, in_bind, out_bind, g, info, draw_bind))

    _flush(len(sub_ops))

    finals = []
    for n in out_names:
        where = loc[(n, cur.get(n, 0))]
        if where[0] != 'mat':
            raise KernelgenUnsupported(
                '?', 'internal: output %s not materialized' % n)
        finals.append(where[1])

    n_args = len(arg_names)
    interp = _interpret()

    def core(xs, keys):
        from ...core import executor as _ex
        mats = {}
        for ix in range(n_args):
            mats[ix] = xs[ix]
        for st in steps:
            kind = st[0]
            if kind == 'draw':
                _, mid, si, rule, sattrs, davals = st
                mats[mid] = rule.draw(keys[si], _AvalsView(davals),
                                      sattrs)
            elif kind == 'glue':
                _, mid, fn, ins_ = st
                mats[mid] = fn(*[mats[m] for m in ins_])
            elif kind == 'dstep':
                _, sub, run, in_mids, out_bind = st
                ins_vals = {}
                for slot, mids_ in in_mids.items():
                    vals = [mats[m] for m in mids_]
                    ins_vals[slot] = vals \
                        if sub['input_is_list'].get(slot) else vals[0]
                if amp:
                    ins_vals = _ex._amp_sub_ins(sub['type'], ins_vals,
                                                amp)
                outs = run(ins_vals, keys) or {}
                if amp:
                    outs = _ex._amp_sub_outs(sub['type'], sub['attrs'],
                                             outs, amp)
                for slot, binds in out_bind.items():
                    if slot not in outs:
                        continue
                    vals = outs[slot]
                    vals = vals if isinstance(vals, (list, tuple)) \
                        else [vals]
                    for mid, v in zip(binds, vals):
                        if mid is not None and v is not None:
                            mats[mid] = v
            else:
                _run_kernel(st[1], mats)
        return [mats[m] for m in finals]

    def ref_replay(xs, keys):
        from .. import fused as _fused
        env = dict(zip(arg_names, list(xs)))
        si = 0
        for sub in sub_ops:
            if sub['type'] in rng_types:
                ctx = _OneKeyCtx(keys[si])
                si += 1
            else:
                ctx = _OneKeyCtx(None)
            _fused._run_sub_op(ctx, sub, env, amp)
        return [env[n] for n in out_names]

    fn = jax.custom_vjp(core)

    def _fwd(xs, keys):
        return core(xs, keys), (xs, keys)

    def _bwd(res, cts):
        from ...core.executor import _zero_cotangent
        xs, keys = res
        _, vjp = jax.vjp(lambda xs_: ref_replay(xs_, keys), xs)
        (gxs,) = vjp(list(cts))
        return gxs, tuple(_zero_cotangent(k) for k in keys)

    fn.defvjp(_fwd, _bwd)

    plan = _Plan()
    plan.fn = fn
    plan.ref = ref_replay
    plan.attrs, plan.in_avals, plan.amp = attrs, in_avals, amp
    plan.n_rng = rng_si
    plan.n_kernels = stats['kernels']
    plan.n_glue = stats['glue']
    plan.kernel_ops = stats['kernel_ops']
    plan.n_donated = stats['donated']
    plan.groups = all_groups
    plan.n_dsteps = stats['dsteps']
    plan.n_xla = stats['xla']
    plan.tuned = tuned
    return plan


# ---------------------------------------------------- pallas emission
def _compile_segment(s, esc, amp, reads, final_keys, stats, base=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    blocks = _blocks_for(_block_base() if base is None else base,
                         s.groups)
    if blocks is None:
        raise KernelgenUnsupported(
            'broadcast', 'block lcm exceeds cap %d' % _BLOCK_CAP)
    tiles = {g: -(-g // b) for g, b in blocks.items()}
    grid = max(tiles.values()) if tiles else 1

    outs_meta = []             # (key, n, group-or-None, shape, dt)
    for k in esc:
        shape, dt = s.key_aval[k]
        n = _size(shape)
        outs_meta.append((k, n, n if n > 1 else None, shape, dt))

    def _tile_spec(size):
        t = tiles[size]
        return pl.BlockSpec((blocks[size],),
                            lambda i, t=t: (jnp.minimum(i, t - 1),))

    def _full_spec(size):
        return pl.BlockSpec((size,), lambda i: (0,))

    in_specs = []
    for (mid, kind, size) in s.entries:
        in_specs.append(_tile_spec(size) if kind == 'tile'
                        else _full_spec(size))
    out_specs, out_shape = [], []
    for (k, n, g, shape, dt) in outs_meta:
        out_specs.append(_tile_spec(g) if g is not None
                         else _full_spec(max(n, 1)))
        out_shape.append(jax.ShapeDtypeStruct((max(n, 1),), dt))

    # donation: rule-declared aliases; the donated input must be a plain
    # program value with no other reader anywhere, spec-identical to the
    # output, and (for pid-0-stored scalars) not re-read across steps
    aliases = {}
    esc_ix = {k: j for j, (k, _, _, _, _) in enumerate(outs_meta)}
    for (sub, rule, in_bind, out_bind, g, info, draw_bind) in s.ops:
        for oslot, islot in rule.aliases.items():
            names, binds = out_bind.get(oslot, ((), ()))
            if not binds or binds[0] is None or binds[0] not in esc_ix:
                continue
            iops = in_bind.get(islot)
            if not iops or iops[0][0] != 'ref':
                continue
            _, ix, kind, _D = iops[0]
            if kind not in ('tile', 'scalar') or ix in aliases:
                continue
            if kind == 'scalar' and grid > 1:
                continue       # written once at pid 0, read every step
            src = s.entry_key.get(ix)
            if src is None or reads.get(src, 0) != 1 \
                    or src in final_keys:
                continue
            oj = esc_ix[binds[0]]
            _k, on, _og, _shape, odt = outs_meta[oj]
            _mid, _kind, esize = s.entries[ix]
            if esize != max(on, 1) or s.entry_dt.get(ix) != odt:
                continue
            aliases[ix] = oj
            stats['donated'] += 1

    ops_meta = list(s.ops)
    n_in = len(s.entries)
    root_of = dict(s.keys)

    def body(*refs):
        from ...core.executor import _amp_match_ins
        from ...core.registry import get_op
        from .rules import NO_RNG_CTX
        pid = pl.program_id(0)
        loads = [r[...] for r in refs[:n_in]]
        symv = {}

        def val_of(od, g):
            if od[0] == 'sym':
                return symv[od[1]]
            _, ix, kind, D = od
            if kind == 'tile':
                return loads[ix]
            if kind == 'scalar':
                return loads[ix].reshape(())
            return jnp.tile(loads[ix], blocks[g] // D)

        for (sub, rule, in_bind, out_bind, g, info, draw_bind) \
                in ops_meta:
            ins_vals = {}
            for slot, ops_ in in_bind.items():
                vals = [val_of(od, g) for od in ops_]
                ins_vals[slot] = vals \
                    if sub['input_is_list'].get(slot) else vals[0]
            if amp:
                ins_vals = _amp_match_ins(sub['type'], ins_vals)
            info2 = _OpInfo(blocks[g] if g > 1 else 1, info._in)
            if rule.kind == 'rng':
                draw_val = val_of(draw_bind, g) \
                    if draw_bind is not None else None
                outs = rule.body(ins_vals, sub['attrs'], info2,
                                 draw_val)
            elif rule.body is not None:
                outs = rule.body(ins_vals, sub['attrs'], info2)
            else:
                outs = get_op(sub['type']).impl(
                    NO_RNG_CTX, ins_vals, sub['attrs']) or {}
            for slot, (names, binds) in out_bind.items():
                if slot not in outs:
                    continue
                vals = outs[slot]
                vals = vals if isinstance(vals, (list, tuple)) \
                    else [vals]
                for bk, v in zip(binds, vals):
                    if bk is not None and v is not None:
                        symv[bk] = v

        def _store(ref, v):
            ref[...] = v

        for j, (k, n, g, shape, dt) in enumerate(outs_meta):
            v = symv[root_of[k]]
            ref = refs[n_in + j]
            if g is not None:
                pl.when(pid < tiles[g])(
                    lambda ref=ref, v=v, b=blocks[g]:
                    _store(ref, v.reshape(b)))
            else:
                pl.when(pid == 0)(
                    lambda ref=ref, v=v, n=max(n, 1):
                    _store(ref, jnp.asarray(v).reshape(n)))

    call = pl.pallas_call(
        body,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=_interpret(),
    )
    return {
        'call': call,
        'entries': list(s.entries),
        'outs_meta': outs_meta,
        'out_mids': [],
        'grid': grid,
        'blocks': dict(blocks),
        'donated': dict(aliases),
    }


def _run_kernel(kspec, mats):
    import jax.numpy as jnp
    args = [jnp.reshape(mats[mid], (-1,))
            for (mid, kind, size) in kspec['entries']]
    outs = kspec['call'](*args)
    if not isinstance(outs, (list, tuple)):
        outs = [outs]
    for (k, n, g, shape, dt), mid, o in zip(
            kspec['outs_meta'], kspec['out_mids'], outs):
        mats[mid] = jnp.reshape(o, shape)
