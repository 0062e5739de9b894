"""KERNEL_RULES: the per-op lowering table for the Pallas codegen tier.

Mirrors the ``register_emit`` pattern (core/emit/rules.py) one level
down: where an emit rule replaces a kernel's *tracing*, a KERNEL_RULE
describes how a fused sub-op lowers *inside* one generated Pallas kernel
body, operating on flat 1-D block values instead of logical arrays.

Three rule kinds:

``ew``
    Elementwise compute (activations, binaries, comparisons, optimizer
    updates, fills).  The default body is the op's own registered kernel
    impl applied to the flat block values — elementwise jnp expressions
    are shape-agnostic lane-for-lane, so reusing the impl verbatim makes
    bitwise parity with the replay path *by construction* rather than by
    transcription.  Only ops whose impl reads a logical shape
    (``label_smooth``'s class count, the ``fill_*`` lane counts) carry a
    custom body.

``layout``
    Zero-flop glue (reshape/squeeze/unsqueeze/flatten/transpose/assign-
    like).  No body: the plan builder either treats them as flat-order
    identities inside the kernel or hoists order-changing transposes out
    as XLA glue between kernel segments (see builder docstring).

``rng``
    Sub-ops that draw from ctx.rng.  The *draw* happens outside the
    kernel (``draw(key, ins_avals, attrs)``) with exactly the impl's key
    discipline, and the drawn array rides into the kernel as one more
    tiled ref — bitwise identical to the replay path because the draw IS
    the replay path's draw; only the surrounding arithmetic moves into
    the kernel.

``row`` / ``attention``
    Dedicated whole-op kernels for the cross-element reductions the flat
    1-D tier can't express: single-pass row reductions (softmax,
    layer_norm) and online-softmax tiled attention (flash_attention).
    Instead of joining an elementwise segment, the op owns one
    ``step(ins, attrs, info, tune, interpret)`` call that takes the
    op's logical (un-flattened) inputs and returns its outputs — the
    builder runs it between kernel segments like glue, but it IS a
    generated Pallas kernel inside.  An optional
    ``tune(attrs, avals, interpret)`` hook returns the autotune spec
    (signature / candidates / default / make_ins) that
    kernelgen/autotune.py searches and persists; its winner arrives back
    as ``step``'s ``tune`` argument.  Row bodies replicate the
    registered impls' exact f32 jnp sequences so the kernel stays
    bitwise vs the replay on every backend; flash_attention reuses
    ops/attention.py's own routing (which composes below its Pallas
    thresholds — bitwise on CPU smoke shapes, fused-Pallas on TPU).

Optimizer rules additionally declare ``aliases`` (output slot -> input
slot) so the builder can donate Param/Moment refs through
``input_output_aliases`` — the fused-Adam in-place update.
"""
import os

import jax
import jax.numpy as jnp
from jax import lax

from ...core.dtypes import jax_dtype
from ...core.registry import get_op

__all__ = ['KERNEL_RULES', 'KRule', 'rule_names', 'ALL_KINDS']

# The kinds of generated kernel a plan can hold: 'ew' is the flat 1-D
# elementwise / optimizer / rng-body kernel, 'row' the softmax /
# layer_norm row kernels, 'attention' the flash-attention dispatch.
ALL_KINDS = ('attention', 'ew', 'row')


class KRule(object):
    __slots__ = ('kind', 'body', 'draw', 'aliases', 'bcast_y',
                 'shape_only', 'step', 'tune')

    def __init__(self, kind='ew', body=None, draw=None, aliases=None,
                 bcast_y=False, shape_only=(), step=None, tune=None):
        self.kind = kind              # 'ew'|'layout'|'rng'|'row'|
                                      # 'attention'
        self.body = body              # None => op impl on flat blocks
        self.draw = draw              # rng only: (key, avals, attrs) ->
        self.aliases = aliases or {}  # out slot -> in slot (donation)
        self.bcast_y = bcast_y        # binary op with _bcast_y(Y, axis)
        self.shape_only = shape_only  # slots read for shape, not data
        self.step = step              # row/attention: whole-op kernel
        self.tune = tune              # row/attention: autotune spec


KERNEL_RULES = {}


def rule_names():
    return tuple(sorted(KERNEL_RULES))


def _r(name, **kw):
    KERNEL_RULES[name] = KRule(**kw)


class _NoRngCtx(object):
    """ctx handed to passthrough impl bodies inside a kernel: any rng
    draw at this point is a rule-table bug (rng ops must be kind='rng'
    so their draw happens outside the kernel)."""
    amp = False
    mesh = None
    is_infer = False

    def rng(self, n=0):
        raise RuntimeError('KERNEL_RULES bug: in-kernel ctx.rng draw — '
                           'register the op as an rng rule')

    def sub_ctx(self, sub):
        return self


NO_RNG_CTX = _NoRngCtx()


class _FixedKeyCtx(object):
    """ctx for out-of-kernel rng draws: .rng() returns the stream key the
    caller derived (OpCtx.sub_ctx fold-in on the kernel path, EmitCtx
    stream fold-in on the emit path) — same discipline as the replay."""
    amp = False
    mesh = None
    is_infer = False

    def __init__(self, key):
        self._key = key

    def rng(self, n=0):
        return self._key


# --------------------------------------------------- elementwise compute
# Default bodies (impl passthrough).  _bcast_y binaries are flagged so the
# builder can align Y through the same axis/reshape semantics the impl
# would apply before the values reach the kernel.
for _name in ('elementwise_add', 'elementwise_sub', 'elementwise_mul',
              'elementwise_div', 'elementwise_pow', 'elementwise_max',
              'elementwise_min', 'elementwise_mod',
              'elementwise_floordiv', 'equal', 'not_equal', 'less_than',
              'less_equal', 'greater_than', 'greater_equal'):
    _r(_name, bcast_y=True)

for _name in ('scale', 'cast', 'clip', 'relu', 'relu6', 'sigmoid',
              'tanh', 'exp', 'log', 'sqrt', 'rsqrt', 'abs', 'square',
              'sign', 'floor', 'ceil', 'round', 'reciprocal', 'pow',
              'leaky_relu', 'elu', 'selu', 'softplus', 'softsign',
              'brelu', 'hard_sigmoid', 'swish', 'stanh', 'logsigmoid',
              'soft_relu', 'hard_shrink', 'softshrink', 'tanh_shrink',
              'thresholded_relu', 'erf', 'sin', 'cos', 'increment',
              'logical_and', 'logical_or', 'logical_not', 'logical_xor',
              'assign', 'fill_zeros_like'):
    _r(_name)


def _label_smooth_body(ins, attrs, info):
    # ops/tensor.py label_smooth, with the class count taken from the
    # LOGICAL input shape (the flat block lost it)
    x = ins['X']
    eps = attrs.get('epsilon', 0.0)
    if 'PriorDist' in ins:
        return {'Out': (1 - eps) * x + eps * ins['PriorDist']}
    return {'Out': (1 - eps) * x + eps / info.in_shape('X')[-1]}


_r('label_smooth', body=_label_smooth_body)


def _fill_constant_body(ins, attrs, info):
    # ops/tensor.py fill_constant over this value's in-kernel lane count
    from ..tensor import _fill_value
    dtype = jax_dtype(attrs.get('dtype', 'float32'))
    return {'Out': jnp.full((info.lanes,),
                            _fill_value(attrs['value'], dtype),
                            dtype=dtype)}


_r('fill_constant', body=_fill_constant_body)


def _fill_bsl_body(ins, attrs, info):
    from ..tensor import _fill_value
    dtype = jax_dtype(attrs.get('dtype', 'float32'))
    return {'Out': jnp.full((info.lanes,),
                            _fill_value(attrs['value'], dtype),
                            dtype=dtype)}


_r('fill_constant_batch_size_like', body=_fill_bsl_body,
   shape_only=('Input',))

# ------------------------------------------------------------ layout glue
for _name in ('reshape', 'squeeze', 'unsqueeze', 'flatten', 'transpose'):
    _r(_name, kind='layout')


# ------------------------------------------------------------- rng rules
def _dropout_draw(key, avals, attrs):
    # exactly ops/nn.py dropout's mask derivation (keep.astype(x.dtype))
    if attrs.get('is_test', False):
        return None                      # no draw: pure ew on this path
    p = attrs.get('dropout_prob', 0.5)
    shape, dtype = avals.in_aval('X')
    keep = jax.random.bernoulli(key, 1.0 - p, shape)
    return keep.astype(dtype)


def _dropout_body(ins, attrs, info, draw):
    x = ins['X']
    p = attrs.get('dropout_prob', 0.5)
    impl = attrs.get('dropout_implementation', 'downgrade_in_infer')
    if draw is None:                     # is_test: impl passthrough
        out = get_op('dropout').impl(NO_RNG_CTX, ins, attrs)
        return out
    mask = draw
    out = x * mask
    if impl == 'upscale_in_train' and p < 1.0:
        out = out / (1.0 - p)
    return {'Out': out, 'Mask': mask}


_r('dropout', kind='rng', draw=_dropout_draw, body=_dropout_body)


def _impl_draw(name):
    # whole-op draw: the generator IS the op; in-kernel body is identity
    def draw(key, avals, attrs):
        return get_op(name).impl(_FixedKeyCtx(key), {}, attrs)['Out']
    return draw


for _name in ('uniform_random', 'gaussian_random',
              'truncated_gaussian_random'):
    _r(_name, kind='rng', draw=_impl_draw(_name), body=None)

# ----------------------------------- dedicated row-reduction kernels
# softmax / layer_norm lower to single-pass row kernels: the logical
# array reshapes to (rows, cols), the grid tiles rows, and each kernel
# invocation reduces its rows' trailing axis in one VMEM-resident pass.
# The bodies replicate the registered impls' exact f32 jnp sequences
# (ops/nn.py) so the kernel is bitwise vs the replay — rows are
# independent, so partial trailing blocks are safe (Pallas masks the
# out-of-range stores).

_ROW_BLOCK_DEFAULT = 128
_ROW_BLOCK_CANDS = (8, 32, 128, 512)


def _row_view(shape, begin):
    """(rows, cols) of reducing a logical shape's trailing dims from
    ``begin``; both at least 1."""
    rows = cols = 1
    for d in shape[:begin]:
        rows *= int(d)
    for d in shape[begin:]:
        cols *= int(d)
    return max(rows, 1), max(cols, 1)


def _row_candidates(rows):
    cands, seen = [], set()
    for c in _ROW_BLOCK_CANDS:
        eff = min(c, rows)
        if eff in seen:
            continue
        seen.add(eff)
        cands.append({'block_rows': eff})
    return cands


def _row_tune_spec(stype, rows, cols, dt, extra_sig, make_ins,
                   interpret):
    from . import autotune
    if interpret and rows * cols > autotune.interpret_size_cap():
        return None
    return {
        'signature': (stype, rows, cols, dt, extra_sig, bool(interpret)),
        'candidates': _row_candidates(rows),
        'default': {'block_rows': min(_ROW_BLOCK_DEFAULT, rows)},
        'make_ins': make_ins,
    }


def _row_block(tune, rows):
    br = (tune or {}).get('block_rows', _ROW_BLOCK_DEFAULT)
    return max(min(int(br), rows), 1)


def _softmax_axis(attrs, ndim):
    ax = attrs.get('axis', -1)
    return ax + ndim if ax < 0 else ax


def _softmax_step(ins, attrs, info, tune, interpret):
    from jax.experimental import pallas as pl
    x = ins['X']
    if _softmax_axis(attrs, x.ndim) != x.ndim - 1:
        from .builder import KernelgenUnsupported
        raise KernelgenUnsupported(
            'softmax', 'axis %r is not the trailing dim (the row kernel '
            'reduces the last axis)' % (attrs.get('axis', -1),))
    rows, cols = _row_view(x.shape, x.ndim - 1)
    br = _row_block(tune, rows)

    def kernel(x_ref, o_ref):
        # jax.nn.softmax's forward sequence on f32 (ops/nn.py casts in):
        # max-subtract, exp, sum-normalize — per row
        xf = x_ref[...].astype(jnp.float32)
        m = jnp.max(xf, axis=-1, initial=-jnp.inf, keepdims=True)
        u = jnp.exp(xf - m)
        o_ref[...] = (u / jnp.sum(u, axis=-1, keepdims=True)).astype(
            o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec((br, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        interpret=interpret,
    )(x.reshape(rows, cols))
    return {'Out': out.reshape(x.shape)}


def _softmax_tune(attrs, avals, interpret):
    from . import autotune
    shape, dt = avals.in_aval('X')
    if _softmax_axis(attrs, len(shape)) != len(shape) - 1:
        return None                  # step will raise; nothing to tune
    rows, cols = _row_view(shape, len(shape) - 1)

    def make_ins():
        return {'X': autotune.synth_value(shape, dt)}

    return _row_tune_spec('softmax', rows, cols, str(dt), (), make_ins,
                          interpret)


def _layer_norm_step(ins, attrs, info, tune, interpret):
    from jax.experimental import pallas as pl
    x = ins['X']
    begin = attrs.get('begin_norm_axis', 1)
    eps = attrs.get('epsilon', 1e-5)
    rows, cols = _row_view(x.shape, begin)
    scale, bias = ins.get('Scale'), ins.get('Bias')
    two_pass = os.environ.get('PT_TWO_PASS_NORM', '0') == '1'
    br = _row_block(tune, rows)

    def kernel(*refs):
        it = iter(refs)
        x_ref = next(it)
        s_ref = next(it) if scale is not None else None
        b_ref = next(it) if bias is not None else None
        y_ref, m_ref, v_ref = next(it), next(it), next(it)
        # ops/nn.py layer_norm's exact f32 statistics, per row
        xf = x_ref[...].astype(jnp.float32)
        if two_pass:
            m = jnp.mean(xf, axis=-1, keepdims=True)
            v = jnp.mean(jnp.square(xf - m), axis=-1, keepdims=True)
            y = (xf - m) * lax.rsqrt(v + eps)
        else:
            c = lax.stop_gradient(xf[:, :1])
            d = xf - c
            md = jnp.mean(d, axis=-1, keepdims=True)
            v = jnp.maximum(
                jnp.mean(jnp.square(d), axis=-1, keepdims=True)
                - jnp.square(md), 0.0)
            m = md + c
            y = (d - md) * lax.rsqrt(v + eps)
        if s_ref is not None:
            y = y * s_ref[...]
        if b_ref is not None:
            y = y + b_ref[...]
        y_ref[...] = y.astype(y_ref.dtype)
        m_ref[...] = m
        v_ref[...] = v

    # every ref is 2-D: Mosaic lays a rank-1 f32 array out in 1024-lane
    # tiles, so (br,) statistic blocks and in-kernel (cols,)->(1, cols)
    # reshapes do not compile; (br, 1) / (1, cols) blocks do
    in_specs = [pl.BlockSpec((br, cols), lambda i: (i, 0))]
    args = [x.reshape(rows, cols)]
    for p in (scale, bias):
        if p is not None:
            in_specs.append(pl.BlockSpec((1, cols), lambda i: (0, 0)))
            args.append(p.reshape(1, cols))
    y2, m1, v1 = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(rows, br),),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((br, cols), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, cols), x.dtype),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=interpret,
    )(*args)
    lead = tuple(x.shape[:begin])
    return {'Y': y2.reshape(x.shape), 'Mean': m1.reshape(lead),
            'Variance': v1.reshape(lead)}


def _layer_norm_tune(attrs, avals, interpret):
    from . import autotune
    shape, dt = avals.in_aval('X')
    begin = attrs.get('begin_norm_axis', 1)
    rows, cols = _row_view(shape, begin)
    slots = [s for s in ('X', 'Scale', 'Bias')
             if s == 'X' or _has_slot(avals, s)]

    def make_ins():
        return {s: autotune.synth_value(*avals.in_aval(s))
                for s in slots}

    return _row_tune_spec('layer_norm', rows, cols, str(dt),
                          (begin, len(slots)), make_ins, interpret)


def _has_slot(avals, slot):
    try:
        avals.in_aval(slot)
        return True
    except KeyError:
        return False


_r('softmax', kind='row', step=_softmax_step, tune=_softmax_tune)
_r('layer_norm', kind='row', step=_layer_norm_step,
   tune=_layer_norm_tune)


# ------------------------------------------ flash-attention dispatch
def _flash_step(ins, attrs, info, tune, interpret):
    # ops/attention.py owns the online-softmax Pallas kernel, its causal
    # + k_len masking, and its composed fallback below the Pallas
    # thresholds; the rule forwards the tuned block sizes and nothing
    # else, so fused and unfused launches share one routing (and are
    # bitwise on the composed route).
    from .. import attention as _att
    q, k, v = ins['Q'], ins['K'], ins['V']
    k_len = ins.get('KLength')
    if k_len is not None and getattr(k_len, 'ndim', 0) > 1:
        k_len = k_len.reshape(-1)
    kw = {}
    if tune:
        kw = {'block_q': int(tune['block_q']),
              'block_k': int(tune['block_k'])}
    return {'Out': _att.flash_attention(
        q, k, v, causal=attrs.get('causal', False),
        scale=attrs.get('scale'), k_len=k_len, **kw)}


def _flash_tune(attrs, avals, interpret):
    from . import autotune
    from .. import attention as _att
    if interpret:
        # no TPU: flash_attention composes (or interprets) — emulated
        # timings say nothing about Mosaic block behavior
        return None
    qs, qdt = avals.in_aval('Q')
    ks, _ = avals.in_aval('K')
    if len(qs) != 4 or len(ks) != 4:
        return None
    Tq, D = int(qs[2]), int(qs[3])
    Tk = int(ks[2])
    if D % 8 or Tk < _att._FWD_PALLAS_MIN_T:
        return None                  # composed route: blocks unused
    bqs = [b for b in (128, 256, 512) if Tq % b == 0]
    bks = [b for b in (128, 256, 512) if Tk % b == 0]
    cands = [{'block_q': bq, 'block_k': bk}
             for bq in bqs for bk in bks]
    if not cands:
        return None

    def make_ins():
        out = {s: autotune.synth_value(*avals.in_aval(s))
               for s in ('Q', 'K', 'V')}
        if _has_slot(avals, 'KLength'):
            import numpy as np
            ls, ldt = avals.in_aval('KLength')
            out['KLength'] = jnp.asarray(np.full(ls, Tk, ldt))
        return out

    return {
        'signature': ('flash_attention', tuple(qs), tuple(ks), str(qdt),
                      bool(attrs.get('causal', False)),
                      attrs.get('scale'), _has_slot(avals, 'KLength')),
        'candidates': cands,
        'default': None,             # impl's own 128/128 defaults
        'make_ins': make_ins,
    }


_r('flash_attention', kind='attention', step=_flash_step,
   tune=_flash_tune)

# ------------------------------------------------- optimizer updates
# impl passthrough + donation aliases (the fused-Adam in-place story)
_r('sgd', aliases={'ParamOut': 'Param'})
_r('momentum', aliases={'ParamOut': 'Param', 'VelocityOut': 'Velocity'})
_r('adam', aliases={'ParamOut': 'Param', 'Moment1Out': 'Moment1',
                    'Moment2Out': 'Moment2', 'Beta1PowOut': 'Beta1Pow',
                    'Beta2PowOut': 'Beta2Pow'})
_r('adamax', aliases={'ParamOut': 'Param', 'MomentOut': 'Moment',
                      'InfNormOut': 'InfNorm'})
_r('adagrad', aliases={'ParamOut': 'Param', 'MomentOut': 'Moment'})
_r('decayed_adagrad', aliases={'ParamOut': 'Param',
                               'MomentOut': 'Moment'})
_r('adadelta', aliases={'ParamOut': 'Param',
                        'AvgSquaredGradOut': 'AvgSquaredGrad',
                        'AvgSquaredUpdateOut': 'AvgSquaredUpdate'})
_r('rmsprop', aliases={'ParamOut': 'Param', 'MeanSquareOut': 'MeanSquare',
                       'MomentOut': 'Moment', 'MeanGradOut': 'MeanGrad'})
_r('ftrl', aliases={'ParamOut': 'Param',
                    'SquaredAccumOut': 'SquaredAccumulator',
                    'LinearAccumOut': 'LinearAccumulator'})
