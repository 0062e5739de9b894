"""Shape/layout/indexing ops.

Parity: reference operators: reshape_op, transpose_op, concat_op, split_op,
stack_op, gather_op, scatter_op, slice_op, expand_op, pad_op, one_hot_op,
lookup_table_op, topk_op, argsort/arg_min_max, fill_constant*, assign, etc.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register
from ..core.dtypes import convert_dtype, jax_dtype


@register('reshape')
def reshape(ctx, ins, attrs):
    x = ins['X']
    shape = list(attrs['shape'])
    # fluid semantics: 0 -> copy input dim, -1 -> infer
    out_shape = []
    for i, d in enumerate(shape):
        if d == 0:
            out_shape.append(x.shape[i])
        else:
            out_shape.append(int(d))
    return {'Out': x.reshape(out_shape), 'XShape': None}


@register('squeeze')
def squeeze(ctx, ins, attrs):
    x = ins['X']
    axes = attrs.get('axes', [])
    if not axes:
        return {'Out': jnp.squeeze(x)}
    axes = tuple(a % x.ndim for a in axes)
    return {'Out': jnp.squeeze(x, axis=axes)}


@register('unsqueeze')
def unsqueeze(ctx, ins, attrs):
    x = ins['X']
    for a in sorted(attrs['axes']):
        x = jnp.expand_dims(x, a)
    return {'Out': x}


@register('transpose')
def transpose(ctx, ins, attrs):
    return {'Out': jnp.transpose(ins['X'], attrs['axis']), 'XShape': None}


@register('flatten')
def flatten(ctx, ins, attrs):
    x = ins['X']
    ax = attrs.get('axis', 1)
    lead = int(np.prod(x.shape[:ax])) if ax > 0 else 1
    return {'Out': x.reshape(lead, -1)}


@register('concat')
def concat(ctx, ins, attrs):
    xs = ins['X']
    xs = xs if isinstance(xs, (list, tuple)) else [xs]
    return {'Out': jnp.concatenate(xs, axis=attrs.get('axis', 0))}


@register('split')
def split(ctx, ins, attrs):
    x = ins['X']
    axis = attrs.get('axis', 0)
    sections = attrs.get('sections', [])
    num = attrs.get('num', 0)
    if sections:
        idx = np.cumsum(sections)[:-1].tolist()
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, num, axis=axis)
    return {'Out': list(outs)}


@register('stack')
def stack(ctx, ins, attrs):
    xs = ins['X']
    xs = xs if isinstance(xs, (list, tuple)) else [xs]
    return {'Y': jnp.stack(xs, axis=attrs.get('axis', 0))}


@register('unstack')
def unstack(ctx, ins, attrs):
    x = ins['X']
    axis = attrs.get('axis', 0)
    n = x.shape[axis]
    return {'Y': [jnp.squeeze(a, axis) for a in jnp.split(x, n, axis)]}


@register('expand')
def expand(ctx, ins, attrs):
    x = ins['X']
    times = attrs['expand_times']
    return {'Out': jnp.tile(x, times)}


@register('slice')
def slice_op(ctx, ins, attrs):
    x = ins['Input']
    axes = attrs['axes']
    starts = attrs['starts']
    ends = attrs['ends']
    idx = [slice(None)] * x.ndim
    for a, s, e in zip(axes, starts, ends):
        dim = x.shape[a]
        s = s + dim if s < 0 else s
        e = e + dim if e < 0 else min(e, dim)
        idx[a] = slice(s, e)
    return {'Out': x[tuple(idx)]}


@register('strided_slice')
def strided_slice(ctx, ins, attrs):
    x = ins['Input']
    idx = [slice(None)] * x.ndim
    for a, s, e, st in zip(attrs['axes'], attrs['starts'], attrs['ends'],
                           attrs['strides']):
        idx[a] = slice(s, e, st)
    return {'Out': x[tuple(idx)]}


@register('gather')
def gather(ctx, ins, attrs):
    index = ins['Index']
    if index.ndim == 2 and index.shape[1] == 1:
        index = index[:, 0]
    return {'Out': jnp.take(ins['X'], index, axis=0)}


@register('scatter')
def scatter(ctx, ins, attrs):
    x, ids, updates = ins['X'], ins['Ids'], ins['Updates']
    if ids.ndim == 2 and ids.shape[1] == 1:
        ids = ids[:, 0]
    if attrs.get('overwrite', True):
        return {'Out': x.at[ids].set(updates)}
    return {'Out': x.at[ids].add(updates)}


@register('gather_nd')
def gather_nd(ctx, ins, attrs):
    x, index = ins['X'], ins['Index']
    return {'Out': x[tuple(jnp.moveaxis(index, -1, 0))]}


@register('pad')
def pad(ctx, ins, attrs):
    x = ins['X']
    p = attrs['paddings']
    pad_width = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return {'Out': jnp.pad(x, pad_width,
                           constant_values=attrs.get('pad_value', 0.0))}


@register('pad2d')
def pad2d(ctx, ins, attrs):
    x = ins['X']  # NCHW
    p = attrs['paddings']  # [top, bottom, left, right]
    mode = attrs.get('mode', 'constant')
    pw = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    if attrs.get('data_format', 'NCHW') == 'NHWC':
        pw = [(0, 0), (p[0], p[1]), (p[2], p[3]), (0, 0)]
    if mode == 'constant':
        return {'Out': jnp.pad(x, pw,
                               constant_values=attrs.get('pad_value', 0.0))}
    jmode = {'reflect': 'reflect', 'edge': 'edge'}[mode]
    return {'Out': jnp.pad(x, pw, mode=jmode)}


@register('pad_constant_like')
def pad_constant_like(ctx, ins, attrs):
    x, y = ins['X'], ins['Y']
    pw = [(0, xs - ys) for xs, ys in zip(x.shape, y.shape)]
    return {'Out': jnp.pad(y, pw, constant_values=attrs.get('pad_value', 0.0))}


@register('one_hot')
def one_hot(ctx, ins, attrs):
    x = ins['X']
    depth = attrs['depth']
    if x.ndim >= 2 and x.shape[-1] == 1:
        x = x[..., 0]
    return {'Out': jax.nn.one_hot(x, depth, dtype=jnp.float32)}


@register('lookup_table')
def lookup_table(ctx, ins, attrs):
    # reference lookup_table_op.cc: ids [..., 1] int64, W [V, D].
    # jnp.take wraps negative ids and fills out-of-range rows with NaN
    # (corruption SURFACES via executor check_nan); backward is XLA's
    # scatter-add, duplicate-id-correct.
    w, ids = ins['W'], ins['Ids']
    padding_idx = attrs.get('padding_idx', -1)
    squeeze_last = ids.ndim >= 2 and ids.shape[-1] == 1
    idx = ids[..., 0] if squeeze_last else ids
    out = jnp.take(w, idx, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (idx != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return {'Out': out}


def _fill_value(value, dtype):
    """Normalize a fill value before it reaches jnp.full: a 64-bit numpy
    scalar (program serialization hands these back) or an out-of-range
    Python int would hit jax's x32 warn-and-truncate inside the trace.
    Narrow HERE with explicit C-style wraparound so the truncation is
    ours — same numerics, silent under warnings-as-error.  numpy >= 1.24
    raises its own RuntimeWarning on an overflowing astype, so the
    wraparound cast runs under errstate suppression."""
    try:
        with np.errstate(over='ignore', invalid='ignore'):
            return np.asarray(value).astype(dtype)
    except (OverflowError, TypeError, ValueError):
        return value


@register('fill_constant')
def fill_constant(ctx, ins, attrs):
    dtype = jax_dtype(attrs.get('dtype', 'float32'))
    shape = [int(d) for d in attrs['shape']]
    return {'Out': jnp.full(shape, _fill_value(attrs['value'], dtype),
                            dtype=dtype)}


@register('fill_constant_batch_size_like')
def fill_constant_batch_size_like(ctx, ins, attrs):
    ref = ins['Input']
    shape = list(attrs['shape'])
    in_idx = attrs.get('input_dim_idx', 0)
    out_idx = attrs.get('output_dim_idx', 0)
    shape[out_idx] = ref.shape[in_idx]
    dtype = jax_dtype(attrs.get('dtype', 'float32'))
    return {'Out': jnp.full(shape, _fill_value(attrs['value'], dtype),
                            dtype=dtype)}


@register('fill_zeros_like')
def fill_zeros_like(ctx, ins, attrs):
    return {'Out': jnp.zeros_like(ins['X'])}


@register('assign')
def assign(ctx, ins, attrs):
    return {'Out': ins['X']}


@register('assign_value')
def assign_value(ctx, ins, attrs):
    dtype = convert_dtype(attrs.get('dtype', 'float32'))
    vals = np.array(attrs['values'], dtype=dtype).reshape(attrs['shape'])
    return {'Out': jnp.asarray(vals)}


@register('shape')
def shape_op(ctx, ins, attrs):
    return {'Out': jnp.array(ins['Input'].shape, dtype=jnp.int32)}


@register('top_k')
def top_k(ctx, ins, attrs):
    x = ins['X']
    k = attrs['k']
    vals, idx = lax.top_k(x, k)
    return {'Out': vals, 'Indices': idx.astype(jax_dtype('int64'))}


@register('arg_max')
def arg_max(ctx, ins, attrs):
    return {'Out': jnp.argmax(ins['X'], axis=attrs.get('axis', -1))
            .astype(jax_dtype('int64'))}


@register('arg_min')
def arg_min(ctx, ins, attrs):
    return {'Out': jnp.argmin(ins['X'], axis=attrs.get('axis', -1))
            .astype(jax_dtype('int64'))}


@register('argsort')
def argsort(ctx, ins, attrs):
    x = ins['X']
    axis = attrs.get('axis', -1)
    idx = jnp.argsort(x, axis=axis)
    return {'Out': jnp.sort(x, axis=axis), 'Indices': idx.astype(jax_dtype('int64'))}


@register('reverse')
def reverse(ctx, ins, attrs):
    x = ins['X']
    return {'Out': jnp.flip(x, axis=tuple(a % x.ndim for a in attrs['axis']))}


@register('multiplex')
def multiplex(ctx, ins, attrs):
    ids = ins['Ids']  # [B, 1] int
    xs = jnp.stack(ins['X'], axis=0)  # [n, B, D]
    idx = ids[:, 0]
    return {'Out': xs[idx, jnp.arange(xs.shape[1])]}


@register('expand_as')
def expand_as(ctx, ins, attrs):
    x, y = ins['X'], ins['target_tensor']
    reps = [t // s for s, t in zip(x.shape, y.shape)]
    return {'Out': jnp.tile(x, reps)}


@register('label_smooth')
def label_smooth(ctx, ins, attrs):
    x = ins['X']
    eps = attrs.get('epsilon', 0.0)
    if 'PriorDist' in ins:
        prior = ins['PriorDist']
        return {'Out': (1 - eps) * x + eps * prior}
    return {'Out': (1 - eps) * x + eps / x.shape[-1]}


@register('space_to_depth')
def space_to_depth(ctx, ins, attrs):
    x = ins['X']  # NCHW
    bs = attrs['blocksize']
    n, c, h, w = x.shape
    x = x.reshape(n, c, h // bs, bs, w // bs, bs)
    x = x.transpose(0, 3, 5, 1, 2, 4)
    return {'Out': x.reshape(n, c * bs * bs, h // bs, w // bs)}


@register('shuffle_channel')
def shuffle_channel(ctx, ins, attrs):
    x = ins['X']
    g = attrs['group']
    n, c, h, w = x.shape
    return {'Out': x.reshape(n, g, c // g, h, w).transpose(0, 2, 1, 3, 4)
            .reshape(n, c, h, w)}


@register('where_index')
def where_index(ctx, ins, attrs):
    """Coordinates of nonzero elements (parity: reference
    where_index_op — its output shape is data-dependent, which XLA
    cannot compile).  TPU-native fixed-K contract (the multiclass_nms
    pattern): attr `max_count` (default: condition size, always exact)
    bounds the result; outputs are Out int64 [K, rank] with valid rows
    FIRST in row-major scan order and -1 padding after, plus Count
    int64 [1] with the true number of nonzeros.  Count > max_count
    means truncation: callers picking a smaller K own that bound."""
    cond = ins['Condition']
    rank = max(cond.ndim, 1)
    flat = (cond != 0).reshape(-1)
    n = flat.shape[0]
    K = int(attrs.get('max_count') or n)
    pos = jnp.arange(n)
    # stable compaction: valid positions first, in scan order
    order = jnp.argsort(jnp.where(flat, pos, pos + n))[:K]
    valid = jnp.arange(K) < flat.sum()
    coords = []
    rem = order
    for d in range(rank - 1, -1, -1):
        dim = cond.shape[d] if cond.ndim else 1
        coords.append(rem % dim)
        rem = rem // dim
    out = jnp.stack(coords[::-1], axis=1).astype(jax_dtype('int64'))
    out = jnp.where(valid[:, None], out, -1)
    return {'Out': out, 'Count': flat.sum().reshape(1).astype(jax_dtype('int64'))}


@register('py_func')
def py_func_op(ctx, ins, attrs):
    """Host-callback op (parity: reference py_func_op.cc).  The Python
    callable runs on the host inside the jitted step via
    jax.pure_callback; backward_func becomes a custom VJP that also runs
    as a host callback.  Callables must be pure (XLA may re-run them)."""
    xs = ins['X']
    xs = list(xs) if isinstance(xs, (list, tuple)) else [xs]
    func = attrs['func']
    bwd = attrs.get('backward_func')
    # canonicalize (int64 -> int32 etc. without x64), like jnp ops do
    dtypes = [jax.dtypes.canonicalize_dtype(np.dtype(convert_dtype(d)))
              for d in attrs['out_dtypes']]
    batch = xs[0].shape[0] if xs and getattr(xs[0], 'ndim', 0) else 1

    def _static_shape(shp):
        # -1 means "the batch dim" and is only meaningful at axis 0;
        # pure_callback needs every other dim static at trace time.
        out = []
        for ax, s in enumerate(shp):
            if s == -1:
                if ax != 0:
                    raise ValueError(
                        'py_func out_shape %r: -1 is only supported at '
                        'axis 0 (the batch dim); XLA needs static shapes '
                        'for every other dim' % (list(shp),))
                out.append(batch)
            else:
                out.append(s)
        return tuple(out)

    result = tuple(
        jax.ShapeDtypeStruct(_static_shape(shp), d)
        for shp, d in zip(attrs['out_shapes'], dtypes))

    def host_fwd(*arrays):
        r = func(*[np.asarray(a) for a in arrays])
        r = list(r) if isinstance(r, (list, tuple)) else [r]
        return tuple(np.asarray(v).astype(d) for v, d in zip(r, dtypes))

    if bwd is None:
        # reference semantics without backward_func: no grad propagates
        outs = jax.pure_callback(
            host_fwd, result, *[lax.stop_gradient(x) for x in xs])
        return {'Out': list(outs)}

    skip = set(attrs.get('skip_bwd_idx', ()))

    float_pos = [i for i, x in enumerate(xs)
                 if jnp.issubdtype(x.dtype, jnp.floating)]
    float_xs = [xs[i] for i in float_pos]

    def host_bwd(*arrays):
        # backward_func returns one grad per input (reference contract);
        # only the float ones are consumed
        r = bwd(*[np.asarray(a) for a in arrays])
        r = list(r) if isinstance(r, (list, tuple)) else [r]
        return tuple(np.asarray(r[i]).astype(xs[i].dtype)
                     for i in float_pos)

    @jax.custom_vjp
    def call(*args):
        return jax.pure_callback(host_fwd, result, *args)

    def call_fwd(*args):
        outs = jax.pure_callback(host_fwd, result, *args)
        return outs, (args, outs)

    def call_bwd(res, g):
        args, outs = res
        bwd_in = [a for i, a in enumerate(list(args) + list(outs))
                  if i not in skip] + list(g)
        dx_shape = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype)
                         for x in float_xs)
        dxs = list(jax.pure_callback(host_bwd, dx_shape, *bwd_in))
        full = []
        for x in args:
            if jnp.issubdtype(x.dtype, jnp.floating):
                full.append(dxs.pop(0))
            else:  # integer inputs get symbolic-zero cotangents
                full.append(np.zeros(x.shape, jax.dtypes.float0))
        return tuple(full)

    call.defvjp(call_fwd, call_bwd)
    return {'Out': list(call(*xs))}


@register('hash')
def hash_op(ctx, ins, attrs):
    x = ins['X'].astype(jax_dtype('int64'))
    num_hash = attrs.get('num_hash', 1)
    mod_by = attrs.get('mod_by', 100000007)
    outs = []
    for i in range(num_hash):
        h = jnp.sum(x * jnp.asarray(1000003 ** (i + 1) &
                    0x7fffffff, jax_dtype('int64')), axis=-1,
                    keepdims=True)
        outs.append(jnp.abs(h) % mod_by)
    return {'Out': jnp.concatenate(outs, axis=-1)}


@register('uniform_random_batch_size_like')
def uniform_random_batch_size_like(ctx, ins, attrs):
    ref = ins['Input']
    shape = list(attrs['shape'])
    shape[attrs.get('output_dim_idx', 0)] = \
        ref.shape[attrs.get('input_dim_idx', 0)]
    # jax_dtype, not convert_dtype: the astype happens INSIDE the trace,
    # and asking for a 64-bit dtype there warn-and-truncates per trace
    dtype = jax_dtype(attrs.get('dtype', 'float32'))
    key = ctx.rng()
    return {'Out': jax.random.uniform(
        key, shape, dtype=jnp.float32,
        minval=attrs.get('min', -1.0),
        maxval=attrs.get('max', 1.0)).astype(dtype)}


@register('gaussian_random_batch_size_like')
def gaussian_random_batch_size_like(ctx, ins, attrs):
    ref = ins['Input']
    shape = list(attrs['shape'])
    shape[attrs.get('output_dim_idx', 0)] = \
        ref.shape[attrs.get('input_dim_idx', 0)]
    dtype = jax_dtype(attrs.get('dtype', 'float32'))  # in-trace astype
    key = ctx.rng()
    out = attrs.get('mean', 0.0) + attrs.get('std', 1.0) * \
        jax.random.normal(key, shape, dtype=jnp.float32)
    return {'Out': out.astype(dtype)}


@register('print')
def print_op(ctx, ins, attrs):
    import jax
    x = ins['X']
    jax.debug.print(attrs.get('message', '') + ' {}', x)
    return {'Out': x}


@register('is_empty')
def is_empty_op(ctx, ins, attrs):
    return {'Out': jnp.asarray(ins['X'].size == 0)}


@register('split_lod_tensor')
def split_lod_tensor(ctx, ins, attrs):
    """IfElse row split (ref operators/split_lod_tensor_op.cc).  The
    reference compacts rows into two shorter batches; under static-shape
    XLA both branch bodies run the full batch and merge_lod_tensor picks
    rows, so the 'split' is a passthrough."""
    x = ins['X']
    return {'OutTrue': x, 'OutFalse': x}


@register('merge_lod_tensor')
def merge_lod_tensor(ctx, ins, attrs):
    """IfElse row merge (ref operators/merge_lod_tensor_op.cc): row i of
    the output comes from InTrue where Mask[i] else InFalse — one fused
    select."""
    t, f, m = ins['InTrue'], ins['InFalse'], ins['Mask']
    m = m.reshape((-1,) + (1,) * (t.ndim - 1)).astype(bool)
    return {'Out': jnp.where(m, t, f)}


@register('batched_gather')
def batched_gather(ctx, ins, attrs):
    """Per-row gather: X [N, M, ...], Index [N, K] -> [N, K, ...]
    (rows of Index select rows of the matching batch element)."""
    x, idx = ins['X'], ins['Index']
    return {'Out': jnp.take_along_axis(
        x, idx.astype(jnp.int32).reshape(idx.shape[0], idx.shape[1],
                                         *([1] * (x.ndim - 2))), axis=1)}
