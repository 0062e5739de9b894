"""NN ops: conv / pool / norm / softmax / dropout / resize.

Parity: reference conv_op, pool_op, batch_norm_op, layer_norm_op,
group_norm_op, softmax_op, dropout_op, lrn_op, interpolate_op, etc.
Convs/pools use lax.conv_general_dilated / lax.reduce_window in NCHW — XLA
lays them out for the MXU; no cuDNN-style algo selection needed.
"""
import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v,) * n


@register('conv2d')
def conv2d(ctx, ins, attrs):
    x, w = ins['Input'], ins['Filter']
    strides = _pair(attrs.get('strides', [1, 1]))
    pads = _pair(attrs.get('paddings', [0, 0]))
    dil = _pair(attrs.get('dilations', [1, 1]))
    groups = attrs.get('groups', 1) or 1
    out = lax.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dil, feature_group_count=groups,
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
    if 'Bias' in ins:
        out = out + ins['Bias'].reshape(1, -1, 1, 1)
    return {'Output': out}


@register('conv3d')
def conv3d(ctx, ins, attrs):
    x, w = ins['Input'], ins['Filter']
    strides = _pair(attrs.get('strides', [1, 1, 1]), 3)
    pads = _pair(attrs.get('paddings', [0, 0, 0]), 3)
    dil = _pair(attrs.get('dilations', [1, 1, 1]), 3)
    groups = attrs.get('groups', 1) or 1
    out = lax.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(p, p) for p in pads],
        rhs_dilation=dil, feature_group_count=groups,
        dimension_numbers=('NCDHW', 'OIDHW', 'NCDHW'))
    return {'Output': out}


def _transpose_filter(w, groups, spatial_axes):
    """[in_c, out_c/g, *k] -> flipped [out_c, in_c/g, *k] for the
    gradient-of-conv formulation (grouped: per-group O/I swap)."""
    w = jnp.flip(w, spatial_axes)
    if groups == 1:
        return w.swapaxes(0, 1)
    in_c, ocg = w.shape[0], w.shape[1]
    k = w.shape[2:]
    wg = w.reshape((groups, in_c // groups, ocg) + k)
    wg = wg.swapaxes(1, 2)  # [g, out_c/g, in_c/g, *k]
    return wg.reshape((groups * ocg, in_c // groups) + k)


@register('conv2d_transpose')
def conv2d_transpose(ctx, ins, attrs):
    x, w = ins['Input'], ins['Filter']  # w: [in_c, out_c/groups, kh, kw]
    strides = _pair(attrs.get('strides', [1, 1]))
    pads = _pair(attrs.get('paddings', [0, 0]))
    dil = _pair(attrs.get('dilations', [1, 1]))
    groups = attrs.get('groups', 1) or 1
    kh, kw = w.shape[2], w.shape[3]
    # gradient-of-conv formulation: lhs_dilation = stride
    out = lax.conv_general_dilated(
        x, _transpose_filter(w, groups, (2, 3)),
        window_strides=(1, 1),
        padding=[(dil[0] * (kh - 1) - pads[0], dil[0] * (kh - 1) - pads[0]),
                 (dil[1] * (kw - 1) - pads[1], dil[1] * (kw - 1) - pads[1])],
        lhs_dilation=strides, rhs_dilation=dil,
        feature_group_count=groups,
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
    return {'Output': out}


@register('conv3d_transpose')
def conv3d_transpose(ctx, ins, attrs):
    x, w = ins['Input'], ins['Filter']
    strides = _pair(attrs.get('strides', [1, 1, 1]), 3)
    pads = _pair(attrs.get('paddings', [0, 0, 0]), 3)
    dil = _pair(attrs.get('dilations', [1, 1, 1]), 3)
    groups = attrs.get('groups', 1) or 1
    ks = w.shape[2:]
    out = lax.conv_general_dilated(
        x, _transpose_filter(w, groups, (2, 3, 4)),
        window_strides=(1, 1, 1),
        padding=[(dil[i] * (ks[i] - 1) - pads[i],) * 2 for i in range(3)],
        lhs_dilation=strides, rhs_dilation=dil,
        feature_group_count=groups,
        dimension_numbers=('NCDHW', 'OIDHW', 'NCDHW'))
    return {'Output': out}


def _pool(x, ksize, strides, pads, ptype, exclusive, ceil_mode,
          global_pool, adaptive=False, nd=2):
    if global_pool:
        axes = tuple(range(2, 2 + nd))
        if ptype == 'max':
            return jnp.max(x, axis=axes, keepdims=True)
        return jnp.mean(x, axis=axes, keepdims=True)
    ksize = _pair(ksize, nd)
    strides = _pair(strides, nd)
    pads = _pair(pads, nd)
    window = (1, 1) + ksize
    wstrides = (1, 1) + strides
    padding = [(0, 0), (0, 0)]
    for i in range(nd):
        hi = pads[i]
        if ceil_mode:
            size = x.shape[2 + i]
            out = -(-(size + 2 * pads[i] - ksize[i]) // strides[i]) + 1
            needed = (out - 1) * strides[i] + ksize[i] - size - pads[i]
            hi = max(pads[i], needed)
        padding.append((pads[i], hi))
    if ptype == 'max':
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, wstrides, padding)
    s = lax.reduce_window(x, 0.0, lax.add, window, wstrides, padding)
    if exclusive:
        ones = jnp.ones_like(x)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, wstrides, padding)
        return s / cnt
    return s / float(np.prod(ksize))


@register('pool2d')
def pool2d(ctx, ins, attrs):
    return {'Out': _pool(ins['X'], attrs.get('ksize', [2, 2]),
                         attrs.get('strides', [1, 1]),
                         attrs.get('paddings', [0, 0]),
                         attrs.get('pooling_type', 'max'),
                         attrs.get('exclusive', True),
                         attrs.get('ceil_mode', False),
                         attrs.get('global_pooling', False), nd=2)}


@register('pool3d')
def pool3d(ctx, ins, attrs):
    return {'Out': _pool(ins['X'], attrs.get('ksize', [2, 2, 2]),
                         attrs.get('strides', [1, 1, 1]),
                         attrs.get('paddings', [0, 0, 0]),
                         attrs.get('pooling_type', 'max'),
                         attrs.get('exclusive', True),
                         attrs.get('ceil_mode', False),
                         attrs.get('global_pooling', False), nd=3)}


def _adaptive_pool(x, out_size, ptype, nd=2):
    axes_sizes = x.shape[2:2 + nd]
    out_size = _pair(out_size, nd)
    # decompose into even windows when divisible (common case), else resize
    ks = []
    for s, o in zip(axes_sizes, out_size):
        assert s % o == 0, 'adaptive pool needs divisible sizes on TPU'
        ks.append(s // o)
    return _pool(x, ks, ks, [0] * nd, ptype, True, False, False, nd=nd)


@register('adaptive_pool2d')
def adaptive_pool2d(ctx, ins, attrs):
    return {'Out': _adaptive_pool(ins['X'], attrs['ksize'],
                                  attrs.get('pooling_type', 'max'), 2)}


@register('adaptive_pool3d')
def adaptive_pool3d(ctx, ins, attrs):
    return {'Out': _adaptive_pool(ins['X'], attrs['ksize'],
                                  attrs.get('pooling_type', 'max'), 3)}


def _bn_shapes(x, ch_axis):
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]
    return axes, bshape


# The guard of _bn_train_fwd.  With the pilot md away from the batch mean
# the f32 subtraction E[d^2] - E[d]^2 returns the variance with the sums'
# own rounding times 1 + md^2/v: nothing to speak of while the pilot is
# near (a moving mean that tracks the batch), tens of per cent at 10^3
# sigma (a moving mean still at its initial 0 under such an input).  So
# where md^2 > _BN_RESHIFT * v_raw in ANY channel, the pilot more than 16
# sigma out, the sums are taken again around the mean the first read
# found.  Below the threshold at most 8 of f32's 24 bits go: 5e-4 of the
# variance at 16 sigma in a sequential sum over 1,152 elements, less in
# a tree (tests/test_batch_norm_vjp.py holds 1e-3 at 10^3 and 10^4
# sigma, where the unguarded form is off by 2 and 126 times the variance).
# The test cannot be fooled by a v_raw that has already cancelled: its
# error is a few ulp of md^2, under 2^-20 of it, so a v_raw of pure
# rounding, of either sign, still reads md^2 > 256 * v_raw.  And it does
# not fire on a sound network: activations lie within a few sigma of 0
# at the first step and within a fraction of a sigma of the moving mean
# after it; where it does fire, it costs one more pass over x.
_BN_RESHIFT = 256.0


def _bn_shifted_sums(x, c, axes):
    """(md, v_raw) of x about the pilot c: mean(x - c) and the raw
    one-pass variance mean((x - c)^2) - md^2, f32, keepdims."""
    d = x.astype(jnp.float32) - c
    md = jnp.mean(d, axis=axes, keepdims=True)
    return md, jnp.mean(jnp.square(d), axis=axes, keepdims=True) \
        - jnp.square(md)


def _bn_train_fwd(x, scale, bias, pilot, ch_axis, eps):
    axes, bshape = _bn_shapes(x, ch_axis)
    # the pilot does not depend on x (batch_norm hands in the moving
    # mean), so the two sums can sit in the epilogue of whatever writes
    # x: a pilot cut from x itself is a value that producer has not
    # finished, and costs every batch norm a pass of its own over x
    c = pilot.reshape(bshape)
    md, v_raw = _bn_shifted_sums(x, c, axes)

    def reshift(x, c, md, v_raw):
        # the rare branch, and the only second read of x: the same sums
        # about the mean the first read found
        c = c + md
        return (c,) + _bn_shifted_sums(x, c, axes)

    # behind a barrier: XLA:TPU otherwise sinks the broadcasts of these
    # vectors into both branches and the conditional returns an f32
    # array of x's shape (53 of them in ResNet-50: 0.8 GB of temporaries)
    c, md, v_raw = lax.optimization_barrier(lax.cond(
        jnp.any(jnp.square(md) > _BN_RESHIFT * jnp.maximum(v_raw, 0.0)),
        reshift, lambda x, c, md, v_raw: (c, md, v_raw), x, c, md, v_raw))
    v = jnp.maximum(v_raw, 0.0)
    m = (md + c).reshape(x.shape[ch_axis])
    v = v.reshape(x.shape[ch_axis])
    y = (x.astype(jnp.float32) - c - md) * (
        scale.reshape(bshape) * lax.rsqrt(v.reshape(bshape) + eps)) + \
        bias.reshape(bshape)
    # residuals: x as it came (the convolution's bf16 output under AMP,
    # which exists anyway) and per-channel f32 vectors; no array of x's
    # shape in f32
    return (y.astype(x.dtype), m, v), (x, c, md, v_raw, scale)


def _bn_train_bwd(ch_axis, eps, res, cts):
    """The gradient AD derives from _bn_train_fwd's formula, from x in
    its own dtype: d - md is recomputed in f32 inside the fusions that
    read it, every sum is f32, dx returns in x's dtype.  gm and gv are
    the cotangents of the saved mean and variance (zero in a training
    step, where only stop_gradient'd moving statistics read them).  The
    pilot gets none: the shift cancels out of every result."""
    x, c, md, v_raw, scale = res
    dy, gm, gv = cts
    axes, bshape = _bn_shapes(x, ch_axis)
    n = x.size // x.shape[ch_axis]
    scale, gm, gv = (a.reshape(bshape) for a in (scale, gm, gv))
    r = lax.rsqrt(jnp.maximum(v_raw, 0.0) + eps)
    dyf = dy.astype(jnp.float32)
    dm = x.astype(jnp.float32) - c - md
    dbias = jnp.sum(dyf, axis=axes, keepdims=True)
    dyd = jnp.sum(dyf * dm, axis=axes, keepdims=True)
    # through the variance: r = (v + eps) ** -0.5, and the clamp passes
    # nothing where it binds (half at a tie: AD's rule for maximum)
    g_raw = (gv - 0.5 * scale * dyd * r * r * r) * jnp.where(
        v_raw > 0.0, 1.0, jnp.where(v_raw == 0.0, 0.5, 0.0))
    sr = scale * r
    dx = sr * dyf + (2.0 / n) * g_raw * dm + (gm - sr * dbias) / n
    ch = x.shape[ch_axis]
    return (dx.astype(x.dtype), (dyd * r).reshape(ch), dbias.reshape(ch),
            jnp.zeros(ch, jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _bn_train(x, scale, bias, pilot, ch_axis, eps):
    """Training-mode batch norm over every axis but ch_axis: one-pass f32
    statistics shifted by `pilot`, a [C] f32 vector that must not depend
    on x (see batch_norm), y in x's dtype, the batch mean and variance
    per channel."""
    return _bn_train_fwd(x, scale, bias, pilot, ch_axis, eps)[0]


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


@register('batch_norm')
def batch_norm(ctx, ins, attrs):
    x = ins['X']
    scale, bias = ins['Scale'], ins['Bias']
    mean, var = ins['Mean'], ins['Variance']
    eps = attrs.get('epsilon', 1e-5)
    momentum = attrs.get('momentum', 0.9)
    is_test = attrs.get('is_test', False)
    layout = attrs.get('data_layout', 'NCHW')
    ch_axis = 1 if layout == 'NCHW' else x.ndim - 1
    axes, bshape = _bn_shapes(x, ch_axis)
    # statistics always accumulate in f32 (bf16 mean/var over B*H*W
    # elements would lose ~5 bits); y returns in the input dtype so AMP
    # activations stay half-width in HBM
    xf = x.astype(jnp.float32)

    if is_test or attrs.get('use_global_stats', False):
        m, v = mean, var
        y = (xf - m.reshape(bshape)) * (
            scale.reshape(bshape) * lax.rsqrt(v.reshape(bshape) + eps)) + \
            bias.reshape(bshape)
        return {'Y': y.astype(x.dtype), 'MeanOut': mean, 'VarianceOut': var,
                'SavedMean': m, 'SavedVariance': v}
    # one-pass statistics (f32 accumulation): the two-pass
    # mean(square(x - m)) form reads the conv-sized activation TWICE
    # per BN.  The sums are SHIFTED by a per-channel pilot c so that the
    # subtraction E[d^2] - E[d]^2 does not cancel when |mean| >> std;
    # the shift is analytically a no-op.  The pilot is the MOVING mean:
    # a vector that exists before x does, so XLA fuses the two sums
    # into the epilogue of the convolution that writes x (a pilot cut
    # from x gives each of ResNet-50's 53 batch norms a pass of its own
    # over x, 2.7 GB a step at batch 128), and in steady state a
    # closer one than an element of the sample.  Where it is far from
    # the batch mean (a moving mean still at its initial 0 under an
    # input with |mean| > 16 std) _bn_train_fwd sees that in the
    # vectors it already has and only then takes the sums again around
    # the mean it found (_BN_RESHIFT): the guarantee does not rest on
    # the moving mean being any good.  PT_TWO_PASS_NORM=1 is the exact
    # two-pass form under AD's own backward: the oracle to hold the
    # one-pass form against.
    if os.environ.get('PT_TWO_PASS_NORM', '0') == '1':
        m = jnp.mean(xf, axis=axes)
        v = jnp.mean(jnp.square(xf - m.reshape(bshape)), axis=axes)
        y = (xf - m.reshape(bshape)) * (
            scale.reshape(bshape) * lax.rsqrt(v.reshape(bshape) + eps)) + \
            bias.reshape(bshape)
        new_mean = lax.stop_gradient(momentum * mean + (1 - momentum) * m)
        new_var = lax.stop_gradient(momentum * var + (1 - momentum) * v)
        return {'Y': y.astype(x.dtype), 'MeanOut': new_mean,
                'VarianceOut': new_var, 'SavedMean': m,
                'SavedVariance': v}
    # the backward is written out (_bn_train): its residuals are x in
    # its own dtype and per-channel vectors, where AD of this formula
    # saves two f32 arrays of x's shape (2d and d - md) and leaves it
    # to the compiler to recompute them from x
    from ..observability import metrics
    metrics.counter('batch_norm.recompute_vjp').inc()
    y, m, v = _bn_train(x, scale.astype(jnp.float32),
                        bias.astype(jnp.float32),
                        lax.stop_gradient(mean.astype(jnp.float32)),
                        ch_axis, eps)
    new_mean = lax.stop_gradient(momentum * mean + (1 - momentum) * m)
    new_var = lax.stop_gradient(momentum * var + (1 - momentum) * v)
    return {'Y': y, 'MeanOut': new_mean,
            'VarianceOut': new_var, 'SavedMean': m, 'SavedVariance': v}


@register('layer_norm')
def layer_norm(ctx, ins, attrs):
    x = ins['X']
    begin = attrs.get('begin_norm_axis', 1)
    eps = attrs.get('epsilon', 1e-5)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)  # f32 statistics; output in input dtype
    # shifted one-pass statistics like batch_norm above: one read, and
    # the per-row pilot shift bounds the E[d^2]-E[d]^2 cancellation
    # (PT_TWO_PASS_NORM=1 restores the exact two-pass form)
    if os.environ.get('PT_TWO_PASS_NORM', '0') == '1':
        m = jnp.mean(xf, axis=axes, keepdims=True)
        v = jnp.mean(jnp.square(xf - m), axis=axes, keepdims=True)
        y = (xf - m) * lax.rsqrt(v + eps)
    else:
        c = lax.stop_gradient(xf[tuple(
            slice(None) if i < begin else slice(0, 1)
            for i in range(x.ndim))])
        d = xf - c
        md = jnp.mean(d, axis=axes, keepdims=True)
        v = jnp.maximum(
            jnp.mean(jnp.square(d), axis=axes, keepdims=True)
            - jnp.square(md), 0.0)
        m = md + c
        y = (d - md) * lax.rsqrt(v + eps)
    norm_shape = x.shape[begin:]
    if 'Scale' in ins:
        y = y * ins['Scale'].reshape(norm_shape)
    if 'Bias' in ins:
        y = y + ins['Bias'].reshape(norm_shape)
    return {'Y': y.astype(x.dtype), 'Mean': m.reshape(x.shape[:begin]),
            'Variance': v.reshape(x.shape[:begin])}


@register('group_norm')
def group_norm(ctx, ins, attrs):
    x = ins['X']  # NCHW
    g = attrs.get('groups', 1)
    eps = attrs.get('epsilon', 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, g, c // g) + x.shape[2:]).astype(jnp.float32)
    axes = tuple(range(2, xg.ndim))
    m = jnp.mean(xg, axis=axes, keepdims=True)
    v = jnp.mean(jnp.square(xg - m), axis=axes, keepdims=True)
    y = ((xg - m) * lax.rsqrt(v + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if 'Scale' in ins:
        y = y * ins['Scale'].reshape(bshape)
    if 'Bias' in ins:
        y = y + ins['Bias'].reshape(bshape)
    return {'Y': y.astype(x.dtype), 'Mean': m.reshape(n, g),
            'Variance': v.reshape(n, g)}


@register('data_norm')
def data_norm(ctx, ins, attrs):
    x = ins['X']
    sizes, sums, sqsums = ins['BatchSize'], ins['BatchSum'], ins['BatchSquareSum']
    means = sums / sizes
    scales = lax.rsqrt(sqsums / sizes - jnp.square(means) + 1e-4)
    return {'Y': (x - means) * scales, 'Means': means, 'Scales': scales}


@register('softmax')
def softmax(ctx, ins, attrs):
    x = ins['X']  # exp/sum in f32; result back in input dtype
    out = jax.nn.softmax(x.astype(jnp.float32), axis=attrs.get('axis', -1))
    return {'Out': out.astype(x.dtype)}


@register('log_softmax')
def log_softmax(ctx, ins, attrs):
    x = ins['X']
    out = jax.nn.log_softmax(x.astype(jnp.float32),
                             axis=attrs.get('axis', -1))
    return {'Out': out.astype(x.dtype)}


@register('dropout')
def dropout(ctx, ins, attrs):
    x = ins['X']
    p = attrs.get('dropout_prob', 0.5)
    is_test = attrs.get('is_test', False)
    impl = attrs.get('dropout_implementation', 'downgrade_in_infer')
    if is_test:
        out = x * (1.0 - p) if impl == 'downgrade_in_infer' else x
        return {'Out': out, 'Mask': jnp.ones_like(x)}
    seed = attrs.get('seed', 0)
    key = jax.random.key(seed) if seed else ctx.rng()
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    mask = keep.astype(x.dtype)
    out = x * mask
    if impl == 'upscale_in_train' and p < 1.0:
        out = out / (1.0 - p)
    return {'Out': out, 'Mask': mask}


@register('lrn')
def lrn(ctx, ins, attrs):
    x = ins['X']  # NCHW
    n = attrs.get('n', 5)
    k = attrs.get('k', 2.0)
    alpha = attrs.get('alpha', 1e-4)
    beta = attrs.get('beta', 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    return {'Out': x / jnp.power(mid, beta), 'MidOut': mid}


@register('l2_norm_layer')
def l2_norm_layer(ctx, ins, attrs):
    x = ins['X']
    return {'Out': x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True))}


def _resize(x, out_h, out_w, method, align_corners):
    n, c, h, w = x.shape
    if not align_corners:
        xt = x.transpose(0, 2, 3, 1)
        out = jax.image.resize(xt, (n, out_h, out_w, c), method=method)
        return out.transpose(0, 3, 1, 2)

    # align_corners=True (the reference default): src = i*(in-1)/(out-1)
    def coords(out_size, in_size):
        if out_size == 1:
            return jnp.zeros((1,))
        return jnp.arange(out_size) * ((in_size - 1) / (out_size - 1))

    ys = coords(out_h, h)
    xs = coords(out_w, w)
    if method == 'nearest':
        yi = jnp.round(ys).astype(jnp.int32)
        xi = jnp.round(xs).astype(jnp.int32)
        return x[:, :, yi][:, :, :, xi]
    y0 = jnp.clip(jnp.floor(ys).astype(jnp.int32), 0, h - 1)
    x0 = jnp.clip(jnp.floor(xs).astype(jnp.int32), 0, w - 1)
    y1 = jnp.minimum(y0 + 1, h - 1)
    x1 = jnp.minimum(x0 + 1, w - 1)
    wy = (ys - y0).reshape(1, 1, -1, 1).astype(x.dtype)
    wx = (xs - x0).reshape(1, 1, 1, -1).astype(x.dtype)
    tl = x[:, :, y0][:, :, :, x0]
    tr = x[:, :, y0][:, :, :, x1]
    bl = x[:, :, y1][:, :, :, x0]
    br = x[:, :, y1][:, :, :, x1]
    top = tl * (1 - wx) + tr * wx
    bot = bl * (1 - wx) + br * wx
    return top * (1 - wy) + bot * wy


@register('bilinear_interp')
def bilinear_interp(ctx, ins, attrs):
    x = ins['X']
    out_h, out_w = attrs['out_h'], attrs['out_w']
    if 'OutSize' in ins:
        pass  # dynamic size unsupported under XLA; use attrs
    return {'Out': _resize(x, out_h, out_w, 'bilinear',
                           attrs.get('align_corners', True))}


@register('nearest_interp')
def nearest_interp(ctx, ins, attrs):
    x = ins['X']
    return {'Out': _resize(x, attrs['out_h'], attrs['out_w'], 'nearest',
                           attrs.get('align_corners', True))}


@register('affine_channel')
def affine_channel(ctx, ins, attrs):
    x, scale, bias = ins['X'], ins['Scale'], ins['Bias']
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    return {'Out': x * scale.reshape(bshape) + bias.reshape(bshape)}


@register('row_conv')
def row_conv(ctx, ins, attrs):
    # lookahead row convolution over time (ref row_conv_op.cc); x: [B, T, D]
    x, w = ins['X'], ins['Filter']  # w: [future_ctx, D]
    k = w.shape[0]
    pad = jnp.pad(x, [(0, 0), (0, k - 1), (0, 0)])
    out = sum(pad[:, i:i + x.shape[1]] * w[i] for i in range(k))
    return {'Out': out}


@register('conv_shift')
def conv_shift(ctx, ins, attrs):
    x, y = ins['X'], ins['Y']  # [B, M], [B, N] N odd
    m, n = x.shape[1], y.shape[1]
    half = n // 2
    idx = (jnp.arange(m)[:, None] + jnp.arange(-half, half + 1)[None, :]) % m
    return {'Out': jnp.einsum('bmn,bn->bm', x[:, idx], y)}


@register('im2sequence')
def im2sequence(ctx, ins, attrs):
    x = ins['X']  # NCHW
    kh, kw = attrs['kernels']
    sh, sw = attrs.get('strides', [1, 1])
    n, c, h, w = x.shape
    patches = []
    oh = (h - kh) // sh + 1
    ow = (w - kw) // sw + 1
    for i in range(oh):
        for j in range(ow):
            patches.append(x[:, :, i * sh:i * sh + kh, j * sw:j * sw + kw]
                           .reshape(n, -1))
    out = jnp.stack(patches, axis=1)  # [N, oh*ow, c*kh*kw]
    return {'Out': out}


@register('grid_sampler')
def grid_sampler(ctx, ins, attrs):
    x, grid = ins['X'], ins['Grid']  # x NCHW, grid [N, H, W, 2] in [-1,1]
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1) * (w - 1) / 2
    gy = (grid[..., 1] + 1) * (h - 1) / 2
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1

    def sample(yi, xi):
        yi = jnp.clip(yi, 0, h - 1)
        xi = jnp.clip(xi, 0, w - 1)
        bidx = jnp.arange(n)[:, None, None]
        return x[bidx, :, yi, xi]  # [N, H, W, C]

    wa = ((x1 - gx) * (y1 - gy))[..., None]
    wb = ((x1 - gx) * (gy - y0))[..., None]
    wc = ((gx - x0) * (y1 - gy))[..., None]
    wd = ((gx - x0) * (gy - y0))[..., None]
    out = wa * sample(y0, x0) + wb * sample(y1, x0) + \
        wc * sample(y0, x1) + wd * sample(y1, x1)
    return {'Output': out.transpose(0, 3, 1, 2)}


@register('affine_grid')
def affine_grid(ctx, ins, attrs):
    theta = ins['Theta']  # [N, 2, 3]
    _, _, h, w = attrs['output_shape'] if 'output_shape' in attrs else \
        (0, 0, 0, 0)
    ys = jnp.linspace(-1, 1, h)
    xs = jnp.linspace(-1, 1, w)
    gy, gx = jnp.meshgrid(ys, xs, indexing='ij')
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], axis=-1)  # [H, W, 3]
    out = jnp.einsum('hwk,nik->nhwi', base, theta)
    return {'Output': out}


@register('add_position_encoding')
def add_position_encoding(ctx, ins, attrs):
    x = ins['X']  # [B, T, D]
    alpha = attrs.get('alpha', 1.0)
    beta = attrs.get('beta', 1.0)
    b, t, d = x.shape
    pos = jnp.arange(t, dtype=x.dtype)[:, None]
    half = d // 2
    div = jnp.power(10000.0, jnp.arange(half, dtype=x.dtype) / half)
    pe = jnp.concatenate([jnp.sin(pos / div), jnp.cos(pos / div)], axis=1)
    return {'Out': alpha * x + beta * pe[None, :, :]}


@register('similarity_focus')
def similarity_focus(ctx, ins, attrs):
    x = ins['X']
    axis = attrs['axis']
    indexes = attrs['indexes']
    sel = jnp.take(x, jnp.array(indexes), axis=axis)
    mx = jnp.max(sel, axis=axis, keepdims=True)
    mask = (x == jnp.max(mx, axis=tuple(range(2, x.ndim)), keepdims=True))
    return {'Out': jnp.where(mask, jnp.ones_like(x), jnp.zeros_like(x))}


@register('tree_conv')
def tree_conv(ctx, ins, attrs):
    """Tree-based convolution (TBCNN).

    Ref: paddle/fluid/operators/tree_conv_op.h + math/tree2col.cc.  The
    reference builds per-root "patches" by depth-limited DFS on the host and
    runs a gemm per sample.  TPU-native formulation: depth-d reachability is
    A^d (boolean matmul chain, d < max_depth), and the eta_t/eta_l/eta_r
    coefficient matrices are built densely so the whole op is a few (N+1)^2
    matmuls + one (N, 3F) x (3F, out*nf) gemm per sample — all MXU work, no
    host graph traversal.

    Inputs: NodesVector (B, N, F); EdgeSet (B, E, 2) int, 1-based (parent,
    child) pairs, zero-terminated; Filter (F, 3, out_size, num_filters).
    Output: (B, N, out_size, num_filters).
    """
    nodes, edges, filt = ins['NodesVector'], ins['EdgeSet'], ins['Filter']
    max_depth = int(attrs.get('max_depth', 2))
    B, N, F = nodes.shape
    fdim, three, out_size, nf = filt.shape
    w2d = filt.reshape(3 * F, out_size * nf)
    fd = float(max_depth)

    def one(sample_nodes, sample_edges):
        u = sample_edges[:, 0].astype(jnp.int32)
        v = sample_edges[:, 1].astype(jnp.int32)
        ok = (u != 0) & (v != 0)
        # reference construct_tree breaks at the first invalid edge
        valid = (jnp.cumprod(ok.astype(jnp.int32)) > 0)
        node_count = valid.sum() + 1
        A = jnp.zeros((N + 1, N + 1), nodes.dtype)
        A = A.at[jnp.where(valid, u, 0), jnp.where(valid, v, 0)].add(
            valid.astype(nodes.dtype))
        A = A.at[0, 0].set(0.0).clip(0.0, 1.0)
        # sibling order (1-based) and sibling count per child edge
        same_parent = (u[:, None] == u[None, :]) & valid[None, :]
        E = u.shape[0]
        earlier = jnp.tril(jnp.ones((E, E), jnp.int32), -1)
        order = (same_parent.astype(jnp.int32) * earlier).sum(-1) + 1
        pclen = same_parent.astype(jnp.int32).sum(-1)
        temp_e = jnp.where(pclen == 1, 0.5,
                           (order - 1.0) / jnp.maximum(pclen - 1.0, 1e-6))
        node_temp = jnp.zeros((N + 1,), nodes.dtype)
        node_temp = node_temp.at[jnp.where(valid, v, 0)].set(
            jnp.where(valid, temp_e.astype(nodes.dtype), 0.0))
        # reachability at each depth d = A^d restricted to d < max_depth
        M_t = jnp.eye(N + 1, dtype=nodes.dtype)  # root: eta_t=1, eta_l=eta_r=0
        M_l = jnp.zeros((N + 1, N + 1), nodes.dtype)
        M_r = jnp.zeros((N + 1, N + 1), nodes.dtype)
        Rd = jnp.eye(N + 1, dtype=nodes.dtype)
        for d in range(1, max_depth):
            Rd = (Rd @ A > 0).astype(nodes.dtype)
            et = (fd - d) / fd
            el = (1.0 - et) * node_temp[None, :]
            er = (1.0 - et) * (1.0 - el)
            M_t = M_t + Rd * et
            M_l = M_l + Rd * el
            M_r = M_r + Rd * er
        feat = jnp.concatenate(
            [jnp.zeros((1, F), nodes.dtype), sample_nodes], axis=0)
        p_t = (M_t @ feat)[1:]
        p_l = (M_l @ feat)[1:]
        p_r = (M_r @ feat)[1:]
        patch = jnp.stack([p_l, p_r, p_t], axis=-1).reshape(N, 3 * F)
        active = (jnp.arange(1, N + 1) <= node_count)[:, None]
        out = jnp.where(active, patch, 0.0) @ w2d
        return out.reshape(N, out_size, nf)

    return {'Out': jax.vmap(one)(nodes, edges)}


@register('rms_norm')
def rms_norm(ctx, ins, attrs):
    """Root-mean-square LayerNorm (no mean-centering, no bias) — the LLaMA
    norm.  New vs reference (it predates RMSNorm); fused by XLA into the
    surrounding matmuls."""
    x = ins['X']
    w = ins.get('Scale')
    eps = attrs.get('epsilon', 1e-6)
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    if w is not None:
        out = out * w.astype(jnp.float32)
    return {'Y': out.astype(dt)}


@register('rope')
def rope(ctx, ins, attrs):
    """Rotary position embedding on [B, H, T, D] (D even): rotate feature
    pairs by position-dependent angles.  theta: base frequency (LLaMA-3
    uses 500000).  `Positions` (optional int [B, T]) overrides 0..T-1."""
    x = ins['X']
    theta = attrs.get('theta', 10000.0)
    B, H, T, D = x.shape
    pos = ins.get('Positions')
    if pos is None:
        pos = jnp.arange(T)[None, :]                       # [1, T]
    freqs = theta ** (-jnp.arange(0, D // 2) * 2.0 / D)    # [D/2]
    ang = pos[:, None, :, None].astype(jnp.float32) * freqs  # [B,1,T,D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(B, H, T, D)
    return {'Out': out.astype(x.dtype)}


@register('chunk_eval')
def chunk_eval(ctx, ins, attrs):
    """Chunk detection eval (NER-style): counts inferred/label/correct
    chunks under IOB/IOE/IOBES/plain tag schemes.

    Parity: reference paddle/fluid/operators/chunk_eval_op.h semantics
    (ChunkBegin/ChunkEnd rule tables), re-expressed as a vectorized
    position-parallel computation: a chunk is identified by its (start,
    end, type) triple; starts come from a running max over begin markers,
    and a correct chunk is an aligned (end, start, type) match — no
    sequential segment walk, so the whole batch evals in one fused XLA op.
    """
    scheme = attrs.get('chunk_scheme', 'IOB')
    num_chunk_types = attrs['num_chunk_types']
    excluded = attrs.get('excluded_chunk_types') or []
    n_tag = {'IOB': 2, 'IOE': 2, 'IOBES': 4, 'plain': 1}[scheme]
    # tag-type codes per scheme; -1 = not present
    tb, ti, te, ts = {'IOB': (0, 1, -1, -1), 'IOE': (-1, 0, 1, -1),
                      'IOBES': (0, 1, 2, 3), 'plain': (-1, -1, -1, -1)}[
                          scheme]
    other = num_chunk_types

    inf = ins['Inference']
    lab = ins['Label']
    if inf.ndim == 3:
        inf = inf[..., 0]
    if lab.ndim == 3:
        lab = lab[..., 0]
    B, T = inf.shape
    lens = ins.get('SeqLength')
    if lens is None:
        lens = jnp.full((B,), T, jnp.int32)
    lens = lens.reshape(B).astype(jnp.int32)
    valid = jnp.arange(T)[None, :] < lens[:, None]          # [B, T]

    def marks(tags):
        ctype = jnp.where(valid, tags // n_tag, other)
        ttype = tags % n_tag
        # shift: position 0 sees prev_type = other
        pt = jnp.concatenate([jnp.full((B, 1), other), ctype[:, :-1]], 1)
        ptag = jnp.concatenate([jnp.full((B, 1), -1), ttype[:, :-1]], 1)
        is_other = ctype == other
        prev_other = pt == other
        # ChunkBegin(prev, cur) rule table (see reference chunk_eval_op.h)
        begin = jnp.where(
            prev_other, ~is_other,
            jnp.where(is_other, False,
                      jnp.where(ctype != pt, True,
                                (ttype == tb) | (ttype == ts) |
                                (((ttype == ti) | (ttype == te)) &
                                 ((ptag == te) | (ptag == ts))))))
        # ChunkEnd(cur, next): close at i when the i+1 transition says so
        nt = jnp.concatenate([ctype[:, 1:], jnp.full((B, 1), other)], 1)
        ntag = jnp.concatenate([ttype[:, 1:], jnp.full((B, 1), -1)], 1)
        end = jnp.where(
            is_other, False,
            jnp.where(nt == other, True,
                      jnp.where(nt != ctype, True,
                                (ttype == te) | (ttype == ts) |
                                (((ttype == tb) | (ttype == ti)) &
                                 ((ntag == tb) | (ntag == ts))))))
        begin = begin & valid
        end = end & valid
        # chunk start position aligned to each index: running max of
        # begin-marked indices
        idx = jnp.arange(T)[None, :]
        start_of = jax.lax.cummax(jnp.where(begin, idx, -1), axis=1)
        keep = jnp.ones((B, T), bool)
        for ex in excluded:
            keep = keep & (ctype != ex)
        return begin & keep, end & keep, ctype, start_of

    ib, ie, it, istart = marks(inf.astype(jnp.int32))
    lb, le, lt, lstart = marks(lab.astype(jnp.int32))
    num_inf = ib.sum()
    num_lab = lb.sum()
    correct = (ie & le & (istart == lstart) & (it == lt)).sum()

    num_inf_f = num_inf.astype(jnp.float32)
    num_lab_f = num_lab.astype(jnp.float32)
    cor_f = correct.astype(jnp.float32)
    precision = jnp.where(num_inf_f > 0, cor_f / num_inf_f, 0.0)
    recall = jnp.where(num_lab_f > 0, cor_f / num_lab_f, 0.0)
    f1 = jnp.where(precision + recall > 0,
                   2 * precision * recall / (precision + recall), 0.0)
    i64 = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    return {'Precision': precision.reshape(1),
            'Recall': recall.reshape(1),
            'F1-Score': f1.reshape(1),
            'NumInferChunks': num_inf.astype(i64).reshape(1),
            'NumLabelChunks': num_lab.astype(i64).reshape(1),
            'NumCorrectChunks': correct.astype(i64).reshape(1)}


@register('edit_distance')
def edit_distance(ctx, ins, attrs):
    """Levenshtein distance between hypothesis and reference id sequences.

    Parity: reference operators/edit_distance_op (CPU/GPU DP kernels).
    TPU-native: one lax.scan over hypothesis rows; within a row the
    d[i][j-1] dependency is folded into a prefix-min —
    row[j] = j + cummin_j(f[j] - j) with f = min(prev+1, shift(prev)+cost)
    — so each row is a fused vector op instead of a scalar inner loop.
    """
    hyps = ins['Hyps']
    refs = ins['Refs']
    if hyps.ndim == 3:
        hyps = hyps[..., 0]
    if refs.ndim == 3:
        refs = refs[..., 0]
    B, Th = hyps.shape
    Tr = refs.shape[1]
    hl = ins.get('HypsLength')
    rl = ins.get('RefsLength')
    hl = (jnp.full((B,), Th, jnp.int32) if hl is None
          else hl.reshape(B).astype(jnp.int32))
    rl = (jnp.full((B,), Tr, jnp.int32) if rl is None
          else rl.reshape(B).astype(jnp.int32))
    normalized = attrs.get('normalized', True)
    ignored = attrs.get('ignored_tokens') or []

    def squeeze_ignored(seq, length):
        if not ignored:
            return seq, length
        keep = jnp.ones(seq.shape, bool)
        for t in ignored:
            keep = keep & (seq != t)
        keep = keep & (jnp.arange(seq.shape[0]) < length)
        idx = jnp.argsort(~keep, stable=True)  # kept tokens first, in order
        return seq[idx], keep.sum().astype(jnp.int32)

    def one(h, r, hlen, rlen):
        h, hlen = squeeze_ignored(h, hlen)
        r, rlen = squeeze_ignored(r, rlen)
        j = jnp.arange(Tr + 1)
        row0 = j.astype(jnp.int32)

        def step(prev, hi):
            cost = jnp.where(hi == r, 0, 1).astype(jnp.int32)  # [Tr]
            diag = prev[:-1] + cost
            up = prev[1:] + 1
            f = jnp.concatenate([(prev[:1] + 1), jnp.minimum(diag, up)])
            row = jax.lax.cummin(f - row0) + row0
            return row, row

        _, rows = jax.lax.scan(step, row0, h)
        all_rows = jnp.concatenate([row0[None], rows])     # [Th+1, Tr+1]
        d = all_rows[hlen, rlen].astype(jnp.float32)
        if normalized:
            d = d / jnp.maximum(rlen.astype(jnp.float32), 1.0)
        return d

    out = jax.vmap(one)(hyps.astype(jnp.int32), refs.astype(jnp.int32),
                        hl, rl)
    i64 = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    return {'Out': out.reshape(B, 1),
            'SequenceNum': jnp.asarray([B], i64)}
