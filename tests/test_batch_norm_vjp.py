"""batch_norm's written-out backward (ops/nn.py:_bn_train) against AD of
the plain formula, what it keeps for the backward pass, and the pilot
its one-pass statistics are shifted by.

The oracle below is the training branch of ops/nn.py:batch_norm as it
stood before the custom_vjp: the same shifted one-pass forward, with
jax's own AD as its backward.  Its pilot is the first element of each
channel; the op's is an argument (the moving mean, since PR 60), and
`_first` hands `_bn_train` the oracle's where the two are held bit
against bit."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import paddle_tpu as fluid
import paddle_tpu.observability as obs
from paddle_tpu import layers
from paddle_tpu.core import emit, registry
from paddle_tpu.ops import nn

EPS = 1e-5
F32_RTOL = 1e-5
BF16_DISTANCE = 1e-2


def _oracle(x, scale, bias, ch_axis, eps=EPS):
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]
    xf = x.astype(jnp.float32)
    c = lax.stop_gradient(xf[tuple(
        slice(None) if i == ch_axis else slice(0, 1)
        for i in range(x.ndim))])
    d = xf - c
    md = jnp.mean(d, axis=axes, keepdims=True)
    v = jnp.maximum(
        jnp.mean(jnp.square(d), axis=axes, keepdims=True)
        - jnp.square(md), 0.0)
    m = (md + c).reshape(x.shape[ch_axis])
    v = v.reshape(x.shape[ch_axis])
    y = (d - md) * (
        scale.reshape(bshape) * lax.rsqrt(v.reshape(bshape) + eps)) + \
        bias.reshape(bshape)
    return y.astype(x.dtype), m, v


def _oracle_op(ctx, ins, attrs):
    """The op as it was, for the Program tests: training branch only."""
    x = ins['X']
    assert not attrs.get('is_test', False)
    momentum = attrs.get('momentum', 0.9)
    ch_axis = 1 if attrs.get('data_layout', 'NCHW') == 'NCHW' \
        else x.ndim - 1
    y, m, v = _oracle(x, ins['Scale'], ins['Bias'], ch_axis,
                      attrs.get('epsilon', 1e-5))
    return {'Y': y, 'SavedMean': m, 'SavedVariance': v,
            'MeanOut': lax.stop_gradient(
                momentum * ins['Mean'] + (1 - momentum) * m),
            'VarianceOut': lax.stop_gradient(
                momentum * ins['Variance'] + (1 - momentum) * v)}


def _first(x, ch_axis):
    """The oracle's pilot as `_bn_train`'s argument: [C], f32."""
    return jnp.moveaxis(x, ch_axis, 0).reshape(
        x.shape[ch_axis], -1)[:, 0].astype(jnp.float32)


def _bn_train_first(x, scale, bias, ch_axis, eps):
    return nn._bn_train(x, scale, bias, _first(x, ch_axis), ch_axis, eps)


def _two_pass(x, scale, bias, ch_axis, eps=EPS):
    """The exact form: the mean, then the mean of squared distances
    from it, with AD's own backward."""
    axes, bshape = nn._bn_shapes(x, ch_axis)
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=axes, keepdims=True)
    v = jnp.mean(jnp.square(xf - m), axis=axes, keepdims=True)
    y = (xf - m) * (scale.reshape(bshape) * lax.rsqrt(v + eps)) \
        + bias.reshape(bshape)
    ch = x.shape[ch_axis]
    return y.astype(x.dtype), m.reshape(ch), v.reshape(ch)


def _distance(a, b):
    """|a - b| / |b| over all entries as one vector (PR 26's distance)."""
    a = np.concatenate([np.asarray(t, np.float64).ravel() for t in a])
    b = np.concatenate([np.asarray(t, np.float64).ravel() for t in b])
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _assert_close(got, want, dtype):
    if dtype == jnp.float32:
        for g, w in zip(got, want):
            w = np.asarray(w, np.float64)
            np.testing.assert_allclose(
                np.asarray(g, np.float64), w, rtol=F32_RTOL,
                atol=F32_RTOL * float(np.abs(w).max()) + 1e-30)
    else:
        assert _distance(got, want) < BF16_DISTANCE


SHAPES = {
    ('4d', 'NCHW'): ((4, 6, 5, 7), 1),
    ('4d', 'NHWC'): ((4, 5, 7, 6), 3),
    ('2d', 'NCHW'): ((12, 6), 1),
    ('2d', 'NHWC'): ((12, 6), 1),
}


def _op(layout):
    """The registered op in training mode as fn(x, scale, bias, ch_axis,
    eps) -> (Y, SavedMean, SavedVariance): the channel axis is the op's
    own reading of `data_layout`."""
    def fn(x, scale, bias, ch_axis, eps):
        ch = x.shape[ch_axis]
        outs = registry.get_op('batch_norm').impl(
            None, {'X': x, 'Scale': scale, 'Bias': bias,
                   'Mean': jnp.zeros(ch), 'Variance': jnp.ones(ch)},
            {'epsilon': eps, 'data_layout': layout})
        return outs['Y'], outs['SavedMean'], outs['SavedVariance']
    return fn


BEHIND = {
    'plain': lambda y, skip: y,
    'relu': lambda y, skip: jax.nn.relu(y),
    'residual_relu': lambda y, skip: jax.nn.relu(y + skip),
}


def _inputs(shape, ch_axis, dtype, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    ch = shape[ch_axis]
    x = (jax.random.normal(k[0], shape) * 2.0 + 3.0).astype(dtype)
    scale = 1.0 + 0.5 * jax.random.normal(k[1], (ch,))
    bias = jax.random.normal(k[2], (ch,))
    skip = jax.random.normal(k[3], shape).astype(dtype)
    w = jax.random.normal(k[4], shape)
    wm, wv = jax.random.normal(k[5], (ch,)), jax.random.normal(k[6], (ch,))
    return x, scale, bias, skip, w, wm, wv


def _grads(fn, behind, ch_axis, x, scale, bias, skip, w, wm, wv,
           through_stats=False):
    def loss(x, scale, bias, skip):
        y, m, v = fn(x, scale, bias, ch_axis, EPS)
        out = jnp.sum(BEHIND[behind](y, skip).astype(jnp.float32) * w)
        if through_stats:
            out = out + jnp.sum(m * wm) + jnp.sum(v * wv)
        return out
    return jax.grad(loss, (0, 1, 2, 3))(x, scale, bias, skip)


@pytest.mark.parametrize('behind', sorted(BEHIND))
@pytest.mark.parametrize('rank,layout', sorted(SHAPES))
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['f32', 'bf16'])
def test_gradient_matches_ad_of_plain_formula(dtype, rank, layout, behind):
    shape, ch_axis = SHAPES[rank, layout]
    args = _inputs(shape, ch_axis, dtype)
    want = _grads(_oracle, behind, ch_axis, *args)
    got = _grads(_op(layout), behind, ch_axis, *args)
    assert got[0].dtype == dtype and got[1].dtype == jnp.float32
    _assert_close(got, want, dtype)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['f32', 'bf16'])
def test_forward_outputs_are_the_plain_formulas(dtype):
    x, scale, bias = _inputs((4, 6, 5, 7), 1, dtype)[:3]
    for got, want in zip(_bn_train_first(x, scale, bias, 1, EPS),
                         _oracle(x, scale, bias, 1)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['f32', 'bf16'])
def test_gradient_through_saved_mean_and_variance(dtype):
    """SavedMean / SavedVariance are outputs: their cotangents reach x."""
    shape, ch_axis = SHAPES['4d', 'NCHW']
    args = _inputs(shape, ch_axis, dtype, seed=1)
    want = _grads(_oracle, 'relu', ch_axis, *args, through_stats=True)
    got = _grads(_bn_train_first, 'relu', ch_axis, *args,
                 through_stats=True)
    _assert_close(got, want, dtype)


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['f32', 'bf16'])
def test_per_channel_constant_input(dtype):
    """Every channel constant: the raw variance is exactly 0, the clamp
    sits at its tie, x_hat is 0 and dx = scale*rsqrt(eps)*(dy - mean dy)."""
    shape, ch_axis = (4, 6, 5, 7), 1
    _, scale, bias, skip, w, wm, wv = _inputs(shape, ch_axis, dtype)
    x = jnp.broadcast_to(
        jnp.arange(6, dtype=jnp.float32).reshape(1, 6, 1, 1) - 2.0,
        shape).astype(dtype)
    args = (x, scale, bias, skip, w, wm, wv)
    want = _grads(_oracle, 'plain', ch_axis, *args, through_stats=True)
    got = _grads(_bn_train_first, 'plain', ch_axis, *args,
                 through_stats=True)
    assert all(np.all(np.isfinite(np.asarray(g, np.float32))) for g in got)
    np.testing.assert_array_equal(np.asarray(got[1]), 0.0)   # dscale
    _assert_close(got, want, dtype)


def test_no_gradient_through_a_clamped_variance():
    """Where maximum(., 0) binds (raw variance below 0 by rounding),
    nothing flows through the variance: dx = scale*r*(dy - mean(dy)) +
    gm/n and gv is dropped.  Rounding cannot be made to do that at a
    test's sizes (the pilot is one of the sample, so the raw variance is
    at least 1/n of the second moment), so the backward is handed such
    residuals directly (the pilot handed in is the oracle's)."""
    shape, ch_axis = (4, 6, 5, 7), 1
    x, scale, _, _, w, wm, wv = _inputs(shape, ch_axis, jnp.float32)
    _, (_, c, md, v_raw, _) = nn._bn_train_fwd(
        x, scale, scale, _first(x, ch_axis), ch_axis, EPS)
    clamped = -jnp.abs(v_raw) * 1e-3
    dx, dscale, dbias, dpilot = nn._bn_train_bwd(
        ch_axis, EPS, (x, c, md, clamped, scale), (w, wm, wv))
    np.testing.assert_array_equal(np.asarray(dpilot), 0.0)
    n = x.size // shape[ch_axis]
    r = 1.0 / np.sqrt(EPS)
    sr = np.asarray(scale).reshape(1, 6, 1, 1) * r
    wn = np.asarray(w)
    want = sr * (wn - wn.mean(axis=(0, 2, 3), keepdims=True)) \
        + np.asarray(wm).reshape(1, 6, 1, 1) / n
    np.testing.assert_allclose(np.asarray(dx), want, rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(dbias), wn.sum(axis=(0, 2, 3)),
                               rtol=1e-5)
    x_hat = (np.asarray(x) - np.asarray(c) - np.asarray(md)) * r
    np.testing.assert_allclose(np.asarray(dscale),
                               (wn * x_hat).sum(axis=(0, 2, 3)), rtol=1e-4)


# ------------------------------------------------------------- the pilot

def _moments(x, ch_axis):
    """Per-channel mean and standard deviation in float64."""
    flat = np.moveaxis(np.asarray(x, np.float64), ch_axis, 0)
    flat = flat.reshape(flat.shape[0], -1)
    return flat.mean(axis=1), flat.std(axis=1)


PILOTS = {
    'zero': lambda x, m, s: jnp.zeros(m.shape, jnp.float32),
    'mean': lambda x, m, s: jnp.asarray(m, jnp.float32),
    'first': lambda x, m, s: _first(x, 1),
    'mean_plus_3_sigma': lambda x, m, s: jnp.asarray(m + 3 * s, jnp.float32),
    'mean_minus_3_sigma': lambda x, m, s: jnp.asarray(m - 3 * s,
                                                      jnp.float32),
}


@pytest.mark.parametrize('pilot', sorted(PILOTS))
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['f32', 'bf16'])
def test_the_shift_is_a_no_op(dtype, pilot):
    """y, the batch mean and variance and all three gradients against
    the two-pass form under AD, whatever the statistics are shifted by:
    every pilot here lies within the guard's 16 sigma, so each is the
    one read."""
    shape, ch_axis = (4, 6, 5, 7), 1
    args = _inputs(shape, ch_axis, dtype, seed=2)
    x, scale, bias = args[:3]
    c = PILOTS[pilot](x, *_moments(x, ch_axis))
    assert np.array_equal(
        np.asarray(nn._bn_train_fwd(x, scale, bias, c, ch_axis, EPS)[1][1]
                   ).ravel(), np.asarray(c)), 'the guard took the sums again'

    def op(x, scale, bias, ch_axis, eps):
        return nn._bn_train(x, scale, bias, c, ch_axis, eps)
    _assert_close(op(x, scale, bias, ch_axis, EPS),
                  _two_pass(x, scale, bias, ch_axis), dtype)
    want = _grads(_two_pass, 'relu', ch_axis, *args, through_stats=True)
    got = _grads(op, 'relu', ch_axis, *args, through_stats=True)
    _assert_close(got, want, dtype)


def _far_from_zero(ratio, seed=4):
    """f32 [8, 6, 12, 12] with |mean| = ratio * sigma in every channel,
    signs alternating, and its variance per channel in float64."""
    shape = (8, 6, 12, 12)
    sigma = np.array([0.5, 1.0, 2.0, 0.25, 4.0, 1.0]).reshape(1, 6, 1, 1)
    sign = np.array([1, -1, 1, -1, 1, -1]).reshape(1, 6, 1, 1)
    z = np.random.RandomState(seed).normal(size=shape)
    x = jnp.asarray(sigma * (z + ratio * sign), jnp.float32)
    return x, np.asarray(x, np.float64).var(axis=(0, 2, 3))


def _cold_variance(x):
    outs = registry.get_op('batch_norm').impl(
        None, {'X': x, 'Scale': jnp.ones(6), 'Bias': jnp.zeros(6),
               'Mean': jnp.zeros(6), 'Variance': jnp.ones(6)},
        {'epsilon': EPS})
    return np.asarray(outs['SavedVariance'], np.float64)


@pytest.mark.parametrize('ratio,reshifted', [(1.0, False), (1e3, True),
                                             (1e4, True)],
                         ids=['1_sigma', '1e3_sigma', '1e4_sigma'])
def test_a_cold_moving_mean_does_not_cancel(ratio, reshifted):
    """The first step: the moving mean is still 0 and the input's mean
    is `ratio` standard deviations away.  The op's variance stays within
    1e-3 of the two-pass form, because the forward sees the cancellation
    in md and v_raw and takes the sums again around the mean it found;
    at one sigma it does not (the residual pilot is the one handed in)."""
    x, want = _far_from_zero(ratio)
    np.testing.assert_allclose(_cold_variance(x), want, rtol=1e-3)
    _, (_, c, md, _, _) = nn._bn_train_fwd(
        x, jnp.ones(6), jnp.zeros(6), jnp.zeros(6), 1, EPS)
    assert bool(np.any(np.asarray(c) != 0.0)) == reshifted
    if reshifted:    # the second pilot is the first read's mean
        assert np.all(np.abs(np.asarray(md)) * ratio
                      < np.abs(np.asarray(c)))


@pytest.mark.parametrize('ratio', [1e3, 1e4], ids=['1e3_sigma', '1e4_sigma'])
def test_without_the_guard_a_cold_moving_mean_cancels(monkeypatch, ratio):
    """The control of the test above: the same input through the same
    code with the guard's threshold out of reach is the unshifted
    E[x^2] - E[x]^2, wrong by far more than 1e-3."""
    monkeypatch.setattr(nn, '_BN_RESHIFT', float('inf'))
    x, want = _far_from_zero(ratio)
    assert np.max(np.abs(_cold_variance(x) - want) / want) > 2e-2


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['f32', 'bf16'])
def test_the_moving_mean_gets_no_gradient_and_moves_none(dtype):
    """Through the op: no cotangent reaches `Mean`, though MeanOut and
    the pilot both read it, and x's gradient is the same to rounding
    whether the moving mean is 0 or the batch's own mean."""
    shape, ch_axis = (4, 6, 5, 7), 1
    x, scale, bias, _, w, wm, wv = _inputs(shape, ch_axis, dtype, seed=5)

    def loss(x, mean):
        outs = registry.get_op('batch_norm').impl(
            None, {'X': x, 'Scale': scale, 'Bias': bias, 'Mean': mean,
                   'Variance': jnp.ones(6)}, {'epsilon': EPS})
        return jnp.sum(jax.nn.relu(outs['Y']).astype(jnp.float32) * w) \
            + jnp.sum(outs['MeanOut'] * wm) \
            + jnp.sum(outs['SavedMean'] * wm) \
            + jnp.sum(outs['SavedVariance'] * wv)
    batch_mean = jnp.asarray(_moments(x, ch_axis)[0], jnp.float32)
    dx0, dmean0 = jax.grad(loss, (0, 1))(x, jnp.zeros(6))
    dx1, dmean1 = jax.grad(loss, (0, 1))(x, batch_mean)
    np.testing.assert_array_equal(np.asarray(dmean0), 0.0)
    np.testing.assert_array_equal(np.asarray(dmean1), 0.0)
    _assert_close([dx0], [dx1], dtype)


# ------------------------------------------------ what the backward keeps

def _saved(fn, *args):
    from jax._src.ad_checkpoint import saved_residuals
    return [(tuple(aval.shape), str(aval.dtype))
            for aval, _ in saved_residuals(fn, *args)]


def test_bf16_input_saves_one_bf16_array_and_no_f32_copy():
    """The point of the op's custom_vjp: with a bf16 [8,16,14,14] input
    the backward keeps x as it came, and nothing of x's shape in f32."""
    shape = (8, 16, 14, 14)
    x, scale, bias = _inputs(shape, 1, jnp.bfloat16)[:3]
    new = _saved(lambda x, s, b: nn._bn_train(
        x, s, b, jnp.zeros(16), 1, EPS)[0], x, scale, bias)
    full = [dt for shp, dt in new if shp == shape]
    assert full == ['bfloat16'], new
    assert all(int(np.prod(shp)) <= 16 for shp, _ in new if shp != shape)
    # what AD of the plain formula keeps: two f32 arrays of x's shape
    old = _saved(lambda x, s, b: _oracle(x, s, b, 1)[0], x, scale, bias)
    assert [dt for shp, dt in old if shp == shape] == ['float32'] * 2, old


def test_f32_input_saves_one_array_where_ad_saves_two():
    shape = (8, 16, 14, 14)
    x, scale, bias = _inputs(shape, 1, jnp.float32)[:3]
    new = _saved(lambda x, s, b: nn._bn_train(
        x, s, b, jnp.zeros(16), 1, EPS)[0], x, scale, bias)
    assert [dt for shp, dt in new if shp == shape] == ['float32'], new


# ------------------------------------------------------ through a Program

def _conv_bn_relu_conv():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            img = layers.data('img', shape=[3, 8, 8], dtype='float32')
            c = layers.conv2d(img, num_filters=8, filter_size=3, padding=1,
                              bias_attr=False)
            b = layers.batch_norm(c, act='relu')
            c2 = layers.conv2d(b, num_filters=4, filter_size=3, padding=1,
                               bias_attr=False)
            loss = layers.reduce_mean(layers.square(c2))
            pg = fluid.append_backward(loss)
    main.set_amp(True)
    return main, startup, [loss] + [g for _, g in pg]


def _run_program(feed):
    main, startup, fetches = _conv_bn_relu_conv()
    scope = fluid.Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        exe.run(startup)
        return [np.asarray(o, np.float32)
                for o in exe.run(main, feed=feed, fetch_list=fetches)]


@pytest.mark.parametrize('pt_emit', ['0', '1'], ids=['executor', 'emit'])
def test_conv_bn_relu_conv_program_under_amp(monkeypatch, pt_emit):
    """Loss and every parameter gradient of the AMP Program (bf16 conv
    output into batch_norm) against the same Program over the op as it
    was, on the traced path and on the emitter's."""
    monkeypatch.setenv('PT_EMIT', pt_emit)
    monkeypatch.setenv('PT_CACHE', '0')
    feed = {'img': np.random.RandomState(3).normal(
        size=(4, 3, 8, 8)).astype('float32')}
    emit.clear_memo()
    got = _run_program(feed)
    emit.clear_memo()
    monkeypatch.setattr(registry.get_op('batch_norm'), 'impl', _oracle_op)
    try:
        want = _run_program(feed)
    finally:
        emit.clear_memo()
    assert len(got) == len(want) == 5      # loss, 2 filters, scale, bias
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    assert all(np.abs(w).max() > 0 for w in want)
    assert _distance(got[1:], want[1:]) < BF16_DISTANCE


# -------------------------------------------------------------- the counter

def _resnet50_training_block():
    from paddle_tpu.models import resnet
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            built = resnet.build(data_shape=(3, 64, 64), class_dim=10,
                                 depth=50, lr=0.05, data_set='imagenet')
    main.set_amp(True)
    return main, startup, built


@pytest.mark.parametrize('pt_emit,lowered', [('0', 53), ('1', 9)],
                         ids=['executor', 'emit'])
def test_counter_counts_lowerings_of_resnet50(monkeypatch, pt_emit, lowered):
    """`batch_norm.recompute_vjp` counts LOWERINGS of the op in training
    mode: one trace of ResNet-50's training block adds one per batch
    norm (53) where every op is traced on its own, and one per distinct
    signature (nine shapes) where the emitter memoizes an op's function
    by signature.  Read as a delta around one trace."""
    from paddle_tpu.core import executor as ex, passes
    monkeypatch.setenv('PT_EMIT', pt_emit)
    main, startup, built = _resnet50_training_block()
    ops = [op for op in main.global_block().ops if op.type == 'batch_norm']
    assert len(ops) == 53
    scope = fluid.Scope()
    fluid.Executor().run(startup, scope=scope)
    feed_names, fetch_names = ['data', 'label'], [built['loss'].name]
    opt, _ = passes.maybe_optimize(main, fetch_names)
    engine = emit.build_engine(opt, feed_names, fetch_names) \
        if emit.enabled() else None
    emit.clear_memo()
    jit_fn, params_in, _ = ex._lower(opt, feed_names, fetch_names,
                                     emit_engine=engine)
    params = {n: jax.ShapeDtypeStruct(np.shape(scope.vars[n]),
                                      scope.vars[n].dtype)
              for n in params_in}
    feeds = {'data': jax.ShapeDtypeStruct((2, 3, 64, 64), jnp.float32),
             'label': jax.ShapeDtypeStruct((2, 1), jnp.int64)}
    counter = obs.metrics.counter('batch_norm.recompute_vjp')
    before = counter.value
    try:
        jit_fn.trace(params, feeds, jax.ShapeDtypeStruct((), jnp.uint32))
    finally:
        emit.clear_memo()
    assert counter.value - before == lowered


def _conv_bn_loss(feed, mesh):
    """One SGD step's loss of conv -> batch_norm -> relu from a fresh
    start-up (moving mean 0), on one device or over `data=4`."""
    from paddle_tpu.parallel import ParallelExecutor, make_mesh
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        img = layers.data('img', shape=[3, 8, 8], dtype='float32')
        c = layers.conv2d(img, num_filters=8, filter_size=3, padding=1,
                          bias_attr=False)
        loss = layers.reduce_mean(layers.square(
            layers.batch_norm(c, act='relu')))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    fluid.Executor().run(startup, scope=scope)
    if mesh:
        pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                              scope=scope, mesh=make_mesh(
                                  data=4, devices=jax.devices()[:4]))
        out, = pe.run(fetch_list=[loss.name], feed=feed)
    else:
        out, = fluid.Executor().run(main, feed=feed, fetch_list=[loss],
                                    scope=scope)
    return float(np.asarray(out).ravel()[0])


@pytest.mark.parametrize('offset', [0.0, 1e4], ids=['near', 'far'])
def test_the_guard_under_a_data_mesh(offset, monkeypatch):
    """The conditional's predicate comes from sums over the sharded
    batch: every device takes the same branch, the common one and the
    one that reads x again (images 10^4 sigma from 0), and the step is
    the one-device step."""
    monkeypatch.setenv('PT_CACHE', '0')
    feed = {'img': (np.random.RandomState(3).normal(size=(8, 3, 8, 8))
                    + offset).astype('float32')}
    want = _conv_bn_loss(feed, mesh=False)
    assert 0.3 < want < 0.7      # E[relu(n)^2] = 0.5 for a standard normal n
    np.testing.assert_allclose(_conv_bn_loss(feed, mesh=True), want,
                               rtol=1e-5)


# ------------------------------------------- what XLA:TPU makes of the pilot
#
# The moving-mean pilot exists before the convolution that writes x
# starts, so XLA:TPU puts the two sums into that convolution's epilogue;
# a pilot cut from x gives every batch norm a loop fusion of its own
# that reads x back (53 of them, 2.7 GB a step, in ResNet-50 at batch
# 128: PERF.md, PR 60).  Read off the compiled text of ResNet-50's
# layer-1 shapes, for a described v5e chip.

LAYER1 = (128, 64, 56, 56)


def _layer1_text(one_v5e_chip):
    """conv 1x1 -> batch_norm -> relu -> conv 3x3 -> batch_norm under AMP
    with a momentum step, at [128,64,56,56], as `Executor._prepare_entry`
    lowers it (rewriter, emitter, `_lower`), compiled for the chip."""
    from paddle_tpu.core import executor as ex, passes
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 23
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        img = layers.data('img', shape=list(LAYER1[1:]), dtype='float32')
        c1 = layers.conv2d(img, num_filters=64, filter_size=1,
                           bias_attr=False)
        b1 = layers.batch_norm(c1, act='relu')
        c2 = layers.conv2d(b1, num_filters=64, filter_size=3, padding=1,
                           bias_attr=False)
        loss = layers.reduce_mean(layers.square(layers.batch_norm(c2)))
        fluid.optimizer.Momentum(learning_rate=0.05,
                                 momentum=0.9).minimize(loss)
    main.set_amp(True)
    scope = fluid.Scope()
    fluid.Executor().run(startup, scope=scope)
    opt, _ = passes.optimize_program(main, (loss.name,))
    emit.clear_memo()
    jit_fn, params_in, _ = ex._lower(
        opt, ('img',), (loss.name,),
        emit_engine=emit.build_engine(opt, ('img',), (loss.name,)))

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)
    params = {n: struct(np.shape(scope.vars[n]), scope.vars[n].dtype)
              for n in params_in}
    try:
        return jit_fn.lower(params, {'img': struct(LAYER1, jnp.float32)},
                            struct((), jnp.uint32)).compile().as_text()
    finally:
        emit.clear_memo()


def _forward_fusions(text):
    """(root type, body, inside a conditional's branch) of every fusion
    in the compiled module that the forward of a batch norm or a
    convolution gave its name."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r'^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$', line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    branches = set()
    for m in re.finditer(r' conditional\(.*?(?:branch_computations=\{'
                         r'([^}]*)\}|true_computation=([^,]*), '
                         r'false_computation=([^,\s]*))', text):
        branches.update(n.strip().lstrip('%') for g in m.groups() if g
                        for n in g.split(','))
    out = []
    for comp, lines in comps.items():
        for line in lines:
            m = re.match(r'\s+(?:ROOT )?%[\w.\-]+ = (.*?) fusion\(.*?'
                         r'calls=%([\w.\-]+)', line)
            op = re.search(r'op_name="([^"]*)"', line)
            if m and op and 'transpose(' not in op.group(1) and (
                    'jvp(batch_norm)' in op.group(1)
                    or 'jvp(conv2d)' in op.group(1)):
                out.append((m.group(1), '\n'.join(comps[m.group(2)]),
                            comp in branches))
    return out


def _census(text):
    """(loop fusions OUTSIDE any conditional that reduce an array of the
    activation's extents, forward convolution fusions that return the
    two sums beside their bf16 output, conditionals, conditionals that
    return an array of the activation's extents)."""
    act = r'\[%d,%d,%d,%d\]' % LAYER1
    fusions = _forward_fusions(text)
    reads = [1 for _, body, in_branch in fusions
             if not in_branch and ' convolution(' not in body
             and ' reduce(' in body and re.search(act, body)]
    sums = [1 for root, body, _ in fusions if ' convolution(' in body
            and re.match(r'\(f32\[64\]\S*, f32\[64\]\S*, bf16' + act, root)]
    conds = re.findall(r'= (.*?) conditional\(', text)
    return (len(reads), len(sums), len(conds),
            sum(1 for c in conds if re.search(act, c)))


def test_the_sums_ride_in_the_convolution_that_writes_their_input(
        one_v5e_chip):
    """No fusion of its own reads a convolution's output back for batch
    norm's statistics; both forward convolutions return (sum, sum of
    squares, bf16 output); the guard is one conditional a batch norm,
    whose results are per-channel vectors."""
    assert _census(_layer1_text(one_v5e_chip)) == (0, 2, 2, 0)


def test_a_pilot_cut_from_the_input_would_show(one_v5e_chip, monkeypatch):
    """The control: with the pilot the op's first element, as it was
    cut until PR 60 (`_oracle_op`), each batch norm reads its input
    back in a loop fusion of its own (and AD's backward in more) and no
    convolution carries a sum."""
    monkeypatch.setattr(registry.get_op('batch_norm'), 'impl', _oracle_op)
    reads, sums, conds, _ = _census(_layer1_text(one_v5e_chip))
    assert reads >= 2 and (sums, conds) == (0, 0)
