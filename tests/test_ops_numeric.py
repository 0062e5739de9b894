"""Per-op numeric checks vs independent numpy references (model:
reference tests/unittests per-op OpTest forward checks) for ops that
previously had build-and-run coverage only (test_layers.py) but no
value assertions."""
import warnings

import numpy as np
import pytest

from paddle_tpu import layers
from test_layers import _run


def test_activation_family_numeric():
    x = layers.data('x', shape=[6], dtype='float32')
    outs = [layers.brelu(x, t_min=-0.5, t_max=0.8),
            layers.soft_relu(x, threshold=40.0),
            layers.relu6(x),
            layers.pow(x, factor=3.0),
            layers.stanh(x, scale_a=0.67, scale_b=1.7159),
            layers.softshrink(x, alpha=0.4),
            layers.hard_shrink(x, threshold=0.4),
            layers.thresholded_relu(x, threshold=0.3),
            layers.selu(x)]
    xv = np.linspace(-2, 2, 12).reshape(2, 6).astype('float32')
    res = _run(outs, {'x': xv})
    np.testing.assert_allclose(res[0], np.clip(xv, -0.5, 0.8), rtol=1e-6)
    np.testing.assert_allclose(res[1], np.log1p(np.exp(xv)), rtol=1e-5)
    np.testing.assert_allclose(res[2], np.clip(xv, 0, 6), rtol=1e-6)
    np.testing.assert_allclose(res[3], xv ** 3, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(res[4], 1.7159 * np.tanh(0.67 * xv),
                               rtol=1e-5)
    np.testing.assert_allclose(
        res[5], np.sign(xv) * np.maximum(np.abs(xv) - 0.4, 0), rtol=1e-5,
        atol=1e-7)
    np.testing.assert_allclose(res[6], np.where(np.abs(xv) > 0.4, xv, 0),
                               rtol=1e-6)
    np.testing.assert_allclose(res[7], np.where(xv > 0.3, xv, 0),
                               rtol=1e-6)
    # selu defaults (reference selu_op): scale/alpha from Klambauer et al.
    scale, alpha = 1.0507009873554805, 1.6732632423543772
    np.testing.assert_allclose(
        res[8], np.where(xv > 0, scale * xv,
                         scale * alpha * (np.exp(xv) - 1)), rtol=1e-5)


def test_shape_manipulation_numeric():
    x = layers.data('x', shape=[2, 3], dtype='float32')
    outs = [layers.expand(x, expand_times=[1, 2, 1]),
            layers.space_to_depth(
                layers.data('sd', shape=[4, 2, 2], dtype='float32'),
                blocksize=2)]
    xv = np.arange(12).reshape(2, 2, 3).astype('float32')
    sdv = np.arange(32).reshape(2, 4, 2, 2).astype('float32')
    res = _run(outs, {'x': xv, 'sd': sdv})
    np.testing.assert_allclose(res[0], np.tile(xv, (1, 2, 1)), rtol=1e-6)
    # space_to_depth blocksize 2 (reference space_to_depth_op.cc layout):
    # [N, C, H, W] -> [N, bs*bs*C, H/2, W/2], block-offset-major channels
    assert res[1].shape == (2, 16, 1, 1)
    ref_sd = sdv.reshape(2, 4, 1, 2, 1, 2).transpose(
        0, 3, 5, 1, 2, 4).reshape(2, 16, 1, 1)
    np.testing.assert_allclose(res[1], ref_sd)


def test_unstack_multiplex_shuffle_channel():
    x = layers.data('x', shape=[2, 3], dtype='float32')
    parts = layers.unstack(x, axis=1)
    a = layers.data('a', shape=[4], dtype='float32')
    b = layers.data('b', shape=[4], dtype='float32')
    idx = layers.data('idx', shape=[1], dtype='int32')
    mux = layers.multiplex([a, b], idx)
    sc = layers.data('sc', shape=[4, 1, 1], dtype='float32')
    shuf = layers.shuffle_channel(sc, group=2)
    xv = np.arange(12).reshape(2, 2, 3).astype('float32')
    av = np.ones((3, 4), 'float32')
    bv = np.zeros((3, 4), 'float32')
    iv = np.array([[0], [1], [0]], 'int32')
    scv = np.arange(8, dtype='float32').reshape(2, 4, 1, 1)
    res = _run([parts[0], parts[1], mux, shuf],
               {'x': xv, 'a': av, 'b': bv, 'idx': iv, 'sc': scv})
    np.testing.assert_allclose(res[0], xv[:, 0])
    np.testing.assert_allclose(res[1], xv[:, 1])
    np.testing.assert_allclose(res[2], np.stack([av[0], bv[1], av[2]]))
    # shuffle_channel group=2 on C=4: [0,1,2,3] -> [0,2,1,3]
    np.testing.assert_allclose(res[3][:, :, 0, 0],
                               scv[:, [0, 2, 1, 3], 0, 0])


def test_pad_crop_numeric():
    x = layers.data('x', shape=[1, 2, 2], dtype='float32')
    big = layers.data('big', shape=[1, 4, 4], dtype='float32')
    outs = [layers.pad2d(x, paddings=[1, 0, 0, 1], pad_value=9.0),
            layers.pad_constant_like(big, x, pad_value=-1.0),
            layers.crop(big, shape=[1, 1, 2, 2], offsets=[0, 0, 1, 1])]
    xv = np.arange(4, dtype='float32').reshape(1, 1, 2, 2)
    bigv = np.arange(16, dtype='float32').reshape(1, 1, 4, 4)
    res = _run(outs, {'x': xv, 'big': bigv})
    ref_pad = np.pad(xv, [(0, 0), (0, 0), (1, 0), (0, 1)],
                     constant_values=9.0)
    np.testing.assert_allclose(res[0], ref_pad)
    ref_pcl = np.pad(xv, [(0, 0), (0, 0), (0, 2), (0, 2)],
                     constant_values=-1.0)
    np.testing.assert_allclose(res[1], ref_pcl)
    np.testing.assert_allclose(res[2], bigv[:, :, 1:3, 1:3])


def test_norm_family_numeric():
    x = layers.data('x', shape=[3, 4], dtype='float32')
    img = layers.data('img', shape=[4, 2, 2], dtype='float32')
    sc = np.array([2.0, -1.0, 0.5, 3.0], 'float32')
    bi = np.array([0.1, 0.2, -0.1, 0.0], 'float32')
    outs = [layers.l2_normalize(x, axis=-1),
            layers.clip_by_norm(x, max_norm=1.0),
            layers.affine_channel(img, scale=layers.assign(sc),
                                  bias=layers.assign(bi)),
            layers.lrn(img, n=3, k=1.0, alpha=1e-2, beta=0.5)]
    rng = np.random.RandomState(0)
    xv = rng.randn(2, 3, 4).astype('float32')
    iv = rng.rand(2, 4, 2, 2).astype('float32')
    res = _run(outs, {'x': xv, 'img': iv})
    np.testing.assert_allclose(
        res[0], xv / np.sqrt((xv * xv).sum(-1, keepdims=True) + 1e-12),
        rtol=1e-5)
    gn = np.sqrt((xv * xv).sum())
    ref_clip = xv * min(1.0, 1.0 / gn)
    np.testing.assert_allclose(res[1], ref_clip, rtol=1e-5)
    np.testing.assert_allclose(
        res[2], iv * sc.reshape(1, 4, 1, 1) + bi.reshape(1, 4, 1, 1),
        rtol=1e-5)
    sq = np.pad(iv * iv, [(0, 0), (1, 1), (0, 0), (0, 0)])
    acc = sum(sq[:, i:i + 4] for i in range(3))
    np.testing.assert_allclose(res[3], iv / (1.0 + 1e-2 * acc) ** 0.5,
                               rtol=1e-5)


def test_add_position_encoding_numeric():
    x = layers.data('x', shape=[4, 6], dtype='float32')
    out = layers.add_position_encoding(x, alpha=0.5, beta=2.0)
    xv = np.random.RandomState(1).randn(2, 4, 6).astype('float32')
    res, = _run([out], {'x': xv})
    T, D = 4, 6
    pe = np.zeros((T, D), 'float32')
    pos = np.arange(T)[:, None].astype('float64')
    # reference add_position_encoding_op: div = 10000^(i / (D/2)),
    # first half sin, second half cos
    div = np.power(10000.0, np.arange(D // 2) / (D // 2))
    pe[:, :D // 2] = np.sin(pos / div)
    pe[:, D // 2:] = np.cos(pos / div)
    np.testing.assert_allclose(res, 0.5 * xv + 2.0 * pe[None], rtol=1e-4,
                               atol=1e-5)


def test_indexing_ops_numeric():
    x = layers.data('x', shape=[5], dtype='float32')
    vals, idxs = layers.topk(x, k=2)
    am = layers.argmax(x, axis=1)
    an = layers.argmin(x, axis=1)
    src = layers.data('src', shape=[4], dtype='float32',
                      append_batch_size=False)
    sidx = layers.data('sidx', shape=[2], dtype='int32',
                       append_batch_size=False)
    upd = layers.data('upd', shape=[2], dtype='float32',
                      append_batch_size=False)
    sc = layers.scatter(src, sidx, upd)
    xv = np.array([[3., 1., 4., 1., 5.], [2., 7., 1., 8., 2.]], 'float32')
    srcv = np.array([0., 10., 20., 30.], 'float32')
    sidxv = np.array([3, 1], 'int32')
    updv = np.array([-1., -2.], 'float32')
    res = _run([vals, idxs, am, an, sc],
               {'x': xv, 'src': srcv, 'sidx': sidxv, 'upd': updv})
    np.testing.assert_allclose(res[0], np.sort(xv, axis=1)[:, -1:-3:-1])
    assert res[1].tolist() == [[4, 2], [3, 1]]
    assert res[2].tolist() == [4, 3]
    assert res[3].tolist() == [1, 2]
    np.testing.assert_allclose(res[4], np.array([0., -2., 20., -1.]))


def test_loss_family_numeric():
    p = layers.data('p', shape=[1], dtype='float32')
    lbl = layers.data('lbl', shape=[1], dtype='float32')
    left = layers.data('left', shape=[1], dtype='float32')
    right = layers.data('right', shape=[1], dtype='float32')
    logits = layers.data('logits', shape=[4], dtype='float32')
    ilbl = layers.data('ilbl', shape=[1], dtype='int64')
    prob = layers.data('prob', shape=[4], dtype='float32')
    outs = [layers.log_loss(p, lbl, epsilon=1e-4),
            layers.rank_loss(lbl, left, right),
            layers.margin_rank_loss(lbl, left, right, margin=0.2),
            layers.huber_loss(p, lbl, delta=1.0),
            layers.bpr_loss(logits, ilbl),
            layers.dice_loss(prob, layers.fill_constant_batch_size_like(
                ilbl, [-1, 1], 'float32', 1.0)),
            layers.teacher_student_sigmoid_loss(p, lbl)]
    rng = np.random.RandomState(2)
    pv = rng.rand(3, 1).astype('float32') * 0.8 + 0.1
    lv = (rng.rand(3, 1) > 0.5).astype('float32')
    lf = rng.randn(3, 1).astype('float32')
    rt = rng.randn(3, 1).astype('float32')
    lg = rng.randn(3, 4).astype('float32')
    il = rng.randint(0, 4, (3, 1)).astype('int64')
    pr = rng.rand(3, 4).astype('float32')
    res = _run(outs, {'p': pv, 'lbl': lv, 'left': lf, 'right': rt,
                      'logits': lg, 'ilbl': il, 'prob': pr})
    np.testing.assert_allclose(
        res[0], -lv * np.log(pv + 1e-4) - (1 - lv) * np.log(1 - pv + 1e-4),
        rtol=1e-5)
    d = lf - rt
    np.testing.assert_allclose(res[1], np.log1p(np.exp(d)) - lv * d,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        res[2], np.maximum(0.0, -lv * (lf - rt) + 0.2), rtol=1e-5,
        atol=1e-7)
    r = lv - pv
    np.testing.assert_allclose(
        res[3], np.where(np.abs(r) <= 1.0, 0.5 * r * r,
                         np.abs(r) - 0.5), rtol=1e-5, atol=1e-7)
    # bpr: mean over non-target classes of -log sigmoid(pos - x_j)
    pos = np.take_along_axis(lg, il.astype(int), axis=1)
    sig = 1 / (1 + np.exp(-(pos - lg)))
    mask = np.ones_like(lg)
    np.put_along_axis(mask, il.astype(int), 0.0, axis=1)
    ref_bpr = (-np.log(sig + 1e-8) * mask).sum(1, keepdims=True) / 3.0
    np.testing.assert_allclose(res[4], ref_bpr, rtol=1e-4)
    ones = np.ones((3, 1), 'float32')
    inter = 2 * (pr * ones).sum(1)
    union = pr.sum(1) + ones.sum(1)
    np.testing.assert_allclose(res[5].ravel(),
                               1 - inter / (union + 1e-5), rtol=1e-5)
    z = pv  # within clip bounds
    np.testing.assert_allclose(
        res[6], np.log1p(np.exp(-np.abs(z))) + np.maximum(z, 0) - z * lv,
        rtol=1e-5)


def test_resize_numeric():
    x = layers.data('x', shape=[1, 2, 2], dtype='float32')
    bi = layers.resize_bilinear(x, out_shape=[3, 3])
    ne = layers.resize_nearest(x, out_shape=[4, 4])
    xv = np.array([[[[0., 1.], [2., 3.]]]], 'float32')
    res = _run([bi, ne], {'x': xv})
    # align_corners=True (reference default): src = i*(in-1)/(out-1)
    ref = np.array([[0., .5, 1.], [1., 1.5, 2.], [2., 2.5, 3.]])
    np.testing.assert_allclose(res[0][0, 0], ref, rtol=1e-5, atol=1e-6)
    # nearest 2x upscale: each source pixel repeated 2x2
    ref_ne = np.repeat(np.repeat(xv, 2, axis=2), 2, axis=3)
    np.testing.assert_allclose(res[1], ref_ne)


def test_mean_iou_numeric():
    pred = layers.data('pred', shape=[4], dtype='int64')
    lab = layers.data('lab', shape=[4], dtype='int64')
    miou, wrong, correct = layers.mean_iou(pred, lab, num_classes=3)
    pv = np.array([[0, 1, 2, 1]], 'int64')
    lv = np.array([[0, 1, 1, 1]], 'int64')
    res = _run([miou, wrong, correct], {'pred': pv, 'lab': lv})
    # class0: i=1 u=1; class1: i=2 u=3 (pred has 2, label has 3, inter 2);
    # class2: i=0 u=1
    np.testing.assert_allclose(res[0], [(1 / 1 + 2 / 3 + 0) / 3],
                               rtol=1e-5)
    np.testing.assert_allclose(res[1], [0., 1., 0.])  # label-row misses
    np.testing.assert_allclose(res[2], [1., 2., 0.])  # diagonal hits


def test_random_ops_shapes_and_ranges():
    g = layers.gaussian_random(shape=[64, 8], mean=1.0, std=2.0, seed=7)
    u = layers.uniform_random_batch_size_like(
        layers.data('x', shape=[3], dtype='float32'), shape=[-1, 5],
        min=-1.0, max=1.0)
    sid = layers.sampling_id(layers.softmax(
        layers.data('pp', shape=[4], dtype='float32')), seed=3)
    xv = np.zeros((6, 3), 'float32')
    ppv = np.random.RandomState(3).rand(6, 4).astype('float32')
    res = _run([g, u, sid], {'x': xv, 'pp': ppv})
    assert res[0].shape == (64, 8)
    assert abs(res[0].mean() - 1.0) < 0.8
    assert res[1].shape == (6, 5)
    assert res[1].min() >= -1.0 and res[1].max() <= 1.0
    assert res[2].shape[0] == 6
    assert ((res[2] >= 0) & (res[2] < 4)).all()


def test_hash_deterministic():
    x = layers.data('x', shape=[2], dtype='int64')
    h = layers.hash(x, hash_size=1000)
    xv = np.array([[3, 5], [3, 5], [7, 9]], 'int64')
    res, = _run([h], {'x': xv})
    assert ((res >= 0) & (res < 1000)).all()
    np.testing.assert_array_equal(res[0], res[1])
    assert not np.array_equal(res[0], res[2])


_GRAD_CASES = [
    # (op, ins builder, attrs) — forward vs numpy is covered above /
    # in test_layers; here the VJP is checked against central difference
    ('l2_norm_layer', lambda r: {'X': r.randn(3, 5)}, {}),
    ('lrn', lambda r: {'X': r.rand(2, 4, 3, 3) + 0.5},
     {'n': 3, 'k': 1.0, 'alpha': 0.01, 'beta': 0.75}),
    ('maxout', lambda r: {'X': r.randn(2, 4, 3, 3)}, {'groups': 2}),
    ('selu', lambda r: {'X': r.randn(3, 4)}, {}),
    ('huber_loss', lambda r: {'X': r.randn(4, 1), 'Y': r.randn(4, 1)},
     {'delta': 1.0}),
    ('prelu', lambda r: {'X': r.randn(3, 4), 'Alpha': np.array([0.25])},
     {'mode': 'all'}),
    ('grid_sampler',
     lambda r: {'X': r.rand(1, 2, 4, 4),
                'Grid': r.uniform(-0.9, 0.9, (1, 3, 3, 2))}, {}),
    ('softshrink', lambda r: {'X': r.randn(3, 4) * 2}, {'lambda': 0.3}),
]


@pytest.mark.parametrize('case', _GRAD_CASES, ids=lambda c: c[0])
def test_op_gradients_vs_numeric_diff(case):
    """Model: reference OpTest.check_grad — analytic (jax.vjp) gradient
    of sum(outputs[first]) wrt each float input vs central difference."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.registry import get_op
    op_type, build, attrs = case
    impl = get_op(op_type).impl
    rng = np.random.RandomState(11)
    ins = {k: np.asarray(v, 'float32') for k, v in build(rng).items()}
    outs = impl(None, {k: jnp.asarray(v) for k, v in ins.items()}, attrs)
    # the primary output, not an auxiliary (lrn also emits MidOut)
    first_out = 'Out' if 'Out' in outs else sorted(outs.keys())[0]

    def f(d):
        out = impl(None, d, attrs)[first_out]
        return jnp.sum(out.astype(jnp.float32))

    grads = jax.grad(lambda d: f({k: jnp.asarray(v) for k, v in
                                  d.items()}))(ins)
    eps = 1e-3
    for name, x in ins.items():
        g = np.asarray(grads[name])
        num = np.zeros_like(x)
        flat = x.ravel()
        nf = num.ravel()
        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + eps
            up = float(f({k: jnp.asarray(v) for k, v in ins.items()}))
            flat[i] = orig - eps
            dn = float(f({k: jnp.asarray(v) for k, v in ins.items()}))
            flat[i] = orig
            nf[i] = (up - dn) / (2 * eps)
        np.testing.assert_allclose(
            g, num, rtol=5e-2, atol=5e-3,
            err_msg='%s grad wrt %s' % (op_type, name))


def test_py_func_forward_and_backward():
    """py_func: host callable as an op (pure_callback lowering), with a
    backward_func-driven custom VJP reaching the parameter gradients."""
    import paddle_tpu as fluid

    def double_plus(a):
        return a * 2.0 + 1.0

    def double_plus_bwd(a, out, dout):
        return dout * 2.0

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            d = layers.data('x', shape=[3], dtype='float32')
            w = layers.create_parameter([3, 3], 'float32', name='pyf_w')
            h = layers.matmul(d, w)
            out_var = layers.create_tensor('float32', name='pyf_out')
            out_var.shape = (-1, 3)
            layers.py_func(double_plus, h, out_var,
                           backward_func=double_plus_bwd)
            loss = layers.reduce_mean(out_var)
            fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
    exe = fluid.Executor()
    scope = fluid.Scope()
    xv = np.ones((2, 3), 'float32')
    with fluid.scope_guard(scope):
        exe.run(startup)
        w0 = np.asarray(scope.get('pyf_w')).copy()
        l1, o1 = exe.run(main, feed={'x': xv}, fetch_list=[loss, out_var])
        w1 = np.asarray(scope.get('pyf_w'))
    np.testing.assert_allclose(o1, xv @ w0 * 2.0 + 1.0, rtol=1e-5)
    # dL/dw = x^T @ (dout * 2) with dout = 1/6
    ref_gw = xv.T @ (np.full((2, 3), 2.0 / 6.0, 'float32'))
    np.testing.assert_allclose(w1, w0 - 0.5 * ref_gw, rtol=1e-4)


def test_py_func_no_backward_cuts_gradient():
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            d = layers.data('x', shape=[3], dtype='float32')
            w = layers.create_parameter([3, 3], 'float32', name='pyf2_w')
            h = layers.matmul(d, w)
            out_var = layers.create_tensor('float32', name='pyf2_out')
            out_var.shape = (-1, 3)
            layers.py_func(lambda a: a + 1.0, h, out_var)
            loss = layers.reduce_mean(out_var)
            fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        w0 = np.asarray(scope.get('pyf2_w')).copy()
        exe.run(main, feed={'x': np.ones((2, 3), 'float32')},
                fetch_list=[loss])
        w1 = np.asarray(scope.get('pyf2_w'))
    np.testing.assert_allclose(w1, w0)  # gradient cut: no update


def test_sequence_erase_compacts_and_relengths():
    import jax.numpy as jnp
    from paddle_tpu.core.registry import get_op
    ids = jnp.asarray([[3, 5, 3, 7, 0, 0],
                       [5, 5, 5, 1, 2, 9]])
    lens = jnp.asarray([4, 6], jnp.int32)
    outs = get_op('sequence_erase').impl(
        None, {'X': ids, 'Length': lens}, {'tokens': [3, 5]})
    np.testing.assert_array_equal(
        np.asarray(outs['Out']),
        [[7, 0, 0, 0, 0, 0],   # row0 [3,5,3,7]: erase 3s and 5s -> [7]
         [1, 2, 9, 0, 0, 0]])  # row1: erase 5s -> [1, 2, 9]
    np.testing.assert_array_equal(np.asarray(outs['OutLength']), [1, 3])


def test_fill_constant_int64_overflow_is_silent():
    """The documented warn-and-truncate contract: an overflowing int64
    fill wraps like the reference C++ cast with NO numpy RuntimeWarning
    (which would be fatal under warnings-as-errors CI)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            c = layers.fill_constant(shape=[2], dtype='int64',
                                     value=2 ** 40)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with warnings.catch_warnings():
            warnings.simplefilter('error')
            out, = exe.run(main_prog, fetch_list=[c])
    # int64 stores as int32 (the TPU warn-and-truncate policy); the
    # out-of-range value truncates (wrap or saturate is backend-defined)
    # — the contract under test is that NO warning escaped above
    assert out.dtype == np.int32
    assert int(out[0]) != 2 ** 40
