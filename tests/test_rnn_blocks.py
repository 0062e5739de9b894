"""StaticRNN / DynamicRNN / IfElse block builders.

Model: reference tests/unittests/test_recurrent_op.py, test_dyn_rnn.py,
test_ifelse.py and the book MT decoder pattern
(tests/book/test_machine_translation.py / test_rnn_encoder_decoder.py).
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core.lod import create_lod_tensor


# ------------------------------------------------------------- StaticRNN

def test_static_rnn_matches_manual_loop():
    T, B, D = 5, 3, 4
    x = fluid.layers.data('x', shape=[T, B, D], dtype='float32',
                          append_batch_size=False)
    h0 = fluid.layers.data('h0', shape=[B, D], dtype='float32',
                           append_batch_size=False)
    rnn = layers.StaticRNN()
    with rnn.step():
        xt = rnn.step_input(x)
        h = rnn.memory(init=h0)
        nh = layers.scale(h, scale=0.5) + xt
        rnn.update_memory(h, nh)
        rnn.output(nh)
    out = rnn()
    assert tuple(out.shape) == (T, B, D)
    rng = np.random.RandomState(0)
    xv = rng.rand(T, B, D).astype('float32')
    h0v = rng.rand(B, D).astype('float32')
    exe = fluid.Executor()
    got, = exe.run(feed={'x': xv, 'h0': h0v}, fetch_list=[out])
    want = np.zeros((T, B, D), np.float32)
    h = h0v
    for t in range(T):
        h = h * 0.5 + xv[t]
        want[t] = h
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_static_rnn_boot_memory_and_training():
    """memory(shape=, batch_ref=) boot path + gradients flow through the
    scan: a tiny seq regressor trains to a much lower loss."""
    T, B, D, H = 4, 8, 3, 6
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[T, B, D], dtype='float32',
                              append_batch_size=False)
        y = fluid.layers.data('y', shape=[B, 1], dtype='float32',
                              append_batch_size=False)
        rnn = layers.StaticRNN()
        with rnn.step():
            xt = rnn.step_input(x)
            h = rnn.memory(shape=[-1, H], batch_ref=xt,
                           init_batch_dim_idx=0, ref_batch_dim_idx=0)
            nh = layers.fc(layers.concat([xt, h], axis=1), H, act='tanh')
            rnn.update_memory(h, nh)
            rnn.output(nh)
        seq = rnn()                      # [T, B, H]
        last = layers.slice(seq, axes=[0], starts=[T - 1], ends=[T])
        last = layers.reshape(last, [B, H])
        pred = layers.fc(last, 1)
        loss = layers.reduce_mean(layers.square(pred - y))
        fluid.optimizer.AdamOptimizer(0.01).minimize(loss)
    rng = np.random.RandomState(1)
    w = rng.rand(D, 1).astype('float32')
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = []
        for _ in range(80):
            xv = rng.rand(T, B, D).astype('float32')
            yv = xv.sum(axis=0) @ w
            lv, = exe.run(main, feed={'x': xv, 'y': yv},
                          fetch_list=[loss])
            losses.append(float(np.asarray(lv).reshape(())))
    assert losses[-1] < losses[0] * 0.2, (losses[0], losses[-1])


def test_static_rnn_memory_without_update_carries_through():
    T, B, D = 3, 2, 2
    x = fluid.layers.data('x', shape=[T, B, D], dtype='float32',
                          append_batch_size=False)
    h0 = fluid.layers.data('h0', shape=[B, D], dtype='float32',
                           append_batch_size=False)
    rnn = layers.StaticRNN()
    with rnn.step():
        xt = rnn.step_input(x)
        h = rnn.memory(init=h0)          # never updated -> constant
        rnn.output(xt + h)
    rng = np.random.RandomState(2)
    xv = rng.rand(T, B, D).astype('float32')
    h0v = rng.rand(B, D).astype('float32')
    got, = fluid.Executor().run(feed={'x': xv, 'h0': h0v},
                                fetch_list=[rnn()])
    np.testing.assert_allclose(np.asarray(got), xv + h0v[None], rtol=1e-6)


# ------------------------------------------------------------ DynamicRNN

def _ragged_batch(rng, lens, D):
    return create_lod_tensor([rng.rand(l, D).astype('float32')
                              for l in lens])


def test_dynamic_rnn_masks_and_freezes():
    """Running sum over ragged rows: outputs are zero past each row's
    length and the memory freezes at the row's last valid step."""
    D = 3
    lens = [4, 2, 5]
    x = fluid.layers.data('x', shape=[D], dtype='float32', lod_level=1)
    drnn = layers.DynamicRNN()
    with drnn.block():
        xt = drnn.step_input(x)
        acc = drnn.memory(shape=[D], value=0.0)
        nacc = acc + xt
        drnn.update_memory(acc, nacc)
        drnn.output(nacc)
    out = drnn()
    last = layers.sequence_last_step(out)
    rng = np.random.RandomState(3)
    lod = _ragged_batch(rng, lens, D)
    exe = fluid.Executor()
    ov, lv = exe.run(feed={'x': lod}, fetch_list=[out, last])
    ov = np.asarray(ov)
    T = max(lens)
    assert ov.shape == (len(lens), T, D)
    for i, L in enumerate(lens):
        want = np.cumsum(lod.padded[i, :L], axis=0)
        np.testing.assert_allclose(ov[i, :L], want, rtol=1e-5)
        # zero padding past the row's length
        np.testing.assert_allclose(ov[i, L:], 0.0)
        # sequence_last_step picks the row's own last valid step
        np.testing.assert_allclose(np.asarray(lv)[i], want[-1], rtol=1e-5)


def test_dynamic_rnn_mt_decoder_trains_and_decodes():
    """The book machine-translation decoder pattern
    (reference tests/book/test_machine_translation.py:68): encoder last
    state boots the decoder DynamicRNN memory; per-step fc emits word
    scores; trained with cross-entropy, then decoded from a test clone."""
    V, E, H = 20, 8, 12
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            src = fluid.layers.data('src', shape=[1], dtype='int64',
                                    lod_level=1)
            trg = fluid.layers.data('trg', shape=[1], dtype='int64',
                                    lod_level=1)
            lab = fluid.layers.data('lab', shape=[1], dtype='int64',
                                    lod_level=1)
            semb = layers.embedding(src, size=[V, E])
            enc = layers.sequence_pool(semb, 'last')    # [B, E]
            enc_h = layers.fc(enc, H, act='tanh')
            temb = layers.embedding(trg, size=[V, E])   # [B, T, E]
            drnn = layers.DynamicRNN()
            with drnn.block():
                word = drnn.step_input(temb)            # [B, E]
                prev = drnn.memory(init=enc_h)
                h = layers.fc(layers.concat([word, prev], axis=1), H,
                              act='tanh')
                drnn.update_memory(prev, h)
                drnn.output(h)
            dec = drnn()                                # [B, T, H] lod
            # dec carries lod, so fc's lod-aware num_flatten_dims bump
            # makes the default a per-token projection (ref: fc(drnn_out,
            # size=V) on the packed LoD tensor)
            logits = layers.fc(dec, V)
            ce = layers.softmax_with_cross_entropy(logits, lab,
                                                   soft_label=False)
            # mean over VALID positions only — padded steps have zeroed
            # decoder outputs and must not contribute loss.  sequence_pool
            # masks by the lod lengths, no static maxlen needed.
            from paddle_tpu.layers.nn import _copy_lod, _len_var
            _copy_lod(lab, ce)
            per_seq = layers.sequence_pool(ce, 'sum')       # [B, 1]
            n_tok = layers.cast(
                layers.reduce_sum(_len_var(lab)), 'float32')
            loss = layers.reduce_sum(per_seq) / n_tok
            fluid.optimizer.AdamOptimizer(0.05).minimize(loss)
    rng = np.random.RandomState(4)

    def batch():
        # 16 rows a step: with 4, the loss of ONE batch is mostly sampling
        # noise over 18 labels (it read 0.9 .. 2.4 across the last dozen
        # steps while the running mean fell), and 480 rows in all see
        # each label as the last token too rarely to learn it
        lens = rng.randint(2, 6, size=16)
        srcs, trgs, labs = [], [], []
        for L in lens:
            s = rng.randint(2, V, (L, 1)).astype('int64')
            # toy task: emit the source's LAST token at every step — the
            # 'last'-pooled encoder state carries exactly that token, so
            # the decoder must preserve its boot memory through the scan
            srcs.append(s)
            trgs.append(np.roll(s, 1, axis=0))
            labs.append(np.full((L, 1), s[-1, 0], 'int64'))
        return {'src': create_lod_tensor(srcs),
                'trg': create_lod_tensor(trgs),
                'lab': create_lod_tensor(labs)}

    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = []
        for _ in range(120):
            lv, = exe.run(main, feed=batch(), fetch_list=[loss])
            losses.append(float(np.asarray(lv).reshape(())))
        assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
        # decode from the inference clone: argmax at each step
        infer = main.clone(for_test=True)
        feed = batch()
        lg, = exe.run(infer, feed=feed, fetch_list=[logits])
    lg = np.asarray(lg)
    assert lg.shape[-1] == V
    dec_ids = lg.argmax(-1)
    # decoded tokens should mostly equal each row's target label
    tgt = feed['lab'].padded[:, 0, 0]
    lens = feed['lab'].lengths
    hits = sum((dec_ids[i, :lens[i]] == tgt[i]).mean()
               for i in range(len(lens))) / len(lens)
    assert hits > 0.6, hits


# ---------------------------------------------------------------- IfElse

def test_ifelse_rowwise_merge():
    B, D = 6, 4
    x = fluid.layers.data('x', shape=[B, D], dtype='float32',
                          append_batch_size=False)
    limit = layers.fill_constant(shape=[B, 1], dtype='float32', value=0.5)
    first = layers.slice(x, axes=[1], starts=[0], ends=[1])   # [B, 1]
    cond = layers.less_than(first, limit)
    ie = layers.IfElse(cond)
    with ie.true_block():
        xt = ie.input(x)
        ie.output(layers.scale(xt, scale=2.0))
    with ie.false_block():
        xf = ie.input(x)
        ie.output(xf + 10.0)
    merged, = ie()
    rng = np.random.RandomState(5)
    xv = rng.rand(B, D).astype('float32')
    got, = fluid.Executor().run(feed={'x': xv}, fetch_list=[merged])
    mask = xv[:, :1] < 0.5
    want = np.where(mask, xv * 2.0, xv + 10.0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_ifelse_fc_branches_train():
    """The reference docstring pattern: different fc stacks per branch,
    merged probabilities trainable end to end."""
    B, D, C = 8, 5, 3
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[B, D], dtype='float32',
                              append_batch_size=False)
        y = fluid.layers.data('y', shape=[B, 1], dtype='int64',
                              append_batch_size=False)
        gate = layers.slice(x, axes=[1], starts=[0], ends=[1])
        half = layers.fill_constant([B, 1], 'float32', 0.5)
        cond = layers.less_than(gate, half)
        ie = layers.IfElse(cond)
        with ie.true_block():
            xt = ie.input(x)
            ie.output(layers.fc(xt, C))
        with ie.false_block():
            xf = ie.input(x)
            ie.output(layers.fc(layers.fc(xf, 16, act='tanh'), C))
        logits, = ie()
        loss = layers.reduce_mean(
            layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.AdamOptimizer(0.05).minimize(loss)
    rng = np.random.RandomState(6)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = []
        for _ in range(60):
            xv = rng.rand(B, D).astype('float32')
            yv = (xv[:, :1] < 0.5).astype('int64')  # branch-correlated
            lv, = exe.run(main, feed={'x': xv, 'y': yv},
                          fetch_list=[loss])
            losses.append(float(np.asarray(lv).reshape(())))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_ifelse_single_branch_zeroes_unselected_rows():
    B = 4
    x = fluid.layers.data('x', shape=[B, 2], dtype='float32',
                          append_batch_size=False)
    first = layers.slice(x, axes=[1], starts=[0], ends=[1])
    half = layers.fill_constant([B, 1], 'float32', 0.5)
    cond = layers.less_than(first, half)
    ie = layers.IfElse(cond)
    with ie.true_block():
        ie.output(ie.input(x) * 3.0)
    outs = ie()
    assert isinstance(outs, list) and len(outs) == 1
    rng = np.random.RandomState(7)
    xv = rng.rand(B, 2).astype('float32')
    got, = fluid.Executor().run(feed={'x': xv}, fetch_list=[outs[0]])
    mask = xv[:, :1] < 0.5
    np.testing.assert_allclose(np.asarray(got),
                               np.where(mask, xv * 3.0, 0.0), rtol=1e-6)


@pytest.mark.parametrize('which', ['all_true', 'all_false'])
def test_ifelse_degenerate_masks(which):
    """Every row takes ONE branch: the select-masking merge must not be
    poisoned by the other (empty) branch — including through gradients
    (NaN/Inf from a degenerate branch would leak via 0*inf)."""
    B, D = 4, 3
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x0 = fluid.layers.data('x', shape=[B, D], dtype='float32',
                                   append_batch_size=False)
            x = layers.fc(x0, D, bias_attr=False,
                          param_attr=fluid.ParamAttr(
                              name='deg_w', initializer=fluid.initializer.
                              NumpyArrayInitializer(np.eye(D, dtype='float32'))))
            limit = layers.fill_constant([B, 1], 'float32',
                                         2.0 if which == 'all_true'
                                         else -2.0)
            first = layers.slice(x, axes=[1], starts=[0], ends=[1])
            cond = layers.less_than(first, limit)   # rows in [0,1)
            ie = layers.IfElse(cond)
            with ie.true_block():
                xt = ie.input(x)
                ie.output(layers.scale(xt, scale=2.0))
            with ie.false_block():
                xf = ie.input(x)
                # sqrt: NaN gradients for the masked-out branch would
                # poison the merge (and the fc weight grad) if wrong
                ie.output(layers.sqrt(xf))
            merged, = ie()
            loss = layers.reduce_mean(merged)
            fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    scope = fluid.Scope()
    xv = np.random.RandomState(0).rand(B, D).astype('float32')
    with fluid.scope_guard(scope):
        exe.run(startup)
        got, lv = exe.run(main, feed={'x': xv},
                          fetch_list=[merged, loss])
        w1 = np.asarray(scope.get('deg_w'))
    want = xv * 2.0 if which == 'all_true' else np.sqrt(xv)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)
    assert np.isfinite(float(np.asarray(lv).ravel()[0]))
    assert np.isfinite(w1).all()  # no NaN grads leaked into the update


def test_switch_default_and_order():
    """Switch: first matching case wins; default fires when none match."""
    def run(lr_val):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                step = layers.fill_constant([1], 'float32', lr_val)
                out = fluid.layers.create_global_var(
                    [1], 0.0, 'float32', persistable=True, name='sw_out')
                with fluid.layers.Switch() as switch:
                    with switch.case(layers.less_than(
                            step, layers.fill_constant([1], 'float32',
                                                       1.0))):
                        layers.assign(layers.fill_constant(
                            [1], 'float32', 111.0), out)
                    with switch.case(layers.less_than(
                            step, layers.fill_constant([1], 'float32',
                                                       2.0))):
                        layers.assign(layers.fill_constant(
                            [1], 'float32', 222.0), out)
                    with switch.default():
                        layers.assign(layers.fill_constant(
                            [1], 'float32', 333.0), out)
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            v, = exe.run(main, fetch_list=['sw_out'])
        return float(np.asarray(v).ravel()[0])

    assert run(0.5) == 111.0    # first case (also matches second)
    assert run(1.5) == 222.0
    assert run(5.0) == 333.0    # default


def test_switch_multi_assign_and_const_values():
    """Every assign in one case body blends with the SAME case mask
    (a per-assign registration would mask the second assign to a no-op),
    and non-Variable values (python lists) materialize correctly."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            step = layers.fill_constant([1], 'float32', 0.5)
            a = fluid.layers.create_global_var([1], 0.0, 'float32',
                                               persistable=True, name='ma')
            b = fluid.layers.create_global_var([2], 0.0, 'float32',
                                               persistable=True, name='mb')
            one = layers.fill_constant([1], 'float32', 1.0)
            with fluid.layers.Switch() as switch:
                with switch.case(layers.less_than(step, one)):
                    layers.assign(layers.fill_constant([1], 'float32',
                                                       11.0), a)
                    layers.assign(np.array([22.0, 33.0], 'float32'), b)
                with switch.default():
                    layers.assign(layers.fill_constant([1], 'float32',
                                                       -1.0), a)
                    layers.assign(np.array([-2.0, -3.0], 'float32'), b)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        av, bv = exe.run(main, fetch_list=['ma', 'mb'])
    np.testing.assert_allclose(np.asarray(av), [11.0])
    np.testing.assert_allclose(np.asarray(bv), [22.0, 33.0])


def test_switch_nested_raises():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        cond = layers.less_than(layers.fill_constant([1], 'float32', 0.0),
                                layers.fill_constant([1], 'float32', 1.0))
        out = fluid.layers.create_global_var([1], 0.0, 'float32',
                                             persistable=True, name='nso')
        with fluid.layers.Switch() as outer:
            with outer.case(cond):
                inner = fluid.layers.Switch()
                with pytest.raises(NotImplementedError):
                    with inner.case(cond):
                        layers.assign(layers.fill_constant(
                            [1], 'float32', 1.0), out)


def test_static_rnn_boot_memory_dynamic_batch():
    """Reference programs built with default append_batch_size=True have
    batch dim -1; StaticRNN.memory(shape=, batch_ref=) must boot via
    fill_constant_batch_size_like (VERDICT r4 #6) — the batch is only
    known at feed time, and different batch sizes run the same program."""
    T, D, H = 3, 2, 4
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        # time-major sequence with an UNKNOWN batch dim
        x = fluid.layers.data('x', shape=[T, -1, D], dtype='float32',
                              append_batch_size=False)
        rnn = layers.StaticRNN()
        with rnn.step():
            xt = rnn.step_input(x)       # [-1, D] step slice
            h = rnn.memory(shape=[-1, D], batch_ref=xt,
                           init_batch_dim_idx=0, ref_batch_dim_idx=0,
                           init_value=0.0)
            nh = layers.elementwise_add(h, xt)   # running sum
            rnn.update_memory(h, nh)
            rnn.output(nh)
        seq = rnn()                      # [T, B, D]
    exe = fluid.Executor()
    for B in (2, 5):                     # same program, two batch sizes
        xv = np.arange(T * B * D, dtype='float32').reshape(T, B, D)
        got, = exe.run(main, feed={'x': xv}, fetch_list=[seq])
        np.testing.assert_allclose(got, np.cumsum(xv, axis=0), rtol=1e-6)
