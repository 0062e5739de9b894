"""The replay of a ``fused_elementwise`` group, through both entries
(``ops.fused.fused_elementwise`` under an executor ``OpCtx``,
``emitter._replay_fused`` under the emitter's key and streams), against
the group's sub-ops called one after another as the unfused lowering of
that entry calls them: bitwise outputs, the per-sub-op AMP policy,
``stop_gradient`` per output, gradients, and the whole step's equations
with the fuse pass on against the pass skipped.

Both sides of every comparison run under ``jax.jit`` (the executor always
jits; eager XLA makes other FMA-contraction choices).  The reference loop
below is written against the executor's op loop (``_exec_ops_plain``) and
shares no code with either replay.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault('JAX_PLATFORMS', 'cpu')

import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402
from jax import lax                                   # noqa: E402

import paddle_tpu as fluid                            # noqa: E402
from paddle_tpu.core import executor as em            # noqa: E402
from paddle_tpu.core import registry                  # noqa: E402
from paddle_tpu.core.emit import emitter              # noqa: E402
from paddle_tpu.ops.fused import fused_elementwise    # noqa: E402

ENTRIES = ('impl', 'emit')
# where the group sits in its program: an unpinned sub-op draws from this
# position, a pinned one (`rng_stream`, as the pipeline stamps it) from
# its own
GROUP_AT = 7


# ------------------------------------------------------------- helpers

def _sub(type_, inputs, outputs, attrs=None, stop_grad=()):
    return {'type': type_, 'inputs': inputs, 'outputs': outputs,
            'input_is_list': {}, 'output_is_list': {},
            'attrs': dict(attrs or {}), 'stop_grad': list(stop_grad)}


def _attrs(sub_ops, arg_names, out_names):
    return {'sub_ops': sub_ops, 'arg_names': list(arg_names),
            'out_names': list(out_names)}


def _rand(rng, shape, dtype='float32', lo=0.25, hi=0.75):
    return jnp.asarray(
        (rng.rand(*shape) * (hi - lo) + lo).astype('float32')).astype(dtype)


def _group_op(attrs):
    return registry._SubOpShim('fused_elementwise', attrs)


def _fused(entry, attrs, xs, key, amp):
    """The group through one of its two entries."""
    if entry == 'impl':
        ctx = registry.ExecCtx(key, amp=amp).for_op(GROUP_AT,
                                                    _group_op(attrs))
        return fused_elementwise(ctx, {'X': list(xs)}, attrs)['Out']
    streams = emitter._op_streams(_group_op(attrs), GROUP_AT)
    return emitter._replay_fused({'X': list(xs)}, attrs, amp, None, key,
                                 streams)['Out']


def _unfused(entry, attrs, xs, key, amp):
    """The sub-ops one after another: per op the executor loop's AMP
    in-cast, match glue and cast-back, the op's own stream, and
    `stop_gradient` at the env write."""
    env = dict(zip(attrs['arg_names'], xs))
    ectx = registry.ExecCtx(key, amp=amp)
    for sub in attrs['sub_ops']:
        od = registry.get_op(sub['type'])
        use_amp = amp and sub['type'] in em._AMP_OPS
        ins = {}
        for slot, names in sub['inputs'].items():
            val = env[names[0]]
            ins[slot] = em._amp_cast(val, jnp.bfloat16) if use_amp else val
        if amp:
            ins = em._amp_match_ins(sub['type'], ins)
        op = registry._SubOpShim(sub['type'], sub['attrs'])
        if entry == 'impl':
            outs = od.impl(ectx.for_op(GROUP_AT, op), ins, sub['attrs'])
        else:
            stream = None
            if sub['type'] in emitter.RNG_OPS:
                stream, = emitter._op_streams(op, GROUP_AT)
            ctx = emitter.EmitCtx(key, stream, amp, None, sub['type'])
            outs = (od.emit or od.impl)(ctx, ins, sub['attrs'])
        if use_amp and sub['type'] in em._AMP_CAST_OPS and \
                not sub['attrs'].get('amp_keep_bf16'):
            outs = {s: em._amp_cast(v, jnp.float32)
                    for s, v in outs.items()}
        for slot, names in sub['outputs'].items():
            val = outs.get(slot)
            if val is None:
                continue
            if names[0] in sub['stop_grad'] and \
                    jnp.issubdtype(val.dtype, jnp.floating):
                val = lax.stop_gradient(val)
            env[names[0]] = val
    return [env[n] for n in attrs['out_names']]


def _both(entry, attrs, xs, amp=False):
    key = jax.random.key(3)
    got = jax.jit(lambda x, k: _fused(entry, attrs, x, k, amp))(
        tuple(xs), key)
    want = jax.jit(lambda x, k: _unfused(entry, attrs, x, k, amp))(
        tuple(xs), key)
    return got, want


def _assert_bitwise(names, got, want):
    assert len(got) == len(want) == len(names)
    for n, g, w in zip(names, got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, n
        np.testing.assert_array_equal(g, w, err_msg=n)


# --------------------------------- the group families (groups as data)

def _activation_chain(rng):
    return [(_attrs(
        [_sub('scale', {'X': ['x']}, {'Out': ['a']},
              {'scale': 1.7, 'bias': 0.3}),
         _sub('tanh', {'X': ['a']}, {'Out': ['b']}),
         _sub('sigmoid', {'X': ['b']}, {'Out': ['c']}),
         _sub('relu', {'X': ['c']}, {'Out': ['d']})],
        ['x'], ['d']), [_rand(rng, (6, 16))])]


def _binary_broadcasts(rng, dtype='float32'):
    return [(_attrs(
        [_sub('elementwise_add', {'X': ['x'], 'Y': ['b']},
              {'Out': ['s']}, {'axis': -1}),
         _sub('elementwise_mul', {'X': ['s'], 'Y': ['c']},
              {'Out': ['m']}, {'axis': -1}),
         _sub('elementwise_max', {'X': ['m'], 'Y': ['x']},
              {'Out': ['o']}, {'axis': -1})],
        ['x', 'b', 'c'], ['o']),
        [_rand(rng, (4, 8), dtype), _rand(rng, (8,)), _rand(rng, (1,))])]


def _compare_and_logic(rng):
    return [(_attrs(
        [_sub('less_than', {'X': ['x'], 'Y': ['y']}, {'Out': ['lt']},
              {'axis': -1}),
         _sub('greater_equal', {'X': ['x'], 'Y': ['y']},
              {'Out': ['ge']}, {'axis': -1}),
         _sub('logical_or', {'X': ['lt'], 'Y': ['ge']},
              {'Out': ['o']})],
        ['x', 'y'], ['lt', 'o']),
        [_rand(rng, (5, 7)), _rand(rng, (5, 7))])]


def _fill_cast_increment(rng):
    return [(_attrs(
        [_sub('fill_constant', {}, {'Out': ['c']},
              {'shape': [3, 4], 'value': 2, 'dtype': 'int32'}),
         _sub('cast', {'X': ['c']}, {'Out': ['cf']},
              {'out_dtype': 'float32', 'in_dtype': 'int32'}),
         _sub('elementwise_pow', {'X': ['x'], 'Y': ['cf']},
              {'Out': ['p']}, {'axis': -1}),
         _sub('increment', {'X': ['p']}, {'Out': ['o']}, {'step': 0.5})],
        ['x'], ['o']), [_rand(rng, (3, 4))])]


def _label_smooth(rng):
    return [(_attrs(
        [_sub('label_smooth', {'X': ['x']}, {'Out': ['o']},
              {'epsilon': 0.1})],
        ['x'], ['o']), [_rand(rng, (6, 10))])]


def _dropout_train_and_test(rng):
    x = _rand(rng, (8, 12))
    return [(_attrs(
        [_sub('scale', {'X': ['x']}, {'Out': ['s']}, {'scale': 2.0}),
         _sub('dropout', {'X': ['s']}, {'Out': ['o'], 'Mask': ['m']},
              dict(extra, rng_stream=4))],
        ['x'], ['o', 'm']), [x])
        for extra in ({'dropout_prob': 0.4,
                       'dropout_implementation': 'upscale_in_train'},
                      {'dropout_prob': 0.4, 'is_test': True})]


def _seeded_dropout(rng):
    return [(_attrs(
        [_sub('dropout', {'X': ['x']}, {'Out': ['o'], 'Mask': ['m']},
              {'dropout_prob': 0.3, 'seed': 11, 'rng_stream': 2,
               'dropout_implementation': 'upscale_in_train'})],
        ['x'], ['o', 'm']), [_rand(rng, (4, 6))])]


def _uniform_random(rng):
    # two draws, one pinned and one at the group's own position
    return [(_attrs(
        [_sub('uniform_random', {}, {'Out': ['u']},
              {'shape': [4, 8], 'min': -1.0, 'max': 1.0,
               'dtype': 'float32', 'rng_stream': 5}),
         _sub('uniform_random', {}, {'Out': ['w']},
              {'shape': [4, 8], 'min': 0.0, 'max': 2.0,
               'dtype': 'float32'}),
         _sub('abs', {'X': ['u']}, {'Out': ['o']})],
        [], ['o', 'w']), [])]


def _transpose_glue(rng):
    return [(_attrs(
        [_sub('scale', {'X': ['x']}, {'Out': ['a']}, {'scale': 3.0}),
         _sub('transpose', {'X': ['a']}, {'Out': ['t']},
              {'axis': [1, 0]}),
         _sub('relu', {'X': ['t']}, {'Out': ['o']})],
        ['x'], ['o']), [_rand(rng, (6, 10))])]


def _flat_reshapes(rng):
    return [(_attrs(
        [_sub('scale', {'X': ['x']}, {'Out': ['a']}, {'scale': 0.5}),
         _sub('reshape', {'X': ['a']}, {'Out': ['r']}, {'shape': [24]}),
         _sub('unsqueeze', {'X': ['r']}, {'Out': ['u']}, {'axes': [0]}),
         _sub('relu', {'X': ['u']}, {'Out': ['o']})],
        ['x'], ['o']), [_rand(rng, (4, 6))])]


def _sgd_momentum(rng):
    p, g, v = (_rand(rng, (3, 5)) for _ in range(3))
    lr = jnp.asarray(np.float32([0.01]))
    return [
        (_attrs([_sub('sgd', {'Param': ['p'], 'Grad': ['g'],
                              'LearningRate': ['lr']},
                      {'ParamOut': ['p']}, {}, stop_grad=['p'])],
                ['p', 'g', 'lr'], ['p']), [p, g, lr]),
        (_attrs([_sub('momentum', {'Param': ['p'], 'Grad': ['g'],
                                   'Velocity': ['v'],
                                   'LearningRate': ['lr']},
                      {'ParamOut': ['p'], 'VelocityOut': ['v']},
                      {'mu': 0.9}, stop_grad=['p', 'v'])],
                ['p', 'g', 'v', 'lr'], ['p', 'v']), [p, g, v, lr])]


def _adam_group(rng):
    """Per-parameter adam subs sharing one lr: the shape the fuse pass
    builds for a whole optimizer step."""
    subs, args, outs, xs = [], [], [], []
    for i, shape in enumerate([(32, 64), (64,), (16, 16), (1, 8)]):
        n = {k: '%s_%d' % (k, i)
             for k in ('p', 'g', 'm1', 'm2', 'b1p', 'b2p')}
        subs.append(_sub(
            'adam',
            {'Param': [n['p']], 'Grad': [n['g']], 'Moment1': [n['m1']],
             'Moment2': [n['m2']], 'Beta1Pow': [n['b1p']],
             'Beta2Pow': [n['b2p']], 'LearningRate': ['lr']},
            {'ParamOut': [n['p']], 'Moment1Out': [n['m1']],
             'Moment2Out': [n['m2']]},
            {'beta1': 0.9, 'beta2': 0.997, 'epsilon': 1e-9},
            stop_grad=[n['p'], n['m1'], n['m2']]))
        for k in ('p', 'g', 'm1', 'm2'):
            args.append(n[k])
            xs.append(_rand(rng, shape))
        for k, b in (('b1p', 0.9), ('b2p', 0.997)):
            args.append(n[k])
            xs.append(jnp.asarray(np.float32([b])))
        outs += [n['p'], n['m1'], n['m2']]
    args.append('lr')
    xs.append(jnp.asarray(np.float32([0.002])))
    return [(_attrs(subs, args, outs), xs)]


def _softmax_2d(rng):
    return [(_attrs(
        [_sub('softmax', {'X': ['x']}, {'Out': ['o']}, {'axis': -1})],
        ['x'], ['o']), [_rand(rng, (6, 33))])]


def _softmax_3d_neighbours(rng):
    return [(_attrs(
        [_sub('scale', {'X': ['x']}, {'Out': ['a']}, {'scale': 1.7}),
         _sub('softmax', {'X': ['a']}, {'Out': ['s']}, {'axis': -1}),
         _sub('relu', {'X': ['s']}, {'Out': ['o']})],
        ['x'], ['o']), [_rand(rng, (2, 5, 9))])]


def _layer_norm(rng, dtype='float32'):
    return [(_attrs(
        [_sub('layer_norm', {'X': ['x'], 'Scale': ['s'], 'Bias': ['b']},
              {'Y': ['y'], 'Mean': ['m'], 'Variance': ['v']},
              {'begin_norm_axis': 1, 'epsilon': 1e-5},
              stop_grad=['m', 'v'])],
        ['x', 's', 'b'], ['y', 'm', 'v']),
        [_rand(rng, (6, 10), dtype), _rand(rng, (10,)), _rand(rng, (10,))])]


def _flash_attention(rng):
    return [(_attrs(
        [_sub('flash_attention', {'Q': ['q'], 'K': ['k'], 'V': ['v']},
              {'Out': ['o']}, {'causal': True})],
        ['q', 'k', 'v'], ['o']),
        [_rand(rng, (2, 2, 16, 8)) for _ in range(3)])]


FAMILIES = {
    'activation_chain': _activation_chain,
    'binary_broadcasts': _binary_broadcasts,
    'compare_and_logic_bool_outputs': _compare_and_logic,
    'fill_cast_increment': _fill_cast_increment,
    'label_smooth': _label_smooth,
    'dropout_train_and_test': _dropout_train_and_test,
    'seeded_dropout': _seeded_dropout,
    'uniform_random': _uniform_random,
    'transpose_glue': _transpose_glue,
    'flat_preserving_reshapes': _flat_reshapes,
    'sgd_momentum': _sgd_momentum,
    'adam_multi_parameter': _adam_group,
    'softmax_2d': _softmax_2d,
    'softmax_3d_fused_neighbours': _softmax_3d_neighbours,
    'layer_norm_three_outputs': _layer_norm,
    'flash_attention': _flash_attention,
}


# ------------------------------------------------------ bitwise sweep

@pytest.mark.parametrize('entry', ENTRIES)
@pytest.mark.parametrize('family', sorted(FAMILIES))
def test_group_is_bitwise_its_sub_ops_in_order(family, entry):
    for attrs, xs in FAMILIES[family](np.random.RandomState(0)):
        got, want = _both(entry, attrs, xs)
        _assert_bitwise(attrs['out_names'], got, want)


def test_the_sweep_draws_what_it_says():
    """The rng families are not vacuous: the train-mode mask drops
    entries, a pinned and an unpinned draw differ, and a seed pins the
    mask whatever the key."""
    rng = np.random.RandomState(0)
    (train, xs), _ = _dropout_train_and_test(rng)
    mask = np.asarray(_fused('impl', train, xs, jax.random.key(3),
                             False)[1])
    assert 0 < mask.astype('float32').mean() < 1
    (draws, _), = _uniform_random(rng)
    o, w = _fused('emit', draws, [], jax.random.key(3), False)
    assert not np.array_equal(np.asarray(o), np.abs(np.asarray(w) - 1.0))
    (seeded, xs), = _seeded_dropout(rng)
    a = _fused('impl', seeded, xs, jax.random.key(3), False)[1]
    b = _fused('impl', seeded, xs, jax.random.key(4), False)[1]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------- AMP

def _amp_flash(rng):
    """f32 activations into an _AMP_CAST_OPS sub-op: bf16 in, f32 back;
    its neighbour then sees f32, as unfused."""
    (attrs, xs), = _flash_attention(rng)
    attrs['sub_ops'].append(
        _sub('scale', {'X': ['o']}, {'Out': ['z']}, {'scale': 0.5}))
    attrs['out_names'] = ['o', 'z']
    return attrs, xs, ['float32', 'float32']


def _amp_flash_keeps_bf16(rng):
    """The per-op opt-out of the cast-back."""
    (attrs, xs), = _flash_attention(rng)
    attrs['sub_ops'][0]['attrs']['amp_keep_bf16'] = True
    return attrs, xs, ['bfloat16']


def _amp_match_glue(rng):
    """A bf16 activation drags the f32 bias and scalar down (the
    elementwise-match glue), sub-op by sub-op."""
    (attrs, xs), = _binary_broadcasts(rng, dtype='bfloat16')
    return attrs, xs, ['bfloat16']


AMP_FAMILIES = {'flash_attention_cast_back': _amp_flash,
                'flash_attention_keep_bf16': _amp_flash_keeps_bf16,
                'binary_broadcasts_match': _amp_match_glue}


@pytest.mark.parametrize('entry', ENTRIES)
@pytest.mark.parametrize('family', sorted(AMP_FAMILIES))
def test_amp_policy_is_applied_per_sub_op(family, entry):
    attrs, xs, dtypes = AMP_FAMILIES[family](np.random.RandomState(1))
    got, want = _both(entry, attrs, xs, amp=True)
    assert [str(g.dtype) for g in got] == dtypes
    _assert_bitwise(attrs['out_names'], got, want)
    if family == 'flash_attention_cast_back':
        # and the policy did something: without AMP the products are f32
        plain, _ = _both(entry, attrs, xs, amp=False)
        assert not np.array_equal(np.asarray(plain[0]), np.asarray(got[0]))


# ------------------------------------------------------------ gradients

GRAD_FAMILIES = {'softmax': _softmax_3d_neighbours,
                 'layer_norm': _layer_norm,
                 'flash_attention': _flash_attention}


def _grads(fn, entry, attrs, xs):
    key = jax.random.key(3)

    def loss(*x):
        return jnp.sum(fn(entry, attrs, x, key, False)[0] ** 2)

    return jax.jit(jax.grad(loss, argnums=tuple(range(len(xs)))))(*xs)


@pytest.mark.parametrize('family', sorted(GRAD_FAMILIES))
def test_gradients_through_a_group_are_the_unfused_ones(family):
    (attrs, xs), = GRAD_FAMILIES[family](np.random.RandomState(2))
    for entry in ENTRIES:
        got = _grads(_fused, entry, attrs, xs)
        want = _grads(_unfused, entry, attrs, xs)
        _assert_bitwise(attrs['arg_names'], got, want)
        assert any(np.abs(np.asarray(g)).max() > 0 for g in got)


def test_stop_gradient_is_per_output():
    """`a` is read twice; only the path through the stopped `s` is cut."""
    attrs = _attrs(
        [_sub('scale', {'X': ['x']}, {'Out': ['a']}, {'scale': 3.0}),
         _sub('tanh', {'X': ['a']}, {'Out': ['s']}, stop_grad=['s']),
         _sub('elementwise_mul', {'X': ['a'], 'Y': ['s']},
              {'Out': ['o']}, {'axis': -1})],
        ['x'], ['o'])
    x = _rand(np.random.RandomState(3), (4, 8))
    for entry in ENTRIES:
        got, = _grads(_fused, entry, attrs, [x])
        want, = _grads(_unfused, entry, attrs, [x])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # d/dx sum((3x * stop(tanh 3x))^2) = 2 o * 3 * tanh(3x)
        a = 3.0 * np.asarray(x)
        np.testing.assert_allclose(np.asarray(got),
                                   2 * a * np.tanh(a) * 3 * np.tanh(a),
                                   rtol=1e-5)


# ------------------------------------- the whole step, pass on and off

def _step_model():
    """One LayerNorm group, one [elementwise_add, relu] group and one
    fused Adam group: the three shapes of fused group the training cells
    hold (tbase's LayerNorms, ResNet-50's block tails, both optimizers)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 41
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[8], dtype='float32')
            lbl = fluid.layers.data('lbl', shape=[1], dtype='int64')
            h = fluid.layers.fc(x, 16)
            h = fluid.layers.layer_norm(fluid.layers.elementwise_add(h, h))
            h = fluid.layers.fc(h, 16, act='relu')
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(
                    fluid.layers.fc(h, 4), lbl))
            fluid.optimizer.Adam(0.01).minimize(loss)
    main.set_amp(True)
    return main, startup, loss


def _step_eqns(main, scope, feed, fetch_names, emit):
    """The step as `Executor._prepare_entry` lowers it (the rewriter, the
    emitter's engine or the traced path, `_lower`), traced to its jaxpr
    with every memoized function inlined and dead equations dropped (the
    emitter prunes a group's undemanded outputs inside the group, an
    op's at the op): each equation's primitive and results, in order.
    `stop_gradient` is left out: a group stops an output where its sub-op
    writes it and the executor again where the group does, once more
    than unfused, and twice is once."""
    from jax._src.interpreters import partial_eval as pe
    from paddle_tpu.core import emit as _emit
    from paddle_tpu.core import passes
    emitter.clear_memo()
    feed_names = tuple(sorted(feed))
    opt, _ = passes.maybe_optimize(main, fetch_names)
    engine = _emit.build_engine(opt, feed_names, fetch_names) if emit \
        else None
    jit_fn, params_in, _ = em._lower(opt, feed_names, fetch_names,
                                     emit_engine=engine)
    args = ({n: scope.vars[n] for n in params_in}, feed, np.uint32(0))
    with jax.disable_jit():
        jaxpr = jax.make_jaxpr(jit_fn)(*args).jaxpr
    jaxpr, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    return opt, [(e.primitive.name, tuple(str(v.aval) for v in e.outvars))
                 for e in jaxpr.eqns if e.primitive.name != 'stop_gradient']


@pytest.mark.parametrize('emit', [True, False], ids=['emit', 'trace'])
def test_step_with_the_fuse_pass_is_the_step_without(monkeypatch, emit):
    main, startup, loss = _step_model()
    scope = fluid.Scope()
    fluid.Executor().run(startup, scope=scope)
    rng = np.random.RandomState(0)
    feed = {'x': jnp.asarray(rng.randn(6, 8).astype('float32')),
            'lbl': jnp.asarray(rng.randint(0, 4, (6, 1)).astype('int64'))}
    monkeypatch.setenv('PT_CACHE', '0')

    monkeypatch.delenv('PT_OPT_SKIP', raising=False)
    opt, fused = _step_eqns(main, scope, feed, (loss.name,), emit)
    monkeypatch.setenv('PT_OPT_SKIP', 'fuse_elementwise')
    bare, unfused = _step_eqns(main, scope, feed, (loss.name,), emit)

    groups = {tuple(sub['type'] for sub in op.attrs['sub_ops'])
              for op in opt.global_block().ops
              if op.type == 'fused_elementwise'}
    assert ('elementwise_add', 'relu') in groups, groups
    assert any('layer_norm' in g for g in groups), groups
    assert any(set(g) == {'adam'} and len(g) > 1 for g in groups), groups
    assert not any(op.type == 'fused_elementwise'
                   for op in bare.global_block().ops)
    assert fused == unfused and len(fused) > 300
    assert not any('pallas' in p or 'custom_vjp' in p for p, _ in fused)
