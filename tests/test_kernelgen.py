"""Pallas codegen tier (ops/kernelgen): per-rule bitwise parity vs the
reference replay, the fused-Adam single-kernel contract, the no-reroute
contract and the default (off on every backend), emitter/launch-signature
integration, AOT disk-cache round trip, and end-to-end parity through run /
run_steps / ParallelExecutor under AMP + dropout.

Parity contract (docs/kernels.md): a generated kernel is BITWISE equal
to the jitted replay of the same fused group — both lower through XLA,
and impl-passthrough bodies run the identical jnp expressions lane for
lane.  Whole-TRAINING-RUN equality is weaker: XLA fuses broadcast-grad
reductions differently around an opaque pallas call than around an
inlined elementwise chain (1-2 ulp per step), so multi-step e2e checks
use a drift tolerance while the first launch stays at 1e-6.
"""
import os
import sys
import warnings

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.setdefault('JAX_PLATFORMS', 'cpu')

import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402

import paddle_tpu as fluid                            # noqa: E402
import paddle_tpu.observability as obs                # noqa: E402
from paddle_tpu.ops import fused as _fused            # noqa: E402
from paddle_tpu.ops import kernelgen as kg            # noqa: E402
from paddle_tpu.ops.kernelgen import builder          # noqa: E402


@pytest.fixture(autouse=True)
def _every_kind_on(monkeypatch):
    """plan_for builds for the kinds that are ON (kg.pallas_kinds());
    this module exercises every kind, in the Pallas interpreter."""
    monkeypatch.setenv('PT_KERNELGEN', '1')


# ------------------------------------------------------------- helpers

def _sub(type_, inputs, outputs, attrs=None, stop_grad=()):
    return {'type': type_, 'inputs': inputs, 'outputs': outputs,
            'input_is_list': {}, 'output_is_list': {},
            'attrs': dict(attrs or {}), 'stop_grad': list(stop_grad)}


def _attrs(sub_ops, arg_names, out_names):
    return {'sub_ops': sub_ops, 'arg_names': list(arg_names),
            'out_names': list(out_names)}


class _SeqKeyCtx(object):
    """Replay ctx: hands out per-rng-sub keys in call order (the same
    keys the kernel path receives), no AMP."""
    amp = False
    mesh = None

    def __init__(self, keys):
        self._keys = list(keys)
        self._i = 0

    def sub_ctx(self, sub):
        return self

    def rng(self, n=0):
        k = self._keys[self._i]
        self._i += 1
        return k


def _replay(attrs, xs, keys, amp=False):
    env = dict(zip(attrs['arg_names'], xs))
    # seeded rng subs derive their own key internally; only unseeded
    # ones pull from ctx.rng — hand the ctx exactly those keys
    unseeded = []
    si = 0
    for sub in attrs['sub_ops']:
        if sub['type'] in kg.rng_rule_types():
            if not sub['attrs'].get('seed', 0):
                unseeded.append(keys[si])
            si += 1
    ctx = _SeqKeyCtx(unseeded)
    ctx.amp = amp
    for sub in attrs['sub_ops']:
        _fused._run_sub_op(ctx, sub, env, amp)
    return [env[n] for n in attrs['out_names']]


def _keys(attrs, seed=3):
    base = jax.random.key(seed)
    return kg._keys_for(attrs, lambda si, sub: jax.random.fold_in(base,
                                                                  si))


def _assert_plan_bitwise(attrs, xs, amp=False, expect_kernels=None):
    """plan.fn vs jitted replay, both under jax.jit (the executor always
    jits; eager XLA makes different FMA-contraction choices)."""
    xs = tuple(xs)
    keys = _keys(attrs)
    plan = kg.plan_for(attrs, kg._in_avals(xs), amp)
    if expect_kernels is not None:
        assert plan.n_kernels == expect_kernels, plan.kernel_ops
    kouts = jax.jit(plan.fn)(xs, keys)
    routs = jax.jit(lambda x, k: _replay(attrs, x, k, amp))(xs, keys)
    assert len(kouts) == len(routs)
    for n, ko, ro in zip(attrs['out_names'], kouts, routs):
        ka, ra = np.asarray(ko), np.asarray(ro)
        assert ka.dtype == ra.dtype and ka.shape == ra.shape, n
        np.testing.assert_array_equal(ka, ra, err_msg=n)
    return plan


def _rand(rng, shape, dtype='float32', lo=0.25, hi=0.75):
    return jnp.asarray(
        (rng.rand(*shape) * (hi - lo) + lo).astype(dtype))


# ------------------------------------------- per-rule bitwise sweep

def test_rule_sweep_activation_chain():
    rng = np.random.RandomState(0)
    attrs = _attrs(
        [_sub('scale', {'X': ['x']}, {'Out': ['a']},
              {'scale': 1.7, 'bias': 0.3}),
         _sub('tanh', {'X': ['a']}, {'Out': ['b']}),
         _sub('sigmoid', {'X': ['b']}, {'Out': ['c']}),
         _sub('relu', {'X': ['c']}, {'Out': ['d']})],
        ['x'], ['d'])
    _assert_plan_bitwise(attrs, [_rand(rng, (6, 16))], expect_kernels=1)


def test_rule_sweep_binary_broadcasts():
    rng = np.random.RandomState(1)
    x = _rand(rng, (4, 8))
    bias = _rand(rng, (8,))
    scalar = _rand(rng, (1,))
    attrs = _attrs(
        [_sub('elementwise_add', {'X': ['x'], 'Y': ['b']},
              {'Out': ['s']}, {'axis': -1}),
         _sub('elementwise_mul', {'X': ['s'], 'Y': ['c']},
              {'Out': ['m']}, {'axis': -1}),
         _sub('elementwise_max', {'X': ['m'], 'Y': ['x']},
              {'Out': ['o']}, {'axis': -1})],
        ['x', 'b', 'c'], ['o'])
    _assert_plan_bitwise(attrs, [x, bias, scalar], expect_kernels=1)


def test_rule_sweep_compare_and_logic_bool_outputs():
    rng = np.random.RandomState(2)
    x, y = _rand(rng, (5, 7)), _rand(rng, (5, 7))
    attrs = _attrs(
        [_sub('less_than', {'X': ['x'], 'Y': ['y']}, {'Out': ['lt']},
              {'axis': -1}),
         _sub('greater_equal', {'X': ['x'], 'Y': ['y']},
              {'Out': ['ge']}, {'axis': -1}),
         _sub('logical_or', {'X': ['lt'], 'Y': ['ge']},
              {'Out': ['o']})],
        ['x', 'y'], ['lt', 'o'])
    _assert_plan_bitwise(attrs, [x, y], expect_kernels=1)


def test_rule_sweep_fill_cast_increment():
    rng = np.random.RandomState(3)
    x = _rand(rng, (3, 4))
    attrs = _attrs(
        [_sub('fill_constant', {}, {'Out': ['c']},
              {'shape': [3, 4], 'value': np.int64(2), 'dtype': 'int64'}),
         _sub('cast', {'X': ['c']}, {'Out': ['cf']},
              {'out_dtype': 'float32', 'in_dtype': 'int64'}),
         _sub('elementwise_pow', {'X': ['x'], 'Y': ['cf']},
              {'Out': ['p']}, {'axis': -1}),
         _sub('increment', {'X': ['p']}, {'Out': ['o']}, {'step': 0.5})],
        ['x'], ['o'])
    with warnings.catch_warnings():
        warnings.simplefilter('error', UserWarning)  # int64 stays silent
        _assert_plan_bitwise(attrs, [x], expect_kernels=1)


def test_rule_sweep_label_smooth_logical_shape():
    rng = np.random.RandomState(4)
    x = _rand(rng, (6, 10))
    attrs = _attrs(
        [_sub('label_smooth', {'X': ['x']}, {'Out': ['o']},
              {'epsilon': 0.1})],
        ['x'], ['o'])
    _assert_plan_bitwise(attrs, [x], expect_kernels=1)


def test_rule_sweep_dropout_train_and_test():
    rng = np.random.RandomState(5)
    x = _rand(rng, (8, 12))
    for extra in ({'dropout_prob': 0.4,
                   'dropout_implementation': 'upscale_in_train'},
                  {'dropout_prob': 0.4, 'is_test': True}):
        attrs = _attrs(
            [_sub('scale', {'X': ['x']}, {'Out': ['s']}, {'scale': 2.0}),
             _sub('dropout', {'X': ['s']}, {'Out': ['o'],
                                            'Mask': ['m']}, extra)],
            ['x'], ['o', 'm'])
        _assert_plan_bitwise(attrs, [x], expect_kernels=1)


def test_rule_sweep_seeded_dropout_matches_impl_seed_path():
    rng = np.random.RandomState(6)
    x = _rand(rng, (4, 6))
    attrs = _attrs(
        [_sub('dropout', {'X': ['x']}, {'Out': ['o'], 'Mask': ['m']},
              {'dropout_prob': 0.3, 'seed': 11,
               'dropout_implementation': 'upscale_in_train'})],
        ['x'], ['o', 'm'])
    _assert_plan_bitwise(attrs, [x], expect_kernels=1)


def test_rule_sweep_uniform_random_whole_draw():
    attrs = _attrs(
        [_sub('uniform_random', {}, {'Out': ['u']},
              {'shape': [4, 8], 'min': -1.0, 'max': 1.0,
               'dtype': 'float32'}),
         _sub('abs', {'X': ['u']}, {'Out': ['o']})],
        [], ['o'])
    _assert_plan_bitwise(attrs, [])


def test_rule_sweep_layout_glue_segments():
    """An order-changing transpose splits the group into two kernels
    with an XLA glue step between — still bitwise."""
    rng = np.random.RandomState(7)
    x = _rand(rng, (6, 10))
    attrs = _attrs(
        [_sub('scale', {'X': ['x']}, {'Out': ['a']}, {'scale': 3.0}),
         _sub('transpose', {'X': ['a']}, {'Out': ['t']},
              {'axis': [1, 0]}),
         _sub('relu', {'X': ['t']}, {'Out': ['o']})],
        ['x'], ['o'])
    plan = _assert_plan_bitwise(attrs, [x])
    assert plan.n_kernels == 2 and plan.n_glue >= 1


def test_rule_sweep_flat_preserving_reshapes_stay_fused():
    rng = np.random.RandomState(8)
    x = _rand(rng, (4, 6))
    attrs = _attrs(
        [_sub('scale', {'X': ['x']}, {'Out': ['a']}, {'scale': 0.5}),
         _sub('reshape', {'X': ['a']}, {'Out': ['r']},
              {'shape': [24]}),
         _sub('unsqueeze', {'X': ['r']}, {'Out': ['u']},
              {'axes': [0]}),
         _sub('relu', {'X': ['u']}, {'Out': ['o']})],
        ['x'], ['o'])
    _assert_plan_bitwise(attrs, [x], expect_kernels=1)


def test_rule_sweep_sgd_momentum():
    rng = np.random.RandomState(9)
    p, g, v = (_rand(rng, (3, 5)) for _ in range(3))
    lr = jnp.asarray(np.float32([0.01]))
    attrs = _attrs(
        [_sub('sgd', {'Param': ['p'], 'Grad': ['g'],
                      'LearningRate': ['lr']},
              {'ParamOut': ['p']}, {}, stop_grad=['p'])],
        ['p', 'g', 'lr'], ['p'])
    _assert_plan_bitwise(attrs, [p, g, lr], expect_kernels=1)
    attrs = _attrs(
        [_sub('momentum', {'Param': ['p'], 'Grad': ['g'],
                           'Velocity': ['v'], 'LearningRate': ['lr']},
              {'ParamOut': ['p'], 'VelocityOut': ['v']},
              {'mu': 0.9}, stop_grad=['p', 'v'])],
        ['p', 'g', 'v', 'lr'], ['p', 'v'])
    _assert_plan_bitwise(attrs, [p, g, v, lr], expect_kernels=1)


# ------------------------------------------------ fused-Adam contract

def _adam_group(shapes, rng):
    """One fused group of per-param adam subs sharing lr (the shape the
    fuse pass builds for a whole optimizer step)."""
    subs, args, outs, xs = [], [], [], []
    lrname = 'lr'
    for i, shape in enumerate(shapes):
        names = {k: '%s_%d' % (k, i) for k in
                 ('p', 'g', 'm1', 'm2', 'b1p', 'b2p')}
        subs.append(_sub(
            'adam',
            {'Param': [names['p']], 'Grad': [names['g']],
             'Moment1': [names['m1']], 'Moment2': [names['m2']],
             'Beta1Pow': [names['b1p']], 'Beta2Pow': [names['b2p']],
             'LearningRate': [lrname]},
            {'ParamOut': [names['p']], 'Moment1Out': [names['m1']],
             'Moment2Out': [names['m2']]},
            {'beta1': 0.9, 'beta2': 0.997, 'epsilon': 1e-9},
            stop_grad=[names['p'], names['m1'], names['m2']]))
        for k in ('p', 'g', 'm1', 'm2'):
            args.append(names[k])
            xs.append(_rand(rng, shape))
        for k in ('b1p', 'b2p'):
            args.append(names[k])
            xs.append(jnp.asarray(np.float32([0.9 if k == 'b1p'
                                              else 0.997])))
        outs += [names['p'], names['m1'], names['m2']]
    args.append(lrname)
    xs.append(jnp.asarray(np.float32([0.002])))
    return _attrs(subs, args, outs), xs


def test_fused_adam_one_kernel_multi_group():
    """Mixed param sizes (multi-group kernel) still plan to ONE pallas
    call, donate the param/moment buffers, and match ops/optimizer_ops
    adam bitwise."""
    rng = np.random.RandomState(10)
    attrs, xs = _adam_group([(32, 64), (64,), (16, 16), (1, 8)], rng)
    plan = _assert_plan_bitwise(attrs, xs, expect_kernels=1)
    assert plan.n_donated > 0

    # cross-check against the registered adam impl applied per param.
    # This is a DIFFERENT compiled program, so XLA's FMA-contraction
    # freedom allows 1-2 ulp (bitwise only holds within one program —
    # the replay comparison above); bound it at float32 ulp scale.
    from paddle_tpu.core.registry import get_op
    adam = get_op('adam').impl
    kouts = plan.fn(tuple(xs), ())
    env = dict(zip(attrs['arg_names'], xs))
    ptr = 0
    for i in range(4):
        ins = {'Param': env['p_%d' % i], 'Grad': env['g_%d' % i],
               'Moment1': env['m1_%d' % i], 'Moment2': env['m2_%d' % i],
               'Beta1Pow': env['b1p_%d' % i],
               'Beta2Pow': env['b2p_%d' % i], 'LearningRate': env['lr']}
        want = jax.jit(lambda ins=ins: adam(
            None, ins, {'beta1': 0.9, 'beta2': 0.997,
                        'epsilon': 1e-9}))()
        for slot in ('ParamOut', 'Moment1Out', 'Moment2Out'):
            np.testing.assert_allclose(
                np.asarray(kouts[ptr]), np.asarray(want[slot]),
                rtol=3e-7, atol=1e-9,
                err_msg='param %d %s' % (i, slot))
            ptr += 1


# --------------------------------------- interpret mode + direct kernel

def test_interpret_mode_on_cpu_and_small_blocks(monkeypatch):
    assert builder._interpret()  # CPU backend => interpret kernels
    monkeypatch.setenv('PT_KERNELGEN_BLOCK', '8')
    kg.clear_plan_cache()
    try:
        rng = np.random.RandomState(11)
        x = _rand(rng, (5, 9))  # 45 lanes: ragged multi-tile grid
        attrs = _attrs(
            [_sub('scale', {'X': ['x']}, {'Out': ['a']}, {'scale': 2.0}),
             _sub('sqrt', {'X': ['a']}, {'Out': ['o']})],
            ['x'], ['o'])
        plan = _assert_plan_bitwise(attrs, [x], expect_kernels=1)
        out = plan.fn((x,), ())
        np.testing.assert_array_equal(
            np.asarray(out[0]), np.asarray(jnp.sqrt(x * 2.0)))
    finally:
        kg.clear_plan_cache()


def test_grad_through_generated_kernel_matches_replay():
    rng = np.random.RandomState(12)
    x = _rand(rng, (4, 8))
    attrs = _attrs(
        [_sub('scale', {'X': ['x']}, {'Out': ['a']}, {'scale': 1.3}),
         _sub('tanh', {'X': ['a']}, {'Out': ['o']})],
        ['x'], ['o'])
    plan = kg.plan_for(attrs, kg._in_avals([x]), False)
    gk = jax.jit(jax.grad(lambda v: jnp.sum(plan.fn((v,), ())[0])))(x)
    gr = jax.jit(jax.grad(
        lambda v: jnp.sum(_replay(attrs, (v,), ())[0])))(x)
    np.testing.assert_array_equal(np.asarray(gk), np.asarray(gr))


# ----------------------------------------------- loud fallback contract

def _unsupported_attrs():
    # reduce_sum: registered op, no KERNEL_RULES entry (softmax no
    # longer qualifies — it graduated to a dedicated row kernel)
    return _attrs(
        [_sub('scale', {'X': ['x']}, {'Out': ['a']}, {'scale': 2.0}),
         _sub('reduce_sum', {'X': ['a']}, {'Out': ['o']},
              {'dim': [-1], 'keep_dim': False})],
        ['x'], ['o'])


class _PlainCtx(object):
    amp = False
    mesh = None

    def sub_ctx(self, sub):
        return self

    def rng(self, n=0):
        return jax.random.key(0)


def test_unsupported_group_raises_naming_sub_op(monkeypatch):
    """No reroute to the replay: a group the tier cannot lower raises,
    naming the sub-op."""
    monkeypatch.setenv('PT_KERNELGEN', '1')
    from paddle_tpu.core.registry import get_op
    x = jnp.ones((2, 3), jnp.float32)
    with pytest.raises(kg.KernelgenUnsupported, match='reduce_sum'):
        get_op('fused_elementwise').impl(_PlainCtx(), {'X': [x]},
                                         _unsupported_attrs())


def test_unsupported_sub_ops_lists_gaps_once():
    assert kg.unsupported_sub_ops(_unsupported_attrs()) == ['reduce_sum']
    assert kg.unsupported_sub_ops(
        _attrs([_sub('relu', {'X': ['x']}, {'Out': ['o']})],
               ['x'], ['o'])) == []


# ----------------------- dedicated kernels: row + attention kinds

def test_softmax_row_kernel_bitwise_with_grad():
    rng = np.random.RandomState(13)
    x = _rand(rng, (6, 33))  # 33 cols + 6 rows: ragged row-block grid
    attrs = _attrs(
        [_sub('softmax', {'X': ['x']}, {'Out': ['o']}, {'axis': -1})],
        ['x'], ['o'])
    plan = _assert_plan_bitwise(attrs, [x])
    assert plan.n_dsteps == 1
    gk = jax.jit(jax.grad(
        lambda v: jnp.sum(plan.fn((v,), ())[0] ** 2)))(x)
    gr = jax.jit(jax.grad(
        lambda v: jnp.sum(_replay(attrs, (v,), ())[0] ** 2)))(x)
    np.testing.assert_array_equal(np.asarray(gk), np.asarray(gr))


def test_softmax_3d_trailing_axis_and_fused_neighbors():
    rng = np.random.RandomState(14)
    x = _rand(rng, (2, 5, 9))
    attrs = _attrs(
        [_sub('scale', {'X': ['x']}, {'Out': ['a']}, {'scale': 1.7}),
         _sub('softmax', {'X': ['a']}, {'Out': ['s']}, {'axis': -1}),
         _sub('relu', {'X': ['s']}, {'Out': ['o']})],
        ['x'], ['o'])
    plan = _assert_plan_bitwise(attrs, [x])
    assert plan.n_dsteps == 1


def test_layer_norm_row_kernel_three_outputs_and_grads():
    rng = np.random.RandomState(15)
    x = _rand(rng, (6, 10))
    scale, bias = _rand(rng, (10,)), _rand(rng, (10,))
    attrs = _attrs(
        [_sub('layer_norm', {'X': ['x'], 'Scale': ['s'], 'Bias': ['b']},
              {'Y': ['y'], 'Mean': ['m'], 'Variance': ['v']},
              {'begin_norm_axis': 1, 'epsilon': 1e-5},
              stop_grad=['m', 'v'])],
        ['x', 's', 'b'], ['y', 'm', 'v'])
    plan = _assert_plan_bitwise(attrs, [x, scale, bias])
    assert plan.n_dsteps == 1
    # AMP policy reproduced (executor _amp_sub_ins/_amp_sub_outs)
    _assert_plan_bitwise(attrs, [x, scale, bias], amp=True)
    gk = jax.jit(jax.grad(
        lambda a, s, b: jnp.sum(plan.fn((a, s, b), ())[0] ** 2),
        argnums=(0, 1, 2)))(x, scale, bias)
    gr = jax.jit(jax.grad(
        lambda a, s, b: jnp.sum(_replay(attrs, (a, s, b), ())[0] ** 2),
        argnums=(0, 1, 2)))(x, scale, bias)
    for name, a, b in zip(('dx', 'dscale', 'dbias'), gk, gr):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_layer_norm_two_pass_env_still_bitwise(monkeypatch):
    monkeypatch.setenv('PT_TWO_PASS_NORM', '1')
    kg.clear_plan_cache()
    try:
        rng = np.random.RandomState(16)
        x = _rand(rng, (4, 8))
        attrs = _attrs(
            [_sub('layer_norm', {'X': ['x']},
                  {'Y': ['y'], 'Mean': ['m'], 'Variance': ['v']},
                  {'begin_norm_axis': 1, 'epsilon': 1e-5},
                  stop_grad=['m', 'v'])],
            ['x'], ['y', 'm', 'v'])
        _assert_plan_bitwise(attrs, [x])
    finally:
        kg.clear_plan_cache()


def test_flash_attention_plan_matches_replay_with_grads():
    """The dstep passes through ops/attention.flash_attention — same
    custom_vjp as the registered impl, so fwd AND grads are bitwise."""
    rng = np.random.RandomState(17)
    q, k, v = (_rand(rng, (2, 2, 16, 8)) for _ in range(3))
    attrs = _attrs(
        [_sub('flash_attention', {'Q': ['q'], 'K': ['k'], 'V': ['v']},
              {'Out': ['o']}, {'causal': True})],
        ['q', 'k', 'v'], ['o'])
    plan = _assert_plan_bitwise(attrs, [q, k, v])
    assert plan.n_dsteps == 1
    gk = jax.jit(jax.grad(
        lambda a, b, c: jnp.sum(plan.fn((a, b, c), ())[0] ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(
        lambda a, b, c: jnp.sum(_replay(attrs, (a, b, c), ())[0] ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip(('dq', 'dk', 'dv'), gk, gr):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


# ------------------------------------------------- tile/block autotuner

def _softmax_attrs():
    return _attrs(
        [_sub('softmax', {'X': ['x']}, {'Out': ['o']}, {'axis': -1})],
        ['x'], ['o'])


def _autotune_counters():
    c = obs.counters()
    return (c.get('kernelgen.autotune_searches') or 0,
            c.get('kernelgen.autotune_cache_hits') or 0)


def test_autotune_searches_once_persists_and_is_deterministic(
        tmp_path, monkeypatch):
    from paddle_tpu.ops.kernelgen import autotune
    monkeypatch.setenv('PT_CACHE', '1')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    monkeypatch.setenv('PT_AUTOTUNE', '1')
    kg.clear_plan_cache()
    autotune.clear_memory()
    try:
        rng = np.random.RandomState(18)
        x = _rand(rng, (64, 16))  # 64 rows: {8, 32, 64} row candidates
        attrs = _softmax_attrs()
        s0, h0 = _autotune_counters()
        plan1 = _assert_plan_bitwise(attrs, [x])
        s1, _ = _autotune_counters()
        assert s1 > s0, 'cold build must pay a timed search'
        assert plan1.tuned and 'block_rows' in plan1.tuned[0]
        store = os.path.join(str(tmp_path), 'autotune')
        assert os.path.isdir(store) and os.listdir(store), \
            'the winning choice must persist in the AOT cache dir'
        # simulate a fresh process: drop the plan cache and the memo;
        # the disk store answers — zero new searches, identical choice
        kg.clear_plan_cache()
        autotune.clear_memory()
        plan2 = kg.plan_for(attrs, kg._in_avals([x]), False)
        s2, h2 = _autotune_counters()
        assert s2 == s1, 'warm rebuild must not re-search'
        assert h2 > h0, 'warm rebuild must hit the persisted store'
        assert plan2.tuned == plan1.tuned
    finally:
        kg.clear_plan_cache()
        autotune.clear_memory()


def test_autotune_cached_mode_uses_static_default(monkeypatch):
    from paddle_tpu.ops.kernelgen import autotune
    monkeypatch.setenv('PT_AUTOTUNE', 'cached')
    monkeypatch.setenv('PT_CACHE', '0')
    kg.clear_plan_cache()
    autotune.clear_memory()
    try:
        rng = np.random.RandomState(19)
        x = _rand(rng, (64, 16))
        s0, _ = _autotune_counters()
        plan = _assert_plan_bitwise(_softmax_attrs(), [x])
        s1, _ = _autotune_counters()
        assert s1 == s0, 'cached mode must never search'
        assert plan.tuned == [{'block_rows': 64}]  # min(128, rows)
    finally:
        kg.clear_plan_cache()
        autotune.clear_memory()


def test_autotune_off_mode_and_lint_ctx_never_time(monkeypatch):
    from paddle_tpu.ops.kernelgen import autotune
    calls = []

    def timer(cand):
        calls.append(cand)
        return 1.0

    monkeypatch.setenv('PT_AUTOTUNE', '0')
    assert autotune.choose('row', ('sig',), [{'a': 1}, {'a': 2}],
                           timer, {'a': 9}, True) == {'a': 9}
    monkeypatch.setenv('PT_AUTOTUNE', '1')
    monkeypatch.setenv('PT_CACHE', '0')
    autotune.clear_memory()
    assert autotune.choose('row', ('sig',), [{'a': 1}, {'a': 2}],
                           timer, {'a': 9}, False) == {'a': 9}
    assert calls == [], 'allow_search=False (lint ctx) must never time'
    autotune.clear_memory()


# ------------------------------------------- which kinds are on where

def test_kinds_default_per_backend(monkeypatch):
    """Unset, the tier is off on every backend (PR 41): the one-chip step
    takes the inline replay the mesh step has taken since PR 26."""
    monkeypatch.delenv('PT_KERNELGEN', raising=False)
    assert kg.pallas_kinds() == () and not kg.enabled(), \
        'CPU session: tier defaults OFF'
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert kg.pallas_kinds() == () and not kg.enabled(), \
        'TPU: the row kernel cost tbase.train_1chip 3 % (PERF.md PR 41)'
    monkeypatch.setenv('PT_KERNELGEN', '0')
    assert not kg.enabled(), 'explicit 0 is the default, spelled out'
    monkeypatch.setattr(jax, 'default_backend', lambda: 'cpu')
    monkeypatch.setenv('PT_KERNELGEN', '1')
    assert kg.pallas_kinds() == kg.ALL_KINDS, 'explicit 1: every kind'


def _default_route_model():
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


def _step_jaxpr(main, scope, feed, fetch_names, emit):
    """The step as `Executor._prepare_entry` lowers it (the rewriter, the
    emitter's engine or the traced path, `_lower`), traced to its jaxpr
    and never compiled: a mocked backend has no device to run on."""
    from paddle_tpu.core import emit as _emit
    from paddle_tpu.core import executor as em
    from paddle_tpu.core import passes
    from paddle_tpu.core.emit import emitter
    emitter.clear_memo()            # or the second build is the first's
    feed_names = tuple(sorted(feed))
    opt, _ = passes.maybe_optimize(main, fetch_names)
    engine = _emit.build_engine(opt, feed_names, fetch_names) if emit \
        else None
    jit_fn, params_in, _ = em._lower(opt, feed_names, fetch_names,
                                     emit_engine=engine)
    args = ({n: scope.vars[n] for n in params_in}, feed, np.uint32(0))
    return opt, str(jit_fn.trace(*args).jaxpr)


@pytest.mark.parametrize('emit', [True, False], ids=['emit', 'trace'])
def test_default_step_is_the_replay_on_a_tpu_backend(monkeypatch, emit):
    """Through both entries (`_emit_fused`, ops/fused.py) the step an unset
    PT_KERNELGEN lowers for a TPU backend is, text for text, the one
    under PT_KERNELGEN=0: no plan, no `custom_vjp` around a group, no
    `pallas_call`; and the tier's counters do not move."""
    main, startup, loss = _default_route_model()
    scope = fluid.Scope()
    fluid.Executor().run(startup, scope=scope)
    feed = {k: jnp.asarray(v) for k, v in _feeds(1)[0].items()}
    monkeypatch.setenv('PT_CACHE', '0')
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    kg.clear_plan_cache()
    before = obs.counters()

    monkeypatch.delenv('PT_KERNELGEN')
    opt, default = _step_jaxpr(main, scope, feed, (loss.name,), emit)
    monkeypatch.setenv('PT_KERNELGEN', '0')
    _, off = _step_jaxpr(main, scope, feed, (loss.name,), emit)

    groups = {tuple(sub['type'] for sub in op.attrs['sub_ops'])
              for op in opt.global_block().ops
              if op.type == 'fused_elementwise'}
    assert ('elementwise_add', 'relu') in groups, groups
    assert any('layer_norm' in g for g in groups), groups
    assert any(set(g) == {'adam'} and len(g) > 1 for g in groups), groups
    assert default == off
    assert 'pallas_call' not in default and 'custom_vjp' not in default
    after = obs.counters()
    for name in ('kernelgen.ops', 'kernelgen.kernels'):
        assert after.get(name, 0) == before.get(name, 0), name
    assert kg.plans() == []


def test_kind_off_runs_as_xla_step_bitwise(monkeypatch):
    """With 'ew' off (chip_smoke.py's MOSAIC_KINDS: what Mosaic
    compiles) a group's elementwise sub-ops run their registered impl as
    XLA steps while its layer_norm stays a generated row kernel — bitwise
    the all-kinds plan and the replay."""
    monkeypatch.setenv('PT_KERNELGEN', '1')
    rng = np.random.RandomState(5)
    x, y = _rand(rng, (6, 16)), _rand(rng, (6, 16))
    s, b = _rand(rng, (16,)), _rand(rng, (16,))
    attrs = _attrs(
        [_sub('elementwise_add', {'X': ['x'], 'Y': ['y']},
              {'Out': ['h']}, {'axis': -1}),
         _sub('layer_norm', {'X': ['h'], 'Scale': ['s'], 'Bias': ['b']},
              {'Y': ['o'], 'Mean': ['m'], 'Variance': ['v']},
              {'begin_norm_axis': 1, 'epsilon': 1e-5})],
        ['x', 'y', 's', 'b'], ['o'])
    avals = kg._in_avals([x, y, s, b])
    full = builder._build_plan(attrs, avals, False)
    part = builder._build_plan(attrs, avals, False,
                               kinds=('attention', 'row'))
    assert (full.n_kernels, full.n_dsteps, full.n_xla) == (1, 1, 0)
    assert (part.n_kernels, part.n_dsteps, part.n_xla) == (0, 1, 1)
    a, = full.fn((x, y, s, b), ())
    c, = part.fn((x, y, s, b), ())
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# ------------------------------------- config tokens and fingerprints

def test_config_token_and_fingerprint_extra(monkeypatch):
    monkeypatch.setenv('PT_KERNELGEN', '1')
    tok_on = kg.config_token()
    monkeypatch.setenv('PT_KERNELGEN', '0')
    tok_off = kg.config_token()
    assert tok_on != tok_off and tok_on[0] == 'kernelgen'
    monkeypatch.setenv('PT_KERNELGEN', '1')
    fp = kg.fingerprint_extra()
    assert fp[0] == 'kernelgen' and fp[1] == kg.KERNELGEN_VERSION
    assert fp[2] == kg.pallas_kinds()
    assert 'adam' in fp[3] and 'dropout' in fp[3]

    # executor composition: kernelgen OFF leaves old fingerprints
    # untouched; ON composes on both emit and trace paths
    from paddle_tpu.core import executor as em
    monkeypatch.setenv('PT_KERNELGEN', '1')
    assert em._compose_fp_extra(None) == fp
    assert em._compose_fp_extra(('emit', 1)) == (('emit', 1), fp)
    monkeypatch.setenv('PT_KERNELGEN', '0')
    assert em._compose_fp_extra(('emit', 1)) == ('emit', 1)
    assert em._compose_fp_extra(None) is None


# --------------------------------------------- end-to-end through fluid

def _train_model(seed=7):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[8], dtype='float32')
            lbl = fluid.layers.data('lbl', shape=[1], dtype='int64')
            h = fluid.layers.fc(x, 16, act='relu')
            h = fluid.layers.dropout(h, dropout_prob=0.4)
            logits = fluid.layers.fc(h, 4)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, lbl))
            fluid.optimizer.Adam(0.01).minimize(loss)
    main.set_amp(True)
    return main, startup, loss


def _feeds(K, batch=6, seed=0):
    rng = np.random.RandomState(seed)
    return [{'x': rng.randn(batch, 8).astype('float32'),
             'lbl': rng.randint(0, 4, (batch, 1)).astype('int64')}
            for _ in range(K)]


def _train(monkeypatch, pt_kg, runner, seed):
    # hermetic vs the shared AOT disk cache and the process-wide emitter
    # memo: either would serve an already-built (kernelgen-built, still
    # correct) callable without re-tracing, and kernelgen.ops only
    # counts fresh builds
    from paddle_tpu.core.emit import emitter
    emitter.clear_memo()
    monkeypatch.setenv('PT_CACHE', '0')
    monkeypatch.setenv('PT_KERNELGEN', pt_kg)
    kg.clear_plan_cache()
    main, startup, loss = _train_model(seed)
    losses, scope = runner(main, startup, loss)
    state = {n: np.asarray(v) for n, v in scope.vars.items()}
    return np.asarray(losses), state


def _assert_parity(monkeypatch, runner, seed):
    """First launch 1e-6, later steps drift-bounded (docstring up top);
    the kernel path must actually engage (kernelgen.ops advances —
    per-test seed keeps the program out of the cross-test lowering
    cache); there is no reroute, so a kernel failure raises."""
    before = obs.counters().get('kernelgen.ops') or 0
    l1, s1 = _train(monkeypatch, '1', runner, seed)
    assert (obs.counters().get('kernelgen.ops') or 0) > before
    l0, s0 = _train(monkeypatch, '0', runner, seed)
    l1, l0 = np.ravel(l1), np.ravel(l0)
    assert abs(l1[0] - l0[0]) <= 1e-6, (l1[0], l0[0])
    np.testing.assert_allclose(l1, l0, rtol=5e-3, atol=5e-4)
    assert set(s1) == set(s0)
    for n in s1:
        np.testing.assert_allclose(s1[n], s0[n], rtol=5e-2, atol=5e-3,
                                    err_msg=n)


def test_e2e_parity_run(monkeypatch):
    def runner(main, startup, loss):
        exe, scope = fluid.Executor(), fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            losses = [np.asarray(exe.run(main, feed=f,
                                         fetch_list=[loss])[0])
                      for f in _feeds(3)]
        return losses, scope
    _assert_parity(monkeypatch, runner, seed=21)


def test_e2e_parity_run_steps(monkeypatch):
    def runner(main, startup, loss):
        exe, scope = fluid.Executor(), fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            stacked, = exe.run_steps(main, feed_list=_feeds(3),
                                     fetch_list=[loss])
        return np.asarray(stacked), scope
    _assert_parity(monkeypatch, runner, seed=22)


def test_e2e_parity_parallel_executor(monkeypatch):
    from paddle_tpu.parallel.parallel_executor import ParallelExecutor

    def runner(main, startup, loss):
        exe, scope = fluid.Executor(), fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                                  scope=scope)
            losses = [np.asarray(pe.run([loss.name], feed=f)[0])
                      for f in _feeds(2, batch=8)]
        return losses, scope
    _assert_parity(monkeypatch, runner, seed=23)


def test_launch_signature_names_kernelgen_flip(monkeypatch):
    """Flipping PT_KERNELGEN between runs of one program is a NAMED
    retrace cause, not a mystery."""
    monkeypatch.setenv('PT_CACHE', '0')
    monkeypatch.setenv('PT_KERNELGEN', '0')
    main, startup, loss = _train_model()
    feed, = _feeds(1)
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        monkeypatch.setenv('PT_KERNELGEN', '1')
        exe.run(main, feed=feed, fetch_list=[loss])
    hits = [r for r in obs.explainer().reports
            if any('kernelgen' in d for d in r['details'])]
    assert hits, 'retrace explainer must name the kernelgen component'


def test_aot_disk_cache_round_trip(tmp_path, monkeypatch):
    """PT_KERNELGEN=1 executables round-trip the AOT disk cache: a
    second fresh-L1 executor loads without tracing, bitwise."""
    from paddle_tpu.core import executor as em
    monkeypatch.setenv('PT_CACHE', '1')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    monkeypatch.setenv('PT_KERNELGEN', '1')
    kg.clear_plan_cache()
    main, startup, loss = _train_model()
    feed, = _feeds(1)
    exe1, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe1.run(startup)
        a, = exe1.run(main, feed=feed, fetch_list=[loss])
    exe2, scope2 = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2.run(startup)
        tc = em._TRACE_COUNT[0]
        b, = exe2.run(main, feed=feed, fetch_list=[loss])
        assert em._TRACE_COUNT[0] == tc, \
            'second executor must load the AOT executable, not retrace'
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_emitter_memo_keys_on_kernelgen_token(monkeypatch):
    """The PR-12 emitter memo must not serve a kernelgen-built callable
    to a kernelgen-off run of the same signature (and vice versa)."""
    from paddle_tpu.core.emit import emitter
    assert emitter._kg_token() == kg.config_token()
    monkeypatch.setenv('PT_KERNELGEN', '1')
    t1 = emitter._kg_token()
    monkeypatch.setenv('PT_KERNELGEN', '0')
    t0 = emitter._kg_token()
    assert t1 != t0


def test_d016_lint_names_uncovered_sub_op():
    from paddle_tpu.analysis import lint_program
    from paddle_tpu.core import passes
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[4], dtype='float32')
            y = fluid.layers.relu(fluid.layers.scale(x, scale=2.0))
    opt, _ = passes.optimize_program(main, (y.name,))
    for op in opt.global_block().ops:
        if op.type == 'fused_elementwise':
            op.attrs['sub_ops'] = list(op.attrs['sub_ops']) + [
                _sub('made_up_op', {}, {})]
    res = lint_program(opt, fetch_names=[y.name])
    d16 = [d for d in res.diagnostics if d.code == 'D016']
    assert d16 and 'made_up_op' in d16[0].message


def test_d016_flags_bare_kernel_tier_op():
    """A softmax the fuse pass could NOT wrap (non-serializable attrs)
    must be flagged as a bare kernel-tier op, naming the escape."""
    from paddle_tpu.analysis import lint_program
    from paddle_tpu.core import passes
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[8], dtype='float32')
            s = fluid.layers.softmax(fluid.layers.scale(x, scale=2.0))
            y = fluid.layers.relu(fluid.layers.scale(s, scale=3.0))
    for op in main.global_block().ops:
        if op.type == 'softmax':
            op.attrs['opaque'] = object()  # blocks _plain_attrs
    opt, _ = passes.optimize_program(main, (y.name,))
    assert any(op.type == 'softmax' for op in opt.global_block().ops)
    res = lint_program(opt, fetch_names=[y.name])
    d16 = [d for d in res.diagnostics if d.code == 'D016']
    assert d16, 'bare kernel-tier softmax must raise a D016'
    assert 'softmax' in d16[0].message
    assert 'not presented' in d16[0].message
    assert 'serializable' in d16[0].message  # the named escape reason
    assert 'plain' in (d16[0].fixit or '')
