"""The fence between a weight's gradient and the optimizer (`_lower`).

On one device the gradient of every rank-2 parameter crosses its own
`jax.lax.optimization_barrier` before an optimizer op reads it, so XLA
compiles the weight-gradient product alone and the update as a loop
fusion (PERF.md section 6, PR 54).  A filter, a vector and every gradient
under a mesh are left as they were.  The barrier is an identity: K fused
steps still equal K single runs bit for bit.
"""
import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.core import emit, passes
from paddle_tpu.observability import metrics
from paddle_tpu.parallel.mesh import make_mesh


def _fc_adam():
    x = fluid.layers.data('x', shape=[8], dtype='float32')
    h = fluid.layers.fc(x, 16, act='relu')
    return fluid.layers.fc(h, 4), fluid.optimizer.Adam(0.01)


def _conv_momentum():
    x = fluid.layers.data('x', shape=[2, 6, 6], dtype='float32')
    h = fluid.layers.conv2d(x, num_filters=4, filter_size=3, act='relu')
    return fluid.layers.fc(h, 4), fluid.optimizer.Momentum(0.05, 0.9)


MODELS = {'fc_adam': (_fc_adam, (8,)),
          'conv_momentum': (_conv_momentum, (2, 6, 6))}


def _program(model):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            logits, optimizer = MODELS[model][0]()
            lbl = fluid.layers.data('lbl', shape=[1], dtype='int64')
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, lbl))
            optimizer.minimize(loss)
    return main, startup, loss


def _feeds(model, K, batch=8):
    rng = np.random.RandomState(0)
    return [{'x': rng.randn(batch, *MODELS[model][1]).astype('float32'),
             'lbl': rng.randint(0, 4, (batch, 1)).astype('int64')}
            for _ in range(K)]


def _barriers(jaxpr):
    """`optimization_barrier` equations in a jaxpr and every jaxpr its
    equations hold."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == 'optimization_barrier'
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _barriers(sub)
    return n


@pytest.mark.parametrize('meshed', [False, True], ids=['one_device', 'mesh'])
@pytest.mark.parametrize('model', sorted(MODELS))
def test_one_barrier_a_rank2_gradient_and_none_under_a_mesh(
        monkeypatch, model, meshed):
    monkeypatch.setenv('PT_CACHE', '0')
    main, startup, loss = _program(model)
    scope = fluid.Scope()
    fluid.Executor().run(startup, scope=scope)
    ranks = sorted(len(p.shape) for p in main.global_block().all_parameters())
    # fc -> fc: two weights, two biases; conv -> fc: a filter, a weight
    # and their biases
    assert ranks == {'fc_adam': [1, 1, 2, 2],
                     'conv_momentum': [1, 1, 2, 4]}[model]
    weights = 0 if meshed else ranks.count(2)

    mesh = make_mesh(data=8, model=1, pipe=1, seq=1) if meshed else None
    feed, = _feeds(model, 1)
    feed_names, fetch_names = tuple(sorted(feed)), (loss.name,)
    opt, _ = passes.maybe_optimize(main, fetch_names)
    jit_fn, params_in, _ = executor_mod._lower(
        opt, feed_names, fetch_names, mesh=mesh,
        emit_engine=emit.build_engine(opt, feed_names, fetch_names))
    fences = metrics.counter('executor.grad_fences')
    before = fences.value
    with jax.disable_jit():
        jaxpr = jax.make_jaxpr(jit_fn)(
            {n: scope.vars[n] for n in params_in}, feed, np.uint32(0)).jaxpr
    assert _barriers(jaxpr) == weights
    assert fences.value - before == weights


def _train(model, feeds, fused):
    """Losses `[K, 1]` and the scope after the feeds, as one K-step launch
    or as K runs."""
    main, startup, loss = _program(model)
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        if fused:
            losses, = exe.run_steps(main, feed_list=feeds, fetch_list=[loss])
        else:
            losses = [exe.run(main, feed=f, fetch_list=[loss])[0]
                      for f in feeds]
    return np.asarray(losses).reshape(len(feeds), -1), scope


def _single_runs(model, feeds):
    return _train(model, feeds, fused=False)


def _unfenced_fused_steps(model, feeds):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax.lax, 'optimization_barrier', lambda x: x)
        return _train(model, feeds, fused=True)


# A convolution inside the CPU's scan rounds its last bit otherwise than
# outside it, at the parent of the fence too, so the filter's program is
# held to the same launch without the barrier and not to single runs.
@pytest.mark.parametrize('model, oracle', [
    ('fc_adam', _single_runs), ('fc_adam', _unfenced_fused_steps),
    ('conv_momentum', _unfenced_fused_steps)],
    ids=['fc_adam-single_runs', 'fc_adam-unfenced', 'conv_momentum-unfenced'])
def test_four_fused_steps_through_the_fence_move_no_bit(model, oracle):
    feeds = _feeds(model, 4)
    want_losses, want = oracle(model, feeds)
    fences = metrics.counter('executor.grad_fences')
    before = fences.value
    losses, scope = _train(model, feeds, fused=True)
    assert fences.value > before        # the fused launch went through it
    assert losses.tobytes() == want_losses.tobytes()
    assert set(scope.vars) == set(want.vars)
    for n in scope.vars:
        assert np.asarray(scope.vars[n]).tobytes() == \
            np.asarray(want.vars[n]).tobytes(), n
