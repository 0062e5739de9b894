"""Direct Program→jaxpr emitter (core/emit): per-rule bitwise parity vs
the kernel reference, whole-program bitwise training parity PT_EMIT=1
vs 0 (run / run_steps / ParallelExecutor, AMP + dropout + Adam, fused
groups, control flow), loud per-program fallback (warn-once counters,
PT_STRICT_EMIT raising, runtime EmitError degradation), launch-report
lowering verdicts, signature-memo sharing, and AOT disk round-trips of
emitted executables."""
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.observability as obs
from paddle_tpu.core import emit, registry
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.core.emit import emitter


def _ctx(op_type, amp=False):
    return emitter.EmitCtx(None, None, amp, None, op_type)


# ------------------------------------------- rule-vs-kernel parity sweep
#
# Every registered emit rule must have at least one case here; the sweep
# below fails if a new rule lands without one.  Cases return (ins,
# attrs) with concrete numpy inputs; kernel impl and emit rule must
# agree BITWISE (the rule is a perf overlay, never a second semantics).

def _adam_case(rng, grad_dtype='float32'):
    import jax.numpy as jnp
    g = jnp.asarray(rng.randn(4, 3).astype('float32')).astype(grad_dtype)
    return ({'Param': rng.randn(4, 3).astype('float32'), 'Grad': g,
             'Moment1': rng.randn(4, 3).astype('float32') * 0.1,
             'Moment2': np.abs(rng.randn(4, 3)).astype('float32') * 0.01,
             'Beta1Pow': np.array([0.9 ** 3], 'float32'),
             'Beta2Pow': np.array([0.999 ** 3], 'float32'),
             'LearningRate': np.array([0.01], 'float32')},
            {'beta1': 0.9, 'beta2': 0.999, 'epsilon': 1e-8})


def _ew_cases(rng):
    x = rng.randn(4, 5).astype('float32')
    return [
        ({'X': x, 'Y': rng.randn(4, 5).astype('float32')}, {}),     # lax
        ({'X': x, 'Y': rng.randn(5).astype('float32')}, {}),        # jnp
        ({'X': x, 'Y': rng.randn(4, 1).astype('float32')},
         {'axis': 0}),                                              # jnp
    ]


_RULE_CASES = {
    'adam': lambda rng: [_adam_case(rng),
                         # bf16 grads over f32 moments (llama bf16):
                         # the rule must defer to the kernel's jnp
                         # promotion, not feed lax mixed dtypes
                         _adam_case(rng, grad_dtype='bfloat16')],
    'reshape': lambda rng: [
        ({'X': rng.randn(2, 3, 4).astype('float32')}, {'shape': [0, 12]}),
        ({'X': rng.randn(6, 4).astype('float32')}, {'shape': [2, 3, 4]}),
    ],
    'transpose': lambda rng: [
        ({'X': rng.randn(2, 3, 4).astype('float32')},
         {'axis': [2, 0, 1]}),
    ],
    'elementwise_add': _ew_cases,
    'elementwise_sub': _ew_cases,
    'elementwise_mul': _ew_cases,
    'elementwise_div': lambda rng: [
        ({'X': rng.randn(4, 5).astype('float32'),
          'Y': np.abs(rng.randn(4, 5)).astype('float32') + 0.5}, {}),
        ({'X': rng.randn(4, 5).astype('float32'),
          'Y': np.abs(rng.randn(5)).astype('float32') + 0.5}, {}),
    ],
}


def _rule_ops():
    return [n for n in registry.op_names()
            if registry.get_op(n).emit is not None]


def test_every_emit_rule_has_a_parity_case():
    missing = [n for n in _rule_ops() if n not in _RULE_CASES]
    assert not missing, ('emit rule(s) registered without a bitwise '
                         'parity case in _RULE_CASES: %s' % missing)


@pytest.mark.parametrize('op_type', sorted(_RULE_CASES))
def test_emit_rule_bitwise_matches_kernel(op_type):
    od = registry.get_op(op_type)
    assert od.emit is not None, 'case exists but rule was unregistered'
    rng = np.random.RandomState(0)
    for ins, attrs in _RULE_CASES[op_type](rng):
        want = od.impl(_ctx(op_type), dict(ins), dict(attrs))
        got = od.emit(_ctx(op_type), dict(ins), dict(attrs))
        assert set(want) == set(got)
        for slot in want:
            if want[slot] is None:
                assert got[slot] is None
                continue
            w, g = np.asarray(want[slot]), np.asarray(got[slot])
            assert w.dtype == g.dtype and w.shape == g.shape, slot
            np.testing.assert_array_equal(w, g, err_msg='%s.%s'
                                          % (op_type, slot))


# --------------------------------------- whole-program bitwise parity

def _train_model(seed=7, amp=True):
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
    if amp:
        main.set_amp(True)
    return main, startup, loss


def _feeds(K, batch=6, seed=0):
    rng = np.random.RandomState(seed)
    return [{'x': rng.randn(batch, 8).astype('float32'),
             'lbl': rng.randint(0, 4, (batch, 1)).astype('int64')}
            for _ in range(K)]


def _train(monkeypatch, pt_emit, runner, amp=True):
    monkeypatch.setenv('PT_EMIT', pt_emit)
    main, startup, loss = _train_model(amp=amp)
    losses, scope = runner(main, startup, loss)
    state = {n: np.asarray(v) for n, v in scope.vars.items()}
    return np.asarray(losses), state


def _assert_bitwise(monkeypatch, runner, amp=True):
    l1, s1 = _train(monkeypatch, '1', runner, amp=amp)
    l0, s0 = _train(monkeypatch, '0', runner, amp=amp)
    np.testing.assert_array_equal(l1, l0)
    assert set(s1) == set(s0)
    for n in s1:   # params AND Adam moments/pows, bit for bit
        np.testing.assert_array_equal(s1[n], s0[n], err_msg=n)


def test_bitwise_parity_run(monkeypatch):
    def runner(main, startup, loss):
        exe, scope = fluid.Executor(), fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            losses = [np.asarray(exe.run(main, feed=f,
                                         fetch_list=[loss])[0])
                      for f in _feeds(4)]
        return losses, scope
    _assert_bitwise(monkeypatch, runner)


def test_bitwise_parity_run_no_amp(monkeypatch):
    def runner(main, startup, loss):
        exe, scope = fluid.Executor(), fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            losses = [np.asarray(exe.run(main, feed=f,
                                         fetch_list=[loss])[0])
                      for f in _feeds(3)]
        return losses, scope
    _assert_bitwise(monkeypatch, runner, amp=False)


def test_bitwise_parity_run_steps(monkeypatch):
    def runner(main, startup, loss):
        exe, scope = fluid.Executor(), fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            stacked, = exe.run_steps(main, feed_list=_feeds(4),
                                     fetch_list=[loss])
        return np.asarray(stacked), scope
    _assert_bitwise(monkeypatch, runner)


def test_bitwise_parity_parallel_executor(monkeypatch):
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
    _assert_bitwise(monkeypatch, runner)


def _control_flow_outputs(monkeypatch, pt_emit):
    from paddle_tpu import layers
    monkeypatch.setenv('PT_EMIT', pt_emit)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[4], dtype='float32')
            i = layers.fill_constant(shape=[1], dtype='int64', value=0)
            n = layers.fill_constant(shape=[1], dtype='int64', value=5)
            acc = layers.fill_constant(shape=[1, 4], dtype='float32',
                                       value=0.0)
            cond = layers.less_than(i, n)
            w = layers.While(cond)
            with w.block():
                layers.assign(acc + fluid.layers.scale(x, scale=1.5), acc)
                layers.increment(i, 1)
                layers.less_than(i, n, cond=cond)
            flag = layers.fill_constant(shape=[1], dtype='bool',
                                        value=True)
            ie = layers.IfElse(flag)
            with ie.true_block():
                ie.output(fluid.layers.scale(acc, scale=2.0))
            with ie.false_block():
                ie.output(fluid.layers.scale(acc, scale=-1.0))
            out, = ie()
    exe, scope = fluid.Executor(), fluid.Scope()
    xv = np.arange(4, dtype='float32').reshape(1, 4) + 0.25
    with fluid.scope_guard(scope):
        iv, av, ov = exe.run(main, feed={'x': xv},
                             fetch_list=[i, acc, out])
    return np.asarray(iv), np.asarray(av), np.asarray(ov)


def test_bitwise_parity_control_flow(monkeypatch):
    """While + IfElse sub-blocks: the engine's dmasks cover sub-block
    ops and the executor threads ectx.emit_engine into _run_block."""
    got = _control_flow_outputs(monkeypatch, '1')
    want = _control_flow_outputs(monkeypatch, '0')
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert got[0][0] == 5


# --------------------------------------------------- signature sharing

def test_rng_stream_shares_one_memo_signature(monkeypatch):
    """Two structurally-identical bias-add+dropout fused groups differ
    only in their RNG streams and var names — streams travel as traced
    arguments and names are alpha-renamed, so both instances must land
    on ONE memoized signature."""
    monkeypatch.setenv('PT_EMIT', '1')
    emit.clear_memo()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[8], dtype='float32')
            h = fluid.layers.dropout(fluid.layers.fc(x, 8),
                                     dropout_prob=0.3)
            h = fluid.layers.dropout(fluid.layers.fc(h, 8),
                                     dropout_prob=0.3)
            out = fluid.layers.fc(h, 8)
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed={'x': np.ones((2, 8), 'float32')},
                fetch_list=[out])
    keys = [k for k in emitter._MEMO if k[0] == 'fused_elementwise'
            and any(sub[0] == 'dropout' for sub in k[1][1])]
    assert len(keys) == 1, keys


# ------------------------------------------------- fallback behavior

def _relu_model():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data('x', shape=[4], dtype='float32')
            out = fluid.layers.relu(fluid.layers.scale(x, scale=2.0))
    return main, startup, out


def test_deny_listed_op_falls_back_loudly(monkeypatch):
    monkeypatch.setenv('PT_EMIT', '1')
    monkeypatch.setattr(emitter, 'DENY_OPS', {'relu'})
    emit.reset_fallbacks()
    main, _, out = _relu_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    xv = np.array([[-1.0, 0.0, 1.0, 2.0]], 'float32')
    before = obs.counters().get('emitter.fallbacks') or 0
    with pytest.warns(RuntimeWarning, match='relu'):
        with fluid.scope_guard(scope):
            got, = exe.run(main, feed={'x': xv}, fetch_list=[out])
    np.testing.assert_array_equal(np.asarray(got),
                                  np.maximum(xv * 2.0, 0.0))
    c = obs.counters()
    assert (c.get('emitter.fallbacks') or 0) == before + 1
    assert (c.get('emitter.fallbacks.relu') or 0) >= 1
    rep = obs.explainer().last_report()
    assert rep['lowering'] == 'emit_fallback:relu'
    # warn-once: the same op type degrading again stays quiet
    with warnings.catch_warnings():
        warnings.simplefilter('error')
        emit.note_fallback('relu', 'again')


def test_strict_emit_raises_naming_op(monkeypatch):
    monkeypatch.setenv('PT_EMIT', '1')
    monkeypatch.setenv('PT_STRICT_EMIT', '1')
    monkeypatch.setattr(emitter, 'DENY_OPS', {'relu'})
    main, _, out = _relu_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        with pytest.raises(emit.EmitFallback, match='relu'):
            exe.run(main, feed={'x': np.ones((1, 4), 'float32')},
                    fetch_list=[out])


def test_runtime_emit_error_degrades_to_traced(monkeypatch):
    """A kernel that draws ctx.rng while its op type is missing from
    the emitter RNG set raises EmitError mid-trace; the executor must
    rebuild that program on the traced path and still produce the
    PT_EMIT=0 numbers."""
    def run_once():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = 11
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data('x', shape=[5], dtype='float32')
                out = fluid.layers.dropout(x, dropout_prob=0.5)
        exe, scope = fluid.Executor(), fluid.Scope()
        with fluid.scope_guard(scope):
            got, = exe.run(main, feed={'x': np.ones((3, 5), 'float32')},
                           fetch_list=[out])
        return np.asarray(got)

    monkeypatch.setenv('PT_EMIT', '0')
    want = run_once()

    monkeypatch.setenv('PT_EMIT', '1')
    monkeypatch.setattr(emitter, 'RNG_OPS',
                        emitter.RNG_OPS - {'dropout'})
    emit.clear_memo()
    emit.reset_fallbacks()
    before = obs.counters().get('emitter.fallbacks') or 0
    with pytest.warns(RuntimeWarning, match='dropout'):
        got = run_once()
    np.testing.assert_array_equal(got, want)
    assert (obs.counters().get('emitter.fallbacks') or 0) == before + 1
    rep = obs.explainer().last_report()
    assert rep['lowering'] == 'emit_fallback:dropout'
    emit.clear_memo()   # drop fns traced under the shrunken RNG set


def test_launch_report_carries_emit_verdict(monkeypatch):
    monkeypatch.setenv('PT_EMIT', '1')
    main, _, out = _relu_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(main, feed={'x': np.ones((2, 4), 'float32')},
                fetch_list=[out])
    rep = obs.explainer().last_report()
    assert rep['lowering'] == 'emit'
    assert 'lowering=emit' in obs.explainer().render_report(rep)


def test_retrace_explainer_names_pt_emit_toggle(monkeypatch):
    main, _, out = _relu_model()
    exe, scope = fluid.Executor(), fluid.Scope()
    xv = np.ones((2, 4), 'float32')
    obs.explainer().reset()
    with fluid.scope_guard(scope):
        monkeypatch.setenv('PT_EMIT', '1')
        exe.run(main, feed={'x': xv}, fetch_list=[out])
        monkeypatch.setenv('PT_EMIT', '0')
        exe.run(main, feed={'x': xv}, fetch_list=[out])
    rep = obs.explainer().last_report()
    assert rep['kind'] == 'retrace'
    assert rep['lowering'] == 'trace'
    assert any('PT_EMIT' in d for d in rep['details'])


def test_unsupported_ops_and_capability():
    from paddle_tpu.core.framework import Operator
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[4], dtype='float32')
        out = fluid.layers.scale(x, scale=2.0)
        blk = main.global_block()
        blk.ops.append(Operator(blk, 'bogus_op', inputs={'X': x},
                                outputs={'Out': out}, attrs={}))
    gaps = emit.unsupported_ops(main)
    assert gaps == [('bogus_op', 'no registered kernel')]
    assert emitter.op_capability('while')[0]          # executor-native
    assert emitter.op_capability('relu') == (True, 'kernel')
    assert emitter.op_capability('adam') == (True, 'rule')


def test_register_emit_guards():
    with pytest.raises(ValueError, match='unregistered'):
        registry.register_emit('never_registered_op')(lambda c, i, a: {})
    with pytest.raises(ValueError, match='already'):
        registry.register_emit('adam')(lambda c, i, a: {})


# ------------------------------------------------- AOT disk round-trip

def test_emitted_executable_disk_round_trip(tmp_path, monkeypatch):
    """PT_EMIT=1 + PT_CACHE=1: a fresh Executor (fresh L1) must serve
    the EMITTED executable from disk without tracing; flipping to
    PT_EMIT=0 must MISS (fingerprints carry the emitter coverage) and
    compile its own traced twin — to the same bits."""
    monkeypatch.setenv('PT_EMIT', '1')
    monkeypatch.setenv('PT_CACHE', '1')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    main, startup, loss = _train_model(amp=False)
    feed = _feeds(1)[0]

    exe1, scope1 = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope1):
        exe1.run(startup)
        a, = exe1.run(main, feed=feed, fetch_list=[loss])

    exe2, scope2 = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2.run(startup)
        tc = executor_mod._TRACE_COUNT[0]
        b, = exe2.run(main, feed=feed, fetch_list=[loss])
        assert executor_mod._TRACE_COUNT[0] == tc, \
            'second executor must load the emitted AOT executable'
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

    monkeypatch.setenv('PT_EMIT', '0')
    misses0 = obs.counters().get('compile_cache.disk_misses') or 0
    exe3, scope3 = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope3):
        exe3.run(startup)
        c, = exe3.run(main, feed=feed, fetch_list=[loss])
    assert (obs.counters().get('compile_cache.disk_misses') or 0) \
        > misses0, 'traced run must not be served an emitted artifact'
    assert np.asarray(a).tobytes() == np.asarray(c).tobytes()


def test_fallback_program_shares_traced_artifacts(tmp_path, monkeypatch):
    """A program that FALLS BACK fingerprints with extra=None, so its
    traced artifact is shared with PT_EMIT=0 runs: the second process
    posture (fresh L1, PT_EMIT=0) must disk-hit the entry the fallback
    run stored."""
    monkeypatch.setenv('PT_CACHE', '1')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    monkeypatch.setattr(emitter, 'DENY_OPS', {'relu'})
    emit.reset_fallbacks()
    main, _, out = _relu_model()
    feed = {'x': np.ones((2, 4), 'float32')}

    monkeypatch.setenv('PT_EMIT', '1')
    exe1, scope1 = fluid.Executor(), fluid.Scope()
    with pytest.warns(RuntimeWarning):
        with fluid.scope_guard(scope1):
            a, = exe1.run(main, feed=feed, fetch_list=[out])

    monkeypatch.setenv('PT_EMIT', '0')
    hits0 = obs.counters().get('compile_cache.disk_hits') or 0
    exe2, scope2 = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope2):
        b, = exe2.run(main, feed=feed, fetch_list=[out])
    assert (obs.counters().get('compile_cache.disk_hits') or 0) > hits0
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
