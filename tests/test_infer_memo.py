"""The memo of abstract evaluation (core/infer_memo.py, PR 67): one entry
point behind `Block._infer_shapes` and the lint gate's shape pass.

What it must never do is change an answer: shapes, dtypes and lint
findings are held against the same build with the memo cleared before
every evaluation, a failing op is named every time, and everything an
impl can observe under `InferCtx` makes its own entry.  Everything here
is a count or an equality on the CPU at small sizes, never a time."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
import paddle_tpu.observability as obs
from paddle_tpu import layers
from paddle_tpu.analysis import lint_program
from paddle_tpu.core import infer_memo, registry
from paddle_tpu.models import resnet, transformer as tr
from paddle_tpu.observability import tracing

ROOT = os.path.join(os.path.dirname(__file__), '..')


def _config(name):
    with open(os.path.join(ROOT, 'benchmarks', 'configs', name + '.json')) as f:
        return json.load(f)


def _build_tbase():
    """`tbase.train_1chip`'s program as benchmarks/runners/train.py
    `_build_transformer` builds it from the cell's config file, two
    layers deep at small widths."""
    cfg = dict(_config('tbase'), n_layer=2, d_model=32, n_head=4,
               d_inner=64, vocab=96)
    return tr.build(src_vocab=cfg['vocab'], trg_vocab=cfg['vocab'],
                    max_len=16, n_layer=cfg['n_layer'], n_head=cfg['n_head'],
                    d_model=cfg['d_model'], d_inner=cfg['d_inner'],
                    dropout=cfg['dropout'], lr=cfg['lr'],
                    warmup_steps=cfg['warmup_steps'],
                    use_flash=cfg['use_flash'])['loss']


def _build_resnet50():
    """`resnet50.train_1chip`'s program as `_build_resnet` builds it from
    the cell's config file: all 50 layers, on 32x32 images."""
    cfg = _config('resnet50')
    return resnet.build(data_shape=(3, 32, 32), class_dim=10,
                        depth=cfg['depth'], lr=cfg['lr'],
                        data_set=cfg['data_set'])['loss']


BUILDERS = {'tbase': _build_tbase, 'resnet50': _build_resnet50}


def _program(name):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 23
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss = BUILDERS[name]()
    main.set_amp(True)
    return main, startup, loss


def _declared(program):
    return [(b.idx, n, None if v.shape is None else tuple(v.shape),
             str(v.dtype)) for b in program.blocks for n, v in b.vars.items()]


def _feeds(program):
    return [n for n, v in program.global_block().vars.items()
            if getattr(v, 'is_data', False)]


def _findings(program, loss):
    res = lint_program(program, feed_names=_feeds(program),
                       fetch_names=[loss.name])
    return [(d.code, d.severity, d.block_idx, d.op_index, d.var, d.message)
            for d in res]


_counts = infer_memo.counts


def _moved(before):
    return tuple(int(a - b) for a, b in zip(_counts(), before))


@pytest.fixture(autouse=True)
def _fresh_memo():
    infer_memo.clear()
    yield
    infer_memo.clear()


@pytest.fixture
def cleared_every_op(monkeypatch):
    """Every evaluation starts from an empty memo: the parent's
    behaviour, one `jax.eval_shape` an op a probe."""
    real = infer_memo.abstract_eval

    def fresh(op, probes):
        out = []
        for ins in probes:
            infer_memo.clear()
            out += real(op, [ins])
        return out
    monkeypatch.setattr(infer_memo, 'abstract_eval', fresh)


@pytest.fixture
def scratch_op(monkeypatch):
    """Register an op type for one test: `scratch_op(name, impl)`."""
    registry.has_op('scale')       # the op modules are loaded first

    def put(name, impl):
        monkeypatch.setitem(registry._REGISTRY, name,
                            registry.OpDef(name, impl))
    return put


def _one_op(type, x_shape=(-1, 4), x_dtype='float32', attrs=None,
            out_dtype='float32'):
    """A program of one data var and one op on it; returns (program, op
    output var).  Raises what the build raises."""
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        blk = prog.global_block()
        x = blk.create_var(name='x', shape=list(x_shape), dtype=x_dtype)
        x.is_data = True
        out = blk.create_var(name='out', dtype=out_dtype)
        blk.append_op(type, inputs={'X': x}, outputs={'Out': out},
                      attrs=dict(attrs or {}))
    return prog, out


# ------------------------------------------------ (1) the same answers

@pytest.mark.parametrize('name', sorted(BUILDERS))
def test_shapes_and_dtypes_equal_the_build_with_the_memo_cleared_every_op(
        name, request):
    main, startup, _ = _program(name)
    assert _counts()[0] > 0                     # the memo did answer
    through_memo = _declared(main), _declared(startup)
    request.getfixturevalue('cleared_every_op')
    before = _counts()
    main, startup, _ = _program(name)
    assert _moved(before)[0] == 0               # and here it never did
    assert (_declared(main), _declared(startup)) == through_memo


@pytest.mark.parametrize('name', sorted(BUILDERS))
def test_lint_findings_equal_the_gate_with_the_memo_cleared_every_op(
        name, request):
    main, _, loss = _program(name)
    through_memo = _findings(main, loss)
    # a program the gate finds fault with, so that equal lists say
    # something: an op whose declared output contradicts what it makes
    blk = main.global_block()
    bad = blk.create_var(name='bad_out', shape=[3, 3], dtype='float32')
    blk.append_op('scale', inputs={'X': loss}, outputs={'Out': bad},
                  attrs={'scale': 2.0}, infer_shape=False)
    faulty = _findings(main, loss)
    assert any(code == 'D003' for code, *_ in faulty)
    request.getfixturevalue('cleared_every_op')
    before = _counts()
    assert _findings(main, loss) == faulty
    assert _moved(before)[0] == 0
    del blk.ops[-1]
    main._bump()
    assert _findings(main, loss) == through_memo


# --------------------------------- (2) a signature is evaluated once

@pytest.mark.parametrize('name', sorted(BUILDERS))
def test_a_second_build_evaluates_nothing(name):
    _program(name)
    first = _counts()
    assert first[1] > 0
    _program(name)
    hits, misses = _moved(first)
    assert misses == 0 and hits > 0


@pytest.mark.parametrize('name', sorted(BUILDERS))
def test_the_gate_evaluates_nothing_the_build_inferred(name, monkeypatch):
    real = infer_memo.abstract_eval
    inferred, missed = set(), []

    def at_build(op, probes):
        inferred.add(id(op))
        return real(op, probes)

    def at_gate(op, probes):
        before = _counts()
        out = real(op, probes)
        if _moved(before)[1]:
            missed.append(op)
        return out

    monkeypatch.setattr(infer_memo, 'abstract_eval', at_build)
    main, _, loss = _program(name)
    monkeypatch.setattr(infer_memo, 'abstract_eval', at_gate)
    before = _counts()
    _findings(main, loss)
    hits, misses = _moved(before)
    assert hits > 0
    assert [op.type for op in missed if id(op) in inferred] == []
    # what the gate does evaluate is what the build did not: ops appended
    # with infer_shape=False (the optimizer's updates)
    assert misses <= 2 * len([op for op in main.global_block().ops
                              if id(op) not in inferred])


def test_the_spans_carry_their_own_deltas():
    tracing.reset()
    _, startup, _ = _program('tbase')
    fluid.Executor().run(startup, scope=fluid.Scope())
    spans = [e for e in obs.recorder().events() if e['ph'] == 'X']
    build, = [e['args'] for e in spans if e['name'] == 'program.build']
    assert build['infer_hits'] > build['infer_misses'] > 0
    # the gate on the start-up program's cold path: every signature it
    # walks was inferred by the build
    lint, = [e['args'] for e in spans if e['name'] == 'executor.lint']
    assert lint['infer_misses'] == 0 and lint['infer_hits'] > 0
    tracing.reset()


# ------------------------- (3) the impl object is part of the key

def test_reregistering_a_type_with_another_impl_misses(scratch_op):
    def first(ctx, ins, attrs):
        return {'Out': ins['X']}

    def second(ctx, ins, attrs):
        return {'Out': jnp.concatenate([ins['X'], ins['X']], axis=1)}

    scratch_op('memo_probe', first)
    _, out = _one_op('memo_probe')
    assert tuple(out.shape) == (-1, 4)
    before = _counts()
    _one_op('memo_probe')
    assert _moved(before) == (2, 0)
    scratch_op('memo_probe', second)
    before = _counts()
    _, out = _one_op('memo_probe')
    assert _moved(before) == (0, 2)
    assert tuple(out.shape) == (-1, 8)


# ------------------------------------ (4) a failure is never stored

def test_a_failing_inference_names_its_own_op_every_time():
    for out_name in ('first_out', 'second_out'):
        prog = fluid.Program()
        with fluid.program_guard(prog, fluid.Program()):
            blk = prog.global_block()
            x = blk.create_var(name='x', shape=[-1, 4], dtype='float32')
            y = blk.create_var(name='y', shape=[-1, 5], dtype='float32')
            out = blk.create_var(name=out_name, dtype='float32')
            before = _counts()
            with pytest.raises(RuntimeError) as err:
                blk.append_op('elementwise_add', inputs={'X': x, 'Y': y},
                              outputs={'Out': out}, attrs={'axis': -1})
        assert 'shape inference failed for op elementwise_add' in str(
            err.value)
        assert out_name in str(err.value)       # ITS op's text
        assert _moved(before) == (0, 1)         # evaluated again, not kept


def test_the_gate_names_both_of_two_ops_with_one_bad_signature():
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        blk = prog.global_block()
        x = layers.data('x', shape=[4], dtype='float32')
        y = layers.data('y', shape=[5], dtype='float32')
        for n in ('o1', 'o2'):
            blk.append_op('elementwise_add', inputs={'X': x, 'Y': y},
                          outputs={'Out': blk.create_var(
                              name=n, shape=[-1, 4], dtype='float32')},
                          attrs={'axis': -1}, infer_shape=False)
    for _ in range(2):
        res = lint_program(prog, feed_names=['x', 'y'],
                           fetch_names=['o1', 'o2'])
        d003 = [d for d in res.errors if d.code == 'D003']
        assert [d.op_index for d in d003] == [0, 1]
        assert all('fails shape/dtype inference on inputs [x, y]'
                   in d.message for d in d003)


def test_a_failure_leaves_no_entry_behind(scratch_op):
    state = {'fail': True}

    def flaky(ctx, ins, attrs):
        if state['fail']:
            raise ValueError('not yet')
        return {'Out': ins['X']}

    scratch_op('memo_flaky', flaky)
    with pytest.raises(RuntimeError, match='not yet'):
        _one_op('memo_flaky')
    state['fail'] = False
    _, out = _one_op('memo_flaky')
    assert tuple(out.shape) == (-1, 4)


# --------------------------------------- (5) x64 is part of the key

def test_x64_on_and_off_give_two_entries():
    def cast_to_int64():
        return _one_op('cast', attrs={'in_dtype': 'float32',
                                      'out_dtype': 'int64'},
                       out_dtype='int64')

    before = _counts()
    cast_to_int64()
    assert _moved(before) == (0, 2)
    with jax.enable_x64(True):
        before = _counts()
        cast_to_int64()
        assert _moved(before) == (0, 2)
        before = _counts()
        cast_to_int64()
        assert _moved(before) == (2, 0)
    before = _counts()
    cast_to_int64()
    assert _moved(before) == (2, 0)
    assert len(infer_memo._MEMO) == 4


# ----------------------------------- (6) what goes past the memo

@pytest.mark.parametrize('attrs', [
    {'fn': len}, {'nested': [{'fn': lambda x: x}]}, {'sub_block': 0},
    {'arr': np.array([object()], dtype=object)}],
    ids=['callable', 'nested-callable', 'sub_block', 'object-array'])
def test_an_attr_without_a_canonical_form_goes_past_the_memo(
        attrs, scratch_op):
    calls = []

    def impl(ctx, ins, attrs):
        calls.append(1)
        return {'Out': ins['X']}

    scratch_op('memo_past', impl)
    before = _counts()
    _one_op('memo_past', attrs=attrs)
    _one_op('memo_past', attrs=attrs)
    assert len(calls) == 4                      # two probes, twice
    assert _moved(before) == (0, 0)
    assert not infer_memo._MEMO


def test_a_data_dependent_type_goes_past_the_memo(scratch_op, monkeypatch):
    calls = []

    def impl(ctx, ins, attrs):
        calls.append(1)
        return {'Out': ins['X']}

    scratch_op('memo_dd', impl)
    monkeypatch.setattr(infer_memo, 'DATA_DEPENDENT',
                        infer_memo.DATA_DEPENDENT | {'memo_dd'})
    before = _counts()
    _one_op('memo_dd')
    _one_op('memo_dd')
    assert len(calls) == 4 and _moved(before) == (0, 0)


# ------------------------- the key holds what an impl can observe

@pytest.mark.parametrize('a,b', [
    ({'v': 1}, {'v': 1.0}), ({'v': 1}, {'v': True}),
    ({'v': [1, 2]}, {'v': [2, 1]}), ({'v': [1, 2]}, {'v': (1, 2)}),
    ({'v': np.zeros(3, 'float32')}, {'v': np.zeros(3, 'float64')}),
    ({'v': np.zeros(3, 'float32')}, {'v': np.ones(3, 'float32')}),
    ({'v': np.zeros((1, 3), 'float32')}, {'v': np.zeros((3, 1), 'float32')}),
    ({'v': np.float32(1)}, {'v': np.float64(1)}),
    ({'v': {'a': 1}}, {'v': {'a': 2}}), ({'v': 1}, {'w': 1}),
    ({'v': None}, {}),
], ids=lambda x: None)
def test_attrs_an_impl_can_tell_apart_make_two_entries(a, b, scratch_op):
    scratch_op('memo_attrs', lambda ctx, ins, attrs: {'Out': ins['X']})
    _one_op('memo_attrs', attrs=a)
    before = _counts()
    _one_op('memo_attrs', attrs=dict(a))         # an equal copy hits
    assert _moved(before) == (2, 0)
    before = _counts()
    _one_op('memo_attrs', attrs=b)
    assert _moved(before) == (0, 2)


def test_equal_arrays_and_lists_share_an_entry(scratch_op):
    scratch_op('memo_attrs', lambda ctx, ins, attrs: {'Out': ins['X']})
    _one_op('memo_attrs', attrs={'v': np.arange(6.).reshape(2, 3),
                                 'l': [1, [2.0, 'x']]})
    before = _counts()
    _one_op('memo_attrs', attrs={'v': np.arange(6.).reshape(2, 3),
                                 'l': [1, [2.0, 'x']]})
    assert _moved(before) == (2, 0)


def test_slots_shapes_dtypes_and_weak_types_are_in_the_key():
    op = type('Op', (), {'type': 'scale', 'attrs': {'scale': 2.0}})()

    def ins(shape=(7, 4), dtype='float32', weak=False, slot='X', as_list=False):
        s = jax.ShapeDtypeStruct(shape, np.dtype(dtype), weak_type=weak)
        return {slot: [s] if as_list else s}

    infer_memo.abstract_eval(op, [ins()])
    for other in (ins(shape=(11, 4)), ins(dtype='bfloat16'),
                  ins(weak=True)):
        before = _counts()
        out, = infer_memo.abstract_eval(op, [other])
        assert _moved(before) == (0, 1)
        assert out['Out'].shape == other['X'].shape
    before = _counts()
    infer_memo.abstract_eval(op, [ins(), ins(shape=(11, 4)), ins(weak=True)])
    assert _moved(before) == (3, 0)
    # a list slot and a plain one are not the same input to an impl
    assert infer_memo._inputs_key(ins(as_list=True)) != \
        infer_memo._inputs_key(ins())
    assert infer_memo._inputs_key(ins(slot='Y')) != \
        infer_memo._inputs_key(ins())


def test_the_builds_int64_and_the_gates_int32_are_one_entry():
    """`eval_shape` hands the impl the canonical dtype, so a variable
    declared int64 (the build's `np_dtype`) and its narrowed reading
    (the gate's `jax_dtype`) are the same input with x64 off."""
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        ids = layers.data('ids', shape=[6], dtype='int64')
        before = _counts()
        out = layers.cast(ids, 'float32')
        assert _moved(before) == (0, 2)
    before = _counts()
    res = lint_program(prog, feed_names=['ids'], fetch_names=[out.name])
    assert _moved(before) == (2, 0)
    assert not res.errors


def test_the_memo_starts_over_when_full(monkeypatch, scratch_op):
    monkeypatch.setattr(infer_memo, '_MAX_ENTRIES', 4)
    scratch_op('memo_attrs', lambda ctx, ins, attrs: {'Out': ins['X']})
    for i in range(5):
        _one_op('memo_attrs', attrs={'i': i})
    assert 0 < len(infer_memo._MEMO) <= 4
    _, out = _one_op('memo_attrs', attrs={'i': 0})
    assert tuple(out.shape) == (-1, 4)
