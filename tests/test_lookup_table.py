"""`lookup_table` has ONE lowering, XLA's own gather (`jnp.take`).

The per-row DMA gather that stood beside it until PR 55 is deleted with
its switch (PERF.md section 6, PR 55), so these tests hold the OP, called
as the executor calls it and through a program, to numpy: rows for ids
of any shape, the gradient of duplicate ids a dense scatter-add,
`jnp.take`'s treatment of ids outside the table, `padding_idx`, and the
same jaxpr whatever the backend and the mesh.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import registry
from paddle_tpu.parallel.mesh import make_mesh

VOCAB, WIDTH = 40, 16
ID_SHAPES = {'N': (12,), 'BT': (3, 4), 'BT1': (3, 4, 1)}


def _table(dtype='float32', seed=0):
    w = np.random.RandomState(seed).randn(VOCAB, WIDTH).astype('float32')
    # bf16 tables hold values bf16 represents, so a lookup moves no bit
    return np.asarray(jnp.asarray(w, dtype)) if dtype != 'float32' else w


def _ids(shape, seed=1):
    ids = np.random.RandomState(seed).randint(0, VOCAB, shape)
    ids.reshape(-1)[:3] = 7                  # duplicates, always
    return ids.astype('int64')


def _op(w, ids, padding_idx=-1, mesh=None):
    """The registered impl, called as `_lower`'s op loop calls it."""
    ctx = registry.ExecCtx(jax.random.key(0), mesh=mesh)
    return registry.get_op('lookup_table').impl(
        ctx, {'W': jnp.asarray(w), 'Ids': jnp.asarray(ids)},
        {'padding_idx': padding_idx, 'is_sparse': False})['Out']


def _program(id_shape, dtype='float32', padding_idx=None, lr=None):
    """ids -> embedding [-> sum of squares / 2, SGD]: (main, startup,
    fetch, the table's name)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            ids = fluid.layers.data('ids', shape=list(id_shape),
                                    dtype='int64', append_batch_size=False)
            emb = fluid.layers.embedding(ids, size=[VOCAB, WIDTH],
                                         padding_idx=padding_idx,
                                         dtype=dtype)
            fetch = emb
            if lr is not None:
                fetch = fluid.layers.reduce_sum(emb * emb) * 0.5
                fluid.optimizer.SGD(lr).minimize(fetch)
    table, = main.global_block().all_parameters()
    return main, startup, fetch, table.name


def _through_a_program(w, ids, padding_idx=None, lr=None):
    """(fetch, the table after the run) with `w` written over the
    initializer's draw."""
    main, startup, fetch, name = _program(ids.shape, str(w.dtype),
                                          padding_idx, lr)
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        assert np.shape(scope.vars[name]) == w.shape
        scope.vars[name] = jnp.asarray(w)
        out, = exe.run(main, feed={'ids': ids}, fetch_list=[fetch])
        return np.asarray(out), np.asarray(scope.vars[name])


def _rows(w, ids):
    idx = ids[..., 0] if ids.ndim >= 2 and ids.shape[-1] == 1 else ids
    return np.asarray(w)[idx]


@pytest.mark.parametrize('entry', ['op', 'program'])
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('shape', sorted(ID_SHAPES))
def test_rows_are_numpys(shape, dtype, entry):
    w, ids = _table(dtype), _ids(ID_SHAPES[shape])
    if entry == 'op':
        got = np.asarray(_op(w, ids))
    else:
        got, _ = _through_a_program(w, ids)
    want = _rows(w, ids)
    assert got.shape == want.shape and got.dtype == want.dtype
    # a lookup copies rows: bitwise, no tolerance
    assert got.tobytes() == want.tobytes()


def _dense_scatter_add(ids, g):
    dw = np.zeros((VOCAB, WIDTH), 'float32')
    np.add.at(dw, ids.reshape(-1), g.reshape(-1, WIDTH))
    return dw


@pytest.mark.parametrize('entry', ['op', 'program'])
def test_gradient_of_duplicate_ids_is_a_dense_scatter_add(entry):
    w, ids = _table(), _ids(ID_SHAPES['BT1'])
    assert len(set(ids.reshape(-1))) < ids.size
    # d/dw of sum(rows ** 2) / 2 is the rows themselves, added per id
    want = _dense_scatter_add(ids, _rows(w, ids))
    if entry == 'op':
        got = jax.grad(lambda w: (_op(w, ids) ** 2).sum() * 0.5)(
            jnp.asarray(w))
    else:
        lr = 0.25
        _, after = _through_a_program(w, ids, lr=lr)
        got = (w - after) / lr
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('entry', ['op', 'program'])
def test_negative_ids_wrap_and_ids_past_the_table_give_nan_rows(entry):
    w = _table()
    ids = _ids(ID_SHAPES['N'])
    ids[0], ids[1], ids[2] = -5, VOCAB + 960, -VOCAB - 1
    if entry == 'op':
        got = np.asarray(_op(w, ids))
    else:
        got, _ = _through_a_program(w, ids)
    assert got[0].tobytes() == w[VOCAB - 5].tobytes()
    assert np.isnan(got[1]).all() and np.isnan(got[2]).all()
    assert got[3:].tobytes() == w[ids[3:]].tobytes()


@pytest.mark.parametrize('entry', ['op', 'program'])
def test_padding_idx_zeroes_its_rows_and_their_gradient(entry):
    w, ids = _table(), _ids(ID_SHAPES['BT1'])
    pad = 7
    assert (ids == pad).sum() >= 3
    rows = _rows(w, ids) * (ids != pad)
    want_dw = _dense_scatter_add(ids, rows)
    assert not want_dw[pad].any() and want_dw.any()
    if entry == 'op':
        got = np.asarray(_op(w, ids, padding_idx=pad))
        dw = jax.grad(
            lambda w: (_op(w, ids, padding_idx=pad) ** 2).sum() * 0.5)(
            jnp.asarray(w))
    else:
        got, _ = _through_a_program(w, ids, padding_idx=pad)
        lr = 0.25
        _, after = _through_a_program(w, ids, padding_idx=pad, lr=lr)
        dw = (w - after) / lr
    assert got.tobytes() == rows.tobytes()
    np.testing.assert_allclose(np.asarray(dw), want_dw, rtol=1e-5, atol=1e-6)


def _primitives(jaxpr):
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(_primitives(sub))
    return names


def test_the_cells_lookup_is_one_gather_whatever_the_backend_and_the_mesh(
        monkeypatch):
    """tbase.train_1chip's lookup (24,576 ids into `f32[32000,512]`, the
    shapes the deleted kernel engaged at) traced where the program
    believes it is on a chip: one `gather`, no `pallas_call`, and under
    an 8-device mesh the same jaxpr text for text."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    w = jax.ShapeDtypeStruct((32000, 512), jnp.float32)
    ids = jax.ShapeDtypeStruct((96, 256, 1), jnp.int32)

    def text_and_names(mesh):
        closed = jax.make_jaxpr(
            lambda w, ids: _op(w, ids, mesh=mesh))(w, ids)
        return str(closed), _primitives(closed.jaxpr)

    one, names = text_and_names(None)
    assert names.count('gather') == 1, names
    assert 'pallas_call' not in names and 'custom_vjp_call' not in names
    assert 'f32[96,256,512]' in one
    meshed, _ = text_and_names(make_mesh(data=8, model=1, pipe=1, seq=1))
    assert meshed == one


def test_the_dma_gather_and_its_switch_are_gone():
    root = os.path.dirname(os.path.abspath(fluid.__file__))
    assert not os.path.exists(os.path.join(root, 'ops', 'gather.py'))
    switch = 'PT_PALLAS_' + 'GATHER'
    readers = []
    for folder, _, files in os.walk(root):
        for name in files:
            if name.endswith('.py'):
                with open(os.path.join(folder, name)) as f:
                    if switch in f.read():
                        readers.append(os.path.join(folder, name))
    assert not readers, readers
