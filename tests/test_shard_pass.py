"""GSPMD-style shard pass (core/passes/shard.py): spec completion,
explicit collectives, ZeRO-sharded optimizer state, bitwise
sharded-vs-single-device parity, the memplan ZeRO divisor, and the
checkpoint sharding adoption (docs/passes.md, "The shard pass")."""
import re
import tempfile

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.analysis import lint_program
from paddle_tpu.core import passes
from paddle_tpu.core.passes import shard
from paddle_tpu.core.sharding import spec_from_jsonable, normalize_spec
from paddle_tpu.parallel.mesh import make_mesh

COLLECTIVES = set(shard.COLLECTIVE_OPS)


def _mesh2():
    import jax
    return make_mesh(data=2, devices=jax.devices()[:2])


def _build(mesh=True, dropout=False, amp=False, seed=7):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[8], dtype='float32')
        h = fluid.layers.fc(x, size=8, act='relu')
        if dropout:
            h = fluid.layers.dropout(h, dropout_prob=0.3)
        y = fluid.layers.fc(h, size=4)
        loss = fluid.layers.reduce_mean(y * y)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    if amp:
        main.set_amp(True)
    if mesh:
        main.set_mesh_axes({'data': 2})
        x.sharding = (None, None)   # replicated feed => bitwise parity
    return main, startup, loss


def _collective_ops(program):
    return [op for b in program.blocks for op in b.ops
            if op.type in COLLECTIVES]


# ------------------------------------------------------- the rewrite

def test_no_mesh_is_inert():
    main, _, loss = _build(mesh=False)
    opt, stats = passes.optimize_program(main, (loss.name,))
    s = stats['passes']['shard']
    assert not _collective_ops(opt)
    assert s['reshards_inserted'] == s['grad_allreduce'] == \
        s['all_gathers'] == s['zero_params'] == 0


def test_pt_shard_0_disables(monkeypatch):
    monkeypatch.setenv('PT_SHARD', '0')
    main, _, loss = _build()
    opt, stats = passes.optimize_program(main, (loss.name,))
    assert not _collective_ops(opt)
    assert shard.config_token() == ('shard_off',)


def test_config_token_in_pipeline_token(monkeypatch):
    t1 = passes.config_token()
    assert 'shard_on' in t1
    monkeypatch.setenv('PT_SHARD_ZERO', '0')
    t2 = passes.config_token()
    assert t1 != t2 and 'nozero' in t2


def test_explicit_collectives_and_zero_state():
    main, _, loss = _build()
    opt, stats = passes.optimize_program(main, (loss.name,))
    s = stats['passes']['shard']
    # 4 params (2 w + 2 b): each gets exactly one grad_allreduce and,
    # because their only post-backward reader is their own update op,
    # one forward all_gather
    assert s['zero_params'] == 4
    assert s['zero_state_vars'] == 8      # moment1+moment2 per param
    assert s['grad_allreduce'] == 4
    assert s['all_gathers'] == 4
    gblock = opt.global_block()
    ars = [op for op in _collective_ops(opt) if op.type == 'grad_allreduce']
    assert sorted(op.attrs['param'] for op in ars) == \
        sorted(v.name for v in gblock.all_parameters())
    for op in _collective_ops(opt):
        assert isinstance(op.attrs['bytes'], int) and op.attrs['bytes'] > 0
        assert op.attrs['dst_spec'] is not None
    # ZeRO layout landed on the vars: dim 0 split over 'data'
    for p in gblock.all_parameters():
        assert gblock.vars[p.name]._sharding_spec[0] == 'data'


def test_pass_is_idempotent():
    main, _, loss = _build(dropout=True)
    opt, _ = passes.optimize_program(main, (loss.name,))
    opt2, stats2 = passes.optimize_program(opt, (loss.name,))
    s = stats2['passes']['shard']
    assert s['reshards_inserted'] == s['grad_allreduce'] == \
        s['all_gathers'] == s['specs_completed'] == 0
    assert len(_collective_ops(opt2)) == len(_collective_ops(opt))


def test_optimized_program_lints_clean():
    main, _, loss = _build(dropout=True)
    opt, _ = passes.optimize_program(main, (loss.name,))
    res = lint_program(opt, feed_names=('x',), fetch_names=(loss.name,))
    assert not [d for d in res.diagnostics
                if d.code in ('D017', 'D018', 'D019')]


def test_trailing_replication_equivalence_no_reshard():
    # (None,) on the bias vs (None, None) on the activation is the SAME
    # placement: neither the lint nor the pass may reshard it
    main, _, loss = _build()
    opt, stats = passes.optimize_program(main, (loss.name,))
    assert stats['passes']['shard']['reshards_inserted'] == 0


# ---------------------------------------- D018 <-> reshard bytes parity

def test_d018_bytes_equal_reshard_op_bytes():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[8], dtype='float32')
        h = fluid.layers.fc(x, size=8)
        loss = fluid.layers.reduce_mean(h * h)
    main.set_mesh_axes({'data': 2})
    x.sharding = ('data', None)
    # annotation fights the dataflow layout => one D018 edge on h
    hv = main.global_block().vars[h.name]
    hv.sharding = (None, None)
    res = lint_program(main, feed_names=('x',), fetch_names=(loss.name,))
    d18 = [d for d in res.diagnostics
           if d.code == 'D018' and d.var == h.name]
    assert d18, 'expected an implicit-reshard warning on %s' % h.name
    est = int(re.search(r'~(\d+) bytes/device', d18[0].message).group(1))
    opt, stats = passes.optimize_program(main, (loss.name,))
    reshards = [op for op in _collective_ops(opt) if op.type == 'reshard'
                and (op.outputs.get('Out') or [None])[0] == h.name]
    assert len(reshards) == 1
    assert reshards[0].attrs['bytes'] == est
    assert normalize_spec(spec_from_jsonable(
        reshards[0].attrs['dst_spec'])) == (None, None)
    # and the rewritten program no longer carries the D018
    res2 = lint_program(opt, feed_names=('x',), fetch_names=(loss.name,))
    assert not [d for d in res2.diagnostics if d.code == 'D018']


def test_adjacent_collectives_fuse():
    from paddle_tpu.core.framework import Operator
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[8], dtype='float32')
        y = fluid.layers.relu(x)
        loss = fluid.layers.reduce_mean(y)
    main.set_mesh_axes({'data': 2})
    block = main.global_block()
    # hand-build a reshard -> reshard chain on the relu output edge
    mid = block.create_var(name='y_mid', dtype=y.dtype, shape=y.shape)
    out = block.create_var(name='y_out', dtype=y.dtype, shape=y.shape)
    r1 = Operator(block, 'reshard', inputs={'X': y.name},
                  outputs={'Out': 'y_mid'},
                  attrs={'src_spec': ['data', None],
                         'dst_spec': [None, None], 'bytes': 16})
    r2 = Operator(block, 'reshard', inputs={'X': 'y_mid'},
                  outputs={'Out': 'y_out'},
                  attrs={'src_spec': [None, None],
                         'dst_spec': ['data', None], 'bytes': 32})
    idx = next(i for i, op in enumerate(block.ops)
               if op.type == 'reduce_mean')
    block.ops[idx:idx] = [r1, r2]
    mid.op, out.op = r1, r2
    block.ops[idx + 2].inputs['X'] = ['y_out']
    main._bump()
    opt, stats = passes.optimize_program(main, (loss.name,))
    assert stats['passes']['shard']['collectives_fused'] >= 1
    chain = [op for op in _collective_ops(opt)]
    assert len(chain) == 1
    assert chain[0].attrs['src_spec'] == ['data', None]
    assert chain[0].attrs['dst_spec'] == ['data', None]


# ----------------------------------------------------- bitwise parity

def _train(mesh, steps=3, use_run_steps=False):
    main, startup, loss = _build(mesh=mesh, dropout=True, amp=True)
    exe = fluid.Executor(mesh=_mesh2() if mesh else None)
    scope = fluid.Scope()
    feeds = [{'x': np.random.RandomState(i).rand(4, 8).astype('float32')}
             for i in range(steps)]
    with fluid.scope_guard(scope):
        exe.run(startup)
        if use_run_steps:
            out = exe.run_steps(main, feed_list=feeds, fetch_list=[loss])
            losses = [float(v) for v in np.asarray(out[0]).reshape(-1)]
        else:
            losses = [np.asarray(exe.run(main, feed=f,
                                         fetch_list=[loss])[0]).item()
                      for f in feeds]
        state = {n: np.asarray(scope.find_var(n).get_tensor())
                 for n in sorted(main.global_block().vars)
                 if main.global_block().vars[n].persistable
                 and scope.find_var(n) is not None}
    return losses, state


def _assert_state_equal(s1, s2):
    assert len(s1) == len(s2)
    for (n1, a), (n2, b) in zip(sorted(s1.items()), sorted(s2.items())):
        assert np.array_equal(a, b), (n1, n2)


@pytest.mark.parametrize('use_run_steps', [False, True])
def test_bitwise_parity_mesh_vs_single_device(use_run_steps):
    # AMP + dropout on, ZeRO-sharded params/moments on the mesh side:
    # losses AND end-of-run param/Adam state must be bitwise equal
    l1, s1 = _train(False, use_run_steps=use_run_steps)
    l2, s2 = _train(True, use_run_steps=use_run_steps)
    assert l1 == l2
    _assert_state_equal(s1, s2)


def test_zero_state_physically_sharded():
    import jax
    main, startup, loss = _build(mesh=True)
    exe, scope = fluid.Executor(mesh=_mesh2()), fluid.Scope()
    feed = {'x': np.random.RandomState(0).rand(4, 8).astype('float32')}
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(2):
            exe.run(main, feed=feed, fetch_list=[loss])
        total = dev0 = 0
        for n in main.global_block().vars:
            arr = scope.vars.get(n)
            v = main.global_block().vars[n]
            if arr is None or not v.persistable or \
                    not hasattr(arr, 'addressable_shards'):
                continue
            total += arr.nbytes
            dev0 += sum(s.data.nbytes for s in arr.addressable_shards
                        if s.device == jax.devices()[0])
    # params + moments halve; scalar beta-pows/LR stay replicated
    assert dev0 <= 0.6 * total


# -------------------------------------------------- memplan ZeRO divisor

def test_memplan_divides_by_zero_divisor(monkeypatch):
    from paddle_tpu.analysis.passes.memplan import plan_memory
    main, _, loss = _build(mesh=False)
    p0 = plan_memory(main)
    # fc8(w 8x8 + b 8) + fc4(w 8x4 + b 4), f32
    assert p0.params_bytes == 432
    # 2 moments per param (864) + 8 beta-pow scalars (32) + lr (4)
    assert p0.opt_state_bytes == 900
    main.set_mesh_axes({'data': 2})
    p1 = plan_memory(main)
    assert p1.params_bytes == 216            # all four shard: 432 / 2
    assert p1.opt_state_bytes == 432 + 36    # moments halve, scalars don't
    assert (p1.params_bytes + p1.opt_state_bytes) <= \
        0.6 * (p0.params_bytes + p0.opt_state_bytes)
    monkeypatch.setenv('PT_SHARD', '0')
    p2 = plan_memory(main)
    assert p2.params_bytes == 432 and p2.opt_state_bytes == 900
    monkeypatch.delenv('PT_SHARD')
    # an optimized program (specs applied) plans the same — no double div
    opt, _ = passes.optimize_program(main, (loss.name,))
    p3 = plan_memory(opt)
    assert p3.params_bytes == 216 and p3.opt_state_bytes == 468


# --------------------------------------------- checkpoint spec adoption

def test_restore_adopts_manifest_sharding():
    from paddle_tpu.train.checkpoint import Checkpointer, CheckpointConfig

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data('x', shape=[8], dtype='float32')
            y = fluid.layers.fc(
                x, size=4, param_attr=fluid.ParamAttr(name='ckw'),
                bias_attr=fluid.ParamAttr(name='ckb'))
            loss = fluid.layers.reduce_mean(y * y)
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        return main, startup, loss

    d = tempfile.mkdtemp()
    cfg = CheckpointConfig(d, step_interval=1, async_write=False,
                           handle_signals=False, sharded=True)
    main, startup, _ = build()
    main.global_block().vars['ckw'].sharding = ('data', None)
    exe, scope = fluid.Executor(), fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        Checkpointer(cfg, exe, main_program=main).save(0, 1, blocking=True)

    main2, startup2, _ = build()
    assert main2.global_block().vars['ckw'].sharding is None
    scope2, exe2 = fluid.Scope(), fluid.Executor()
    with fluid.scope_guard(scope2):
        exe2.run(startup2)
        ck2 = Checkpointer(cfg, exe2, main_program=main2)
        before = obs.metrics.counter('ckpt.sharding_adopted').value
        assert ck2.restore() is not None
        adopted = obs.metrics.counter('ckpt.sharding_adopted').value - before
    assert adopted >= 1
    assert main2.global_block().vars['ckw'].sharding == ('data', None)


# ------------------------------------------- accumulator spec inheritance

def test_accumulators_inherit_param_spec():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[8], dtype='float32')
        y = fluid.layers.fc(x, size=4,
                            param_attr=fluid.ParamAttr(name='aw'),
                            bias_attr=False)
        loss = fluid.layers.reduce_mean(y * y)
        main.global_block().vars['aw'].sharding = (None, 'model')
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    b = main.global_block()
    moments = [n for n in b.vars if 'aw_moment' in n]
    assert len(moments) == 2
    for n in moments:
        assert b.vars[n].sharding == (None, 'model')
    pows = [n for n in b.vars if 'aw_beta' in n]
    assert pows and all(b.vars[n].sharding is None for n in pows)
