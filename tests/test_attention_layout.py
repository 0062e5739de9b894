"""The projections beside a TILED attention write and read the tile
loop's own layout (core/passes/attn_layout.py, ops/attention.py
`heads_in_loop_layout` / `project_from_loop_layout`).

Two kinds of test, as in test_generation_layout.py.  The COMPILER'S TEXT
of a two-layer transformer-base step at the benchmark cell's shapes, for
a described (not attached) v5e: no `copy` of an activation's extent and
no `split` pass is left around the tile loops, on one device and over a
`data=4` mesh; at a batch that takes the whole-batch route the pass
changes nothing.  And EXACTNESS at toy sizes on the CPU: loss, every
parameter's gradient and one Adam step against the same program with the
pass skipped.  Counts, shapes and values, never a time."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import emit, executor as executor_mod, passes
from paddle_tpu.models import transformer as tr
from paddle_tpu.observability import metrics
from paddle_tpu.ops import attention as att


def _counters():
    return tuple(metrics.counter('attention.' + n).value for n in (
        'composed_tiled', 'composed_whole', 'operands_in_loop_layout'))


def _unfilled():
    return metrics.counter('attention.tile_buffers_unfilled').value


def _moved(before):
    return tuple(a - b for a, b in zip(_counters(), before))


# ------------------------------------------------ exactness, at toy sizes

B, T, D_MODEL, HEADS = 8, 16, 32, 4


def _stack(kind):
    """Two pre-norm attention sublayers of one kind over `[B, T, d]`:
    'self' projects q, k, v as one `d x 3d` product and splits it,
    'cross' q alone and k, v as `d x 2d`, 'causal' is 'self' masked."""
    x = layers.data('x', shape=[T, D_MODEL], dtype='float32')
    mem = layers.data('mem', shape=[T, D_MODEL], dtype='float32')
    lens = layers.data('lens', shape=[], dtype='int32')
    h = x
    for i in range(2):
        ln = layers.layer_norm(h, begin_norm_axis=2)
        h = layers.elementwise_add(h, tr.multi_head_attention(
            ln, mem if kind == 'cross' else ln, None, D_MODEL, HEADS, 0.0,
            True, 'l%d' % i, use_flash=True, causal=kind == 'causal',
            kv_lengths=lens))
    loss = layers.mean(layers.square(h))
    fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return loss


def _built(kind, amp):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 50
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        if kind == 'transformer':
            loss = tr.build(src_vocab=40, trg_vocab=40, max_len=T, n_layer=2,
                            n_head=HEADS, d_model=D_MODEL,
                            d_inner=2 * D_MODEL, dropout=0.0,
                            warmup_steps=4, use_flash=True)['loss']
        else:
            loss = _stack(kind)
    main.set_amp(amp)
    return main, startup, loss


def _feed(kind):
    rng = np.random.RandomState(50)
    if kind == 'transformer':
        return tr.synthetic_batch(rng, B, T, vocab=40)
    return {'x': rng.randn(B, T, D_MODEL).astype('float32'),
            'mem': rng.randn(B, T, D_MODEL).astype('float32'),
            'lens': rng.randint(T // 2, T + 1, B).astype('int32')}


def _one_step(main, loss, state, feed, mesh=None):
    """(loss, gradients by parameter, state after one Adam step) of
    `main` from `state`, through the executor: the rewriter, the emitter
    and the AMP policy as a trainer runs them."""
    scope = fluid.Scope()
    for n, v in state.items():
        scope.vars[n] = jnp.array(v, copy=True)
    params = [p.name for p in main.global_block().all_parameters()]
    got = fluid.Executor(mesh=mesh).run(
        main, feed=feed, scope=scope,
        fetch_list=[loss] + [n + '@GRAD' for n in params])
    return (float(np.asarray(got[0]).ravel()[0]),
            dict(zip(params, (np.asarray(g, np.float32) for g in got[1:]))),
            {n: np.asarray(scope.vars[n], np.float32) for n in state})


@pytest.mark.parametrize('amp', [False, True], ids=['f32', 'amp'])
@pytest.mark.parametrize('kind', ['self', 'cross', 'causal', 'transformer'])
def test_the_rewrite_is_the_same_arithmetic(kind, amp, monkeypatch):
    """With the projections written in the loop's layout the step is what
    it is with the pass skipped: float32 to round-off (the same products,
    summed in another order), AMP to one bf16 rounding of each operand."""
    per_seq = HEADS * T * T * 4
    monkeypatch.setattr(att, '_COMPOSED_TILE_BYTES', 2 * per_seq)
    # the lint gate evaluates every op of the RAW program abstractly, and
    # `_composed_attention` counts that too: off, so that the counters
    # below are the lowering's alone
    monkeypatch.setenv('PT_LINT', '0')
    emit.clear_memo()      # a signature an earlier case lowered counts 0
    assert att.takes_tile_loop(B, HEADS, T, T, D_MODEL // HEADS)
    main, startup, loss = _built(kind, amp)
    scope = fluid.Scope()
    fluid.Executor().run(startup, scope=scope)
    state = {n: np.asarray(v) for n, v in scope.vars.items()}
    feed = _feed(kind)

    before = _counters()
    got = _one_step(main, loss, state, feed)
    tiled, whole, in_layout = _moved(before)
    # every tiled lowering engaged (the emitter lowers one attention a
    # signature, so the count is of signatures, not of layers)
    assert tiled == in_layout > 0

    monkeypatch.setenv('PT_OPT_SKIP', 'attn_layout')
    before = _counters()
    want = _one_step(main, loss, state, feed)
    tiled, whole, in_layout = _moved(before)
    assert tiled > 0 and in_layout == 0

    tol = dict(rtol=2e-2, atol=2e-3) if amp else dict(rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-3 if amp else 1e-6)
    assert sorted(got[1]) == sorted(want[1])
    for n in want[1]:
        np.testing.assert_allclose(got[1][n], want[1][n], err_msg=n + '@GRAD',
                                   **tol)
    # the state after the step.  Adam's moments are linear in the
    # gradient; its first update is lr * g / (|g| + eps), a sign, which
    # turns the rounding of a gradient near zero into a step: parameters
    # are compared in float32, where their gradient is not round-off
    params = set(want[1])
    moved = False
    for n in want[2]:
        a, b = got[2][n], want[2][n]
        if n in params:
            if amp:
                continue
            firm = np.abs(want[1][n]) > 1e-6
            a, b = a[firm], b[firm]
        np.testing.assert_allclose(
            a, b, err_msg=n, **(tol if amp else dict(rtol=2e-5, atol=2e-5)))
        moved = moved or not np.array_equal(want[2][n], state[n])
    assert moved


@pytest.mark.parametrize('kind', ['self', 'cross'])
def test_the_rewrite_is_the_same_arithmetic_over_a_data_mesh(
        kind, monkeypatch):
    """`tbase.train_dp4`'s arrangement at toy sizes: the batch over
    `data=2`, the weights ZeRO-sharded and rejoined by the shard pass's
    `all_gather`, whose result is the weight the attention slices; loss
    and gradients are what they are with the pass skipped."""
    from paddle_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(data=2, devices=jax.devices()[:2])
    monkeypatch.setattr(att, '_COMPOSED_TILE_BYTES', 2 * HEADS * T * T * 4)
    monkeypatch.setenv('PT_LINT', '0')
    emit.clear_memo()
    assert att.takes_tile_loop(B, HEADS, T, T, D_MODEL // HEADS, mesh)
    main, startup, loss = _built(kind, False)
    main.set_mesh_axes({'data': 2})
    opt, _ = passes.optimize_program(main, (loss.name,),
                                     skip={'fuse_elementwise'})
    attns = [op for op in opt.global_block().ops
             if op.type == 'flash_attention']
    assert attns and all(n.endswith('_w@FULL') for op in attns
                         for n in op.inputs['ProjW'])
    scope = fluid.Scope()
    fluid.Executor().run(startup, scope=scope)
    state = {n: np.asarray(v) for n, v in scope.vars.items()}
    feed = _feed(kind)
    before = _counters()
    got = _one_step(main, loss, state, feed, mesh)
    tiled, _, in_layout = _moved(before)
    assert tiled == in_layout > 0
    monkeypatch.setenv('PT_OPT_SKIP', 'attn_layout')
    want = _one_step(main, loss, state, feed, mesh)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    assert sorted(got[1]) == sorted(want[1])
    for n in want[1]:
        np.testing.assert_allclose(got[1][n], want[1][n], rtol=2e-5,
                                   atol=2e-6, err_msg=n + '@GRAD')


def test_the_pass_adds_inputs_and_removes_nothing():
    """Every op and variable of the split-heads chain stays; the
    attention learns its projections, the output projection its
    attention, as an op of the attention's module (`mul` knows nothing
    of either).  An attention fed by anything else is left alone."""
    main, _, loss = _built('transformer', True)
    raw = [op.type for op in main.global_block().ops]
    opt, stats = passes.optimize_program(
        main, (loss.name,), skip={'fuse_elementwise'})
    assert stats['passes']['attn_layout']['attentions'] == 6
    assert stats['passes']['attn_layout']['operands'] == 18
    assert stats['passes']['attn_layout']['outputs'] == 6
    ops = opt.global_block().ops
    chain = ('mul', 'split', 'reshape', 'transpose', 'flash_attention')
    assert [op.type.replace('attn_out_proj', 'mul') for op in ops
            if op.type in chain + ('attn_out_proj',)] == \
        [t for t in raw if t in chain]
    attns = [op for op in ops if op.type == 'flash_attention']
    assert [op.attrs['proj'] for op in attns[:1] + attns[-1:]] == [
        [0, 0, 0, 3, 0, 0, 1, 3, 0, 0, 2, 3],        # one d x 3d product
        [0, 0, 0, 1, 1, 1, 0, 2, 1, 1, 1, 2]]        # q alone; k, v of d x 2d
    for op in attns:
        assert all(n.endswith('_w') for n in op.inputs['ProjW'])
    marked = [op for op in ops if 'AttnOut' in op.inputs]
    assert [op.type for op in marked] == ['attn_out_proj'] * 6
    assert all(op.inputs['X'] and set(op.attrs) >= {'x_num_col_dims'}
               for op in marked)
    assert sorted(op.inputs['Y'][0] for op in marked) == sorted(
        p.name for p in main.global_block().all_parameters()
        if p.name.endswith('_o_w'))
    # idempotent, and blind to an attention over plain inputs
    again, stats = passes.optimize_program(opt, (loss.name,))
    assert stats['passes']['attn_layout'] == {
        'attentions': 0, 'operands': 0, 'outputs': 0,
        'ms': stats['passes']['attn_layout']['ms']}
    plain, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(plain, startup), fluid.unique_name.guard():
        q = layers.data('q', shape=[HEADS, T, 8], dtype='float32')
        out = layers.flash_attention(q, q, q)
    _, stats = passes.optimize_program(plain, (out.name,))
    assert stats['passes']['attn_layout']['attentions'] == 0


def _heads(x, n_head):
    x = layers.reshape(x, [0, 0, n_head, D_MODEL // HEADS])
    return layers.transpose(x, perm=[0, 2, 1, 3])


def _separate(case):
    """One attention over three separate projections (llama's shape of
    the chain, 2 kv heads under 4 query heads), with one thing in the way
    of `case`'s operand."""
    x = layers.data('x', shape=[T, D_MODEL], dtype='float32')
    fc = lambda size, **kw: layers.fc(  # noqa: E731
        x, size, num_flatten_dims=2, bias_attr=False, **kw)
    d_kv = D_MODEL // 2
    q, k, v = fc(D_MODEL), fc(d_kv), fc(d_kv)
    if case == 'k_scaled':                   # an op between (rope's place)
        k = layers.scale(k, scale=0.5)
    if case == 'v_biased':                   # fc's bias is an add between
        v = layers.fc(x, d_kv, num_flatten_dims=2)
    if case == 'x_rewritten':                # x has two writers
        layers.assign(layers.scale(x, scale=2.0), output=x)
    qh, kh, vh = _heads(q, HEADS), _heads(k, HEADS // 2), \
        _heads(v, HEADS // 2)
    if case == 'q_folded':                   # not the rows it came with
        qh = layers.transpose(layers.reshape(
            q, [-1, T // 2, HEADS, D_MODEL // HEADS]), perm=[0, 2, 1, 3])
        kh, vh = (layers.transpose(layers.reshape(
            t, [-1, T // 2, HEADS // 2, D_MODEL // HEADS]),
            perm=[0, 2, 1, 3]) for t in (k, v))
    o = layers.flash_attention(qh, kh, vh, causal=True)
    o = layers.reshape(layers.transpose(o, perm=[0, 2, 1, 3]),
                       [-1, T // 2, D_MODEL] if case == 'o_folded'
                       else [0, 0, D_MODEL])
    o = layers.fc(o, D_MODEL, num_flatten_dims=2, bias_attr=False)
    if case == 'x_rewritten':                # ... and a reader after both
        o = layers.elementwise_add(o, x)
    loss = layers.mean(layers.square(o))
    fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return loss


@pytest.mark.parametrize('case, operands, outputs', [
    ('plain', [0, 1, 2], 1), ('k_scaled', [0, 2], 1),
    ('v_biased', [0, 1], 1), ('x_rewritten', [], 1), ('q_folded', [], 1),
    ('o_folded', [0, 1, 2], 0)])
def test_only_a_plain_projection_is_taken(case, operands, outputs,
                                          monkeypatch):
    """An operand is projected in the loop's layout only where the chain
    behind it is exactly mul -> reshape -> transpose over names written
    once, and the output projection only where the chain in front of it
    keeps the attention's rows (`o_folded` merges the heads into other
    rows, `[2B, T/2, H*D]`); the others, and the attention, lower as
    they did, and the step is the same arithmetic whichever mixture
    results."""
    monkeypatch.setattr(att, '_COMPOSED_TILE_BYTES', 2 * HEADS * T * T * 4)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 50
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss = _separate(case)
    opt, stats = passes.optimize_program(
        main, (loss.name,), skip={'fuse_elementwise'})
    attn, = (op for op in opt.global_block().ops
             if op.type == 'flash_attention')
    proj = attn.attrs.get('proj', [-1] * 12)
    assert [i for i in range(3) if proj[4 * i] >= 0] == operands
    assert all(proj[4 * i + 2:4 * i + 4] == [0, 1] for i in operands)
    assert stats['passes']['attn_layout']['outputs'] == outputs

    scope = fluid.Scope()
    fluid.Executor().run(startup, scope=scope)
    state = {n: np.asarray(v) for n, v in scope.vars.items()}
    feed = {'x': _feed('self')['x']}
    got = _one_step(main, loss, state, feed)
    monkeypatch.setenv('PT_OPT_SKIP', 'attn_layout')
    want = _one_step(main, loss, state, feed)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for n in want[1]:
        np.testing.assert_allclose(got[1][n], want[1][n], rtol=2e-5,
                                   atol=2e-6, err_msg=n + '@GRAD')


# -------------------- the compiler's text, at the benchmark cell's shapes

CELL = dict(seq=256, d_model=512, n_head=8, d_inner=2048, vocab=32000)
FEEDS = {'src_word': ((CELL['seq'], 1), jnp.int32),
         'trg_word': ((CELL['seq'], 1), jnp.int32),
         'lbl_word': ((CELL['seq'], 1), jnp.int32),
         'src_pad': ((CELL['seq'],), jnp.float32),
         'trg_pad': ((CELL['seq'],), jnp.float32)}


# dots, copies and fusions in the two-layer step at 16 sequences as
# compiled at commit 99e2d25, the parent of the PR that added the pass
# (fused computations' bodies included).  Since PR 54 the gradient of a
# rank-2 weight is fenced from its Adam update (`_lower`), so the 23
# products that carried an update in their epilogue are a product and a
# loop fusion each: 465 fusions then, 488 now; dots and copies as then.
WHOLE_BATCH_CENSUS = {'convolution': 105, 'copy': 160, 'fusion': 488}


@pytest.fixture(scope='module')
def v5e_2x2():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - no libtpu here: nothing to test
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)


@pytest.fixture(scope='module')
def cell_program():
    """transformer-base as `tbase.train_1chip` builds it (benchmarks/
    runners/train.py), two layers deep, AMP, and its start-up's shapes."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 23
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss = tr.build(
            src_vocab=CELL['vocab'], trg_vocab=CELL['vocab'],
            max_len=CELL['seq'], n_layer=2, n_head=CELL['n_head'],
            d_model=CELL['d_model'], d_inner=CELL['d_inner'], dropout=0.0,
            lr=2.0, warmup_steps=4000, use_flash=True)['loss']
    main.set_amp(True)
    scope = fluid.Scope()
    fluid.Executor().run(startup, scope=scope)
    return main, loss, {n: (np.shape(v), v.dtype)
                        for n, v in scope.vars.items()}


def _compiled_step(cell_program, topo, monkeypatch, batch, chips=1,
                   skip=()):
    """The text XLA:TPU makes of one training step of `cell_program` at
    `batch` sequences a chip, as `Executor._prepare_entry` lowers it (the
    rewriter, the emitter, `_lower`), and what the attention counters
    moved while it traced."""
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)
    main, loss, shapes = cell_program
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    mesh = None
    if chips > 1:
        # as ParallelExecutor arms the shard pass: ZeRO shards the
        # weights and rejoins them in front of their first reader
        mesh = Mesh(np.array(topo.devices[:chips]), ('data',))
        main = main.clone()
        main.set_mesh_axes({'data': chips})
    opt, _ = passes.optimize_program(main, (loss.name,), skip=set(skip))
    names = tuple(sorted(FEEDS))
    jit_fn, params_in, _ = executor_mod._lower(
        opt, names, (loss.name,), mesh=mesh,
        emit_engine=emit.build_engine(opt, names, (loss.name,)))

    def struct(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(
            shape, dtype,
            sharding=SingleDeviceSharding(topo.devices[0]) if mesh is None
            else NamedSharding(mesh, spec))

    params = {n: struct(shapes[n][0], shapes[n][1],
                        opt._sharding.get(n, P())) for n in params_in}
    feeds = {n: struct((batch * chips,) + s, d, P('data'))
             for n, (s, d) in FEEDS.items()}
    before = _counters()
    text = jit_fn.lower(params, feeds, struct((), jnp.uint32)) \
        .compile().as_text()
    return text, _moved(before)


def _relayouts(text, batch):
    """(`copy` operations whose result has a `[batch, 256, 512]`
    activation's extents in either order, fusions made of the program's
    `split`) in a compiled step."""
    seq, d = CELL['seq'], CELL['d_model']
    copies = re.findall(
        r'= (?:bf16|f32)\[%d,(?:%d,%d|%d,%d)\]\{[^}]*\} copy\('
        % (batch, seq, d, d, seq), text)
    splits = [line for line in text.splitlines() if ' fusion(' in line
              and re.search(r'op_name="[^"]*/split(/[^"/]*)?"', line)]
    return len(copies), len(splits)


ATTENTIONS = 6     # two layers: 2 encoder, 2 decoder self, 2 cross


def _tile_buffers(text):
    """(`broadcast`s that fill a tile loop's result buffer `bf16[6,16,8,
    256,64]`, `copy`s of one, `AllocateBuffer` calls: buffers a loop
    takes unfilled, `while`s) in a compiled step at 96 sequences."""
    shape = r'= bf16\[6,16,8,256,64\]\{[^}]*\} '
    return (len(re.findall(shape + r'broadcast\(', text)),
            len(re.findall(shape + r'copy\(', text)),
            text.count('custom_call_target="AllocateBuffer"'),
            text.count(' while('))


def _skeleton(text):
    """A compiled module less what names its instructions and where they
    came from: two lowerings of one computation share it."""
    text = re.sub(r'\n(FileNames|FunctionNames|FileLocations|StackFrames)\n'
                  r'(\d+ [^\n]*\n)*', '\n', text)
    text = re.sub(r', metadata=\{[^}]*\}', '', text)
    text = re.sub(r'%[\w.\-]+', '%', text)
    text = re.sub(r'\b([A-Za-z_][A-Za-z_\-]*)(\.[\w\-]+)+', r'\1', text)
    return '\n'.join(sorted(text.splitlines()))


def test_no_relayout_is_left_around_the_tile_loops(
        cell_program, v5e_2x2, monkeypatch):
    """96 sequences a step tile (6 x 16): XLA hands each `while` its
    operands `[6,16,8,256,64]` with T on the lanes.  Without the pass
    every attention costs a `split` pass and seven copies of
    `bf16[96,256,512]` (q, k, v in; the result's cotangent in; dq, dk, dv
    out); with it the dots write and read that layout themselves."""
    unfilled = _unfilled()
    text, (tiled, _, in_layout) = _compiled_step(
        cell_program, v5e_2x2, monkeypatch, batch=96)
    assert ' while(' in text and 'bf16[6,16,8,256,64]{3,4,2,1,0' in text
    assert tiled == in_layout > 0
    assert _relayouts(text, 96) == (0, 0)
    # each attention's two loops carry four results (the forward's; dq,
    # dk, dv), and no pass over HBM fills one before its loop writes it
    assert _tile_buffers(text) == (0, 0, 4 * ATTENTIONS, 2 * ATTENTIONS)
    # a signature's traces: the op's own (differentiation drops it), its
    # forward rule, its backward rule's three
    assert _unfilled() - unfilled == 5 * tiled


@pytest.mark.parametrize('chips', [1, 4])
def test_a_filled_buffer_would_show(chips, cell_program, v5e_2x2,
                                    monkeypatch):
    """The counts above are of something: the same step with the loops'
    buffers allocated as zeros holds one `broadcast` of `bf16[6,16,8,
    256,64]` a buffer, in front of the same loops."""
    monkeypatch.setattr(att, '_unfilled',
                        lambda like: jnp.zeros(like.shape, like.dtype))
    emit.clear_memo()
    text, (tiled, _, in_layout) = _compiled_step(
        cell_program, v5e_2x2, monkeypatch, batch=96, chips=chips)
    emit.clear_memo()      # no later case meets these lowerings
    assert tiled == in_layout > 0
    assert _tile_buffers(text) == (4 * ATTENTIONS, 0, 0, 2 * ATTENTIONS)
    assert 'bf16[6,16,8,256,64]{3,4,2,1,0' in text
    assert _relayouts(text, 96) == (0, 0)


def test_the_pass_is_what_removes_them(cell_program, v5e_2x2, monkeypatch):
    text, (tiled, _, in_layout) = _compiled_step(
        cell_program, v5e_2x2, monkeypatch, batch=96, skip={'attn_layout'})
    assert tiled > 0 and in_layout == 0
    copies, splits = _relayouts(text, 96)
    # 6 attentions; XLA shares some copies between neighbours
    assert copies >= 5 * 6 and splits == 6


def test_the_whole_batch_route_lowers_as_before(
        cell_program, v5e_2x2, monkeypatch):
    """16 sequences' scores fit the tile budget: no loop, and the marked
    ops lower to what the unmarked ones do: the same module, less
    instruction names and metadata, with the parent's counts of dots,
    copies and fusions."""
    assert not att.takes_tile_loop(16, 8, 256, 256, 64)
    text, (tiled, whole, in_layout) = _compiled_step(
        cell_program, v5e_2x2, monkeypatch, batch=16)
    assert (tiled, in_layout) == (0, 0) and whole > 0
    unmarked, _ = _compiled_step(cell_program, v5e_2x2, monkeypatch,
                                 batch=16, skip={'attn_layout'})
    assert _skeleton(text) == _skeleton(unmarked)
    assert {op: len(re.findall(r' %s\(' % op, text))
            for op in WHOLE_BATCH_CENSUS} == WHOLE_BATCH_CENSUS


def test_no_relayout_is_left_under_a_data_mesh(
        cell_program, v5e_2x2, monkeypatch):
    """`tbase.train_dp4`: 384 sequences over `data=4`, the tile loop in a
    `shard_map`, the projections partitioned by GSPMD on the batch: one
    module after partitioning, the same dots, the same layout."""
    text, (tiled, _, in_layout) = _compiled_step(
        cell_program, v5e_2x2, monkeypatch, batch=96, chips=4)
    assert 'all-reduce' in text
    assert ' while(' in text and 'bf16[6,16,8,256,64]{3,4,2,1,0' in text
    assert tiled == in_layout > 0
    assert _relayouts(text, 96) == (0, 0)
    assert _tile_buffers(text) == (0, 0, 4 * ATTENTIONS, 2 * ATTENTIONS)
