#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Drives the system's main paths once, through the entry points a user
calls (``import paddle_tpu as fluid``), at the full width of the models
the repo benchmarks, with seeded random weights:

  train_transformer  transformer-base (6 layers, d_model 512, 8 heads,
                     d_inner 2048, vocab 32000, AMP, use_flash), B=32
                     T=256: startup, three `Executor.run` steps, two
                     `run_steps(K=8)` launches on one repeated batch
  train_resnet50     ResNet-50, 224x224, 1000 classes, B=128, AMP: three
                     steps (the conv / bf16 flow-through side)
  kernels            every Pallas kernel a launch can select (flash,
                     ssm_step, kda_step, latent_attention, latent_prefill),
                     compiled by Mosaic and compared with its reference;
                     the composed attention's loop over tiles of the
                     batch, whose result buffers start uninitialised; and
                     the three routes of the routed experts' products on
                     one routing (the grouped one through `experts.gmm`),
                     with each route's time a layer
  serve              GenerationEngine over DecodeRuntime at the llama_1b
                     widths: four concurrent streams, twice, same tokens
  multichip          (>= 4 devices) transformer-base through
                     ParallelExecutor over make_mesh(data=4), shard pass
                     and ZeRO on

One process, no subprocess, no probe, and no `except` around a phase: any
failure propagates and the run exits non-zero without a result line.  With
no TPU it exits non-zero before running anything.  Each phase prints its
outcome on its own line; the last stdout line is the result the driver
parses, one JSON object with exactly the keys ``ok`` and ``device``:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.

The phases are functions of a size (``SIZES``): tests/test_chip_smoke.py
calls each at the ``tiny`` size on the CPU, Pallas in interpret mode —
which is also the rehearsal before spending chip time.
"""
import json
import math
import sys
import time

import numpy as np

SEED = 21

SIZES = {
    'full': {
        'transformer': dict(n_layer=6, d_model=512, n_head=8, d_inner=2048,
                            vocab=32000, batch=32, seq=256, fused_steps=8),
        'resnet': dict(depth=50, side=224, classes=1000, batch=128,
                       data_set='imagenet'),
        'kernels': dict(
            # flash: llama-class heads; T=4096 keeps the dK/dV kernel's
            # q rows VMEM-resident, T=8192 streams them
            flash=dict(heads=16, kv_heads=8, head_dim=128,
                       seq_resident=4096, seq_streamed=8192),
            # the tbase cells' attention: 96 sequences a chip, 6 tiles
            tile_loop=dict(batch=96, heads=8, seq=256, head_dim=64, tiles=6),
            # the falconh1_34b cell's scan state, 15 of 32 slots live
            ssm_step=dict(state=(32, 6, 32, 128, 256), groups=2, live=15),
            # the kimi_linear cell's matrix state a slot (32 slots of its
            # 128: the check holds four host copies), 20 live
            kda_step=dict(state=(32, 6, 32, 128, 128), taps=4, live=20),
            # the axk1 cell's latent pool: 64 heads over one 640-wide row
            latent=dict(slots=8, heads=64, v_dim=512, width=640, page_len=16,
                        pages=2049, layers=2, max_pages=449,
                        lengths=(0, 1, 16, 300, 4097, 5000, 7184, 777),
                        dtype='bfloat16', tol=2e-2),
            # the axk1 cell's chunk: 512 queries of 64 heads over a table
            # of 7,184 rows; (offset, real tokens) at offset 0, on and
            # inside a block of 1,024, with a padded tail
            latent_prefill=dict(
                heads=64, chunk=512, rows=7184, key_block=1024, kv_rank=512,
                nope=128, rope=64, v=128, width=640,
                cases=((0, 512), (4608, 512), (5000, 512), (6144, 37)),
                dtype='bfloat16', tol=1e-4),
            # the kimi_linear cell's expert layer in a decode step: 128
            # slots, 60 live with 2 held picks each, over 25 / 40 / 64 of
            # the 64 held experts
            expert_route=dict(tokens=128, live=60, d_model=2304,
                              d_expert=1024, n_routed=256, top_k=8, ranks=4,
                              with_rows=(25, 40, 64), dtype='bfloat16',
                              tol=2e-3, timed=20),
            # the lfm2_8b_a1b cell's K/V pool: 32 query / 8 kv heads of 64,
            # two kv heads to a 128-lane row
            paged_narrow=dict(slots=8, heads=32, kv_heads=8, head_dim=64,
                              page_len=16, pages=1793, layers=4,
                              max_pages=224,
                              lengths=(0, 1, 16, 300, 2049, 3000, 3584, 777),
                              dtype='bfloat16', tol=2e-2)),
        'serve': dict(config='llama_1b', n_layer=16, slots=8,
                      prompt_lens=(64, 192, 320, 512), max_new=32,
                      prefill_chunk=128, decode_window=8),
        'multichip': dict(n_layer=6, d_model=512, n_head=8, d_inner=2048,
                          vocab=32000, batch=128, seq=256, fused_steps=8,
                          devices=4),
    },
    'tiny': {
        'transformer': dict(n_layer=1, d_model=32, n_head=2, d_inner=64,
                            vocab=128, batch=4, seq=16, fused_steps=2),
        'resnet': dict(depth=8, side=32, classes=10, batch=4,
                       data_set='cifar10'),
        'kernels': dict(
            flash=dict(heads=2, kv_heads=1, head_dim=64,
                       seq_resident=128, seq_streamed=256),
            tile_loop=dict(batch=8, heads=2, seq=16, head_dim=8, tiles=4),
            ssm_step=dict(state=(4, 2, 8, 16, 128), groups=2, live=2),
            kda_step=dict(state=(4, 2, 3, 8, 8), taps=4, live=2),
            latent=dict(slots=4, heads=4, v_dim=128, width=256, page_len=4,
                        pages=41, layers=2, max_pages=6,
                        lengths=(0, 1, 13, 24), dtype='float32', tol=2e-5),
            latent_prefill=dict(
                heads=4, chunk=8, rows=60, key_block=16, kv_rank=16, nope=8,
                rope=4, v=8, width=128,
                cases=((0, 8), (24, 8), (40, 3)),
                dtype='float32', tol=2e-5),
            expert_route=dict(tokens=24, live=10, d_model=32, d_expert=24,
                              n_routed=32, top_k=4, ranks=4,
                              with_rows=(3, 8), dtype='float32', tol=2e-5,
                              timed=1),
            paged_narrow=dict(slots=4, heads=8, kv_heads=4, head_dim=32,
                              page_len=4, pages=41, layers=2, max_pages=6,
                              lengths=(0, 1, 13, 24), dtype='float32',
                              tol=2e-5)),
        'serve': dict(config='tiny', n_layer=2, slots=8,
                      prompt_lens=(4, 8, 12, 16), max_new=8,
                      prefill_chunk=4, decode_window=4),
        'multichip': dict(n_layer=1, d_model=32, n_head=2, d_inner=64,
                          vocab=128, batch=8, seq=16, fused_steps=2,
                          devices=4),
    },
}

_FALLBACK_COUNTERS = ('emitter.fallbacks',)
_COMPILE_SECONDS = ('executor.emit_s', 'executor.trace_s',
                    'executor.backend_compile_s')


def _say(phase, **fields):
    print('%s: %s' % (phase, json.dumps(fields, sort_keys=True)),
          flush=True)


def _counters():
    import paddle_tpu.observability as obs
    return {k: float(v) for k, v in obs.counters().items()
            if isinstance(v, (int, float))}


def _since(before, name):
    return _counters().get(name, 0.0) - before.get(name, 0.0)


def _assert_no_fallbacks():
    c = _counters()
    got = {k: c.get(k, 0.0) for k in _FALLBACK_COUNTERS}
    assert not any(got.values()), 'a fallback path ran: %r' % (got,)


def _peak_hbm():
    """Peak bytes in use per device, or None where the backend does not
    report it (the CPU)."""
    import jax
    stats = [d.memory_stats() for d in jax.devices()]
    if any(s is None for s in stats):
        return None
    return [int(s['peak_bytes_in_use']) for s in stats]


def _compile_report(before):
    """What a phase spent getting executables: in-process compile seconds
    (emit + trace + backend), seconds loading from the disk cache, and the
    disk tier's hits and misses."""
    from paddle_tpu.core import compile_cache
    return {
        'compile_s': round(sum(_since(before, k)
                               for k in _COMPILE_SECONDS), 2),
        'backend_compile_s': round(
            _since(before, 'executor.backend_compile_s'), 2),
        'cache_load_s': round(_since(before, 'compile_cache.load_s'), 2),
        'disk_hits': int(_since(before, 'compile_cache.disk_hits')),
        'disk_misses': int(_since(before, 'compile_cache.disk_misses')),
        'cache_dir': compile_cache.cache_dir(),
    }


def _assert_resident(scope):
    """Every array the scope holds lives on the default backend's devices
    — nothing was left on (or silently moved to) the host platform."""
    import jax
    platform = jax.devices()[0].platform
    for name, value in scope.vars.items():
        assert hasattr(value, 'devices'), \
            'scope var %s is a host array after training' % name
        where = {d.platform for d in value.devices()}
        assert where == {platform}, (name, where, platform)


def _transformer_program(fluid, cfg):
    from paddle_tpu.models import transformer as tr
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = SEED
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            # warmup_steps=400 (the bench keeps Noam's 8000): the learning
            # rate reaches 1e-5 * step instead of 1e-7 * step, so twenty
            # steps on one batch move the loss by more than bf16 noise
            out = tr.build(src_vocab=cfg['vocab'], trg_vocab=cfg['vocab'],
                           max_len=cfg['seq'], n_layer=cfg['n_layer'],
                           n_head=cfg['n_head'], d_model=cfg['d_model'],
                           d_inner=cfg['d_inner'], dropout=0.0,
                           use_flash=True, warmup_steps=400)
    main.set_amp(True)
    feed = tr.synthetic_batch(np.random.RandomState(SEED), cfg['batch'],
                              cfg['seq'], cfg['vocab'])
    return main, startup, out['loss'], feed


def _check_losses(losses, expect0, tol0, why0):
    assert all(math.isfinite(x) for x in losses), losses
    assert abs(losses[0] - expect0) < tol0, \
        'step-0 loss %.4f is not within %.2f of %.4f (%s)' \
        % (losses[0], tol0, expect0, why0)
    assert losses[-1] < losses[0], \
        'loss did not fall on the repeated batch: %r' % (losses,)


def _train(fluid, main, startup, loss, feed, single_steps, fused_steps,
           fused_launches):
    """startup, `single_steps` Executor.run steps, `fused_launches`
    run_steps(K=fused_steps) launches, all on one repeated batch.  The
    first launch of each kind is the warm-up; none after it may lower."""
    import jax
    import jax.numpy as jnp
    exe, scope = fluid.Executor(), fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed = {k: jax.device_put(v) for k, v in feed.items()}
        for i in range(single_steps):
            if i == 1:
                warm = _counters()
            value, = exe.run(main, feed=feed, fetch_list=[loss])
            losses.append(float(np.asarray(value).ravel()[0]))
        relowered = _since(warm, 'executor.lowerings')
        if fused_steps:
            stacked = {k: jnp.stack([v] * fused_steps)
                       for k, v in feed.items()}
            for i in range(fused_launches):
                if i == 1:
                    warm = _counters()
                values, = exe.run_steps(main, feed_list=stacked,
                                        steps=fused_steps,
                                        fetch_list=[loss])
                losses.extend(float(x) for x in np.asarray(values).ravel())
            relowered += _since(warm, 'executor.lowerings')
        assert relowered == 0, \
            '%d lowering(s) after warm-up' % relowered
        _assert_resident(scope)
    return losses


def train_transformer(cfg):
    import paddle_tpu as fluid
    t0, before = time.perf_counter(), _counters()
    main, startup, loss, feed = _transformer_program(fluid, cfg)
    losses = _train(fluid, main, startup, loss, feed, single_steps=3,
                    fused_steps=cfg['fused_steps'], fused_launches=2)
    # Xavier-initialised logits have variance d*2/(d+V) << 1, so the
    # label-smoothed cross entropy starts at the uniform prediction's
    # ln(V), plus half that variance
    _check_losses(losses, math.log(cfg['vocab']), 1.0,
                  'ln(vocab): near-uniform prediction at initialisation')
    _assert_no_fallbacks()
    out = dict(_compile_report(before), loss_first=round(losses[0], 4),
               loss_last=round(losses[-1], 4), steps=len(losses),
               peak_hbm_bytes=_peak_hbm(),
               wall_s=round(time.perf_counter() - t0, 1))
    _say('train_transformer', **out)
    return out


def train_resnet50(cfg):
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet
    t0, before = time.perf_counter(), _counters()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = SEED
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            # lr 0.01, not the bench's 0.1: with momentum 0.9 and no
            # warm-up 0.1 overshoots on a repeated batch (7.06, 2.98,
            # 8.70, 11.85 on the CPU at B=16) while 0.01 falls steadily
            out = resnet.build(
                data_shape=(3, cfg['side'], cfg['side']),
                class_dim=cfg['classes'], depth=cfg['depth'], lr=0.01,
                data_set=cfg['data_set'])
    main.set_amp(True)
    rng = np.random.RandomState(SEED)
    feed = {'data': rng.rand(cfg['batch'], 3, cfg['side'],
                             cfg['side']).astype('float32'),
            'label': rng.randint(0, cfg['classes'],
                                 (cfg['batch'], 1)).astype('int64')}
    losses = _train(fluid, main, startup, out['loss'], feed,
                    single_steps=3, fused_steps=0, fused_launches=0)
    # the softmax head starts near uniform, a little above ln(classes):
    # against ln(1000) = 6.91, ResNet-50 measured 7.06 on the CPU at B=16
    # and 7.62 on the v5e at B=128 (PR 21)
    _check_losses(losses, math.log(cfg['classes']), 1.5,
                  'ln(classes): near-uniform softmax at initialisation')
    _assert_no_fallbacks()
    out = dict(_compile_report(before), loss_first=round(losses[0], 4),
               loss_last=round(losses[-1], 4), steps=len(losses),
               peak_hbm_bytes=_peak_hbm(),
               wall_s=round(time.perf_counter() - t0, 1))
    _say('train_resnet50', **out)
    return out


# ------------------------------------------------------------- kernels

def _mosaic_calls(fn, *args):
    """Compile `fn` ahead of time and count the Mosaic kernels in its
    optimized HLO (interpret-mode Pallas leaves none)."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text().count('tpu_custom_call')


def _assert_mosaic(what, n_calls, at_least):
    """On an accelerator the kernel went through Mosaic; on the CPU (the
    rehearsal) it ran in interpret mode, and nowhere else."""
    from paddle_tpu.ops import _pallas
    if _pallas.interpret():
        import jax
        assert jax.default_backend() == 'cpu', 'interpret mode off the CPU'
        return
    assert n_calls >= at_least, \
        '%s compiled %d Mosaic kernel(s), expected >= %d' \
        % (what, n_calls, at_least)


def _close(what, got, ref, tol):
    """max|got - ref| <= tol * max|ref|: one bound for tensors whose
    entries span orders of magnitude (gradients)."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    assert got.shape == ref.shape, (what, got.shape, ref.shape)
    assert np.isfinite(got).all(), '%s: non-finite values' % what
    err = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    assert err <= tol * scale, \
        '%s: max error %.3e exceeds %.0e of max|ref| %.3e' \
        % (what, err, tol, scale)
    return err / scale if scale else 0.0


def _flash_check(cfg, seq):
    """flash_attention forward + backward at [1, H, seq, D] bf16, causal,
    GQA, against the composed f32 attention one kv head at a time (the
    dense f32 scores of all heads would not fit beside the kernels)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention as att
    H, Hkv, D = cfg['heads'], cfg['kv_heads'], cfg['head_dim']
    g = H // Hkv
    kq, kk, kv, kg = jax.random.split(jax.random.key(SEED + seq), 4)
    q = jax.random.normal(kq, (1, H, seq, D), jnp.bfloat16)
    k = jax.random.normal(kk, (1, Hkv, seq, D), jnp.bfloat16)
    v = jax.random.normal(kv, (1, Hkv, seq, D), jnp.bfloat16)
    ct = jax.random.normal(kg, (1, H, seq, D), jnp.bfloat16)

    def kernel(q, k, v, ct):
        out, pull = jax.vjp(
            lambda q, k, v: att.flash_attention(q, k, v, causal=True),
            q, k, v)
        return (out,) + pull(ct)

    compiled, n_calls = _mosaic_calls(kernel, q, k, v, ct)
    # forward, dQ, dK/dV: three kernels — fewer means the static rule
    # routed this shape to the composed path and nothing was tested
    _assert_mosaic('flash_attention T=%d' % seq, n_calls, 3)
    got = compiled(q, k, v, ct)

    @jax.jit
    def reference(q, k, v, ct):
        f32 = jnp.float32
        out, pull = jax.vjp(
            lambda q, k, v: att._ref_attention(q, k, v, True, D ** -0.5),
            q.astype(f32), k.astype(f32), v.astype(f32))
        return (out,) + pull(ct.astype(f32))

    with jax.default_matmul_precision('highest'):
        parts = [reference(q[:, h * g:(h + 1) * g], k[:, h:h + 1],
                           v[:, h:h + 1], ct[:, h * g:(h + 1) * g])
                 for h in range(Hkv)]
    ref = [jnp.concatenate([p[i] for p in parts], axis=1)
           for i in range(4)]
    # bf16 results carry 8 mantissa bits (relative 2^-8 = 0.4%); the
    # kernels also reassociate the softmax sums blockwise and add the
    # GQA group's dK/dV in bf16.  2% of the tensor's largest entry is
    # five roundings of margin, and far below a masking or indexing bug
    return {name: round(_close('flash T=%d %s' % (seq, name), a, b, 2e-2),
                        5)
            for name, a, b in zip(('out', 'dq', 'dk', 'dv'), got, ref)}


def _tile_loop_check(cfg):
    """The composed attention's loop over tiles of the batch at
    [B, H, T, D] bf16, causal, ragged lengths: output, dq, dk and dv
    against `_ref_attention` on the whole batch, TWICE in one process on
    other inputs.  Both loops start from buffers nothing fills
    (`ops.attention._unfilled`): a tile no iteration wrote would hold
    what the memory held, at best the first call's results, so the
    second call is the one that tells.  The CPU's `lax.empty` is a zero
    fill and cannot show it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention as att
    B, H, T, D = (cfg[n] for n in ('batch', 'heads', 'seq', 'head_dim'))
    tiles = cfg['tiles']
    assert att.takes_tile_loop(B, H, T, T, D) \
        and att._composed_tile(B, H, T, T) * tiles == B, \
        'the smoke shape no longer takes %d tiles' % tiles

    def inputs(run):
        keys = jax.random.split(jax.random.key(SEED + run), 5)
        q, k, v, ct = (jax.random.normal(key, (B, H, T, D), jnp.bfloat16)
                       for key in keys[:4])
        return q, k, v, ct, jax.random.randint(keys[4], (B,), T // 2, T + 1)

    def pulled_back(attention):
        def fn(q, k, v, ct, k_len):
            out, pull = jax.vjp(
                lambda q, k, v: attention(q, k, v, k_len), q, k, v)
            return (out,) + pull(ct)
        return fn

    before = _counters()
    compiled = jax.jit(pulled_back(lambda q, k, v, k_len: att.flash_attention(
        q, k, v, causal=True, k_len=k_len))).lower(*inputs(0)).compile()
    assert _since(before, 'attention.composed_tiled') == 1
    assert _since(before, 'attention.tile_buffers_unfilled') == 4
    if jax.default_backend() != 'cpu':
        text = compiled.as_text()
        filled = 'bf16[%d,%d,%d,%d,%d]' % (tiles, B // tiles, H, T, D)
        assert text.count('"AllocateBuffer"') == 4 and not [
            line for line in text.splitlines()
            if ' broadcast(' in line and '= ' + filled in line], \
            'the tile loops\' result buffers are filled before the loops'
    reference = jax.jit(pulled_back(lambda q, k, v, k_len: att._ref_attention(
        q, k, v, True, D ** -0.5, k_len)))
    out = {}
    for run in range(2):
        args = inputs(run)
        # one bf16 rounding of each result, as `_flash_check`; a tile
        # left unwritten differs by the size of the values themselves
        for name, a, b in zip(('out', 'dq', 'dk', 'dv'), compiled(*args),
                              reference(*args)):
            out['%s_%d' % (name, run)] = round(_close(
                'tile loop, call %d, %s' % (run, name), a, b, 2e-2), 5)
    return out


def _ssm_step_check(cfg):
    """`ssm_step` (one decode step of the Mamba-2 recurrence, in place
    over the live slots) against `scan_step` over every slot, on one
    layer of a whole state array: no slot live (the kernel visits one
    block and must hand it back), one, and ``live`` of them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.generation import ssm
    shape, G = tuple(cfg['state']), cfg['groups']
    S, L, H, P, N = shape
    assert ssm.ssm_step_eligible(shape, jnp.float32), \
        'smoke shape is not eligible'
    layer = L // 2
    rng = np.random.RandomState(SEED)
    f32 = jnp.float32
    x = jnp.asarray(rng.randn(S, H, P), f32)
    dt = jnp.asarray(np.log1p(np.exp(rng.randn(S, H))), f32)
    A = jnp.asarray(-np.exp(rng.randn(H)), f32)
    B, C = (jnp.asarray(rng.randn(S, G, N), f32) for _ in range(2))
    D = jnp.asarray(rng.randn(H), f32)

    def fresh():
        return jax.random.normal(jax.random.key(SEED), shape, f32)

    def kernel(state, active):
        return ssm.ssm_step(x, dt, A, B, C, D, state, layer, active)

    compiled = jax.jit(kernel, donate_argnums=(0,)).lower(
        fresh(), jnp.zeros(S, bool)).compile()
    _assert_mosaic('ssm_step', compiled.as_text().count('tpu_custom_call'),
                   1)
    before = np.asarray(fresh())
    want_y, want_S = (np.asarray(a) for a in jax.jit(ssm.scan_step)(
        x, dt, A, B, C, D, before[:, layer]))
    out = {}
    for n in (0, 1, cfg['live']):
        active = np.zeros(S, bool)
        active[rng.permutation(S)[:n]] = True
        y, state = (np.asarray(a) for a in compiled(fresh(),
                                                    jnp.asarray(active)))
        # what the kernel did not visit is what it was, bit for bit
        untouched = np.ones((S, L), bool)
        untouched[active, layer] = False
        np.testing.assert_array_equal(state[untouched], before[untouched])
        np.testing.assert_array_equal(y[~active], 0.0)
        if n:
            # the same f32 expressions; y sums d_state products in
            # another order than XLA's reduction
            _close('ssm_step state', state[active, layer], want_S[active],
                   1e-6)
            out['y_err_live_%d' % n] = float('%.2e' % _close(
                'ssm_step y', y[active], want_y[active], 1e-5))
    return out


def _kda_step_check(cfg):
    """`kda_step` (one decode step of a `kda` layer from the projections
    on, in place over the live slots' state and convolution tails)
    against the convolution, silu, the unit norms and the delta rule
    written out over every slot, on one layer of the whole arrays: no
    slot live (the kernel visits one block of each and must hand it
    back), one, and ``live`` of them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.generation import kda
    shape = tuple(cfg['state'])
    S, L, H, d, _ = shape
    K = cfg['taps']
    tails_shape = (S, L, K - 1, 3 * H, d)
    assert kda.kda_step_eligible(shape, tails_shape, jnp.float32), \
        'smoke shape is not eligible'
    layer = L // 2
    rng = np.random.RandomState(SEED)
    f32 = jnp.float32
    x = jnp.asarray(rng.randn(S, 3 * H * d), f32)
    taps = jnp.asarray(rng.randn(K, 3 * H * d) / K ** 0.5, f32)
    a = jnp.asarray(np.exp(-0.01 * np.abs(rng.randn(S, H, d))), f32)
    beta = jnp.asarray(rng.rand(S, H), f32)

    def fresh():
        k1, k2 = jax.random.split(jax.random.key(SEED))
        return (jax.random.normal(k1, shape, f32),
                jax.random.normal(k2, tails_shape, f32))

    def kernel(state, tails, active):
        return kda.kda_step(x, taps, a, beta, state, tails, layer, active)

    compiled = jax.jit(kernel, donate_argnums=(0, 1)).lower(
        *fresh(), jnp.zeros(S, bool)).compile()
    _assert_mosaic('kda_step', compiled.as_text().count('tpu_custom_call'),
                   1)
    before = [np.asarray(b) for b in fresh()]

    def plain(S0, tail):                   # every slot, elementwise in f32
        full = jnp.concatenate([tail.reshape(S, K - 1, -1), x[:, None]], 1)
        conv = jnp.sum(full * taps, axis=1)
        y = (conv * jax.nn.sigmoid(conv)).reshape(S, 3, H, d)
        q, k = (y[:, c] * jax.lax.rsqrt(
            jnp.sum(y[:, c] ** 2, -1, keepdims=True) + 1e-6)
            for c in range(2))
        q, v = q * d ** -0.5, y[:, 2]
        Sd = a[..., None] * S0
        u = beta[..., None] * (v - jnp.sum(Sd * k[..., None], axis=-2))
        new = Sd + k[..., None] * u[:, :, None, :]
        return jnp.sum(new * q[..., None], axis=-2), new, \
            full[:, 1:].reshape(tail.shape)

    want_o, want_S, want_tail = (np.asarray(r) for r in jax.jit(plain)(
        before[0][:, layer], before[1][:, layer]))
    out = {}
    for n in (0, 1, cfg['live']):
        active = np.zeros(S, bool)
        active[rng.permutation(S)[:n]] = True
        o, state, tails = (np.asarray(r) for r in compiled(
            *fresh(), jnp.asarray(active)))
        # what the kernel did not visit is what it was, bit for bit
        untouched = np.ones((S, L), bool)
        untouched[active, layer] = False
        np.testing.assert_array_equal(state[untouched], before[0][untouched])
        np.testing.assert_array_equal(tails[untouched], before[1][untouched])
        np.testing.assert_array_equal(o[~active], 0.0)
        if n:
            _close('kda_step state', state[active, layer], want_S[active],
                   1e-5)
            _close('kda_step tails', tails[active, layer],
                   want_tail[active], 1e-6)
            out['o_err_live_%d' % n] = float('%.2e' % _close(
                'kda_step o', o[active], want_o[active], 1e-5))
    return out


def _latent_attention_check(cfg):
    """`latent_attention` (one decode step's attention over a latent
    pool in place: every head reads the one row a token has) against
    `latent_attention_composed` on gathered rows: a dead slot (it reads
    nothing and gets zeros), one position, lengths that end on and
    inside a page and a block, a full slot."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention as att
    S, H, V, W = cfg['slots'], cfg['heads'], cfg['v_dim'], cfg['width']
    PL, M, dt = cfg['page_len'], cfg['max_pages'], jnp.dtype(cfg['dtype'])
    shape = (cfg['pages'], cfg['layers'], PL, W)
    assert att.latent_attention_eligible(shape, dt, V), \
        'smoke shape is not eligible'
    rng = np.random.RandomState(SEED)
    pool = jax.random.normal(jax.random.key(SEED), shape, jnp.float32) \
        .astype(dt)
    # every slot its own pages, in no order (page 0 is never mapped)
    bt = np.stack([rng.permutation(np.arange(1, cfg['pages']))[:M]
                   for _ in range(S)]).astype(np.int32)
    n = jnp.asarray(cfg['lengths'], jnp.int32)
    q_lat = jnp.asarray(rng.randn(S, H, V), jnp.float32)
    q_r = jnp.asarray(rng.randn(S, H, W - V), jnp.float32)
    layer, scale = cfg['layers'] - 1, 0.05

    def kernel(pool, bt, n):
        return att.latent_attention(q_lat, q_r, pool, bt, n, layer, scale)

    compiled, n_calls = _mosaic_calls(kernel, pool, jnp.asarray(bt), n)
    _assert_mosaic('latent_attention', n_calls, 1)
    got = np.asarray(compiled(pool, jnp.asarray(bt), n))
    rows = pool[jnp.asarray(bt), layer].reshape(S, M * PL, W)
    want = np.asarray(att.latent_attention_composed(
        q_lat[:, :, None], q_r[:, :, None], rows, (n - 1)[:, None],
        scale)[:, :, 0])
    live = np.asarray(n) > 0
    np.testing.assert_array_equal(got[~live], 0.0)
    return {'slots': int(live.sum()), 'err': float('%.2e' % _close(
        'latent_attention', got[live], want[live], cfg['tol']))}


def _paged_narrow_check(cfg):
    """`paged_attention` over a pool of a head NARROWER than the 128 lanes
    (64: two kv heads side by side in a row, `paged_pool_heads`) against
    `cached_attention` on gathered rows with the kv heads apart: a dead
    slot (it reads nothing and gets zeros), one position, lengths that end
    on and inside a page and a block, a full slot."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention as att
    S, H, Hkv, D = (cfg[k] for k in ('slots', 'heads', 'kv_heads',
                                     'head_dim'))
    PL, M, dt = cfg['page_len'], cfg['max_pages'], jnp.dtype(cfg['dtype'])
    hp, wide = att.paged_pool_heads(Hkv, D)
    assert wide == 128 and hp * wide == Hkv * D, 'smoke shape does not pack'
    shape = (cfg['pages'], cfg['layers'], PL, hp, wide)
    assert att.paged_attention_eligible(shape, dt), \
        'smoke shape is not eligible'
    rng = np.random.RandomState(SEED)
    kpool, vpool = (jax.random.normal(jax.random.key(SEED + i), shape,
                                      jnp.float32).astype(dt)
                    for i in range(2))
    bt = np.stack([rng.permutation(np.arange(1, cfg['pages']))[:M]
                   for _ in range(S)]).astype(np.int32)
    n = jnp.asarray(cfg['lengths'], jnp.int32)
    q = jnp.asarray(rng.randn(S, H, D), jnp.float32).astype(dt)
    layer = cfg['layers'] - 1

    def kernel(kpool, vpool, bt, n):
        return att.paged_attention(q, kpool, vpool, bt, n, layer)

    compiled, n_calls = _mosaic_calls(kernel, kpool, vpool, jnp.asarray(bt),
                                      n)
    _assert_mosaic('paged_attention', n_calls, 1)
    got = np.asarray(compiled(kpool, vpool, jnp.asarray(bt), n), np.float32)

    def apart(pool):
        rows = pool[jnp.asarray(bt), layer]          # [S, M, PL, hp, wide]
        return rows.reshape(S, M * PL, Hkv, D).transpose(0, 2, 1, 3)

    want = np.asarray(att.cached_attention(
        q[:, :, None], apart(kpool), apart(vpool),
        (n - 1)[:, None])[:, :, 0], np.float32)
    live = np.asarray(n) > 0
    np.testing.assert_array_equal(got[~live], 0.0)
    return {'slots': int(live.sum()), 'err': float('%.2e' % _close(
        'paged_attention at a narrow head', got[live], want[live],
        cfg['tol']))}


def _latent_prefill_check(cfg):
    """`latent_prefill` (a `latent_moe` chunk's attention, its scores on
    chip) against the composed block loop it replaces
    (`latent._block_loop`) on the same queries, rows and up-projection:
    per case (offset, real tokens) the chunk's output on both routes.  A
    block the chunk visits holds rows past its context (masked), and the
    padded tail's queries see them on both routes."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention as att
    from paddle_tpu.serving.generation import latent
    H, C, Tk, BK = cfg['heads'], cfg['chunk'], cfg['rows'], cfg['key_block']
    kr, nope, rope, v = cfg['kv_rank'], cfg['nope'], cfg['rope'], cfg['v']
    W, dt = cfg['width'], jnp.dtype(cfg['dtype'])
    assert att.latent_prefill_eligible((1, 1, 16, W), dt, C, BK, kr, nope,
                                       v), 'smoke shape is not eligible'
    ks = jax.random.split(jax.random.key(SEED), 5)
    q_nope = jax.random.normal(ks[0], (H, C, nope), jnp.float32)
    q_r = jax.random.normal(ks[1], (H, C, rope), jnp.float32)
    rows = jax.random.normal(ks[2], (-(-Tk // BK) * BK, W), jnp.float32)
    rows = rows.at[:, kr + rope:].set(0.0).astype(dt)      # the pad columns
    wk = (0.05 * jax.random.normal(ks[3], (H, nope, kr))).astype(dt)
    wv = (0.05 * jax.random.normal(ks[4], (H, kr, v))).astype(dt)
    scale = 0.1

    def kernel(q_nope, q_r, rows, wk, wv, offset, count):
        q = jnp.concatenate(
            [q_nope, jnp.pad(q_r, ((0, 0), (0, 0), (0, W - kr - rope)))],
            axis=-1).astype(dt)
        return att.latent_prefill(q, rows, wk, wv, offset + jnp.arange(C),
                                  offset + count, scale, BK)

    def composed(q_nope, q_r, rows, wk, wv, offset, count):
        q = jnp.concatenate([q_nope, q_r], axis=-1).astype(dt)
        return latent._block_loop(q, rows, wk, wv, offset + jnp.arange(C),
                                  offset + count, scale, BK, kr, rope)

    zero = jnp.int32(0)
    ops = (q_nope, q_r, rows, wk, wv)
    kernel, n_calls = _mosaic_calls(kernel, *ops, zero, zero)
    _assert_mosaic('latent_prefill', n_calls, 1)
    composed, n_calls = _mosaic_calls(composed, *ops, zero, zero)
    assert n_calls == 0, 'the composed route holds a Mosaic kernel'
    out = {}
    for offset, count in cfg['cases']:
        args = ops + (jnp.int32(offset), jnp.int32(count))
        out['err_%d_%d' % (offset, count)] = float('%.2e' % _close(
            'latent_prefill at %d + %d' % (offset, count),
            kernel(*args), composed(*args), cfg['tol']))
    return out


def _expert_route_check(cfg):
    """The three routes of `experts.routed` on ONE routing each can take
    (a decode step of ``tokens`` slots, ``live`` of them with two held
    picks each over ``with_rows`` of the held experts): grouped and
    unbatched against the batched route, and each route's time a layer
    (median of ``timed`` calls, the weights handed in as arguments)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.generation import experts
    T, live, k = cfg['tokens'], cfg['live'], cfg['top_k']
    moe = dict(n_routed=cfg['n_routed'], top_k=k, d_expert=cfg['d_expert'],
               n_shared=1, scale=2.5, ranks=cfg['ranks'], rank=1)
    first, G = experts.held(moe)
    dt = jnp.dtype(cfg['dtype'])
    ks = jax.random.split(jax.random.key(SEED), 4)
    shapes = experts.weight_shapes(cfg['d_model'], moe)
    w = [(0.03 * jax.random.normal(ks[i], shapes[n], jnp.float32)).astype(dt)
         for i, n in enumerate(('moe_fc1_w', 'moe_fc3_w', 'moe_fc2_w'))]
    h = jax.random.normal(ks[3], (T, cfg['d_model']), jnp.float32)
    rng = np.random.RandomState(SEED)
    wts = jnp.asarray(rng.rand(T, k), jnp.float32)
    valid = np.zeros(T, bool)
    valid[rng.permutation(T)[:live]] = True
    # what is timed: (route, its grouped products through `experts.gmm`)
    variants = {'grouped': ('grouped', True),
                'grouped_ragged_dot': ('grouped', False),
                'batched': ('batched', False),
                'unbatched': ('unbatched', False)}
    names = tuple(variants)
    assert experts.gmm_eligible(w[0].shape), 'smoke shape is not eligible'

    def one(route, kernel):
        def fn(h, w1, w3, w2, picks, wts, valid):
            return experts._routes(h, w1, w3, w2, picks, wts, valid, moe,
                                   kernel)[1][route]()
        return jax.jit(fn)

    fns = {name: one(*variants[name]) for name in names}
    out = {}
    for n_exp in cfg['with_rows']:
        # every pick elsewhere but two a live token, which cover n_exp
        # of the held experts
        picks = rng.randint(0, first, (T, k)).astype(np.int32)
        some = first + rng.permutation(G)[:n_exp]
        pairs = np.concatenate([some, rng.choice(some, 2 * live)])[:2 * live]
        picks[valid, :2] = rng.permutation(pairs).reshape(live, 2)
        args = (h, *w, jnp.asarray(picks), wts, jnp.asarray(valid))
        got, ms = {}, {}
        for name in names:
            got[name] = np.asarray(fns[name](*args))
            times = []
            for _ in range(cfg['timed']):
                t0 = time.perf_counter()
                fns[name](*args).block_until_ready()
                times.append(time.perf_counter() - t0)
            ms[name] = round(1e3 * float(np.median(times)), 3)
        np.testing.assert_array_equal(got['batched'][~valid], 0.0)
        assert np.abs(got['batched'][valid]).max(axis=1).min() > 0, \
            'a live token got nothing from its held experts'
        out['with_rows_%d' % n_exp] = dict(
            ms=ms, **{'err_' + name: float('%.2e' % _close(
                'experts.routed, %s' % name, got[name], got['batched'],
                cfg['tol'])) for name in names if name != 'batched'})
    return out


def kernels(cfg):
    from paddle_tpu.ops import attention as att
    t0 = time.perf_counter()
    flash = cfg['flash']
    assert att._FWD_PALLAS_MIN_T <= flash['seq_resident'] \
        <= att._DKV_RESIDENT_MAX_T < flash['seq_streamed'], \
        'smoke shapes no longer sit on both sides of the dK/dV switch'
    out = {
        'flash_resident': _flash_check(flash, flash['seq_resident']),
        'flash_streamed': _flash_check(flash, flash['seq_streamed']),
        'tile_loop': _tile_loop_check(cfg['tile_loop']),
        'ssm_step': _ssm_step_check(cfg['ssm_step']),
        'kda_step': _kda_step_check(cfg['kda_step']),
        'latent_attention': _latent_attention_check(cfg['latent']),
        'latent_prefill': _latent_prefill_check(cfg['latent_prefill']),
        'expert_route': _expert_route_check(cfg['expert_route']),
        'paged_narrow': _paged_narrow_check(cfg['paged_narrow']),
    }
    _assert_no_fallbacks()
    out['wall_s'] = round(time.perf_counter() - t0, 1)
    _say('kernels', **out)
    return out


# --------------------------------------------------------------- serve

def serve(cfg):
    import jax
    from paddle_tpu.models import llama
    from paddle_tpu.serving.engine import ServingConfig
    from paddle_tpu.serving.generation import (
        DecodeRuntime, GenerationConfig, GenerationEngine, SamplingParams,
        dense_reference, random_weights)
    t0, before = time.perf_counter(), _counters()
    model = dict(llama.CONFIGS[cfg['config']])
    cut = None
    if cfg['n_layer'] != model['n_layer']:
        cut = 'depth %d of %d' % (cfg['n_layer'], model['n_layer'])
        model['n_layer'] = cfg['n_layer']
    weights = random_weights(model, seed=SEED)
    rt = DecodeRuntime(weights, model, slots=cfg['slots'],
                       prefill_chunk=cfg['prefill_chunk'])
    del weights
    rt.warmup(steps=cfg['decode_window'])
    compiles = int(_since(before, 'generation.compiles'))
    vocab = model['vocab']
    rng = np.random.RandomState(SEED)
    prompts = [rng.randint(1, vocab, (n,)).astype(np.int32)
               for n in cfg['prompt_lens']]

    # the paged, chunked prefill against the dense page-free reference on
    # one small prompt.  Both sides multiply f32 at the backend's default
    # precision; they differ in shape (max_len masked keys against P keys,
    # chunks against one pass), so sums reassociate and a bf16-pass
    # operand may round the other way: 2% of the largest logit
    probe = rng.randint(1, vocab, (cfg['prompt_lens'][0],)).astype(np.int32)
    slot = rt.alloc_slot()
    start = rt.try_begin(slot, probe, 1)
    for off in range(start, probe.size, rt.prefill_chunk):
        first, logits = rt.prefill(slot, probe[off:off + rt.prefill_chunk],
                                   off, SamplingParams())
    rt.free_slot(slot)
    assert logits.shape == (vocab,) and np.isfinite(logits).all()
    assert int(first) == int(np.argmax(logits)), 'greedy token != argmax'
    _, _, ref_logits = dense_reference(rt.w, model, probe)
    logit_err = _close('prefill logits', logits, ref_logits, 2e-2)

    engine = GenerationEngine(
        rt, config=ServingConfig(drain_timeout_s=120.0),
        gen_config=GenerationConfig(decode_window=cfg['decode_window'])
    ).start()

    def one_pass():
        streams = [engine.generate(
            p, max_new=cfg['max_new'], seed=SEED + i,
            temperature=0.8 if i % 2 else 0.0, top_k=40 if i % 2 else 0,
            timeout_s=600.0) for i, p in enumerate(prompts)]
        out = []
        for s in streams:
            reply = s.result(600.0)
            assert reply.ok and reply.reason == 'max_tokens', reply
            ids = [int(t) for t in reply.outputs[0]]
            assert len(ids) == cfg['max_new'], len(ids)
            assert all(0 <= t < vocab for t in ids), ids
            out.append(ids)
        return out

    try:
        first_pass = one_pass()
        second_pass = one_pass()
    finally:
        drained = engine.drain(120.0)
        engine.stop()
    assert second_pass == first_pass, \
        'same prompts and seeds gave other tokens on the second pass'
    assert drained, 'the engine did not drain'
    assert rt.free_slots() == rt.slots, 'kv slots leaked'
    if rt.prefix is not None:
        rt.prefix.reset()      # cached prompt pages are holds, not leaks
    assert rt.pool.in_use() == 0, 'kv pages leaked'
    c = _counters()
    assert c.get('serving.deadlocks', 0.0) == 0.0
    assert _since(before, 'generation.compiles') == compiles, \
        'an executable compiled after warm-up'
    _assert_no_fallbacks()
    out = {'model': cfg['config'], 'cut': cut,
           'compiles': compiles,
           'disk_hits': int(_since(before, 'compile_cache.disk_hits')),
           'disk_misses': int(_since(before, 'compile_cache.disk_misses')),
           'streams': len(prompts), 'passes': 2,
           'tokens': int(_since(before, 'generation.tokens')),
           'prefill_logit_err': round(logit_err, 5),
           'deadlocks': 0, 'peak_hbm_bytes': _peak_hbm(),
           'wall_s': round(time.perf_counter() - t0, 1)}
    if cut:
        print('serve: model cut to %s (widths untouched)' % cut)
    _say('serve', **out)
    return out


# ----------------------------------------------------------- multichip

def multichip(cfg):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.parallel import ParallelExecutor, make_mesh
    n = cfg['devices']
    devices = jax.devices()[:n]
    t0, before = time.perf_counter(), _counters()
    main, startup, loss, feed = _transformer_program(fluid, cfg)
    scope = fluid.Scope()
    fluid.Executor().run(startup, scope=scope)

    # the one-chip loss of the SAME global batch from the same initial
    # parameters: the forward-only clone (the training step at this batch
    # does not fit one chip's HBM, its forward does)
    forward = main.clone(for_test=True)
    forward.set_amp(True)
    one_chip, = fluid.Executor().run(forward, feed=feed, fetch_list=[loss],
                                     scope=scope)
    one_chip = float(np.asarray(one_chip).ravel()[0])

    mesh = make_mesh(data=n, devices=devices)
    pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                          scope=scope, mesh=mesh)
    value, = pe.run([loss], feed=feed)
    losses = [float(np.asarray(value).ravel()[0])]
    K = cfg['fused_steps']
    stacked = {k: np.stack([v] * K) for k, v in feed.items()}
    values, = pe.run_steps(feed_list=stacked, steps=K, fetch_list=[loss])
    losses.extend(float(x) for x in np.asarray(values).ravel())
    _check_losses(losses, math.log(cfg['vocab']), 1.0,
                  'ln(vocab): near-uniform prediction at initialisation')
    # every row's matmuls are the same on one chip and on four; only the
    # f32 sum of the per-token losses is taken in another order (per
    # shard, then across shards): 1e-3 relative is far above that and far
    # below a wrong shard, a dropped row or a doubled gradient
    assert abs(losses[0] - one_chip) <= 1e-3 * abs(one_chip), \
        'step-0 loss %.6f over %d chips, %.6f on one' \
        % (losses[0], n, one_chip)

    persist = sorted(v.name for v in main.list_vars()
                     if v.persistable and v.name in scope.vars)
    per_device = {d.id: 0 for d in devices}
    total = sharded_total = sharded_dev0 = 0
    for name in persist:
        arr = scope.vars[name]
        shards = arr.addressable_shards
        assert {s.device.id for s in shards} == set(per_device), \
            '%s lives on %r' % (name, sorted(s.device.id for s in shards))
        total += arr.nbytes
        for s in shards:
            per_device[s.device.id] += s.data.nbytes
        if shards[0].data.shape != arr.shape:
            sharded_total += arr.nbytes
            sharded_dev0 += shards[0].data.nbytes
    assert sharded_total > 0.9 * total, \
        'ZeRO sharded only %d of %d state bytes' % (sharded_total, total)
    share = sharded_dev0 / sharded_total
    assert abs(share - 1.0 / n) < 0.01, \
        'a device holds %.3f of the ZeRO-sharded state, not 1/%d' \
        % (share, n)
    in_use = [(d.memory_stats() or {}).get('bytes_in_use') for d in devices]
    if jax.devices()[0].platform == 'tpu':
        assert all(in_use), 'a chip reports no bytes in use: %r' % (in_use,)
    _assert_no_fallbacks()
    out = dict(_compile_report(before), devices=n,
               loss_one_chip=round(one_chip, 6),
               loss_first=round(losses[0], 6),
               loss_last=round(losses[-1], 4), steps=len(losses),
               state_bytes=total, state_bytes_per_device=per_device,
               zero_sharded_bytes=sharded_total,
               zero_share_per_device=round(share, 4),
               bytes_in_use=in_use, peak_hbm_bytes=_peak_hbm(),
               wall_s=round(time.perf_counter() - t0, 1))
    _say('multichip', **out)
    return out


# ---------------------------------------------------------------- main

def device_report():
    """The device as JAX reports it.  Exits non-zero, before any phase
    and without a result, when that is not a TPU."""
    from importlib import metadata
    import jax
    import jaxlib
    dev0 = jax.devices()[0]
    device = {'platform': dev0.platform, 'kind': str(dev0.device_kind),
              'count': len(jax.devices())}
    if dev0.platform != 'tpu':
        sys.exit('chip_smoke: JAX found no TPU (platform %r, %d device(s) '
                 'of kind %r); nothing was run'
                 % (device['platform'], device['count'], device['kind']))
    print('device: %s' % json.dumps(dict(
        device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=metadata.version('libtpu'))), flush=True)
    return device


def result_line(device):
    """The last stdout line: exactly ``ok`` and ``device``, the device
    exactly ``platform``, ``kind`` (text) and ``count`` (a whole number).
    The driver refuses any other key, so the phases' outcomes are
    printed on the lines before it, never in it."""
    return json.dumps({'ok': True, 'device': {
        'platform': str(device['platform']), 'kind': str(device['kind']),
        'count': int(device['count'])}})


def main():
    t0 = time.perf_counter()
    if not __debug__:
        sys.exit('chip_smoke: run without -O — its checks are asserts')
    import paddle_tpu.observability as obs
    assert obs.enabled(), 'the smoke reads its counters: PT_OBS must be on'
    device = device_report()
    size = SIZES['full']
    train_transformer(size['transformer'])
    train_resnet50(size['resnet'])
    kernels(size['kernels'])
    serve(size['serve'])
    if device['count'] >= size['multichip']['devices']:
        multichip(size['multichip'])
    else:
        print('multichip: not run (%d device)' % device['count'])
    print('chip_smoke: every phase passed in %.0f s'
          % (time.perf_counter() - t0))
    sys.stderr.flush()
    print(result_line(device), flush=True)


if __name__ == '__main__':
    main()
