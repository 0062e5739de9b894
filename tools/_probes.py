"""One-shot measurement harnesses behind PERF.md's numbers, plus the
reference model-matrix benchmark.  The perf lab fronts both:

    python tools/perflab.py probe decompose   # step-time split by surgery
    python tools/perflab.py probe longctx     # llama long-context steps
    python tools/perflab.py probe attn        # pallas-vs-composed attn grad
    python tools/perflab.py probe soak        # 500-step stability
    python tools/perflab.py probe hlo         # per-HLO xplane ledger
    python tools/perflab.py probe convprobe   # conv fwd/dx/dw microbench
    python tools/perflab.py probe allreduce   # psum/all-gather BW, mesh
    python tools/perflab.py models --model resnet --batch_size 64

(tools/measure.py and tools/fluid_benchmark.py forward here for the old
invocations.)  Probes run on a live chip; every harness prints its
table and exits.  These are the scripts that produced the round-4
PERF.md sections — kept runnable so future rounds re-measure instead of
trusting stale numbers.  Ledgered, gated numbers come from the perflab
scenario matrix instead (docs/perflab.md).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

PROBES = ('decompose', 'longctx', 'attn', 'soak', 'hlo', 'convprobe',
          'allreduce')


def _sync(x):
    return np.asarray(x)


def _timed_loop(exe, main, feed, loss, steps=30):
    import jax
    feed = {k: jax.device_put(v) for k, v in feed.items()}
    for _ in range(3):
        o, = exe.run(main, feed=feed, fetch_list=[loss])
    _sync(o)
    t0 = time.perf_counter()
    for _ in range(steps):
        o, = exe.run(main, feed=feed, fetch_list=[loss],
                     return_numpy=False)
    _sync(o)
    return (time.perf_counter() - t0) / steps * 1e3


def decompose():
    """Forward / backward / optimizer / CE split (PERF.md
    'Step-time decomposition')."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models import transformer as tr
    B, T, V = 32, 256, 32000
    feeds = tr.synthetic_batch(np.random.RandomState(0), B, T)

    def run(tag, build):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                loss = build()
        main.set_amp(True)
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            ms = _timed_loop(exe, main, feeds, loss)
        print('%-28s %7.2f ms' % (tag, ms), flush=True)
        return ms

    def tf(**kw):
        out = tr.transformer(V, V, max_len=T, n_layer=6, n_head=8,
                             d_model=512, d_inner=2048, dropout=0.0,
                             use_flash=True, **kw)
        return out

    run('fwd only', lambda: tf(is_train=False)['loss'])

    def with_opt(opt):
        def build():
            out = tf()
            opt().minimize(out['loss'])
            return out['loss']
        return build
    run('fwd+bwd+SGD', with_opt(lambda: fluid.optimizer.SGD(1e-4)))
    run('fwd+bwd+Adam', with_opt(lambda: fluid.optimizer.Adam(1e-4)))

    def no_ce():
        out = tf()
        loss = layers.reduce_mean(out['logits'])
        fluid.optimizer.Adam(1e-4).minimize(loss)
        return loss
    run('fwd+bwd+Adam, no CE', no_ce)


def longctx():
    """llama long-context train steps (PERF.md 'Long-context llama')."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import llama
    cfg = dict(vocab=32000, d_model=1024, n_layer=8, n_head=16,
               n_kv_head=4, d_ffn=2816, theta=500000.0, max_len=4096)
    for T, B in ((4096, 2), (8192, 1)):
        c = dict(cfg, max_len=T)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                out = llama.build(c, lr=1e-4)
        main.set_amp(True)
        exe = fluid.Executor()
        scope = fluid.Scope()
        rng = np.random.RandomState(0)
        feed = llama.make_batch(
            [rng.randint(3, 32000, (T + 1,)) for _ in range(B)], T)
        with fluid.scope_guard(scope):
            exe.run(startup)
            ms = _timed_loop(exe, main, feed, out['loss'], steps=10)
        print('llama T=%5d B=%d: %8.0f tok/s (%.1f ms/step)'
              % (T, B, B * T / ms * 1e3, ms), flush=True)


def attn():
    """pallas vs composed attention fwd+grad (PERF.md crossover table)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention as att
    rng = np.random.RandomState(0)

    def bench_grad(fn, args, iters=10):
        g = jax.jit(jax.grad(
            lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2)))
        out = g(*args)
        _sync(out[0][0, 0, 0, 0])
        t0 = time.perf_counter()
        for _ in range(iters):
            out = g(*args)
        _sync(out[0][0, 0, 0, 0])
        return (time.perf_counter() - t0) / iters * 1e3

    for T in (2048, 4096, 8192):
        q, k, v = (jnp.asarray(rng.randn(2, 8, T, 64), jnp.bfloat16)
                   for _ in range(3))
        att._FWD_PALLAS_MIN_T = 0
        att._BWD_PALLAS_SCORE_BYTES = 0
        tp = bench_grad(
            lambda q, k, v: att.flash_attention(q, k, v, causal=True),
            (q, k, v))
        att._FWD_PALLAS_MIN_T = 1 << 30
        tc = bench_grad(
            lambda q, k, v: att.flash_attention(q, k, v, causal=True),
            (q, k, v))
        print('T=%5d: pallas %7.2f ms   composed %7.2f ms' % (T, tp, tc),
              flush=True)


def soak():
    """500-step stability/convergence (PERF.md 'Sustained-training')."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tr
    B, T, V = 32, 128, 8000
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            out = tr.build(src_vocab=V, trg_vocab=V, max_len=T, n_layer=4,
                           n_head=8, d_model=256, d_inner=1024,
                           dropout=0.1, lr=1.0, warmup_steps=400,
                           use_flash=True)
    main.set_amp(True)
    exe = fluid.Executor()
    scope = fluid.Scope()
    rng = np.random.RandomState(0)

    def copy_batch():
        rows = []
        for _ in range(B):
            n = rng.randint(T // 2, T - 1)
            s = rng.randint(3, V, (n,))
            rows.append((np.concatenate([s, [1]]),
                         np.concatenate([[0], s]),
                         np.concatenate([s, [1]])))
        return tr.make_batch(rows, T)

    pool = [{k: jax.device_put(v) for k, v in copy_batch().items()}
            for _ in range(50)]
    with fluid.scope_guard(scope):
        exe.run(startup)
        t0 = time.perf_counter()
        for step in range(500):
            lv, = exe.run(main, feed=pool[step % 50],
                          fetch_list=[out['loss']], return_numpy=False)
            if (step + 1) % 100 == 0:
                print('step %d loss %.3f (%.1fs/100)' %
                      (step + 1, float(_sync(lv).ravel()[0]),
                       time.perf_counter() - t0), flush=True)
                t0 = time.perf_counter()


def _hlo_category_map(hlo_text):
    """Parse optimized HLO text into {instruction_name: category}.
    Fusions are categorized by what their fused computation BODY
    contains (a '%fusion.740' profiler event name says nothing about
    whether it is a GEMM or elementwise glue)."""
    import re
    # '%name = <type> opcode(operands...' — the type can nest parens
    # (tile/memory-space annotations like T(8,128) or S(1)), but the
    # opcode is always the FIRST lowercase word directly followed by '('
    inst_re = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*.*?'
                         r'[\s)]([a-z][\w\-]*)\(')
    # computation bodies: '%name (params) -> type {' ... instructions
    comp_has = {}
    cur, ops = None, set()
    for line in hlo_text.splitlines():
        m = re.match(r'(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?\s*'
                     r'(?:->.*)?\{\s*$', line)
        if m and not line.lstrip().startswith('%param'):
            if cur is not None:
                comp_has[cur] = ops
            cur, ops = m.group(1), set()
            continue
        m = inst_re.match(line)
        if m:
            ops.add(m.group(2))
    if cur is not None:
        comp_has[cur] = ops

    def body_cat(body_ops):
        if 'dot' in body_ops:
            return 'matmul'
        if 'convolution' in body_ops:
            return 'conv'
        if 'scatter' in body_ops:
            return 'scatter'
        if 'gather' in body_ops or 'dynamic-slice' in body_ops:
            return 'gather/slice'
        if 'custom-call' in body_ops:
            return 'custom-call (pallas)'
        if 'reduce' in body_ops:
            return 'reduce+elementwise'
        return 'elementwise'

    cat = {}
    for line in hlo_text.splitlines():
        m = inst_re.match(line)
        if not m:
            continue
        name, opcode = m.group(1), m.group(2)
        if opcode == 'fusion':
            mc = re.search(r'calls=%?([\w.\-]+)', line)
            body = comp_has.get(mc.group(1), set()) if mc else set()
            cat[name] = body_cat(body)
        elif opcode == 'dot':
            cat[name] = 'matmul'
        elif opcode == 'convolution':
            cat[name] = 'conv'
        elif opcode in ('copy', 'transpose', 'bitcast',
                        'copy-start', 'copy-done'):
            cat[name] = 'copy/transpose'
        elif opcode == 'custom-call':
            cat[name] = 'custom-call (pallas)'
        elif opcode in ('all-reduce', 'all-gather', 'reduce-scatter',
                        'collective-permute'):
            cat[name] = 'collective'
        else:
            cat[name] = opcode
    return cat


def hlo(steps=10, top=30):
    """Per-HLO ledger of the bench train step (PERF.md 'Where the MFU
    ceiling actually is'): trace `steps` steps with jax.profiler, parse
    the xplane with jax.profiler.ProfileData, aggregate the TensorCore
    'XLA Ops' line (serialized sync ops — sums to the step wall) by
    category via the after-optimizations HLO dump, and print the top
    entries.  Async DMA ('Async XLA Ops') overlaps the sync timeline and
    is reported separately, not summed in.  This is HLO granularity —
    the evidence level the round-4 verdict asked for behind any 'the
    gap is diffuse' claim.  PT_HLO_MODEL=resnet profiles the ResNet-50
    bench step instead; PT_HLO_FILTER=<category> lists one category."""
    import glob
    import tempfile
    import jax
    import paddle_tpu as fluid
    if os.environ.get('PT_HLO_MODEL') == 'resnet':
        from paddle_tpu.models import resnet
        main, startup, out, feed = resnet.bench_program()
    else:
        from paddle_tpu.models import transformer as tr
        B, T, V = 32, 256, 32000
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                out = tr.build(src_vocab=V, trg_vocab=V, max_len=T,
                               n_layer=6, n_head=8, d_model=512,
                               d_inner=2048, dropout=0.0, use_flash=True)
        feed = tr.synthetic_batch(np.random.RandomState(0), B, T)
        main.set_amp(True)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed = {k: jax.device_put(v) for k, v in feed.items()}
        for _ in range(3):
            lv, = exe.run(main, feed=feed, fetch_list=[out['loss']])
        _sync(lv)
        tmpdir = tempfile.mkdtemp(prefix='hlo_trace_')
        with jax.profiler.trace(tmpdir):
            for _ in range(steps):
                lv, = exe.run(main, feed=feed, fetch_list=[out['loss']],
                              return_numpy=False)
            _sync(lv)
        # optimized HLO for fusion->category mapping: re-lower+compile
        # the SAME jitted step (deterministic naming)
        entry = next(e for k, e in exe._cache.items() if k[0] == id(main))
        fn, params_in = entry[0], entry[1]
        params = {n: scope.vars[n] for n in params_in}
        hlo_text = fn.lower(params, feed, np.uint32(0)).compile().as_text()
        open('/tmp/hlo_step.txt', 'w').write(hlo_text)
    paths = glob.glob(os.path.join(tmpdir, '**', '*.xplane.pb'),
                      recursive=True)
    if not paths:
        print('no xplane.pb written under %s' % tmpdir)
        return
    cat_map = _hlo_category_map(hlo_text)
    pd = jax.profiler.ProfileData.from_file(paths[0])
    per_op, async_ns, step_ns, nsteps = {}, 0, 0, 0
    for plane in pd.planes:
        if not plane.name.startswith('/device:TPU'):
            continue
        for line in plane.lines:
            if line.name == 'XLA Ops':
                for ev in line.events:
                    per_op[ev.name] = per_op.get(ev.name, 0) + ev.duration_ns
            elif line.name == 'Async XLA Ops':
                async_ns += sum(ev.duration_ns for ev in line.events)
            elif line.name == 'Steps':
                for ev in line.events:
                    step_ns += ev.duration_ns
                    nsteps += 1
    if not per_op:
        print('no sync XLA Ops events found')
        return

    def _cat(event_name):
        iname = event_name.split(' = ')[0].strip().lstrip('%')
        return cat_map.get(iname, 'unmapped')

    total = sum(per_op.values())
    print('%d distinct sync HLO ops; TensorCore busy %.2f ms/step; '
          'step wall %.2f ms (x%d); async DMA span %.2f ms/step (overlapped)'
          % (len(per_op), total / 1e6 / steps,
             step_ns / 1e6 / max(nsteps, 1), nsteps, async_ns / 1e6 / steps))
    cats = {}
    for name, ns in per_op.items():
        c = _cat(name)
        cats[c] = cats.get(c, 0) + ns
    print('\n-- category totals (sync TensorCore time) --')
    for c, ns in sorted(cats.items(), key=lambda kv: -kv[1]):
        print('%-28s %8.3f ms/step  %5.1f%%'
              % (c, ns / 1e6 / steps, 100.0 * ns / total))
    only = os.environ.get('PT_HLO_FILTER')  # show one category's ops
    print('\n-- top %d sync HLO ops%s --'
          % (top, ' [%s]' % only if only else ''))
    shown = 0
    for name, ns in sorted(per_op.items(), key=lambda kv: -kv[1]):
        if only and _cat(name) != only:
            continue
        print('%7.3f ms/step %5.1f%%  [%s]  %s'
              % (ns / 1e6 / steps, 100.0 * ns / total, _cat(name),
                 name[:100]))
        shown += 1
        if shown >= top:
            break


def convprobe():
    """Forward / input-grad / filter-grad conv microbench at
    representative ResNet-50 shapes (round-4 only probed the forward;
    the 0.148-vs-0.20 MFU gap question is whether backward convs run
    slower than the ~20%-of-peak forward ceiling).  bf16, B=128,
    NCHW like the model."""
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    B = 128
    dn = jax.lax.conv_dimension_numbers((1, 1, 1, 1), (1, 1, 1, 1),
                                        ('NCHW', 'OIHW', 'NCHW'))
    shapes = [  # (Cin, Cout, HW, k, stride) mid/late-net ResNet shapes
        (64, 64, 56, 3, 1),
        (128, 128, 28, 3, 1),
        (256, 256, 14, 3, 1),
        (512, 512, 7, 3, 1),
        (64, 256, 56, 1, 1),
        (256, 128, 56, 1, 2),
    ]
    print('conv probe (bf16, B=%d, NCHW); TFLOP/s vs 197 peak' % B)
    for cin, cout, hw, k, s in shapes:
        x = jnp.asarray(rng.randn(B, cin, hw, hw), jnp.bfloat16)
        w = jnp.asarray(rng.randn(cout, cin, k, k), jnp.bfloat16)
        pad = 'SAME' if k > 1 else 'VALID'

        def conv(x, w):
            return jax.lax.conv_general_dilated(
                x, w, (s, s), pad, dimension_numbers=dn)

        out_hw = hw // s
        flops = 2.0 * B * cout * cin * k * k * out_hw * out_hw

        def timed(f, lead, *args):
            """Differential in-jit timing.  (1) the op runs N times
            inside ONE jitted fori_loop at two N values and the
            delta/(N2-N1) cancels the per-launch constant;
            (2) the loop body must consume a FULL reduction of the
            output — consuming one element let XLA slice the probed
            conv down to computing a single output pixel; (3) the
            iteration-decorrelating perturbation must use a NORMAL f32
            constant — 1e-45 is a denormal, which TPU flushes to zero
            and XLA folds away, hoisting the op out of the loop."""

            def many_fn(n):
                @jax.jit
                def many(lead, args):
                    def body(_, acc):
                        pj = (lead.astype(jnp.float32) *
                              (1.0 + acc * 1e-10)).astype(lead.dtype)
                        o = f(pj, *args)
                        return acc + jnp.sum(o.astype(jnp.float32)) * 1e-20
                    return jax.lax.fori_loop(0, n, body, jnp.float32(0.0))
                return many

            def once(m):
                t0 = time.perf_counter()
                _sync(m(lead, args))
                return time.perf_counter() - t0

            times = {}
            for n in (10, 110):
                m = many_fn(n)
                _sync(m(lead, args))  # compile
                times[n] = min(once(m) for _ in range(3))
            return (times[110] - times[10]) / 100.0

        tf_ = timed(lambda x, w: conv(x, w), x, w)
        _, vjp_x = jax.vjp(lambda x: conv(x, w), x)
        ct = jnp.ones((B, cout, out_hw, out_hw), jnp.bfloat16)
        gx = timed(lambda c: vjp_x(c)[0], ct)
        _, vjp_w = jax.vjp(lambda w: conv(x, w), w)
        gw = timed(lambda c: vjp_w(c)[0], ct)
        print('C%4d->%4d %3dx%-3d k%d s%d | fwd %6.2fms %5.1fTF | '
              'dx %6.2fms %5.1fTF | dw %6.2fms %5.1fTF'
              % (cin, cout, hw, hw, k, s,
                 tf_ * 1e3, flops / tf_ / 1e12,
                 gx * 1e3, flops / gx / 1e12,
                 gw * 1e3, flops / gw / 1e12), flush=True)


def allreduce():
    """Collective bandwidth over the local mesh (BASELINE.json headline
    metric #3; the path the reference serves with NCCL —
    nccl_helper.h).  Measures psum (allreduce), all-gather and
    reduce-scatter bus bandwidth; prints null single-chip (one chip has
    no ICI to measure) so the harness degrades gracefully."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    devs = jax.devices()
    if len(devs) < 2:
        print(json.dumps({'devices': len(devs), 'allreduce_gbps': None,
                          'all_gather_gbps': None,
                          'reduce_scatter_gbps': None,
                          'note': 'single device: no interconnect to '
                                  'measure; run on a mesh'}))
        return
    mesh = Mesh(np.array(devs), ('x',))
    nd = len(devs)
    results = {'devices': nd}
    for nbytes in (1 << 20, 16 << 20, 64 << 20):
        n = nbytes // 4 // nd * nd
        x = jnp.ones((n,), jnp.float32)

        def run(body, out_specs):
            f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P('x'),
                                      out_specs=out_specs))
            f(x).block_until_ready()
            t0 = time.perf_counter()
            iters = 10
            for _ in range(iters):
                o = f(x)
            o.block_until_ready()
            return (time.perf_counter() - t0) / iters

        # ring-algorithm bus-bandwidth accounting (the convention NCCL
        # tests print): allreduce moves 2(n-1)/n, gather/scatter (n-1)/n
        dt = run(lambda s: jax.lax.psum(s, 'x'), P(None))
        results['allreduce_gbps_%dMB' % (nbytes >> 20)] = round(
            2 * (nd - 1) / nd * n * 4 / dt / 1e9, 2)
        dt = run(lambda s: jax.lax.all_gather(s, 'x', tiled=True), P(None))
        results['all_gather_gbps_%dMB' % (nbytes >> 20)] = round(
            (nd - 1) / nd * n * 4 / dt / 1e9, 2)
        dt = run(lambda s: jax.lax.psum_scatter(s, 'x', tiled=True), P('x'))
        results['reduce_scatter_gbps_%dMB' % (nbytes >> 20)] = round(
            (nd - 1) / nd * n * 4 / dt / 1e9, 2)
    print(json.dumps(results))


# -------------------------------- model matrix (ex fluid_benchmark.py)
# Parity: reference benchmark/fluid/fluid_benchmark.py + args.py — same
# CLI shape (--model/--batch_size/--iterations/--skip_batch_num/
# --learning_rate), same model set, synthetic data, prints per-model
# throughput.  One whole-step XLA executable per model; the timed loop
# runs async with a single sync at the end (steady-state training
# measures the chip, not per-step RTT).
BENCHMARK_MODELS = ['mnist', 'resnet', 'vgg', 'se_resnext',
                    'machine_translation', 'stacked_dynamic_lstm']


def parse_model_args(argv=None):
    p = argparse.ArgumentParser('paddle_tpu model benchmarks.')
    p.add_argument('--model', type=str, choices=BENCHMARK_MODELS,
                   default='resnet')
    p.add_argument('--batch_size', type=int, default=32)
    p.add_argument('--learning_rate', type=float, default=None,
                   help='override each model\'s default lr/schedule scale')
    p.add_argument('--skip_batch_num', type=int, default=5,
                   help='warmup minibatches excluded from timing')
    p.add_argument('--iterations', type=int, default=30,
                   help='timed minibatches')
    p.add_argument('--seq_len', type=int, default=256,
                   help='sequence length (translation / lstm models)')
    p.add_argument('--class_dim', type=int, default=1000)
    p.add_argument('--image_size', type=int, default=224)
    p.add_argument('--device', type=str, default='TPU',
                   choices=['CPU', 'TPU'],
                   help='CPU forces the host backend')
    return p.parse_args(argv)


def _build_model(args):
    import paddle_tpu as fluid
    rng = np.random.RandomState(0)
    B = args.batch_size

    def lr_kw(default):
        return {'lr': args.learning_rate
                if args.learning_rate is not None else default}

    if args.model == 'mnist':
        from paddle_tpu.models import mnist as m
        out = m.build(**lr_kw(0.001))
        feed = {'pixel': rng.rand(B, 1, 28, 28).astype('float32'),
                'label': rng.randint(0, 10, (B, 1)).astype('int64')}
        unit, per_step = 'images/s', B
    elif args.model in ('resnet', 'vgg', 'se_resnext'):
        shape = (3, args.image_size, args.image_size)
        if args.model == 'resnet':
            from paddle_tpu.models import resnet as m
            out = m.build(data_shape=shape, class_dim=args.class_dim,
                          depth=50, **lr_kw(0.1))
        elif args.model == 'vgg':
            from paddle_tpu.models import vgg as m
            out = m.build(data_shape=shape, class_dim=args.class_dim,
                          **lr_kw(1e-3))
        else:
            from paddle_tpu.models import se_resnext as m
            out = m.build(data_shape=shape, class_dim=args.class_dim,
                          **lr_kw(0.1))
        feed = {'data': rng.rand(B, *shape).astype('float32'),
                'label': rng.randint(0, args.class_dim,
                                     (B, 1)).astype('int64')}
        unit, per_step = 'images/s', B
    elif args.model == 'machine_translation':
        from paddle_tpu.models import transformer as tr
        T = args.seq_len
        out = tr.build(src_vocab=32000, trg_vocab=32000, max_len=T,
                       n_layer=6, n_head=8, d_model=512, d_inner=2048,
                       dropout=0.0, use_flash=True,
                       **lr_kw(2.0))   # lr scales the noam schedule here
        feed = tr.synthetic_batch(rng, B, T)
        unit, per_step = 'tokens/s', B * T
    else:  # stacked_dynamic_lstm
        from paddle_tpu.models import stacked_lstm as m
        from paddle_tpu.core.lod import create_lod_tensor
        out = m.build(**lr_kw(0.002))
        T = min(args.seq_len, 128)
        rows = [rng.randint(2, 5147, (T, 1)).astype('int64')
                for _ in range(B)]
        feed = {'words': create_lod_tensor(rows),
                'label': rng.randint(0, 2, (B, 1)).astype('int64')}
        unit, per_step = 'words/s', B * T
    return out, feed, unit, per_step


def models_main(argv=None):
    args = parse_model_args(argv)
    if args.device == 'CPU':
        import jax
        jax.config.update('jax_platforms', 'cpu')
    import jax
    import paddle_tpu as fluid

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        with fluid.unique_name.guard():
            out, feed, unit, per_step = _build_model(args)
    if args.device != 'CPU':
        main_prog.set_amp(True)

    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed = {k: (v if hasattr(v, 'padded') else jax.device_put(v))
                for k, v in feed.items()}
        t0 = time.perf_counter()
        for _ in range(max(1, args.skip_batch_num)):
            loss, = exe.run(main_prog, feed=feed,
                            fetch_list=[out['loss']])
        np.asarray(loss)
        print('%s: compile+warmup %.1fs'
              % (args.model, time.perf_counter() - t0), file=sys.stderr)
        t0 = time.perf_counter()
        for _ in range(args.iterations):
            loss, = exe.run(main_prog, feed=feed,
                            fetch_list=[out['loss']],
                            return_numpy=False)
        final = float(np.asarray(loss).reshape(()))
        dt = time.perf_counter() - t0
    tput = args.iterations * per_step / dt
    print(json.dumps({
        'model': args.model, 'batch_size': args.batch_size,
        'iterations': args.iterations, 'throughput': round(tput, 1),
        'unit': unit, 'final_loss': round(final, 4),
        'backend': jax.devices()[0].device_kind,
    }))
    return 0


def probe_main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    harness = argv[0] if argv else 'decompose'
    if harness not in PROBES:
        print('unknown probe %r (known: %s)' % (harness,
                                                ', '.join(PROBES)),
              file=sys.stderr)
        return 2
    {'decompose': decompose, 'longctx': longctx,
     'attn': attn, 'soak': soak, 'hlo': hlo,
     'convprobe': convprobe, 'allreduce': allreduce}[harness]()
    return 0


if __name__ == '__main__':
    sys.exit(probe_main())
