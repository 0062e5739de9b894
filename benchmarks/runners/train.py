"""The training runner: one Program trained through Executor.run_steps
(or ParallelExecutor over a mesh), fed by a seeded reader through
data_feeder.FeedPrefetcher, measured in segments.

A segment is `launches_per_segment` K-step launches.  It is closed by
`block_until_ready` on its last fetch AFTER the next launch has been
enqueued, so reading the clock never leaves the chip waiting.  Everything
that belongs to one configuration or traffic mix comes from their files:
the Program is built by `build_program` of the configuration's own
builds/<config>.py where it has one (README, "Adding a cell"),
and by the two fall-backs below for the configurations that have none.
"""
import importlib.util
import itertools
import math
import os
import shutil
import tempfile
import time

import numpy as np

from lib import memory as _memory
from lib import spans as _spans
from lib import traffic as _traffic
from lib import xplane as _xplane

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_COMPILE_COUNTERS = ('executor.lowerings', 'executor.compiles',
                     'executor.retraces')


def _build_transformer(fluid, cfg, traffic):
    from paddle_tpu.models import transformer as tr
    out = tr.build(src_vocab=cfg['vocab'], trg_vocab=cfg['vocab'],
                   max_len=int(traffic['seq']), n_layer=cfg['n_layer'],
                   n_head=cfg['n_head'], d_model=cfg['d_model'],
                   d_inner=cfg['d_inner'], dropout=cfg['dropout'],
                   lr=cfg['lr'], warmup_steps=cfg['warmup_steps'],
                   use_flash=cfg['use_flash'])
    # every token's own loss [batch, seq]: what reduce_sum makes sum_cost of
    block = out['loss'].block
    per_token, = next(op for op in block.ops
                      if out['sum_cost'].name in op.output_names()).input_names()
    return {'loss': out['loss'], 'per_item_loss': block.var(per_token)}


def _build_resnet(fluid, cfg, traffic):
    from paddle_tpu.models import resnet
    side = int(traffic['side'])
    out = resnet.build(data_shape=(3, side, side), class_dim=cfg['classes'],
                       depth=cfg['depth'], lr=cfg['lr'],
                       data_set=cfg['data_set'])
    return out['loss']


# the fall-backs, for a configuration without a builds/<config>.py:
# model family -> (program builder, generator's size argument)
_MODELS = {'transformer': (_build_transformer, 'vocab'),
           'resnet': (_build_resnet, 'classes')}


def _load(kind, name):
    path = os.path.join(HERE, kind, name + '.py')
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        '%s_%s' % (kind, name.replace('.', '_').replace('-', '_')), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(config):
    name = config.get('reference', config['name'])
    ref = _load('references', name)
    if ref is None:
        raise ValueError('no plain reference benchmarks/references/%s.py'
                         % name)
    return ref


def load_build(config):
    """The configuration's own builds/<config>.py, or None: what an
    architecture the runners do not know brings with it."""
    return _load('builds', config['name'])


def program_builder(config, build):
    """(build(fluid, config, traffic), the configuration's key that sizes
    the generator's ids) from the configuration's build file, else from
    the fall-back table.  `build` returns the loss variable, or a dict
    with it under `loss` and, under `per_item_loss`, the variable
    [batch, ...] of each item's own loss where the reference has
    `probes` to compare it with."""
    if build is not None and hasattr(build, 'build_program'):
        return build.build_program, build.SIZE_KEY
    if config.get('model') not in _MODELS:
        raise ValueError(
            'configuration %r has no benchmarks/builds/%s.py with '
            'build_program(fluid, config, traffic), and its model %r is '
            'none of the fall-backs (%s)'
            % (config['name'], config['name'], config.get('model'),
               ', '.join(sorted(_MODELS))))
    return _MODELS[config['model']]


def reseed(params, seed):
    import jax
    import jax.numpy as jnp

    def flip(params, key):
        out = {}
        for i, n in enumerate(sorted(params)):
            p = params[n]
            if p.ndim < 2:
                out[n] = p
                continue
            sign = jax.random.rademacher(jax.random.fold_in(key, i), p.shape,
                                         jnp.int8)
            out[n] = p * sign.astype(p.dtype)
        return out

    key = jax.random.fold_in(jax.random.key(int(seed) & 0x7fffffff),
                             int(seed) >> 31)
    return jax.jit(flip)(params, key)


def _counters():
    import paddle_tpu.observability as obs
    return {k: float(v) for k, v in obs.counters().items()
            if isinstance(v, (int, float))}


def _delta(after, before):
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


class Trainer(object):
    """The cell's Program, built and placed: what `run` measures, and
    what tests/control.py reads the comparison's numbers from over many
    seeds in one process.  `launch(feed)` runs K steps and returns the
    fetches stacked per step: the loss and, where the configuration's
    reference has `probes`, each item's own loss and the gradients the
    reference names, so that what is compared comes out of the very
    executable that is timed."""

    def __init__(self, cell, config, traffic, mark=lambda name: None):
        import jax
        import paddle_tpu as fluid
        self.chips = int(cell['chips'])
        self.K = int(traffic['steps_per_launch'])
        self.own = load_build(config)
        self.ref = load_reference(config)
        build, self.size_key = program_builder(config, self.own)

        # the programs do NOT carry --seed: a seed in a program is another
        # program, which misses the compile cache (22 s of set-up twice
        # over, my chip run, PR 23).  Weights take the seed in set_weights.
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = int(config['program_seed'])
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                built = build(fluid, config, traffic)
        if not isinstance(built, dict):
            built = {'loss': built}
        main.set_amp(bool(config['amp']))
        self.param_names = [v.name
                            for v in main.global_block().all_parameters()]
        loss = built['loss']
        self.fetch_list = [loss]
        self.probe_names = []
        if hasattr(self.ref, 'probes') and 'per_item_loss' in built:
            self.probe_names = list(self.ref.probe_names(config))
            self.fetch_list += [built['per_item_loss']] + [
                n + '@GRAD' for n in self.probe_names]

        self.scope = scope = fluid.Scope()
        fluid.Executor().run(startup, scope=scope)
        mark('startup')

        mesh_axes = traffic.get('mesh')
        if mesh_axes:
            from paddle_tpu.parallel import ParallelExecutor, make_mesh
            mesh = make_mesh(devices=jax.devices()[:self.chips], **mesh_axes)
            pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                                  scope=scope, mesh=mesh)
            self._run_steps = lambda **kw: pe.run_steps(**kw)
        else:
            if self.chips != 1:
                raise ValueError('a cell on %d chips needs a mesh in its '
                                 'traffic file' % self.chips)
            exe = fluid.Executor()
            self._run_steps = lambda **kw: exe.run_steps(main, scope=scope,
                                                         **kw)

    def params(self):
        """A copy of the scope's parameters (the step donates and
        overwrites the scope's own arrays)."""
        import jax
        return {n: jax.numpy.array(self.scope.vars[n], copy=True)
                for n in self.param_names}

    def set_weights(self, seed, base=None):
        """Weights from --seed, on the device, in one jitted call: every
        matrix, filter and table of the start-up's draw (`base`; the
        scope's own where not given) gets a random sign per entry (its
        initialisers are symmetric about zero, so this is another draw of
        the same distribution); vectors (norm scales, biases, running
        statistics) stay.  Returns the weights as set, which the
        reference gets."""
        import jax
        init = reseed(base or {n: self.scope.vars[n]
                               for n in self.param_names}, seed)
        for n in self.param_names:
            self.scope.vars[n] = jax.numpy.array(init[n], copy=True)
        return init

    def launch(self, feed):
        return self._run_steps(feed_list=feed, steps=self.K,
                               fetch_list=self.fetch_list,
                               return_numpy=False)

    def first_step(self, fetched):
        """What the first of a launch's K steps fetched: the loss, and
        the program's side of `probes` (None without them)."""
        loss = float(np.asarray(fetched[0]).ravel()[0])
        if not self.probe_names:
            return loss, None
        grads = {n: np.asarray(g[0], np.float32)
                 for n, g in zip(self.probe_names, fetched[2:])}
        return loss, {'per_item': np.asarray(fetched[1][0], np.float32),
                      'grads': grads}


def distance(a, b, about=None):
    """||a - b|| / ||b - about|| over all entries: how far the program's
    numbers are from the reference's, in units of how much the
    reference's own vary (`about`: their mean for losses, which all sit
    near one value; 0 for gradients)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b)
                 / np.linalg.norm(b - (0.0 if about is None else about)))


def probe_errors(got, want):
    """The two numbers the comparison is decided by where the reference
    has `probes`: the distance of every item's own loss, and of all the
    probed gradients taken as one vector; and, for the record, each
    gradient's own."""
    names = sorted(want['grads'])
    items = np.asarray(want['per_item'], np.float64)
    live = items != 0.0                           # padded positions are 0
    return {
        'per_item': distance(np.asarray(got['per_item'])[live], items[live],
                             about=items[live].mean()),
        'grads': distance(np.concatenate([got['grads'][n].ravel()
                                          for n in names]),
                          np.concatenate([want['grads'][n].ravel()
                                          for n in names])),
        'each_grad': {n: distance(got['grads'][n], want['grads'][n])
                      for n in names}}


def run(cell, config, traffic, seed, seconds, trace, t_start, device, say):
    import jax
    import paddle_tpu.observability as obs
    from paddle_tpu.data_feeder import FeedPrefetcher
    if not obs.enabled():
        raise RuntimeError('the benchmark reads obs counters: PT_OBS is off')

    marks = {'imports': time.perf_counter() - t_start}

    def mark(name):
        marks[name] = time.perf_counter() - t_start

    chips = int(cell['chips'])
    K = int(traffic['steps_per_launch'])
    per_segment = int(traffic['launches_per_segment'])
    c_start = _counters()
    trainer = Trainer(cell, config, traffic, mark)
    # `init` is also what the reference gets
    init = trainer.set_weights(seed)
    launch, size_key, own = trainer.launch, trainer.size_key, trainer.own
    mark('program')
    reader, items_per_step = _traffic.TRAIN_GENERATORS[traffic['generator']](
        traffic, config[size_key], seed)
    spans = _spans.Spans()
    if traffic['feeds'] == 'prefetch':
        # the normal feed path: a worker thread stacks K per-step feeds
        # and uploads them while the previous launch runs
        prefetch = FeedPrefetcher(reader, steps=K, capacity=2,
                                  to_device=bool(traffic.get(
                                      'prefetch_to_device', True)))
        batches, close = iter(prefetch), prefetch.close
    elif traffic['feeds'] == 'device_pool':
        # the pool's superbatches are uploaded ONCE in set-up and cycled:
        # the cell then measures the step, not the host link
        pool = [jax.device_put({k: np.stack([f[k] for f in group])
                                for k in group[0]})
                for group in ([next(reader) for _ in range(K)]
                              for _ in range(int(traffic['pool_batches'])
                                             // K))]
        batches = ((pool[i % len(pool)], K) for i in itertools.count())
        close = lambda: None                                   # noqa: E731
    else:
        raise ValueError('unknown feeds %r' % (traffic['feeds'],))
    mark('feeds')

    def next_feed():
        with spans.span('feed'):
            feed, k = next(batches)
        assert k == K, k
        return feed

    try:
        # ---- warm-up: every shape the window uses is this one launch shape
        feed = next_feed()
        first_feed = {k: np.asarray(v[0]) for k, v in feed.items()}
        loss_first, got = trainer.first_step(launch(feed))
        mark('first_launch')
        for _ in range(int(traffic.get('warm_launches', 2)) - 1):
            jax.block_until_ready(launch(next_feed()))
        mark('warm')
        c_warm = _counters()
        spans.reset()
        memory = _memory.PeakSampler(jax.devices()[:chips])

        # ---- the measured window
        durations, pending, launched = [], None, 0
        t0 = seg_start = time.perf_counter()
        setup_s = t0 - t_start
        while True:
            feed = next_feed()
            with spans.span('launch'):
                out = launch(feed)
            launched += 1
            if launched % per_segment:
                continue
            if pending is not None:
                with spans.span('fetch'):
                    jax.block_until_ready(pending)
                now = time.perf_counter()
                durations.append(now - seg_start)
                seg_start = now
                memory.sample()      # the next launch is running now
                if now - t0 >= seconds:
                    break
            pending = out
        window_s = seg_start - t0
        memory_peak = memory.result()    # before the reference's own use
        say('memory', sampled_in_use_plus_reserved=memory.peak,
            samples=memory.samples, allocator_peak_in_use=memory.live_peak())
        window_spans = dict(spans.seconds)
        last = np.asarray(out[0]).ravel()    # the launch still in flight
        c_end = _counters()
        loss_last = float(np.mean(last))

        # ---- the traced window: the same work, profiler on
        summary = None
        if trace:
            summary = _traced(traffic, spans, next_feed, launch)

        # ---- one more launch from a snapshot of the TRAINED parameters:
        # at initialisation the transformer's loss is ln(vocab) whatever
        # the layers do; after the window it depends on all of them
        trained = trainer.params()
        feed = next_feed()
        trained_feed = {k: np.asarray(v[0]) for k, v in feed.items()}
        loss_trained, _ = trainer.first_step(launch(feed))
    finally:
        close()

    window = _delta(c_end, c_warm)
    compiles = sum(int(window.get(k, 0)) for k in _COMPILE_COUNTERS)
    steps_per_segment = K * per_segment
    n_segments = len(durations)
    say('setup', seconds_since_start=marks)
    say('segments', rates=[items_per_step * steps_per_segment / d / chips
                           for d in durations],
        unit='items/s/chip', steps_per_segment=steps_per_segment)

    # ---- correctness, outside the window
    ref = trainer.ref
    errors, limits = {}, {}
    if got is None:
        ref_loss = float(ref.loss(init, first_feed, config))
    else:
        # the reference's own forward AND backward pass on the seeded
        # weights and the first batch: a state no run's speed changes
        want = ref.probes(init, first_feed, config)
        ref_loss = float(want['loss'])
        errors = probe_errors(got, want)
        limits = {'per_item': float(ref.ITEM_TOL),
                  'grads': float(ref.GRAD_TOL)}
    ref_trained = float(ref.loss(trained, trained_feed, config))
    tol, trained_tol = float(ref.LOSS_RTOL), ref.TRAINED_RTOL
    checks = {
        'first_loss_matches_reference':
            abs(loss_first - ref_loss) <= tol * abs(ref_loss),
        # a reference may say, with its reason, that its trained state is
        # no fair comparison (TRAINED_RTOL None): it is then reported only
        'trained_loss_matches_reference':
            trained_tol is None or abs(loss_trained - ref_trained)
            <= float(trained_tol) * abs(ref_trained),
        'every_item_loss_matches_reference':
            got is None or errors['per_item'] <= limits['per_item'],
        'probed_gradients_match_reference':
            got is None or errors['grads'] <= limits['grads'],
        'loss_finite': bool(np.isfinite(last).all()
                            and math.isfinite(loss_first)),
        'loss_fell': loss_last < loss_first,
        'no_compile_in_window': compiles == 0,
        'ten_segments': n_segments >= 10,
    }
    say('compared', reference='benchmarks/references/%s.py'
        % config.get('reference', config['name']),
        comparing='the first step on the first batch, parameters as '
                  'initialised: its loss and, where the reference has '
                  'probes, every item\'s own loss and the probed gradients '
                  '(distance = |program - reference| / |reference - its '
                  'mean, or 0|); and the loss of one step after the window, '
                  'parameters as trained; float32, matmul precision highest',
        loss_first=loss_first, reference_loss=ref_loss, rtol=tol,
        trained_rtol=trained_tol,
        loss_trained=loss_trained, reference_trained=ref_trained,
        rel_err_first=abs(loss_first - ref_loss) / abs(ref_loss),
        rel_err_trained=abs(loss_trained - ref_trained) / abs(ref_trained),
        distance_per_item=errors.get('per_item'),
        per_item_tol=limits.get('per_item'),
        distance_grads=errors.get('grads'), grads_tol=limits.get('grads'),
        distance_each_grad_max=max(errors['each_grad'].values())
        if errors else None,
        loss_last=loss_last, compiles_after_warmup=compiles,
        segments=n_segments, checks=checks)

    return {
        'correct': all(checks.values()),
        'attempted': n_segments * per_segment,
        'failed': 0,
        'setup_s': setup_s,
        'window_s': window_s,
        'segments': durations,
        'items_per_segment': items_per_step * steps_per_segment,
        'steps': n_segments * steps_per_segment,
        'launched_steps': launched * K,
        'chips': chips,
        'memory_peak_bytes': memory_peak,
        'spans': window_spans,
        'counters': window,
        'setup_counters': _delta(c_warm, c_start),
        'trace': summary,
        'build': own,
        'config': config,
        'traffic': traffic,
        'device': device,
    }


def _traced(traffic, spans, next_feed, launch):
    """Run `trace_launches` more launches of the same work with the
    profiler on; return the trace's summary (None where nothing ran on a
    chip, as on the CPU)."""
    import jax
    trace_dir = tempfile.mkdtemp(prefix='bench_trace_')
    try:
        jax.profiler.start_trace(trace_dir)
        spans.annotate(True)
        try:
            with spans.span('traced_window'):
                pending = None
                for _ in range(int(traffic['trace_launches'])):
                    feed = next_feed()
                    with spans.span('launch'):
                        out = launch(feed)
                    if pending is not None:
                        with spans.span('fetch'):
                            jax.block_until_ready(pending)
                    pending = out
                with spans.span('fetch'):
                    jax.block_until_ready(pending)
        finally:
            spans.annotate(False)
            jax.profiler.stop_trace()
        path = _xplane.find_trace(trace_dir)
        return _xplane.summarize(_xplane.load(path)) if path else None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
