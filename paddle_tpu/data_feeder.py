"""DataFeeder — convert python/numpy minibatch rows into feed dicts.

Parity: reference python/paddle/fluid/data_feeder.py.  Ragged (lod_level>0)
slots become LoDTensors (padded + lengths, core/lod.py).

FeedPrefetcher is the host side of the multi-step execution path
(Executor.run_steps): a bounded background queue that stacks K per-step
feed dicts into one [K, ...] superbatch and device_puts it while the
device runs the current launch, so host->device transfer overlaps compute.

FeedBucketer is the shape-stability half of the compilation-persistence
story (core/compile_cache.py): variable batch/sequence sizes — ragged
epoch tails, LoD sequence lengths — each lower a fresh executable under
whole-block jit.  The bucketer pads the leading batch dim (and declared
sequence dims) up to a small set of boundaries and threads a validity
mask feed, so arbitrary feed streams collapse onto a handful of compile
signatures instead of one trace per shape.
"""
import os
import queue
import threading
import time

import numpy as np

from .core.framework import Variable, default_main_program
from .core.lod import create_lod_tensor
from .core.dtypes import convert_dtype
from .core.retry import retry_with_backoff
from . import observability as _obs
from .observability import flight as _flight
from .testing import faults as _faults

__all__ = ['DataFeeder', 'FeedPrefetcher', 'FeedBucketer',
           'SampleQuarantine']


def _default_boundaries():
    """Powers-of-two with 1.5x midpoints: dense enough that pad waste
    stays under ~25%, sparse enough that a whole training run touches
    only a few signatures.  Override per-instance or via PT_BUCKETS."""
    env = os.environ.get('PT_BUCKETS')
    if env:
        return sorted(int(b) for b in env.replace(',', ' ').split())
    bounds = [1, 2, 4, 6, 8]
    while bounds[-1] < 65536:
        b = bounds[-1]
        # 8, 12, 16, 24, 32, 48, 64, 96, 128, ...
        bounds.append(b + b // 2 if (b & (b - 1)) == 0 else b + b // 3)
    return bounds


class FeedBucketer(object):
    """Pad feeds up to bucket boundaries so variable shapes reuse a small
    fixed set of executables.

    * **Batch dim** (axis 0 of every feed whose leading dim matches the
      batch): padded up to the smallest boundary >= the true batch by
      edge-replicating the last row (every op stays well-defined on pad
      rows; they carry no NaN/div-by-zero hazard).  When `mask_name` is
      set, a float32 ``[B', 1]`` validity mask (1 real / 0 pad) is added
      to the feed — thread it through loss/metric reductions
      (``loss = sum(per_example * mask) / sum(mask)``) and padded rows
      contribute exactly zero to the loss AND to every gradient.
    * **Sequence dims**: feeds named in `seq_names` get axis 1 padded up
      to a boundary with zeros.  LoDTensor feeds already carry true
      lengths in their ``@LENGTH`` companion, and every sequence op masks
      by length — so sequence-bucketed feeds need no extra mask.

    Pad waste is observable: ``bucketer.rows_real`` / ``bucketer.rows_pad``
    counters and the ``bucketer.pad_waste`` gauge (last batch's padded
    fraction) land in the PR 2 metrics registry.

    Compose with the prefetcher as ``FeedPrefetcher(feeds, bucketer=b)``
    or wrap any feed iterable with :meth:`wrap`.
    """

    def __init__(self, boundaries=None, mask_name=None, seq_names=(),
                 pad_mode='edge'):
        self.boundaries = sorted(int(b) for b in
                                 (boundaries or _default_boundaries()))
        if not self.boundaries or self.boundaries[0] < 1:
            raise ValueError('bucket boundaries must be positive ints')
        self.mask_name = mask_name
        self.seq_names = tuple(seq_names)
        if pad_mode not in ('edge', 'zero'):
            raise ValueError("pad_mode must be 'edge' or 'zero'")
        self.pad_mode = pad_mode
        # distinct batch boundaries this instance has materialized — each
        # one is a compile signature, so unbounded growth here (huge
        # batches quantizing to ever-new multiples of the top boundary)
        # is a compile-cache leak; metered as the bucketer.bucket_count
        # gauge and readable via bucket_count()
        self._buckets_seen = set()

    def boundary(self, n):
        """Smallest boundary >= n; beyond the largest boundary, the next
        multiple of it (so huge batches still quantize, coarsely)."""
        n = int(n)
        if n < 1:
            raise ValueError('bucket size must be >= 1, got %d' % n)
        for b in self.boundaries:
            if b >= n:
                return b
        top = self.boundaries[-1]
        return ((n + top - 1) // top) * top

    def _pad_axis(self, arr, axis, target):
        arr = np.asarray(arr)
        gap = target - arr.shape[axis]
        if gap <= 0:
            return arr
        widths = [(0, 0)] * arr.ndim
        widths[axis] = (0, gap)
        if self.pad_mode == 'edge' and axis == 0 and arr.shape[0] > 0:
            return np.pad(arr, widths, mode='edge')
        return np.pad(arr, widths, mode='constant')

    def bucket_feed(self, feed):
        """One feed dict -> (padded feed dict, true batch size).  The mask
        feed (if configured) is ALWAYS present — a full batch gets all
        ones — so the feed-name set, which is part of the compile
        signature, never wobbles between padded and exact batches."""
        from .core.lod import LoDTensor
        arrays = {k: (v if isinstance(v, LoDTensor) else np.asarray(v))
                  for k, v in feed.items()}
        # consensus batch: the leading dim of the first batched feed;
        # arrays with a different leading dim pass through unpadded
        dims = [d for d in (_leading_dim(v) for v in arrays.values())
                if d is not None]
        if not dims:
            raise ValueError('bucket_feed needs at least one batched feed')
        batch = dims[0]
        target = self.boundary(batch)
        out = {}
        for k, v in arrays.items():
            if isinstance(v, LoDTensor):
                if v.outer_lengths is not None:
                    # nested LoD: the inner-row dim is not the batch —
                    # padding it would break the outer offset table
                    out[k] = v
                    continue
                padded, lengths = v.padded, v.lengths
                if padded.shape[0] == batch:
                    padded = self._pad_axis(padded, 0, target)
                    # edge-replicated lengths keep pad rows non-empty:
                    # a zero-length row would NaN length-normalizing
                    # sequence ops, and NaN * mask 0 is still NaN
                    lengths = self._pad_axis(lengths, 0, target)
                if k in self.seq_names:
                    padded = self._pad_axis(padded, 1,
                                            self.boundary(padded.shape[1]))
                out[k] = LoDTensor(padded, lengths)
                continue
            if v.ndim and v.shape[0] == batch:
                v = self._pad_axis(v, 0, target)
            if k in self.seq_names and v.ndim >= 2:
                v = self._pad_axis(v, 1, self.boundary(v.shape[1]))
            out[k] = v
        if self.mask_name:
            mask = np.zeros((target, 1), np.float32)
            mask[:batch] = 1.0
            out[self.mask_name] = mask
        self._buckets_seen.add(target)
        if _obs.enabled():
            _obs.metrics.gauge('bucketer.bucket_count').set(
                len(self._buckets_seen))
            _obs.metrics.counter('bucketer.batches').inc()
            _obs.metrics.counter('bucketer.rows_real').inc(batch)
            _obs.metrics.counter('bucketer.rows_pad').inc(target - batch)
            _obs.metrics.gauge('bucketer.pad_waste').set(
                (target - batch) / float(target))
        return out, batch

    def bucket_count(self):
        """Distinct batch boundaries materialized so far (== the
        ``bucketer.bucket_count`` gauge)."""
        return len(self._buckets_seen)

    def covered_axes(self, name, lod_level=0):
        """Which axes of feed `name` this bucketer stabilizes onto bucket
        boundaries: axis 0 (batch) always, axis 1 when the feed is named
        in seq_names.  Nested-LoD feeds (lod_level > 1) pass through
        bucket_feed unpadded, so nothing is covered.  The lint retrace-
        hazard pass (analysis/passes/retrace.py) consumes this to decide
        which dynamic dims still threaten a per-shape recompile."""
        if lod_level > 1:
            return set()
        axes = {0}
        if name in self.seq_names:
            axes.add(1)
        return axes

    def wrap(self, feeds):
        """Generator over an iterable of feed dicts, bucketing each.
        Yields just the padded feeds (the mask feed carries validity), so
        the result plugs straight into FeedPrefetcher / run_steps."""
        for f in feeds:
            yield self.bucket_feed(f)[0]

    @staticmethod
    def trim(fetches, batch):
        """Slice per-example fetch arrays back to the true batch size.
        Arrays whose leading dim is not the padded batch (scalar losses,
        stacked [K, B, ...] fetches get their SECOND dim trimmed) pass
        through untouched where no dim matches."""
        out = []
        for f in fetches:
            a = np.asarray(f)
            if a.ndim >= 1 and a.shape[0] >= batch:
                out.append(a[:batch])
            else:
                out.append(a)
        return out


def _leading_dim(v):
    from .core.lod import LoDTensor
    if isinstance(v, LoDTensor):
        return v.padded.shape[0]
    a = np.asarray(v)
    return a.shape[0] if a.ndim else None


class FeedPrefetcher(object):
    """Bounded background prefetch queue over an iterable of feed dicts.

    Pulls per-step feed dicts from `feeds`, stacks every `steps` of them
    on a new leading axis (np.stack on host — ONE device_put per
    superbatch instead of one per step), optionally uploads the stack,
    and parks the result in a bounded queue.  A single worker thread
    preserves order; reader exhaustion flushes the partial tail (its true
    length is yielded alongside) and drains cleanly; a reader exception
    is re-raised in the consumer at the point it would have been read.

    Iterating yields (stacked_feed_dict, k) with k == steps except for
    the final partial superbatch.  Feed Executor.run_steps directly:

        for superbatch, k in FeedPrefetcher(batches, steps=8):
            losses = exe.run_steps(prog, feed_list=superbatch, steps=k,
                                   fetch_list=[loss])
    """

    def __init__(self, feeds, steps=1, capacity=2, to_device=True,
                 bucketer=None, skip_steps=0):
        if steps < 1:
            raise ValueError('steps must be >= 1, got %r' % (steps,))
        if capacity < 1:
            raise ValueError('capacity must be >= 1, got %r' % (capacity,))
        if skip_steps < 0:
            raise ValueError('skip_steps must be >= 0, got %r'
                             % (skip_steps,))
        # bucketing happens on the worker thread, before stacking: padded
        # per-step feeds share one shape, so a ragged epoch tail batch no
        # longer breaks np.stack — nor costs a fresh compile signature
        self._src = iter(bucketer.wrap(feeds) if bucketer is not None
                         else feeds)
        # checkpoint resume: fast-forward past the steps a previous run
        # already consumed (the cursor() of the checkpointed prefetcher)
        self._skip = int(skip_steps)
        self._steps_out = 0
        self._superbatches_out = 0
        self._steps = int(steps)
        self._to_device = to_device
        self._q = queue.Queue(maxsize=int(capacity))
        self._terminal = None   # ('done',) | ('error', exc) | ('closed',)
        # telemetry: is the consumer currently blocked on an empty queue?
        # (pack work done while it ISN'T waiting overlapped its compute)
        self._consumer_waiting = False
        # lifetime totals behind the prefetch.upload_overlap_ratio gauge
        self._upload_s = 0.0
        self._overlap_s = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._worker, name='FeedPrefetcher', daemon=True)
        self._thread.start()

    def _pack(self, buf):
        names = set(buf[0])
        for f in buf[1:]:
            if set(f) != names:
                raise ValueError('per-step feeds disagree on keys: %s vs %s'
                                 % (sorted(names), sorted(f)))
        obs_on = _obs.enabled()
        overlapped = obs_on and not self._consumer_waiting
        with _obs.span('prefetch.pack', cat='prefetch', steps=len(buf),
                       overlapped=overlapped) as pack:
            stacked = {k: np.stack([np.asarray(f[k]) for f in buf])
                       for k in buf[0]}
            if self._to_device:
                import jax
                stacked = jax.device_put(stacked)
        if obs_on:
            dt = pack.seconds
            _obs.metrics.counter('prefetch.superbatches').inc()
            _obs.metrics.counter('prefetch.upload_s').inc(dt)
            self._upload_s += dt
            if overlapped:
                # stacking+upload ran while the consumer was busy running
                # the previous launch — the overlap the prefetcher exists
                # to buy.  Upload time with the consumer parked on the
                # queue is exposed transfer latency instead.
                _obs.metrics.counter('prefetch.upload_overlap_s').inc(dt)
                self._overlap_s += dt
            _obs.metrics.gauge('prefetch.upload_overlap_ratio').set(
                self._overlap_s / self._upload_s if self._upload_s else 0.0)
            return (stacked, len(buf)), (pack.t0, pack.t1)
        return (stacked, len(buf)), None

    def _put(self, item):
        # bounded put that stays responsive to close(): never blocks
        # forever on a consumer that went away
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                if _obs.enabled():
                    _obs.metrics.gauge('prefetch.queue_depth').set(
                        self._q.qsize())
                return True
            except queue.Full:
                continue
        return False

    def _read_next(self):
        """One reader pull behind the shared transient-IO retry policy
        (core/retry.py): a flaky reader — an NFS blip, an object-store
        hiccup, the deterministic ``feed_read`` fault site — is absorbed
        with bounded backoff instead of killing the trainer.
        StopIteration propagates immediately: exhaustion is not an
        error."""
        def read():
            if _faults.any_active():
                _faults.maybe_fail('feed_read')
            return next(self._src)
        return retry_with_backoff(read, base_delay=0.01, max_delay=0.2,
                                  retry_on=(OSError,),
                                  give_up_on=(StopIteration,),
                                  name='feed_read')

    def _worker(self):
        try:
            skipped = 0
            while skipped < self._skip:
                if self._stop.is_set():
                    return
                try:
                    self._read_next()
                except StopIteration:
                    self._put(('done', None, None))
                    return
                skipped += 1
            if skipped and _obs.enabled():
                _obs.metrics.counter('prefetch.skipped_steps').inc(skipped)
            buf = []
            while True:
                if self._stop.is_set():
                    return
                try:
                    f = self._read_next()
                except StopIteration:
                    break
                buf.append(f)
                if len(buf) == self._steps:
                    if _faults.any_active():
                        _faults.maybe_sleep('prefetch_stall')
                    payload, span = self._pack(buf)
                    if not self._put(('batch', payload, span)):
                        return
                    buf = []
            if buf:
                payload, span = self._pack(buf)
                if not self._put(('batch', payload, span)):
                    return
            self._put(('done', None, None))
        except BaseException as e:  # noqa: BLE001 - relayed to consumer
            self._put(('error', e, None))

    def __iter__(self):
        while True:
            if self._terminal is not None:
                # exhausted/errored/closed: iterating again yields nothing
                # instead of blocking on a queue no worker will ever fill
                return
            obs_on = _obs.enabled()
            starved = obs_on and self._q.empty()
            if obs_on:
                self._consumer_waiting = True
                t0 = time.perf_counter()
            kind, payload, pack_span = self._q.get()
            if obs_on:
                self._consumer_waiting = False
                _obs.metrics.gauge('prefetch.queue_depth').set(
                    self._q.qsize())
                if starved:
                    wait_t1 = time.perf_counter()
                    wait = wait_t1 - t0
                    # split the empty-queue wait: time spent with an
                    # upload IN FLIGHT (the pack span overlapped the wait)
                    # is transfer latency, not reader starvation — the two
                    # need different fixes (bigger capacity / async upload
                    # vs a faster reader)
                    overlap = 0.0
                    if pack_span is not None:
                        overlap = max(0.0, min(wait_t1, pack_span[1]) -
                                      max(t0, pack_span[0]))
                        if overlap <= 1e-4:
                            overlap = 0.0
                    if overlap > 0.0:
                        _obs.metrics.counter('prefetch.upload_waits').inc()
                        _obs.metrics.counter(
                            'prefetch.upload_wait_s').inc(overlap)
                        _obs.tracing.add_span(
                            'prefetch.upload_wait', t0, wait_t1,
                            cat='prefetch')
                    starve_s = wait - overlap
                    if overlap == 0.0 or starve_s > 1e-4:
                        # the training loop wanted the next superbatch and
                        # the queue was empty: the reader is the bottleneck
                        _obs.metrics.counter(
                            'prefetch.starvation_count').inc()
                        _obs.metrics.counter(
                            'prefetch.starvation_s').inc(starve_s)
                        _obs.tracing.add_span(
                            'prefetch.starved', t0, wait_t1,
                            cat='prefetch')
            if kind == 'done':
                self._terminal = ('done',)
                return
            if kind == 'error':
                self._terminal = ('error', payload)
                raise payload
            self._superbatches_out += 1
            self._steps_out += payload[1]
            yield payload

    def cursor(self):
        """Absolute position in the feed stream — save it in checkpoint
        ``extra_meta`` and pass ``skip_steps=cursor()['steps']`` to the
        resumed prefetcher to fast-forward past consumed batches."""
        return {'steps': self._skip + self._steps_out,
                'superbatches': self._superbatches_out,
                'skipped': self._skip}

    def close(self):
        """Stop the worker and release the queue (safe to call twice)."""
        if self._terminal is None:
            self._terminal = ('closed',)
        self._stop.set()
        while True:  # unblock a worker parked on a full queue
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)


class DataFeeder(object):
    def __init__(self, feed_list, place=None, program=None):
        self.feed_dtypes = []
        self.feed_names = []
        self.feed_shapes = []
        self.feed_lod_level = []
        if program is None:
            program = default_main_program()
        for each_var in feed_list:
            if isinstance(each_var, str):
                each_var = program.global_block().var(each_var)
            if not isinstance(each_var, Variable):
                raise TypeError('feed_list should hold Variables')
            self.feed_dtypes.append(each_var.dtype)
            self.feed_names.append(each_var.name)
            self.feed_lod_level.append(each_var.lod_level)
            shape = each_var.shape
            # strip batch (and time, for lod vars) dims
            if each_var.lod_level > 0:
                shape = shape[2:]
            else:
                shape = shape[1:]
            self.feed_shapes.append(shape)
        self.place = place

    def feed(self, iterable):
        rows = list(iterable)
        feed = {}
        for i, name in enumerate(self.feed_names):
            dtype = convert_dtype(self.feed_dtypes[i])
            shape = self.feed_shapes[i]
            col = [row[i] for row in rows]
            if self.feed_lod_level[i] > 0:
                seqs = [np.asarray(c, dtype=dtype) for c in col]
                seqs = [s.reshape(len(s), *shape) if shape else
                        s.reshape(len(s), 1) for s in
                        (s.reshape(-1) if s.ndim == 1 else s for s in seqs)]
                feed[name] = create_lod_tensor([s for s in seqs])
            else:
                tshape = tuple(int(abs(d)) for d in shape)
                # each element reshapes to the slot shape INDIVIDUALLY
                # (reference DataToLoDTensorConverter semantics): rows
                # may arrive flat (mnist 784) or already shaped
                elems = [np.asarray(c, dtype=dtype).reshape(tshape)
                         for c in col]
                feed[name] = (np.stack(elems) if elems else
                              np.zeros((0,) + tshape, dtype))
        return feed

    def feed_parallel(self, iterable, num_places=None):
        # one merged batch; sharding over devices happens inside pjit
        merged = []
        for batch in iterable:
            merged.extend(batch)
        return self.feed(merged)

    def decorate_reader(self, reader, multi_devices=False, num_places=None,
                        drop_last=True):
        def _reader():
            for batch in reader():
                yield self.feed(batch)
        return _reader


def default_sample_index(step, row, batch_size):
    """Default (step, batch row) -> reader sample index mapping: a
    single-pass sequential reader emitting fixed-size batches.  Epoch
    loops or shuffled readers must supply their own ``index_of`` so
    quarantined indices stay stable across passes."""
    return int(step) * int(batch_size) + int(row)


class SampleQuarantine(object):
    """Persistent set of condemned reader sample indices.

    When forensics (train/forensics.py) names the batch rows that
    poisoned a step, `add` records their reader indices here and
    `apply` keeps them out of every future feed by replacing each
    quarantined row with the nearest healthy row of the same batch —
    shapes stay fixed, so no retrace, and a reference run with the same
    quarantine pre-seeded builds bitwise-identical feeds.  The set rides
    checkpoint META (`state`/`restore`, train/checkpoint.py) so a
    resumed run never re-trips on a sample it already condemned; an
    optional ``path`` additionally persists it as a standalone JSON file
    for inspection and cross-job sharing.
    """

    def __init__(self, path=None, index_of=None):
        self._set = set()
        self.path = path
        self.index_of = index_of or default_sample_index
        if path and os.path.exists(path):
            self._load()

    def __len__(self):
        return len(self._set)

    def __contains__(self, idx):
        return int(idx) in self._set

    def state(self):
        """JSON-able snapshot (sorted sample indices)."""
        return sorted(self._set)

    def restore(self, state):
        """Merge a snapshot back in — union, never shrink: an index
        condemned after the snapshot was taken stays condemned."""
        self._set.update(int(i) for i in (state or ()))
        if _obs.enabled():
            _obs.metrics.gauge('feed.quarantine_size').set(len(self._set))

    def add(self, indices, reason='forensics'):
        """Quarantine reader indices; counts only the NEW ones into
        ``feed.quarantined`` and persists when a path is set."""
        fresh = [int(i) for i in indices if int(i) not in self._set]
        if not fresh:
            return 0
        self._set.update(fresh)
        if _obs.enabled():
            _obs.metrics.counter('feed.quarantined').inc(len(fresh))
            _obs.metrics.gauge('feed.quarantine_size').set(len(self._set))
        _flight.record('feed.quarantine', indices=fresh, reason=reason,
                       total=len(self._set))
        if self.path:
            self._persist()
        return len(fresh)

    def _load(self):
        import json

        def read():
            with open(self.path) as f:
                return json.load(f)
        try:
            data = retry_with_backoff(read, retry_on=(OSError,),
                                      give_up_on=(FileNotFoundError,),
                                      name='quarantine_read')
        except (FileNotFoundError, ValueError):
            return
        self.restore(data.get('indices', ()))

    def _persist(self):
        import json
        payload = json.dumps({'indices': self.state()})

        def write():
            tmp = self.path + '.tmp'
            with open(tmp, 'w') as f:
                f.write(payload)
            os.replace(tmp, self.path)
        retry_with_backoff(write, retry_on=(OSError,),
                           name='quarantine_write')

    # ---------------------------------------------------------- feed-time
    def _clean_rows(self, step, batch):
        """(quarantined rows, replacement row per quarantined row) for one
        step's batch.  Each bad row maps to the NEAREST healthy row
        (preferring earlier), deterministically."""
        bad = [r for r in range(batch)
               if self.index_of(step, r, batch) in self._set]
        if not bad or len(bad) == batch:
            # nothing to do — or nothing healthy left to substitute
            # (the whole batch is condemned; the caller's skip-batch
            # rung handles it)
            if bad and _obs.enabled():
                _obs.metrics.counter('feed.quarantine_saturated').inc()
            return ([], {}) if len(bad) != batch else (bad, {})
        bad_set = set(bad)
        repl = {}
        for r in bad:
            for d in range(1, batch):
                for cand in (r - d, r + d):
                    if 0 <= cand < batch and cand not in bad_set:
                        repl[r] = cand
                        break
                if r in repl:
                    break
        return bad, repl

    def apply(self, feed, step0, steps=1):
        """Return (feed', replaced_count) with quarantined rows replaced.

        Handles the three launch feed forms the executor accepts: one
        per-step dict (batch axis 0), a stacked superbatch dict (step
        axis 0, batch axis 1), or a list of per-step dicts.  Every array
        of the batch's leading size is substituted — labels included —
        so the replacement row is a fully-consistent duplicate sample."""
        if not self._set:
            return feed, 0
        if isinstance(feed, (list, tuple)):
            out = []
            n = 0
            for i, f in enumerate(feed):
                f2, k = self.apply(f, int(step0) + i, 1)
                out.append(f2)
                n += k
            return (list(out) if isinstance(feed, list) else tuple(out)), n
        arrays = {k: np.asarray(v) for k, v in feed.items()}
        if not arrays:
            return feed, 0
        stacked = int(steps) > 1
        dims = [a.shape[1] if stacked else a.shape[0]
                for a in arrays.values()
                if a.ndim >= (2 if stacked else 1)]
        if not dims or len(set(dims)) != 1:
            return feed, 0   # no consistent batch axis to substitute on
        batch = dims[0]
        replaced = 0
        out = dict(feed)
        steps_n = int(steps) if stacked else 1
        for si in range(steps_n):
            step = int(step0) + si
            bad, repl = self._clean_rows(step, batch)
            if not repl:
                continue
            for k, a in arrays.items():
                if a.ndim < (2 if stacked else 1):
                    continue
                b = np.array(np.asarray(out[k]), copy=True)
                for r, src in repl.items():
                    if stacked:
                        b[si, r] = b[si, src]
                    else:
                        b[r] = b[src]
                out[k] = b
            replaced += len(repl)
        if replaced and _obs.enabled():
            _obs.metrics.counter('feed.quarantined_rows').inc(replaced)
        return out, replaced

    def wrap(self, feeds, start_step=0):
        """Wrap a per-step feed iterable: each yielded dict has its
        quarantined rows replaced (step ids count up from start_step).
        Compose under a FeedPrefetcher so quarantine applies before
        superbatch packing."""
        def gen():
            for i, f in enumerate(feeds):
                yield self.apply(f, int(start_step) + i, 1)[0]
        return gen()
