"""AsyncExecutor: train straight from record files through the native C++
data pipeline.

Parity: reference python/paddle/fluid/async_executor.py + the C++
paddle/fluid/framework/async_executor.cc (multi-threaded file-fed training).
TPU-native redesign: the reference runs one CPU trainer thread per file, each
stepping its own program copy; on TPU there is ONE jitted train step, so the
parallelism that matters is host-side — the C++ BatchReader's reader/shuffle/
batch threads overlap file IO with the host-side FeedPrefetcher, which
stacks `steps_per_launch` batches into a superbatch and device_puts it while
the device runs the current launch (Executor.run_steps: K iterations fused
into one lax.scan executable = one dispatch).
"""
import numpy as np

from .core.executor import Executor
from .core.framework import default_main_program
from .data_feeder import FeedPrefetcher
from .native import BatchReader, DataFeedDesc

__all__ = ['AsyncExecutor']


class AsyncExecutor(object):
    def __init__(self, place=None, run_mode=''):
        self._exe = Executor(place)

    def run(self, program, data_feed, filelist, thread_num=1,
            fetch=None, mode='', debug=False, fetch_every_n_steps=1,
            steps_per_launch=1):
        """Run `program` once over every batch the data feed yields.

        data_feed: a native.DataFeedDesc (slot names map batch fields to
        feed vars) or a ready BatchReader whose field order matches
        `feed_order` slots.  thread_num tunes the native prefetch depth
        AND the superbatch queue bound.  steps_per_launch=K fuses K
        iterations into one device launch.
        Returns the list of fetch results from the last step.
        """
        program = program or default_main_program()
        if isinstance(data_feed, DataFeedDesc):
            slot_names = [s[0] for s in data_feed.slots]
            reader = BatchReader(
                filelist or data_feed.paths, data_feed.batch_size,
                shuffle_capacity=data_feed.shuffle_capacity,
                seed=data_feed.seed, drop_last=data_feed.drop_last,
                prefetch=max(2, int(thread_num)))
        elif isinstance(data_feed, BatchReader):
            reader = data_feed
            slot_names = getattr(data_feed, 'slot_names', None)
            if slot_names is None:
                raise ValueError('BatchReader needs .slot_names to map '
                                 'fields to feed vars')
        else:
            raise TypeError('data_feed must be DataFeedDesc or BatchReader')

        fetch = fetch or []
        feeds = ({n: np.asarray(v) for n, v in zip(slot_names, fields)}
                 for fields in reader)
        prefetcher = FeedPrefetcher(feeds, steps=max(1, steps_per_launch),
                                    capacity=max(2, int(thread_num)))
        last = None
        step = 0
        try:
            for superbatch, k in prefetcher:
                out = self._exe.run_steps(program, feed_list=superbatch,
                                          steps=k, fetch_list=fetch)
                step += k
                if fetch:
                    # fetches come back stacked [k, ...]; the contract is
                    # the LAST step's values
                    last = [np.asarray(o[-1]) for o in out]
                    if debug and (step - 1) % max(1, fetch_every_n_steps) \
                            < k:
                        print('step %d: %s' %
                              (step - 1,
                               [np.asarray(o).ravel()[:4] for o in last]))
        finally:
            prefetcher.close()
        return last

    def config_distributed_nodes(self, *a, **k):
        raise NotImplementedError(
            'pserver-mode AsyncExecutor is obsoleted; use '
            'parallel.transpiler tpu_collective mode')
