"""The benchmark's own host spans around its calls into the program.

`span(name)` times a block on the host clock, adds the time to a
per-name total, and, while a profiler trace is being taken, writes a
`bench:<name>` annotation into the trace so that idle gaps on the chip can
be named after what the host was doing.
"""
import contextlib
import time


class Spans(object):
    def __init__(self):
        self.seconds = {}
        self.counts = {}
        self._annotate = None

    def annotate(self, on):
        """Turn trace annotations on (during the traced window) or off."""
        if on:
            from jax.profiler import TraceAnnotation
            self._annotate = TraceAnnotation
        else:
            self._annotate = None

    @contextlib.contextmanager
    def span(self, name):
        ann = self._annotate('bench:' + name) if self._annotate else None
        if ann is not None:
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            self.seconds[name] = self.seconds.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1

    def reset(self):
        self.seconds.clear()
        self.counts.clear()
