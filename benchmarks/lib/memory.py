"""Device memory at its fullest INSTANT, on the fullest chip.

On this runtime an executable's scratch is a reservation, not an
allocation: while ResNet-50's step runs, the allocator reads 1.8 GB in
use and 9.95 GB reserved where XLA planned 10.01 GB of temporaries (my
chip run, PR 23; offline compile, PR 21).  So what a chip holds at one
moment is `bytes_in_use + bytes_reserved` read together.  The two PEAKS
the allocator keeps need not coincide (weights are copied in set-up, the
scratch is held in the window), so they are never added: the peak is the
largest sum seen at one of the sampled instants, and never less than the
allocator's own peak of live buffers.
"""


class PeakSampler(object):
    def __init__(self, devices):
        self.devices = list(devices)
        self.peak = 0
        self.samples = 0

    def sample(self):
        """Read every chip once, while work is in flight on it."""
        for d in self.devices:
            stats = d.memory_stats() or {}
            self.peak = max(self.peak, int(stats.get('bytes_in_use', 0))
                            + int(stats.get('bytes_reserved', 0)))
        self.samples += 1

    def live_peak(self):
        return max(int((d.memory_stats() or {}).get('peak_bytes_in_use', 0))
                   for d in self.devices)

    def result(self):
        return max(self.peak, self.live_peak())
