"""memory_peak_bytes is the fullest INSTANT, never a sum of two peaks."""
import pytest

from lib import memory

GB = 10 ** 9


class _Chip(object):
    """An allocator whose readings follow a script, one per call."""

    def __init__(self, readings, peak_in_use):
        self.readings = list(readings)
        self.peak_in_use = peak_in_use

    def memory_stats(self):
        in_use, reserved = self.readings[0]
        if len(self.readings) > 1:
            self.readings.pop(0)
        return {'bytes_in_use': in_use, 'bytes_reserved': reserved,
                'peak_bytes_in_use': self.peak_in_use,
                'peak_bytes_reserved': max(r for _, r in self.readings)}


@pytest.mark.parametrize('readings, peak_in_use, want', [
    # weights copied in set-up (3 GB live), scratch held in the window
    # (10 GB beside 1 GB live): 11 GB, not 3 + 10
    ([(1 * GB, 10 * GB), (1 * GB, 0)], 3 * GB, 11 * GB),
    # no sample caught the scratch: the allocator's live peak stands
    ([(1 * GB, 0), (1 * GB, 0)], 3 * GB, 3 * GB),
    # the fullest of several instants
    ([(2 * GB, 1 * GB), (2 * GB, 5 * GB), (4 * GB, 1 * GB)], 4 * GB, 7 * GB),
])
def test_the_peak_is_the_fullest_sampled_instant(readings, peak_in_use, want):
    sampler = memory.PeakSampler([_Chip(readings, peak_in_use)])
    for _ in readings:
        sampler.sample()
    assert sampler.samples == len(readings)
    assert sampler.result() == want


def test_the_fullest_chip_counts_and_a_host_without_statistics_reads_zero():
    class _Cpu(object):
        def memory_stats(self):
            return None
    chips = [_Chip([(1 * GB, 1 * GB)], 1 * GB), _Chip([(1 * GB, 4 * GB)], GB)]
    sampler = memory.PeakSampler(chips)
    sampler.sample()
    assert sampler.result() == 5 * GB
    idle = memory.PeakSampler([_Cpu()])
    idle.sample()
    assert idle.result() == 0
