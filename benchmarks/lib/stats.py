"""Arithmetic of the end-to-end metrics.  Pure Python, no JAX."""
import math
import statistics


def median(values):
    values = list(values)
    if not values:
        raise ValueError('median of nothing')
    return float(statistics.median(values))


def quantile(values, q):
    """The q-quantile by linear interpolation between order statistics
    (position q*(n-1); numpy's default).  Infinite entries sort last."""
    xs = sorted(values)
    if not xs:
        raise ValueError('quantile of nothing')
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    if xs[lo] == xs[hi]:
        return float(xs[lo])
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def spread(values):
    """Distance between the quartiles of statistics.quantiles(n=4), as a
    share of the median: the contract's measure of a metric's noise."""
    q1, _, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / statistics.median(values)


def segment_rate(durations_s, items_per_segment):
    """(whole-window rate, median segment rate, stall share).

    A window is cut into segments of equal WORK (`items_per_segment`
    items each); `durations_s` are their wall times, contiguous, so their
    sum is the window's wall time.  The whole-window rate is all the items
    over all that time: the end-to-end rate, which a stall lowers.  The
    median rate is items over the median duration: the pace between
    stalls.  The stall share is what separates the two:
    1 - (time the window would have taken at the median pace) / (time it
    took).  One stalled segment in ten of 1 s, taking 2 s, gives
    1 - 10/11 = 9.09 %.  Segments faster than the median cannot make it
    negative by more than rounding, so it is floored at 0.
    """
    durations = [float(d) for d in durations_s]
    if len(durations) < 2:
        raise ValueError('a window needs at least two segments, got %d'
                         % len(durations))
    if min(durations) <= 0.0:
        raise ValueError('a segment took no time: %r' % (durations,))
    mid = statistics.median(durations)
    wall = sum(durations)
    return (items_per_segment * len(durations) / wall,
            items_per_segment / mid,
            max(0.0, 1.0 - mid * len(durations) / wall))


def tail_with_failures(latencies, worst, q):
    """The q-quantile over ALL requests of a window.

    `latencies` holds one entry per request: a number for a request that
    got its answer, None for one that failed, was refused or never
    answered.  A None counts as `worst` (the caller passes the longest
    time any request of the run was waited for), so failures can only
    raise a tail and never drop out of it.
    """
    if not latencies:
        raise ValueError('no request in the window')
    filled = [worst if x is None else float(x) for x in latencies]
    return quantile(filled, q)


def ttft_ms(requests, drained_at_s):
    """(first-token latency from the DUE time in ms, or None for a request
    that failed or never answered; the worst: the longest any request of
    the run was waited for)."""
    lat = [None if (r['first'] is None or not r['ok'])
           else (r['first'] - r['due']) * 1e3 for r in requests]
    worst = max([x for x in lat if x is not None]
                + [(drained_at_s - r['due']) * 1e3
                   for r, x in zip(requests, lat) if x is None])
    return lat, worst
