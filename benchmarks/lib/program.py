"""Readers of the program's own counters (`ctx['counters']`: the window's
deltas of `paddle_tpu.observability` counters) share one rule: a ratio whose
denominator did not move is no reading, and the metric is left out.  That
is also what a program without the counter gives (the parent of the PR that
added it)."""


def ratio(numerator, denominator):
    return numerator / denominator if denominator else None
