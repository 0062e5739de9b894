"""scheduler.host_gap_share

Share of the scheduler thread's time spent in rounds but in neither
runtime call: admission, sweep, emitting tokens, page bookkeeping: the chip's
idle time that the scheduler's own host work causes.  (generation.round_s -
prefill_s - window_s) over the thread's whole time, round_s + idle_wait_s:
the counters run from the warm-up's end to the drain's, not just the
generator's window, so they are divided by their own clock.
"""
from lib.program import ratio

META = {'name': 'scheduler.host_gap_share', 'unit': '%', 'better': 'lower', 'source': 'program_counter',
        'layer': 'scheduler (continuous batching)',
        'moves': 'tpot_p50_ms'}


def read(ctx):
    c = ctx['counters']
    rounds = c.get('generation.round_s', 0.0)
    own = rounds - c.get('generation.prefill_s', 0.0) \
        - c.get('generation.window_s', 0.0)
    return ratio(100.0 * own, rounds + c.get('generation.idle_wait_s', 0.0))
