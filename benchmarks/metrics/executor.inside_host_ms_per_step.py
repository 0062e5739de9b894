"""executor.inside_host_ms_per_step

Host time of Executor._run_impl per training step on the executor's own
clock, less what it reports blocked on the device ((executor.run_s -
executor.host_blocked_s) over executor.steps): executor.host_ms_per_step
without the subtraction across two clocks.
"""
from lib.program import ratio

META = {'name': 'executor.inside_host_ms_per_step', 'unit': 'ms', 'better': 'lower', 'source': 'program_counter',
        'layer': 'entry: executor and parallel executor',
        'moves': 'train_rate'}


def read(ctx):
    c = ctx['counters']
    return ratio(1e3 * (c.get('executor.run_s', 0.0)
                        - c.get('executor.host_blocked_s', 0.0)),
                 c.get('executor.steps', 0.0))
