"""executor.host_ms_per_step

Host time inside run_steps per training step, less the time the executor
itself reports blocked on the device (executor.host_blocked_s).
"""
META = {'name': 'executor.host_ms_per_step', 'unit': 'ms', 'better': 'lower', 'source': 'host_clock',
        'layer': 'entry: executor and parallel executor',
        'moves': 'train_rate'}


def read(ctx):
    if 'segments' not in ctx:
        return None
    host = ctx['spans'].get('launch', 0.0) \
        - ctx['counters'].get('executor.host_blocked_s', 0.0)
    return 1e3 * host / ctx['launched_steps']
