"""setup.first_launches_s

The set-up's launches on the program's own clock, net of the cold path:
training, executor.run_s less executor.prepare_s over set-up (dispatch,
the device loading the program, and the fetch where the executor makes it;
None where the program has no executor.prepare_s: run_s then still holds
the cold path); serving, generation.round_s over set-up (the rounds of the
warm requests).
"""
META = {'name': 'setup.first_launches_s', 'unit': 's', 'better': 'lower', 'source': 'program_counter',
        'layer': 'rewriter, emitter and compile cache',
        'moves': 'setup_s'}


def read(ctx):
    c = ctx['setup_counters']
    if 'warmup_s' in ctx:
        return c.get('generation.round_s')
    if 'executor.prepare_s' not in c or 'executor.run_s' not in c:
        return None
    return max(0.0, c['executor.run_s'] - c['executor.prepare_s'])
