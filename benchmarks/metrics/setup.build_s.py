"""setup.build_s

Seconds of set-up spent building what is launched: training, the Program
(program.build_s: layers, append_backward and minimize inside the outermost
program_guard); serving, the runtime (generation.init_s: weights adopted,
pool and recurrent state allocated).  None where the program has no such
counter.
"""
META = {'name': 'setup.build_s', 'unit': 's', 'better': 'lower', 'source': 'program_counter',
        'layer': 'rewriter, emitter and compile cache',
        'moves': 'setup_s'}


def read(ctx):
    c = ctx['setup_counters']
    return c.get('generation.init_s' if 'warmup_s' in ctx
                 else 'program.build_s')
