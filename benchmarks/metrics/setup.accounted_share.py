"""setup.accounted_share

How much of setup_s the program can name: (setup.before_program_s +
setup.import_s + setup.build_s + the whole of executor.prepare_s or
generation.warmup_s + setup.first_launches_s) over setup_s.  In training
the last two are executor.run_s over set-up (the cold paths lie inside the
launches that took them); in serving generation.warmup_s +
generation.round_s.  The rest is the runner's own work between those
phases (weights from the seed, the reader, the pool's upload, the waits
for the set-up's launches).  None where the program lacks one of the
counters; a process start that cannot be read counts as 0.
"""
META = {'name': 'setup.accounted_share', 'unit': '%', 'better': 'higher', 'source': 'program_counter',
        'layer': 'rewriter, emitter and compile cache',
        'moves': 'setup_s'}

# training: executor.run_s over set-up holds executor.prepare_s
_TRAIN = ('program.build_s', 'executor.run_s')
_SERVE = ('generation.init_s', 'generation.warmup_s', 'generation.round_s')


def read(ctx):
    import paddle_tpu.observability as obs
    live, c = obs.counters(), ctx['setup_counters']
    phases = _SERVE if 'warmup_s' in ctx else _TRAIN
    if (any(k not in c for k in phases) or 'process.import_s' not in live
            or not ctx.get('setup_s')):
        return None
    return 100.0 * (live.get('process.before_import_s', 0.0)
                    + live['process.import_s']
                    + sum(c[k] for k in phases)) / ctx['setup_s']
