"""setup.import_s

Seconds `import paddle_tpu` took, first line of the package's __init__ to
its last (process.import_s; read from the live registry, because it is in
place before the runner's first snapshot).  None where the program has no
such counter.
"""
META = {'name': 'setup.import_s', 'unit': 's', 'better': 'lower', 'source': 'program_counter',
        'layer': 'rewriter, emitter and compile cache',
        'moves': 'setup_s'}


def read(ctx):
    import paddle_tpu.observability as obs
    return obs.counters().get('process.import_s')
