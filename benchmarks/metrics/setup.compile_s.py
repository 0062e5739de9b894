"""setup.compile_s

Seconds of set-up spent compiling in this process: executor.emit_s + trace_s
+ backend_compile_s (training); the host clock around DecodeRuntime.warmup
when generation.compiles moved (serving). 0 when every executable came from
the cache.
"""
META = {'name': 'setup.compile_s', 'unit': 's', 'better': 'lower', 'source': 'program_counter',
        'layer': 'rewriter, emitter and compile cache',
        'moves': 'setup_s'}


_TRAIN = ('executor.emit_s', 'executor.trace_s', 'executor.backend_compile_s')


def read(ctx):
    c = ctx['setup_counters']
    if 'warmup_s' in ctx:
        return ctx['warmup_s'] if c.get('generation.compiles', 0) else 0.0
    return sum(c.get(k, 0.0) for k in _TRAIN)
