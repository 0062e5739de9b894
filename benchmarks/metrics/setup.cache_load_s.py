"""setup.cache_load_s

Seconds of set-up spent loading executables from the disk cache:
compile_cache.load_s (training); the host clock around DecodeRuntime.warmup
when nothing compiled (serving).
"""
META = {'name': 'setup.cache_load_s', 'unit': 's', 'better': 'lower', 'source': 'program_counter',
        'layer': 'rewriter, emitter and compile cache',
        'moves': 'setup_s'}


def read(ctx):
    c = ctx['setup_counters']
    if 'warmup_s' in ctx:
        return 0.0 if c.get('generation.compiles', 0) else ctx['warmup_s']
    return c.get('compile_cache.load_s', 0.0)
