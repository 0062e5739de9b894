"""setup.prepare_s

What a WARM start pays to reach its executables, net of loading them:
training, executor.prepare_s (lint, rewriter, emitter build, lowering,
parameter gathering, fingerprint, load, trace + compile, store) less
compile_cache.load_s, executor.emit_s + trace_s + backend_compile_s and
compile_cache.store_s; serving, generation.warmup_s less
compile_cache.load_s, generation.compile_s and compile_cache.store_s.
None where the program has no such umbrella counter.
"""
META = {'name': 'setup.prepare_s', 'unit': 's', 'better': 'lower', 'source': 'program_counter',
        'layer': 'rewriter, emitter and compile cache',
        'moves': 'setup_s'}

_TRAIN = ('executor.prepare_s',
          ('compile_cache.load_s', 'executor.emit_s', 'executor.trace_s',
           'executor.backend_compile_s', 'compile_cache.store_s'))
_SERVE = ('generation.warmup_s',
          ('compile_cache.load_s', 'generation.compile_s',
           'compile_cache.store_s'))


def read(ctx):
    c = ctx['setup_counters']
    umbrella, less = _SERVE if 'warmup_s' in ctx else _TRAIN
    if umbrella not in c:
        return None
    return max(0.0, c[umbrella] - sum(c.get(k, 0.0) for k in less))
