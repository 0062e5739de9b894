"""setup.before_program_s

Seconds from the start of the process (the kernel's start time of
/proc/self) to the first line of `import paddle_tpu`: the interpreter, the
benchmark's own imports, `import jax` and the TPU runtime coming up in
lib/peaks.require_device.  The machine's part of setup_s, named
(process.before_import_s, a gauge set once: read from the live registry,
because it is in place before the runner's first snapshot).  None where the
program has no such gauge, or /proc cannot be read.
"""
META = {'name': 'setup.before_program_s', 'unit': 's', 'better': 'lower', 'source': 'program_counter',
        'layer': 'rewriter, emitter and compile cache',
        'moves': 'setup_s'}


def read(ctx):
    import paddle_tpu.observability as obs
    return obs.counters().get('process.before_import_s')
