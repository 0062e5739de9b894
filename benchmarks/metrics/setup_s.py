"""setup_s

Process start to the first measured step or request: imports, weights, warm-
up, loading or compiling executables.
"""
META = {'name': 'setup_s', 'unit': 's', 'better': 'lower', 'source': 'host_clock'}


def read(ctx):
    return ctx['setup_s']
