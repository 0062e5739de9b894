"""The benchmark's own tests: CPU, tiny sizes, no chip and no topology.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q
"""
import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
os.environ.setdefault('XLA_FLAGS', '--xla_force_host_platform_device_count=4')
os.environ.setdefault('PT_CACHE', '0')

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
