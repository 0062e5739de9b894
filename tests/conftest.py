"""Test config: force CPU backend with 8 virtual devices BEFORE jax import,
so multi-chip sharding paths are exercised without TPU hardware."""
import os

os.environ['JAX_PLATFORMS'] = 'cpu'  # tests never take the chip
# cold caches by default: trace-count and retrace-explainer assertions
# depend on every signature actually compiling; warm-start tests opt back
# in with PT_CACHE=1 and JAX_COMPILATION_CACHE_DIR=<tmp_path> (see
# tests/test_compile_cache.py)
os.environ.setdefault('PT_CACHE', '0')
flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8').strip()

import jax  # noqa: E402

# pytest plugins (jaxtyping) import jax before this conftest runs, so the
# env var alone is too late — force the config directly.
jax.config.update('jax_platforms', 'cpu')

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs + scope + name generator."""
    import paddle_tpu as fluid  # noqa: F401 - warm the package once
    from paddle_tpu.core import framework, unique_name
    from paddle_tpu.core import executor as executor_mod
    main, startup = framework.Program(), framework.Program()
    old_main = framework.switch_main_program(main)
    old_startup = framework.switch_startup_program(startup)
    old_gen = unique_name.switch()
    old_scope = executor_mod._global_scope
    executor_mod._global_scope = executor_mod.Scope()
    yield
    framework.switch_main_program(old_main)
    framework.switch_startup_program(old_startup)
    unique_name.switch(old_gen)
    executor_mod._global_scope = old_scope


@pytest.fixture(scope='session')
def one_v5e_chip():
    """A described, not attached, v5e chip: XLA:TPU and Mosaic compile for
    it here and raise what the chip's compiler would.  Asked for by name,
    by the few tests that read the compiler's text
    (test_paged_attention.py, test_generation_layout.py): the topology is
    described only once one of them runs, never at import."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - no libtpu here: nothing to test
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return SingleDeviceSharding(topo.devices[0])
