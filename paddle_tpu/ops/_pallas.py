"""The two rules every Pallas call site shares.

Interpret mode is what the CPU backend gets (tests, rehearsals), and only
the CPU backend: on an accelerator a kernel compiles through Mosaic or the
launch raises.  Nothing else — no argument, no environment variable —
selects it.  And a kernel is eligible only on a single device.
"""
import jax


def interpret():
    return jax.default_backend() == 'cpu'


def single_device(mesh):
    """A Mosaic kernel is a one-device program.  Under a mesh of several
    devices GSPMD refuses it when the step lowers ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a shard_map."),
    so such launches take the composed XLA path by this static rule: no
    kernel carries its own shard_map (ROADMAP R5: the serving kernels
    under a mesh)."""
    return mesh is None or mesh.size == 1
