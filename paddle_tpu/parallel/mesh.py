"""Device-mesh management.

The mesh is the TPU-native replacement for the reference's places list
(ParallelExecutor) and trainer endpoints (DistributeTranspiler).  Axes:
  data  — batch sharding (data parallel; gradients all-reduce over ICI)
  model — tensor parallelism (weight sharding)
  pipe  — pipeline stages
  seq   — sequence/context parallelism (ring attention)
"""
import numpy as np
import jax
from jax.sharding import Mesh

__all__ = ['make_mesh', 'default_mesh', 'set_default_mesh', 'shard_map']


def shard_map(f, mesh, in_specs, out_specs):
    """jax.shard_map with replication checking off (we use
    psum-to-replicate)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_default_mesh = [None]


def make_mesh(data=None, model=1, pipe=1, seq=1, devices=None):
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if data is None:
        data = n // (model * pipe * seq)
    assert data * model * pipe * seq == n, (
        'mesh %dx%dx%dx%d != %d devices' % (data, model, pipe, seq, n))
    arr = np.array(devices).reshape(data, seq, pipe, model)
    return Mesh(arr, ('data', 'seq', 'pipe', 'model'))


def default_mesh():
    if _default_mesh[0] is None:
        _default_mesh[0] = make_mesh()
    return _default_mesh[0]


def set_default_mesh(mesh):
    _default_mesh[0] = mesh
