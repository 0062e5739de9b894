"""JAX implementations of all registered ops.

Replaces reference paddle/fluid/operators/ (~439 CUDA/CPU kernel files).
Each module registers pure-JAX impls with core.registry; gradients come from
jax.vjp (no *_grad kernels needed), fusion comes from XLA.
"""
from . import math  # noqa
from . import tensor  # noqa
from . import nn  # noqa
from . import loss  # noqa
from . import rand  # noqa
from . import optimizer_ops  # noqa
from . import metric  # noqa
from . import sequence  # noqa
from . import detection  # noqa
from . import attention  # noqa
from . import sampling  # noqa
from . import ctc_crf  # noqa
from . import int8  # noqa
from . import fused  # noqa  (fused_elementwise from core/passes/fuse.py)
from . import collective  # noqa  (explicit collectives from core/passes/shard.py)
