"""paddle_tpu — a TPU-native deep-learning framework with the capabilities
of PaddlePaddle Fluid (reference: BillXW/Paddle @ /root/reference).

Architecture (TPU-first, NOT a port):
  * declarative Program/Block/Op graph API (`paddle_tpu.layers`) — source
    compatible with fluid model code
  * whole-block lowering to ONE XLA executable per train step
    (core/executor.py), autodiff via jax.vjp (core/backward.py)
  * ragged sequences as padded+lengths (core/lod.py), RNNs as lax.scan
  * data/model parallel via jax.sharding Mesh + GSPMD (parallel/)

Use `import paddle_tpu as fluid` for fluid-style code, or
`import paddle_tpu.paddle_compat as paddle` for `paddle.*` dataset/batch
helpers.
"""
import time as _time
_IMPORT_T0 = _time.perf_counter()

from .core import framework  # noqa: E402
from .core.framework import (  # noqa
    Program, Block, Operator, Variable, Parameter, program_guard,
    default_main_program, default_startup_program, switch_main_program,
    name_scope, CPUPlace, CUDAPlace, TPUPlace, CUDAPinnedPlace, cpu_places,
    cuda_places, tpu_places, is_compiled_with_cuda, get_flags, set_flags)
from .core.executor import Executor, Scope, scope_guard, global_scope  # noqa
from .core.async_runtime import FetchFuture  # noqa
from .core.backward import append_backward, gradients, calc_gradient  # noqa
from .core import unique_name  # noqa
from .core.lod import (LoDTensor, create_lod_tensor,  # noqa
                       create_random_int_lodtensor)
from .core import backward  # noqa
from . import layers  # noqa
from . import nets  # noqa
from . import initializer  # noqa
from .initializer import force_init_on_cpu, init_on_cpu  # noqa
from . import optimizer  # noqa
from . import regularizer  # noqa
from . import clip  # noqa
from .clip import set_gradient_clip  # noqa
from . import metrics  # noqa
from . import io  # noqa
from . import profiler  # noqa
from . import param_attr  # noqa
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa
from .data_feeder import DataFeeder, FeedPrefetcher, FeedBucketer  # noqa
from . import reader  # noqa
from .batch import batch  # noqa
from .io import (save_inference_model, load_inference_model,  # noqa
                 save_params, load_params, save_persistables,
                 load_persistables)
from . import compiler  # noqa
from .compiler import CompiledProgram, BuildStrategy, ExecutionStrategy  # noqa
from .parallel.parallel_executor import ParallelExecutor  # noqa
from . import transpiler  # noqa
from .transpiler import (DistributeTranspiler,  # noqa
                         DistributeTranspilerConfig, memory_optimize,
                         release_memory, InferenceTranspiler)
from . import dataset  # noqa
from . import imperative  # noqa
from . import debugger  # noqa
from . import inference  # noqa
from . import serving  # noqa
from . import train  # noqa
from . import average  # noqa
from . import evaluator  # noqa
from . import contrib  # noqa
from . import trainer  # noqa
from . import inferencer  # noqa
from .trainer import Trainer, BeginEpochEvent, EndEpochEvent, \
    BeginStepEvent, EndStepEvent, CheckpointConfig  # noqa
from .inferencer import Inferencer  # noqa
from . import annotations  # noqa
from . import analysis  # noqa
from . import net_drawer  # noqa
from . import recordio_writer  # noqa
from . import async_executor  # noqa
from .async_executor import AsyncExecutor  # noqa
from .data_feed_desc import DataFeedDesc  # noqa


from .core.framework import recompute_scope  # noqa

# submodule aliases for reference-style imports (`from paddle.fluid
# import executor`, `fluid.lod_tensor.create_lod_tensor(...)`, ...)
from .core import executor  # noqa
from .core import layer_helper  # noqa
from .core import lod as lod_tensor  # noqa
from .parallel import parallel_executor  # noqa


def recompute(fn, *args, **kwargs):
    """jax.checkpoint for raw JAX callables (graph programs use
    `recompute_scope()`); SURVEY §2.1 memory_optimize replacement."""
    import jax
    return jax.checkpoint(fn, *args, **kwargs)


def memory_optimize_hint(*a, **k):
    return None


__version__ = '0.1.0'


def _record_import(t0):
    """Set-up from inside (docs/observability.md): what this import took,
    and what the process had already spent when it began (the
    interpreter, the caller's imports, `import jax`, a TPU runtime coming
    up)."""
    from . import observability as obs
    t1 = _time.perf_counter()
    obs.counter('process.import_s').inc(t1 - t0)
    obs.add_span('process.import', t0, t1, cat='setup')
    age = obs.metrics.process_age_s()
    if age is not None:
        obs.gauge('process.before_import_s').set(max(0.0, age - (t1 - t0)))


_record_import(_IMPORT_T0)
