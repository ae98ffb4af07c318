"""paddle_tpu: a TPU-native deep-learning framework with the Fluid
programming model.

Rebuilt from scratch against the behavior of nchuCV/Paddle (PaddlePaddle
Fluid 0.15): same Program/Block/Op graph API, layers, optimizers, readers and
distributed surface — but lowered through JAX to XLA so entire blocks compile
to single fused TPU programs, parallelism is jax.sharding over device meshes,
and ragged sequences are padded+masked (static shapes for the MXU).

Use it like the reference::

    import paddle_tpu as fluid
    img = fluid.layers.data(name="img", shape=[784])
    ...
    exe = fluid.Executor(fluid.TPUPlace())
"""
import time as _time

_import_t0 = _time.perf_counter()  # set-up's account: observability/startup.py

from . import ops as _ops  # registers all op lowering rules  # noqa: F401,E402

from . import core
from . import unique_name
from . import framework
from . import initializer
from . import layers
from . import nets
from . import optimizer
from . import regularizer
from . import clip
from . import backward
from . import io
from . import metrics
from . import average
from . import profiler
from . import lod as lod_tensor_mod
from . import dataset
from . import transpiler
from . import parallel
from . import contrib
from . import debugger
from . import observability
from . import resilience
from . import serving
from . import trainer as trainer_mod
from .trainer import (Trainer, Inferencer, CheckpointConfig, BeginEpochEvent, EndEpochEvent, BeginStepEvent, EndStepEvent, save_checkpoint, load_checkpoint, FailureMonitor)
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig, InferenceTranspiler, memory_optimize, release_memory
from . import reader
from . import recordio_writer
from .reader import batch

from .core import CPUPlace, CUDAPinnedPlace, CUDAPlace, TPUPlace
from .framework import (
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    program_guard,
    name_scope,
)
from .executor import (Executor, LazyFetch, Scope, enable_compilation_cache,
                       global_scope, scope_guard)
from .parallel_executor import ParallelExecutor, ExecutionStrategy, BuildStrategy
from .param_attr import ParamAttr, WeightNormParamAttr
from .data_feeder import DataFeeder
from .lod import LoDArray, LoDTensorArray, create_lod_array, create_lod_tensor, create_random_int_lodtensor
from .evaluator import Evaluator

create_lod_tensor = create_lod_array
LoDTensor = LoDArray

__version__ = "0.1.0"

__all__ = [
    "core",
    "framework",
    "layers",
    "nets",
    "optimizer",
    "initializer",
    "regularizer",
    "clip",
    "backward",
    "io",
    "metrics",
    "average",
    "profiler",
    "unique_name",
    "Program",
    "Variable",
    "default_main_program",
    "default_startup_program",
    "program_guard",
    "name_scope",
    "Executor",
    "ParallelExecutor",
    "ExecutionStrategy",
    "BuildStrategy",
    "Scope",
    "global_scope",
    "scope_guard",
    "CPUPlace",
    "TPUPlace",
    "CUDAPlace",
    "CUDAPinnedPlace",
    "ParamAttr",
    "WeightNormParamAttr",
    "DataFeeder",
    "LoDArray",
    "LoDTensor",
    "LoDTensorArray",
    "create_lod_tensor",
    "create_lod_array",
    "create_random_int_lodtensor",
    "DistributeTranspiler",
    "DistributeTranspilerConfig",
    "InferenceTranspiler",
    "memory_optimize",
    "release_memory",
    "Trainer",
    "Inferencer",
    "CheckpointConfig",
    "FailureMonitor",
    "observability",
    "resilience",
    "serving",
    "recordio_writer",
    "contrib",
    "transpiler",
    "dataset",
    "reader",
    "batch",
    "debugger",
    "trainer",
]

# `import paddle_tpu.fluid as fluid` parity alias
import sys as _sys

fluid = _sys.modules[__name__]
_sys.modules[__name__ + ".fluid"] = fluid

from .observability import startup as _startup  # noqa: E402

_startup.note_import(_import_t0)
