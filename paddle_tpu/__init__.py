"""paddle_tpu: a TPU-native deep-learning framework with the capabilities
of PaddlePaddle Fluid (reference at /root/reference), built on JAX/XLA/
Pallas. The public surface mirrors `paddle.fluid` so reference programs
port by changing the import; execution is whole-program XLA compilation on
TPU (see core/engine.py) with SPMD data/model parallelism over
jax.sharding meshes (see parallel/).
"""
from __future__ import annotations

import time as _time
_IMPORT_P0 = _time.perf_counter()     # the `import` set-up span's start

from .core.compile_cache import configure_compile_cache as _cfg_cache
_cfg_cache()

# ops must register before any program building
from . import ops as _ops  # noqa: F401

from . import framework
from .framework import (  # noqa: F401
    Program, Block, Operator, Variable, Parameter,
    default_main_program, default_startup_program, program_guard,
    unique_name, name_scope, in_dygraph_mode,
)
from . import layers  # noqa: F401
from . import initializer  # noqa: F401
from . import regularizer  # noqa: F401
from . import clip  # noqa: F401
from . import optimizer  # noqa: F401
from . import backward  # noqa: F401
from .backward import append_backward, gradients  # noqa: F401
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401
from .executor import Executor, global_scope, scope_guard  # noqa: F401
from .core.place import (  # noqa: F401
    CPUPlace, TPUPlace, CUDAPlace, CUDAPinnedPlace,
    is_compiled_with_tpu, default_place,
    cpu_places, cuda_places, tpu_places, cuda_pinned_places,
)
from .core.scope import (  # noqa: F401
    Scope, LoDTensor, create_lod_tensor,
)
from .core.scope import TensorArray as LoDTensorArray  # noqa: F401
from .core import scope as core  # compatibility alias module-ish
from .compiler import (  # noqa: F401
    CompiledProgram, BuildStrategy, ExecutionStrategy,
)
from .parallel_executor import ParallelExecutor  # noqa: F401
from . import recordio_writer  # noqa: F401
from . import unique_name  # noqa: F401
from . import io  # noqa: F401
from . import metrics  # noqa: F401
from . import profiler  # noqa: F401
from . import reader  # noqa: F401
from .reader.decorators import DataFeeder, DataFeedDesc  # noqa: F401
from . import dygraph  # noqa: F401
from . import parallel  # noqa: F401
from . import contrib  # noqa: F401
from . import transpiler  # noqa: F401
from .transpiler import (  # noqa: F401
    DistributeTranspiler, DistributeTranspilerConfig, HashName,
    RoundRobin, memory_optimize, release_memory,
)
from . import communicator  # noqa: F401
from . import incubate  # noqa: F401
from . import inference  # noqa: F401
from . import nets  # noqa: F401
from . import dataset  # noqa: F401
from . import average  # noqa: F401
from . import evaluator  # noqa: F401
from . import lod_tensor  # noqa: F401
from .lod_tensor import create_random_int_lodtensor  # noqa: F401
from . import net_drawer  # noqa: F401
from . import install_check  # noqa: F401
from . import dygraph_grad_clip  # noqa: F401
from .core.flags import get_flags, set_flags  # noqa: F401
from .core.enforce import EnforceNotMet, enforce  # noqa: F401

# fluid-compatible helpers
def is_compiled_with_cuda():
    """Reference-compat: reports accelerator availability (TPU here)."""
    return is_compiled_with_tpu()


__version__ = "0.1.0"

# what a job pays for this file, first line to last, as a set-up span
# (observability/tracing.py; docs/TRACING.md "Set-up")
from .observability import tracing as _tracing  # noqa: E402
with _tracing.setup_span("import", p0=_IMPORT_P0):
    pass
