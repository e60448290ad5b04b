"""flexflow_tpu: a TPU-native auto-parallelizing deep-learning framework.

A ground-up rebuild of the capabilities of FlexFlow/Unity (reference:
napplesty/FlexFlow) for TPUs: layer-level model API, parallel computation
graph with per-dim shard/replica degrees, Unity-style joint search over
graph substitutions and device placements against a calibrated cost
model + simulator, and execution via XLA/pjit/GSPMD with Pallas kernels
and ICI/DCN collectives (no CUDA, no Legion, no NCCL).
"""

from .config import FFConfig, FFIterationConfig
from .core.types import (
    ActiMode,
    AggrMode,
    CompMode,
    DataType,
    LossType,
    MetricsType,
    OpType,
    ParameterSyncOption,
    ParameterSyncType,
    PoolType,
)
from .model import FFModel, Tensor
from .runtime.optimizers import AdamOptimizer, Optimizer, SGDOptimizer

__version__ = "0.1.0"

__all__ = [
    "FFConfig",
    "FFIterationConfig",
    "FFModel",
    "Tensor",
    "ActiMode",
    "AggrMode",
    "CompMode",
    "DataType",
    "LossType",
    "MetricsType",
    "OpType",
    "PoolType",
    "ParameterSyncType",
    "ParameterSyncOption",
    "SGDOptimizer",
    "AdamOptimizer",
    "Optimizer",
    # lazy (see __getattr__): round-3 user-facing additions
    "ElasticTrainer",
    "ParallelDim",
    "ParallelTensorView",
    "initialize_distributed",
]


def __getattr__(name):
    # lazy: these pull in orbax / jax.distributed machinery only when used
    if name == "ElasticTrainer":
        from .runtime.elastic import ElasticTrainer

        return ElasticTrainer
    if name in ("ParallelTensorView", "ParallelDim"):
        from .core import parallel_tensor

        return getattr(parallel_tensor, name)
    if name == "initialize_distributed":
        from .parallel.distributed import initialize_distributed

        return initialize_distributed
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(list(globals()) + __all__))


# ``ff.startup.import``: the process's start to here (the interpreter,
# JAX's import where it came first, this package's)
from .obs.steptrace import GLOBAL_STARTUP as _startup  # noqa: E402

_startup.mark_import()
