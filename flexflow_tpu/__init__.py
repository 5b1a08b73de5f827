"""flexflow_tpu — a TPU-native auto-parallelizing deep-learning framework.

A ground-up JAX/XLA/pallas re-design of the capabilities of FlexFlow (the
Legion-based Unity-era auto-parallelizing DNN framework; reference layer map in
SURVEY.md §1): a model and its parallelization are represented together as a
Parallel Computation Graph (PCG); a search (substitutions + DP + a TPU cost
model) picks the best hybrid strategy over a `jax.sharding.Mesh`; execution is
one SPMD `jit`-compiled train step whose collectives XLA emits over ICI.

Where the reference uses Legion regions + FFMapper + NCCL
(reference: src/runtime/model.cc, src/mapper/mapper.cc), this framework uses
GSPMD: a MachineView becomes an assignment of tensor dims to mesh axes, and the
four parallel ops (Repartition/Combine/Replicate/Reduction) become reshardings.
"""
from time import perf_counter_ns as _now

_T0_NS = _now()     # before anything is imported: telemetry's epoch

import jax as _jax

_T_JAX_NS = _now()

# Sharding-invariant RNG. With the legacy (non-partitionable) threefry,
# jitting a random initializer with SHARDED out_shardings produces
# DIFFERENT values than the replicated init of the same key — so a
# hand-sharded strategy (parallel/templates.py) silently trained different
# weights than its data-parallel twin (the standing hybrid_parallel tier-1
# failure). The partitionable counter-based generator makes random values a
# pure function of (key, position), independent of how XLA partitions the
# computation — the property sharded-at-birth init (compile.py init) and
# the ZeRO/pipeline cross-mesh restores all assume.
_jax.config.update("jax_threefry_partitionable", True)

from flexflow_tpu.dtype import DataType
from flexflow_tpu.config import FFConfig
from flexflow_tpu.core.tensor import Tensor, TensorSpec
from flexflow_tpu.core.model import FFModel
from flexflow_tpu.optimizers import SGDOptimizer, AdamOptimizer
from flexflow_tpu.losses import LossType
from flexflow_tpu.metrics import MetricsType
from flexflow_tpu.ops.op_type import OperatorType

from flexflow_tpu import telemetry as _telemetry

__version__ = "0.1.0"

# set by the launcher (python -m flexflow_tpu script.py [flags]; see
# flexflow_tpu/__main__.py — the flexflow_python/flexflow_top analog)
_launch_config = None


def get_launch_config() -> "FFConfig":
    """The FFConfig the launcher parsed from the command line, or a default
    config when the script runs standalone."""
    return _launch_config if _launch_config is not None else FFConfig()


def __getattr__(name):
    # lazy: the serving subsystem pulls in the whole compiler stack, which
    # plain `import flexflow_tpu` (launcher, tests) shouldn't pay for
    if name == "compile_serving":
        from flexflow_tpu.serving.engine import compile_serving

        return compile_serving
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "DataType",
    "FFConfig",
    "FFModel",
    "Tensor",
    "TensorSpec",
    "SGDOptimizer",
    "AdamOptimizer",
    "LossType",
    "MetricsType",
    "OperatorType",
    "compile_serving",
]

# set-up's first span: what importing JAX and then this package's own
# modules cost (jax.experimental.pallas lies behind ops -> kernels)
_T_END_NS = _now()
_telemetry.record("start/import", 0.0, (_T_END_NS - _T0_NS) / 1e3,
                  cat="start", jax_s=(_T_JAX_NS - _T0_NS) / 1e9,
                  package_s=(_T_END_NS - _T_JAX_NS) / 1e9)
