"""flexflow_tpu — a TPU-native distributed DNN training framework.

A from-scratch rebuild of the capabilities of early FlexFlow (the ICML'18
C++/CUDA/Legion system, reference at /root/reference) designed TPU-first:

- an operator-graph model API (``FFModel``) mirroring the reference's
  graph builder (reference: ``include/model.h:197-307``),
- per-operator ``(n, c, h, w)`` parallelization strategies (reference:
  ``include/config.h:39-48``) compiled to a ``jax.sharding.Mesh`` with
  per-op ``PartitionSpec``s — XLA collectives over ICI/DCN replace Legion
  region coherence + GASNet (reference: ``src/mapper/mapper.cc``),
- XLA/pallas kernels in place of cuDNN/cuBLAS leaf tasks
  (reference: ``src/ops/*.cu``),
- SGD with momentum/nesterov/weight-decay matching the reference
  semantics (reference: ``src/runtime/optimizer_kernel.cu:28-41``),
- an offline MCMC strategy search over an event-driven cost simulator
  (reference: ``scripts/simulator.cc``).
"""

from flexflow_tpu.config import FFConfig
from flexflow_tpu.graph import FFModel, TensorSpec
from flexflow_tpu.initializers import (
    GlorotUniform,
    NormInitializer,
    UniformInitializer,
    ZeroInitializer,
)
from flexflow_tpu.optim import SGDOptimizer
from flexflow_tpu.parallel.strategy import ParallelConfig, StrategyStore
from flexflow_tpu.metrics import PerfMetrics

__version__ = "0.1.0"

__all__ = [
    "FFConfig",
    "FFModel",
    "TensorSpec",
    "GlorotUniform",
    "ZeroInitializer",
    "UniformInitializer",
    "NormInitializer",
    "SGDOptimizer",
    "ParallelConfig",
    "StrategyStore",
    "PerfMetrics",
]
