"""apex_tpu — a TPU-native training-acceleration toolbox.

A brand-new JAX/XLA/Pallas implementation of the capabilities of NVIDIA
Apex (reference: ``hanjlu13/apex``, a fork of github.com/NVIDIA/apex):

- ``apex_tpu.amp`` — explicit, functional mixed precision (opt levels
  O0–O3) with dynamic loss scaling.  Replaces ``apex.amp``'s
  monkey-patching with a ``PrecisionPolicy`` applied to pytrees.
- ``apex_tpu.optim`` — fused optimizers (FusedAdam, FusedLAMB, FusedSGD,
  FusedNovoGrad, FusedAdagrad, LARC) as single-jit pytree updates,
  replacing the ``amp_C`` multi-tensor CUDA kernels.
- ``apex_tpu.ops`` — Pallas/XLA kernels: fused layer norm / RMSNorm,
  scaled masked softmax, RoPE, fused attention, memory-saving cross
  entropy — replacing ``csrc/``.
- ``apex_tpu.parallel`` — data parallelism and SyncBatchNorm over a
  device mesh (ICI collectives instead of NCCL).
- ``apex_tpu.transformer`` — tensor / sequence / pipeline / context
  parallelism on a named ``jax.sharding.Mesh`` (Megatron-style port of
  ``apex.transformer``).
- ``apex_tpu.plan`` — AMP-style auto-parallelism planner (beyond the
  reference): ``apex_tpu.plan(model_cfg, devices)`` enumerates
  data/tensor/context/ZeRO/serving layouts, scores them on one unified
  compute/HBM/ICI cost model, and emits the winning mesh +
  PartitionSpecs.

Reference citations in docstrings use upstream NVIDIA Apex repo-relative
paths (e.g. ``apex/amp/frontend.py``); see SURVEY.md for the layer map.
"""

__version__ = "0.1.0"

from apex_tpu.core.precision import PrecisionPolicy
from apex_tpu.core.loss_scale import (
    LossScaleState,
    DynamicLossScale,
    StaticLossScale,
    NoOpLossScale,
    all_finite,
)
from apex_tpu.core.mesh import (
    initialize_mesh,
    MeshConfig,
    get_mesh,
    destroy_mesh,
)

from apex_tpu import amp
from apex_tpu import core
from apex_tpu import data
from apex_tpu import fp16_utils
from apex_tpu import native
from apex_tpu import models
from apex_tpu import ops
from apex_tpu import optim
from apex_tpu import parallel
from apex_tpu import plan
from apex_tpu import transformer
from apex_tpu import contrib
from apex_tpu import resilience
from apex_tpu import serving
from apex_tpu import utils

__all__ = [
    "PrecisionPolicy",
    "LossScaleState",
    "DynamicLossScale",
    "StaticLossScale",
    "NoOpLossScale",
    "all_finite",
    "initialize_mesh",
    "MeshConfig",
    "get_mesh",
    "destroy_mesh",
    "amp",
    "core",
    "data",
    "fp16_utils",
    "native",
    "ops",
    "optim",
    "parallel",
    "plan",
    "transformer",
    "contrib",
    "resilience",
    "serving",
    "utils",
]
