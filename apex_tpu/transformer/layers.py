"""Tensor-parallel layers (Megatron-style) — GSPMD modules + shard_map fns.

Reference: ``apex/transformer/tensor_parallel/layers.py`` —
``ColumnParallelLinear`` (shard out-features; optional gather),
``RowParallelLinear`` (shard in-features; all-reduce output),
``VocabParallelEmbedding`` (shard vocab; masked lookup + all-reduce),
with ``sequence_parallel_enabled`` converting the TP all-reduces into
all-gather/reduce-scatter pairs and ``gradient_accumulation_fusion``
fusing the wgrad GEMM.

TPU translation — the central design pivot (SURVEY.md §2.6): topology is
declarative.  Two equivalent forms are provided:

1. **flax modules** (primary): weights carry ``nn.with_partitioning``
   metadata over the ``tensor`` mesh axis; activations get
   ``with_sharding_constraint`` hints.  Under ``jit`` over a mesh, XLA
   inserts exactly the collectives the reference hand-codes (all-gather
   on entry / reduce-scatter on exit under SP), overlapped by the
   compiler's latency-hiding scheduler — the analogue of the
   reference's async grad all-reduce overlap.  ``gradient_
   accumulation_fusion`` needs no port: XLA accumulates wgrads in fp32
   via ``preferred_element_type`` and fuses the accumulate.
2. **shard_map functions**: explicit per-shard math built on
   :mod:`apex_tpu.transformer.mappings` for schedule-controlled code
   (pipeline stages, custom overlap), mirroring how the reference's
   layers call ``copy_to/reduce_from`` internally.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import flax.linen as nn

from apex_tpu.core import mesh as mesh_lib
from apex_tpu.core.mesh import TENSOR_AXIS
from apex_tpu.transformer import mappings

__all__ = [
    "ColumnParallelLinear",
    "RowParallelLinear",
    "VocabParallelEmbedding",
    "column_parallel_linear",
    "row_parallel_linear",
    "vocab_parallel_embedding",
    "maybe_constrain",
    "sharded_param",
]


def _constrain_to_auto_axes(x, abstract, spec):
    """Constrain ``x`` to ``spec`` against the ambient abstract mesh,
    keeping only its Auto (GSPMD-managed) axes: names the mesh lacks or
    holds Manual (shard_map'ed) drop out; nothing left = ``x`` as is."""
    auto = {n for n, t in zip(abstract.axis_names, abstract.axis_types)
            if t == jax.sharding.AxisType.Auto}
    spec = tuple(s if s in auto else None for s in spec)
    if all(s is None for s in spec):
        return x
    return lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


def maybe_constrain(x, *spec):
    """``with_sharding_constraint`` if a mesh is initialized, else noop.

    Lets the same module run on a laptop (no mesh) and a pod slice.
    Axes not present in the ambient mesh — or manual (shard_map'ed,
    e.g. ``pipe`` inside the pipeline schedule) — are dropped from the
    spec, so TP/SP constraints compose with any surrounding topology.
    """
    abstract = jax.sharding.get_abstract_mesh()
    # the abstract-mesh form of the constraint is only legal under a
    # trace; eagerly (e.g. model.init under jax.set_mesh) fall through
    # to the concrete-mesh NamedSharding path below
    if not abstract.empty and isinstance(x, jax.core.Tracer):
        # inside jax.set_mesh / shard_map
        return _constrain_to_auto_axes(x, abstract, spec)
    # eager: prefer the ambient jax.set_mesh mesh (concrete form), then
    # the library-global one.  Under a trace with no ambient abstract
    # mesh (plain jit), jax.sharding.get_mesh() raises — skip straight
    # to the library-global mesh, whose concrete NamedSharding is legal
    # inside jit.
    try:
        mesh = jax.sharding.get_mesh()
        if mesh.empty:
            mesh = None
    except ValueError:
        mesh = None
    if mesh is None:
        try:
            mesh = mesh_lib.get_mesh()
        except RuntimeError:
            return x
    if mesh.size == 1:
        return x
    # drop axes absent from this mesh (e.g. a user mesh with foreign
    # axis names) so the constraint degrades instead of erroring
    names = set(mesh.axis_names)
    spec = tuple(s if s in names else None for s in spec)
    if all(s is None for s in spec):
        return x
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*spec))
    return lax.with_sharding_constraint(x, sharding)


def sharded_param(module, name, init, names, shape, dtype):
    """``module.param`` for a weight partitioned as ``names``.

    The stored leaf keeps flax's ``nn.with_partitioning`` box (so
    ``nn.get_partition_spec`` reads the layout off a state), but the
    in-trace sharding constraint is applied HERE, like
    :func:`maybe_constrain`: mesh axes the ambient mesh does not have,
    or holds Manual, drop out of the spec.  flax's own unboxing
    constrains to the full spec whenever any ambient mesh exists, which
    the installed jax refuses inside a fully-manual ``shard_map`` — the
    data-parallel and ZeRO train steps — for every model in the zoo.
    """
    boxed = module.param(name, nn.with_partitioning(init, names), shape,
                         dtype, unbox=False)
    if not isinstance(boxed, nn.Partitioned):
        return boxed                  # the caller passed plain leaves
    value = boxed.unbox(apply_constraint=False)
    abstract = jax.sharding.get_abstract_mesh()
    if abstract.empty:                # flax's own rule: no ambient mesh
        return value
    return _constrain_to_auto_axes(value, abstract, boxed.names)


# --------------------------------------------------------------------- #
# flax modules (GSPMD form)
# --------------------------------------------------------------------- #
class ColumnParallelLinear(nn.Module):
    """Linear with output features sharded over the ``tensor`` axis.

    ``gather_output=True`` replicates the output (reference default);
    ``False`` leaves it feature-sharded for a following RowParallel.
    ``sequence_parallel`` marks the input as sequence-sharded: XLA then
    materializes the all-gather on entry (reference:
    ``sequence_parallel_enabled``).
    """

    features: int
    use_bias: bool = True
    gather_output: bool = False
    sequence_parallel: bool = False
    axis: str = TENSOR_AXIS
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x):
        dtype = self.dtype or x.dtype
        kernel = sharded_param(
            self, "kernel", self.kernel_init, (None, self.axis),
            (x.shape[-1], self.features), self.param_dtype)
        if self.sequence_parallel:
            # input arrives sequence-sharded over the tensor axis;
            # the matmul needs it whole: constrain to gathered form.
            x = maybe_constrain(x, "data")
        y = jax.lax.dot_general(
            x.astype(dtype), kernel.astype(dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if self.use_bias:
            bias = sharded_param(
                self, "bias", self.bias_init, (self.axis,),
                (self.features,), self.param_dtype)
            y = y + bias.astype(jnp.float32)
        y = y.astype(dtype)
        if self.gather_output:
            y = maybe_constrain(y, "data")
        else:
            y = maybe_constrain(y, "data", *([None] * (x.ndim - 2)),
                                self.axis)
        return y


class RowParallelLinear(nn.Module):
    """Linear with input features sharded over the ``tensor`` axis.

    Output is the all-reduced full tensor (reference semantics); under
    ``sequence_parallel`` the reduce becomes a reduce-scatter along the
    sequence dim (XLA chooses it from the output constraint).
    """

    features: int
    use_bias: bool = True
    sequence_parallel: bool = False
    input_is_parallel: bool = True
    axis: str = TENSOR_AXIS
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x):
        dtype = self.dtype or x.dtype
        kernel = sharded_param(
            self, "kernel", self.kernel_init, (self.axis, None),
            (x.shape[-1], self.features), self.param_dtype)
        if self.input_is_parallel:
            x = maybe_constrain(x, "data", *([None] * (x.ndim - 2)),
                                self.axis)
        y = jax.lax.dot_general(
            x.astype(dtype), kernel.astype(dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if self.use_bias:
            # bias replicated; added after the (implicit) reduce
            bias = self.param("bias", self.bias_init, (self.features,),
                              self.param_dtype)
            y = y + bias.astype(jnp.float32)
        y = y.astype(dtype)
        if self.sequence_parallel:
            # sequence-sharded output → XLA lowers psum to reduce-scatter
            y = maybe_constrain(y, "data", self.axis)
        else:
            y = maybe_constrain(y, "data")
        return y


class VocabParallelEmbedding(nn.Module):
    """Embedding with the vocab dim sharded over the ``tensor`` axis.

    GSPMD form: the table is partitioned ``(tensor, None)``; the lookup
    compiles to the same masked-gather + all-reduce the reference codes
    by hand.
    """

    num_embeddings: int
    features: int
    axis: str = TENSOR_AXIS
    dtype: Optional[jnp.dtype] = None
    param_dtype: jnp.dtype = jnp.float32
    embedding_init: Callable = nn.initializers.normal(stddev=0.02)

    def setup(self):
        self.embedding = sharded_param(
            self, "embedding", self.embedding_init, (self.axis, None),
            (self.num_embeddings, self.features), self.param_dtype)

    def __call__(self, ids):
        dtype = self.dtype or self.param_dtype
        y = jnp.take(jnp.asarray(self.embedding).astype(dtype), ids,
                     axis=0)
        return maybe_constrain(y, "data")

    def attend(self, x):
        """Logits against the (sharded) table — output-embedding tying
        (vocab-sharded logits out, like the reference's parallel LM head).
        """
        table = jnp.asarray(self.embedding)
        y = jax.lax.dot_general(
            x, table.astype(x.dtype),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
        return maybe_constrain(
            y, "data", *([None] * (x.ndim - 2)), self.axis)


# --------------------------------------------------------------------- #
# shard_map functions (explicit form)
# --------------------------------------------------------------------- #
def column_parallel_linear(x, kernel_shard, bias_shard=None, *,
                           sequence_parallel: bool = False,
                           seq_dim: int = 1,
                           axis: str = TENSOR_AXIS):
    """Per-shard column-parallel linear (inside ``shard_map``).

    ``kernel_shard``: (in, out/tp).  Input: replicated, or
    sequence-sharded when ``sequence_parallel``.
    """
    if sequence_parallel:
        x = mappings.gather_from_sequence_parallel_region(
            x, axis, seq_dim)
    else:
        x = mappings.copy_to_tensor_parallel_region(x, axis)
    y = jax.lax.dot_general(
        x, kernel_shard, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)
    if bias_shard is not None:
        y = y + bias_shard.astype(y.dtype)
    return y


def row_parallel_linear(x, kernel_shard, bias=None, *,
                        sequence_parallel: bool = False,
                        seq_dim: int = 1,
                        axis: str = TENSOR_AXIS):
    """Per-shard row-parallel linear (inside ``shard_map``).

    ``kernel_shard``: (in/tp, out); ``x``: feature-sharded.  Output:
    full (all-reduce) or sequence-sharded (reduce-scatter) under SP.
    """
    y = jax.lax.dot_general(
        x, kernel_shard, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)
    if sequence_parallel:
        y = mappings.reduce_scatter_to_sequence_parallel_region(
            y, axis, seq_dim)
    else:
        y = mappings.reduce_from_tensor_parallel_region(y, axis)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def vocab_parallel_embedding(ids, table_shard, *, axis: str = TENSOR_AXIS):
    """Per-shard vocab-parallel lookup (inside ``shard_map``).

    ``table_shard``: (vocab/tp, features).  Masked local lookup +
    all-reduce, exactly the reference's algorithm.
    """
    per = table_shard.shape[0]
    start = lax.axis_index(axis) * per
    in_range = (ids >= start) & (ids < start + per)
    local_ids = jnp.clip(ids - start, 0, per - 1)
    y = jnp.take(table_shard, local_ids, axis=0)
    y = jnp.where(in_range[..., None], y, 0)
    return mappings.reduce_from_tensor_parallel_region(y, axis)
