"""Materialize a scored layout as concrete placement objects.

The winner of the enumeration/scoring pass becomes a :class:`Plan`:
one ``jax.sharding.Mesh`` (train) or per-replica device slices +
engine kwargs (serve), plus the PartitionSpec surfaces every layer of
the stack already consumes:

- **train**: the batch spec (``P("data")``, sequence additionally on
  ``context`` when the layout uses it), the model's GSPMD layer
  annotations (flax ``get_partition_spec`` over an abstract init — the
  same specs the TP=8 bench leg places with), and a
  :class:`~apex_tpu.parallel.distributed_optim.ZeroConfig` whose state
  placement comes from the *existing* ``zero_shardings`` /
  ``zero_state_specs`` machinery (``Plan.state_shardings`` /
  ``Plan.state_specs`` delegate to it — the planner emits the layout,
  the library owns the choreography);
- **serve**: the ``replicas × tp`` split as device slices +
  ``InferenceServer`` kwargs (tp, and the autotuned
  ``block_size``/``kv_dtype`` adoption), with the sharded pool
  placement delegated to
  :func:`apex_tpu.serving.cache.paged_pool_shardings`.

Nothing here sets the library-global mesh (``set_current=False``
throughout): a plan is a value the caller commits, not ambient state.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec

from apex_tpu.core import mesh as mesh_lib
from apex_tpu.core.mesh import CONTEXT_AXIS, DATA_AXIS, TENSOR_AXIS
from apex_tpu.plan.enumerate import Layout, ModelProfile, profile_of

__all__ = ["Plan", "emit_plan", "model_param_specs"]


def model_param_specs(model_cfg: Any) -> Optional[Any]:
    """The model's GSPMD layer annotations as a PartitionSpec pytree —
    flax ``get_partition_spec`` over an abstract ``init`` (no arrays
    materialized), exactly how the ``gpt2_tp8_full_step`` bench leg
    derives its placement.  Transformer-family configs only; returns
    None for models without partitioning annotations (ResNet, generic
    profiles — their params replicate) and for bare
    :class:`~apex_tpu.plan.enumerate.ModelProfile` inputs (a profile
    carries geometry, not a flax module to trace)."""
    if isinstance(model_cfg, ModelProfile):
        return None
    if not (hasattr(model_cfg, "num_heads")
            and hasattr(model_cfg, "vocab_size")):
        return None
    import jax.numpy as jnp
    import flax.linen as nn

    from apex_tpu.models import BertConfig, BertModel, GPTModel

    model = (BertModel(model_cfg) if isinstance(model_cfg, BertConfig)
             else GPTModel(model_cfg))
    ids = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    return nn.get_partition_spec(shapes)


@dataclasses.dataclass
class Plan:
    """A committed parallelism decision — what ``apex_tpu.plan()``
    returns.

    ``score`` is the winner's scorecard
    (:func:`~apex_tpu.plan.score.score_layout` dict);
    ``alternatives`` every other feasible layout's, best-first — the
    A/B the decision was made on is inspectable, not vibes.
    """

    objective: str
    layout: Layout
    profile: ModelProfile
    mesh: Any                               # jax.sharding.Mesh (train)
    score: Dict[str, Any]
    alternatives: List[Dict[str, Any]]
    devices: List[Any]
    zero: Any = None                        # ZeroConfig | None
    param_specs: Any = None                 # GSPMD annotations | None
    data_spec: PartitionSpec = PartitionSpec()
    # pipeline (layout.pipe > 1): the per-step 1F1B microbatch count
    # the layout was scored with, and the contiguous layer range
    # [start, stop) each stage owns — what the caller stage_split()s
    # the layer stack by
    microbatches: int = 0
    stage_assignment: Optional[List[Tuple[int, int]]] = None
    # serving split
    replicas: int = 1
    tp: int = 1
    engine_kwargs: Dict[str, Any] = dataclasses.field(
        default_factory=dict)
    replica_devices: List[List[Any]] = dataclasses.field(
        default_factory=list)

    def describe(self) -> str:
        """One-line human summary (the examples print it)."""
        return (f"{self.objective} {self.layout.describe()} on "
                f"{len(self.devices)} device(s): "
                f"{self.score['value']:.1f} {self.score['unit']} "
                f"modeled ({self.score['bound']}-bound)")

    # -------------------------------------------------- train surfaces

    def state_specs(self, state: Any) -> Any:
        """``shard_map`` in/out PartitionSpecs for a train state built
        with this plan's ``zero`` config — the existing
        :func:`~apex_tpu.parallel.distributed_optim.zero_state_specs`
        (replicated leaves when the plan is not ZeRO-sharded).  A
        pipelined zero plan (``layout.pipe > 1``) expects the state to
        have gone through :func:`~apex_tpu.parallel.pipeline.
        stage_local_zero` and delegates to
        :func:`~apex_tpu.parallel.pipeline.pipeline_state_specs`
        (stage-stacked leaves on the pipe axis, masters/moments
        stage-local over the data axis)."""
        from apex_tpu.parallel import (
            pipeline_state_specs,
            zero_state_specs,
        )

        if self.zero is not None:
            if self.layout.pipe > 1:
                return pipeline_state_specs(state)
            return zero_state_specs(state)
        if self.layout.pipe > 1:
            # plain (non-ZeRO) pipelined state: stage-stacked leaves
            # — params and the moments initialized from them — on the
            # pipe axis, scalars replicated
            from apex_tpu.parallel.pipeline import _plain_state_specs

            return _plain_state_specs(state, self.layout.pipe)
        return jax.tree.map(lambda _: PartitionSpec(), state)

    def state_shardings(self, state: Any) -> Any:
        """Committed ``NamedSharding`` placement for the train state —
        :func:`~apex_tpu.parallel.distributed_optim.zero_shardings`
        over this plan's mesh for a zero state
        (:func:`~apex_tpu.parallel.pipeline.pipeline_state_shardings`
        when the plan pipelines), replicated otherwise.  Doubles as
        the checkpoint-restore target, exactly like the hand-written
        ``--zero`` example path."""
        from apex_tpu.parallel import (
            pipeline_state_shardings,
            zero_shardings,
        )

        if self.zero is not None:
            if self.layout.pipe > 1:
                return pipeline_state_shardings(state, mesh=self.mesh)
            return zero_shardings(state, mesh=self.mesh)
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            self.state_specs(state),
            is_leaf=lambda x: isinstance(x, PartitionSpec))

    # -------------------------------------------------- serve surfaces

    def replica_meshes(self) -> List[Any]:
        """One tensor-parallel mesh per replica over its device slice
        (:func:`apex_tpu.serving.engine.tp_mesh` — never the
        library-global mesh).  Empty when ``tp == 1`` (single-chip
        replicas need no mesh)."""
        if self.tp <= 1:
            return []
        from apex_tpu.serving import tp_mesh

        return [tp_mesh(self.tp, devs) for devs in self.replica_devices]

    def pool_shardings(self, cache: Any, mesh: Any) -> Any:
        """Sharded paged-pool placement for one replica's cache tree —
        delegates to :func:`apex_tpu.serving.cache.
        paged_pool_shardings` (pool/scale leaves on kv_heads over the
        tensor axis, tables replicated)."""
        from apex_tpu.serving.cache import paged_pool_shardings

        return paged_pool_shardings(cache, mesh, TENSOR_AXIS)


def _zero_config(layout: Layout):
    from apex_tpu.parallel import ZeroConfig

    if layout.objective != "train" or not layout.zero_stage:
        return None
    import jax.numpy as jnp

    wire = {None: None, "bf16": jnp.bfloat16, "int8": "int8"}[
        layout.reduce_dtype]
    return ZeroConfig(axis=DATA_AXIS, stage=layout.zero_stage,
                      reduce_dtype=wire, axis_size=layout.dp)


def emit_plan(model_cfg: Any, layout: Layout,
              devices: Sequence[Any], score: Dict[str, Any],
              alternatives: List[Dict[str, Any]], *,
              microbatches: Optional[int] = None) -> Plan:
    """Build the :class:`Plan` for a chosen layout (the last stage of
    ``apex_tpu.plan()``; callable directly to materialize a hand-picked
    :class:`~apex_tpu.plan.enumerate.Layout`).  ``microbatches``
    records the 1F1B count a pipelined layout was scored with
    (defaults to the score's own; pipelined layouts also get a
    ``stage_assignment`` — the contiguous layer range per stage)."""
    profile = profile_of(model_cfg)
    devices = list(devices)
    if layout.chips != len(devices):
        raise ValueError(
            f"layout {layout.describe()} spans {layout.chips} chips "
            f"but {len(devices)} device(s) were given")
    if layout.objective == "serve":
        tp = layout.tp
        slices = [devices[i * tp:(i + 1) * tp]
                  for i in range(layout.dp)]
        tuned = score.get("autotune") or {}
        kwargs: Dict[str, Any] = {}
        if tuned.get("autotuned"):
            kwargs["block_size"] = tuned["block_size"]
            kwargs["kv_dtype"] = tuned["kv_dtype"]
        if tp > 1:
            kwargs["tp"] = tp
        return Plan(objective="serve", layout=layout, profile=profile,
                    mesh=None, score=score, alternatives=alternatives,
                    devices=devices, replicas=layout.dp, tp=tp,
                    engine_kwargs=kwargs, replica_devices=slices)
    mesh = mesh_lib.initialize_mesh(
        tensor_model_parallel_size=layout.tp,
        pipeline_model_parallel_size=layout.pipe,
        context_parallel_size=layout.cp,
        data_parallel_size=layout.dp,
        devices=devices, set_current=False)
    specs = (model_param_specs(model_cfg)
             if profile.kind == "transformer" else None)
    data_spec = (PartitionSpec(DATA_AXIS, CONTEXT_AXIS)
                 if layout.cp > 1 else PartitionSpec(DATA_AXIS))
    mb = microbatches if microbatches is not None else \
        int(score.get("microbatches", 0))
    assignment = None
    if layout.pipe > 1:
        # contiguous balanced split — the same carve stage_split()
        # applies to a stacked layer tree (the enumeration gate
        # guarantees divisibility)
        per = profile.num_layers // layout.pipe
        assignment = [(s * per, (s + 1) * per)
                      for s in range(layout.pipe)]
    return Plan(objective="train", layout=layout, profile=profile,
                mesh=mesh, score=score, alternatives=alternatives,
                devices=devices, zero=_zero_config(layout),
                param_specs=specs, data_spec=data_spec,
                microbatches=mb, stage_assignment=assignment)
