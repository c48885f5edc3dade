"""Pipeline parallelism over the ``pipe`` mesh axis — 1F1B composed
with ZeRO and tensor parallelism.

Reference: ``apex/transformer/pipeline_parallel`` (Megatron's
1F1B schedule, SURVEY.md §2.6).  The *schedule engine* lives in
:mod:`apex_tpu.transformer.pipeline_parallel.schedules`
(:func:`~apex_tpu.transformer.pipeline_parallel.schedules.
spmd_pipeline_1f1b`: the hand-written one-forward-one-backward tick
table with O(p) live microbatch activations, activations moved between
neighbor stages by the double-buffered ``lax.ppermute`` rings of
``pipeline_parallel.p2p``).  This module is the **composition layer**
that turns the engine into a train *step* on a multi-axis mesh:

- **dp × pipe (+ ZeRO)** — one ``jax.shard_map`` manual over
  ``{data, pipe}`` runs the 1F1B schedule per data replica and the
  ZeRO-1/2 reduce-scatter → shard-local update → all-gather
  choreography (:meth:`~apex_tpu.core.train_state.
  MixedPrecisionTrainState.apply_gradients`) over the data axis *in
  the same body*.  The optimizer state is **stage-local**:
  :func:`stage_local_zero` re-partitions the masters of the
  stage-stacked parameter leaves into ``(p, n, m)`` — stage ``s``'s
  ZeRO shards over the data replicas of stage ``s`` — so every chip
  holds only ``params/p/n`` worth of master/moment state, placed by
  the same :func:`~apex_tpu.parallel.distributed_optim.
  zero_state_specs` convention (:func:`pipeline_state_specs`) that
  checkpoints restore onto.
- **pipe × tp** — only ``pipe`` (and ``data``) go manual; tensor axes
  stay GSPMD-managed inside the stage body, so the existing
  ColumnParallel/RowParallel annotations compose unchanged (the same
  partial-manual contract the engine's driver uses).

The bubble is a first-class quantity: :func:`bubble_fraction` is the
Megatron work-ratio ``(p - 1) / m`` (each stage idles ``p - 1``
microbatch-slots of the ``m`` it processes), which the
``pipeline_train`` bench leg pins against measurement;
:func:`schedule_ticks` is the engine's exact tick count
``m + 2p - 1``.  See ``docs/pipeline.md``.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from apex_tpu.core.loss_scale import all_finite
from apex_tpu.core.mesh import DATA_AXIS, PIPE_AXIS
from apex_tpu.parallel import ddp as _ddp
from apex_tpu.parallel import distributed_optim as zero_lib
from apex_tpu.transformer.pipeline_parallel.schedules import (
    spmd_pipeline_1f1b,
)

__all__ = [
    "bubble_fraction",
    "schedule_ticks",
    "live_microbatches",
    "stage_split",
    "stage_unsplit",
    "stage_specs",
    "stage_shardings",
    "stage_local_zero",
    "pipeline_state_specs",
    "pipeline_state_shardings",
    "sync_grad_overflow",
    "run_1f1b",
    "wrap_pipeline_step",
]


# ------------------------------------------------------------ bubble math

def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """Megatron's 1F1B pipeline bubble ``(p - 1) / m``.

    Fraction of *useful* work the schedule idles: with ``m``
    microbatches through ``p`` stages, each stage sits out ``p - 1``
    microbatch-slots (warmup + drain) for the ``m`` it computes, so
    ``step_time = ideal_time * (1 + (p - 1) / m)``.  This is the
    quantity ``plan/score.py`` charges a pipe layout and the
    ``pipeline_train`` bench leg pins against measurement.
    """
    p, m = int(num_stages), int(num_microbatches)
    if p < 1 or m < 1:
        raise ValueError(f"need num_stages >= 1 and num_microbatches "
                         f">= 1, got p={p}, m={m}")
    return (p - 1) / m


def schedule_ticks(num_stages: int, num_microbatches: int) -> int:
    """Exact tick count of the :func:`spmd_pipeline_1f1b` schedule:
    ``m + 2p - 1`` (each tick runs one fused forward+backward unit; the
    steady state is one-forward-one-backward)."""
    p, m = int(num_stages), int(num_microbatches)
    if p < 1 or m < 1:
        raise ValueError(f"need num_stages >= 1 and num_microbatches "
                         f">= 1, got p={p}, m={m}")
    return m + 2 * p - 1


def live_microbatches(num_stages: int) -> int:
    """Peak live microbatch *activations* per stage under 1F1B: ``p``
    (a microbatch's backward starts at most ``p`` forwards after its
    own — flat in ``m``, the whole point of the schedule)."""
    p = int(num_stages)
    if p < 1:
        raise ValueError(f"need num_stages >= 1, got {p}")
    return p


# ------------------------------------------------------- stage partitioning

def stage_split(params: Any, num_stages: int) -> Any:
    """Split a layer-stacked param tree into ``num_stages`` stage chunks.

    Every array leaf must carry the stacked-layer leading axis
    ``(L, ...)`` with ``L % num_stages == 0`` (the planner's
    layer-divisibility gate); the result's leaves are
    ``(num_stages, L / num_stages, ...)`` — the stage-stacked layout
    :func:`run_1f1b` consumes under ``P(pipe)``.  0-d leaves
    (replicated scalars) pass through.  ``build_model`` produces this
    layout directly for flax stacks; ``stage_split`` is the raw-pytree
    equivalent.
    """
    p = int(num_stages)
    if p < 1:
        raise ValueError(f"need num_stages >= 1, got {p}")

    def split(leaf):
        leaf = jnp.asarray(leaf)
        if not leaf.ndim:
            return leaf
        if leaf.shape[0] % p:
            raise ValueError(
                f"cannot split {leaf.shape[0]} stacked layers into "
                f"{p} equal stages (leaf shape {leaf.shape}) — the "
                f"stage-balance gate requires num_layers % num_stages "
                f"== 0")
        return leaf.reshape(p, leaf.shape[0] // p, *leaf.shape[1:])

    return jax.tree.map(split, params)


def stage_unsplit(staged: Any) -> Any:
    """Inverse of :func:`stage_split`: merge ``(p, L/p, ...)`` leaves
    back to the flat ``(L, ...)`` layer stack (0-d leaves pass
    through)."""
    def merge(leaf):
        leaf = jnp.asarray(leaf)
        if leaf.ndim < 2:
            return leaf
        return leaf.reshape(leaf.shape[0] * leaf.shape[1],
                            *leaf.shape[2:])

    return jax.tree.map(merge, staged)


def stage_specs(staged: Any, *, axis: str = PIPE_AXIS) -> Any:
    """Per-leaf ``PartitionSpec`` tree for a stage-stacked param tree:
    ``P(axis)`` on the stacked-stage leading dim of every array leaf,
    replicated scalars for 0-d leaves."""
    return jax.tree.map(
        lambda a: PartitionSpec(axis) if jnp.ndim(a) else
        PartitionSpec(), staged)


def stage_shardings(staged: Any, *, mesh=None,
                    axis: str = PIPE_AXIS) -> Any:
    """``NamedSharding`` tree committing a stage-stacked param tree to
    its stage placement (``jax.device_put`` target)."""
    from apex_tpu.core import mesh as mesh_lib

    mesh = mesh or mesh_lib.get_mesh()
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        stage_specs(staged, axis=axis),
        is_leaf=lambda x: isinstance(x, PartitionSpec))


# -------------------------------------------------- stage-local ZeRO state

def _staged_keys(params: Any, staged: Optional[Sequence[str]]
                 ) -> Tuple[str, ...]:
    if not isinstance(params, dict):
        raise ValueError(
            "stage-local ZeRO selects staged leaves by top-level key — "
            f"params must be a dict at the top level, got "
            f"{type(params).__name__}")
    keys = tuple(staged if staged is not None else
                 (k for k in params if k == "stages"))
    missing = [k for k in keys if k not in params]
    if missing or not keys:
        raise ValueError(
            f"staged keys {missing or list(keys)} not found in params "
            f"(top-level keys: {sorted(params)}) — pass staged=(...) "
            f"naming the stage-stacked subtrees")
    return keys


def stage_local_zero(state: Any, *, num_stages: int,
                     staged: Optional[Sequence[str]] = None) -> Any:
    """Re-partition a zero-mode train state's masters for a dp × pipe
    mesh: **stage-local ZeRO**.

    At :meth:`~apex_tpu.core.train_state.MixedPrecisionTrainState.
    create` time the masters are plain ``(n, m)`` ZeRO partitions of
    each *full* leaf over the data axis.  Under pipeline parallelism
    the stage-stacked leaves (top-level ``staged`` keys, default
    ``("stages",)``) live split over ``pipe`` — so their optimizer
    state must shard over *the data replicas within each stage*, not
    across stages.  This rebuilds those masters as ``(p, n, m_stage)``
    (stage ``s``, data-shard ``i`` owns row ``[s, i]``) and re-inits
    the inner optimizer state over the new layout (exact at step 0:
    fresh moments are zeros either way — call this right after
    ``create``, before any update).

    Everything about the step choreography then works *unchanged*:
    under :func:`pipeline_state_specs` the local staged master is
    ``(1, 1, m_stage)``, which broadcasts against the ``(1, m_stage)``
    reduce-scattered stage-local grads in the elementwise update, and
    ``all_gather_params`` reassembles exactly the local stage's
    parameter slice.  Returns the new state.
    """
    z = getattr(state, "zero", None)
    if z is None:
        raise ValueError("stage_local_zero expects a zero-mode "
                         "MixedPrecisionTrainState (created with "
                         "zero=ZeroConfig(...))")
    p = int(num_stages)
    if p < 1:
        raise ValueError(f"need num_stages >= 1, got {p}")
    keys = _staged_keys(state.params, staged)
    n = z.axis_size
    master = dict(state.opt_state.master)
    # reconstruct the full fp32 leaves from the (n, m) masters (NOT
    # from state.params — those are storage-dtype under O2 and would
    # round the masters), then partition per stage
    full = zero_lib.zero_unpartition(
        {k: master[k] for k in keys},
        {k: state.params[k] for k in keys})

    def stage_part(leaf):
        leaf = jnp.asarray(leaf)
        if not leaf.ndim:
            return _ddp._pad_rows(jnp.ravel(leaf), n)
        if leaf.shape[0] != p:
            raise ValueError(
                f"staged leaf has leading dim {leaf.shape[0]}, "
                f"expected the stage-stacked dim {p} (shape "
                f"{leaf.shape}) — run stage_split/build_model first")
        rows = leaf.reshape(p, -1)
        return jax.vmap(lambda r: _ddp._pad_rows(r, n))(rows)

    for k in keys:
        master[k] = jax.tree.map(stage_part, full[k])
    new_opt = zero_lib.ZeroOptState(master=master,
                                    inner=state.tx.init(master))
    return state.replace(opt_state=new_opt)


def pipeline_state_specs(state: Any, *, axis: str = PIPE_AXIS) -> Any:
    """Per-leaf ``PartitionSpec`` tree for a stage-local zero-mode
    train state — the ``shard_map`` in/out specs of the composed
    dp × pipe step AND (via :func:`pipeline_state_shardings`) the
    committed placement / checkpoint-restore target.

    Extends :func:`~apex_tpu.parallel.distributed_optim.
    zero_state_specs` (whose placement convention this reuses — plain
    ``(n, m)`` master/moment leaves stay ``P(data)``): the
    ``(p, n, m)`` stage-local leaves produced by
    :func:`stage_local_zero` get ``P(axis, data)``, and the
    corresponding *param* leaves (stage-stacked, identified by their
    3-D master) get ``P(axis)`` on the stacked-stage dim.
    """
    z = getattr(state, "zero", None)
    if z is None:
        raise ValueError("pipeline_state_specs expects a zero-mode "
                         "MixedPrecisionTrainState — for plain staged "
                         "params use stage_specs")
    base = zero_lib.zero_state_specs(state)

    def opt_spec(leaf):
        # static shape metadata only — placement is decided before
        # any trace, on concrete state leaves
        if leaf.ndim >= 3 and leaf.shape[1] == z.axis_size:
            # stage-local master/moment: (p, n, m_stage)
            return PartitionSpec(axis, z.axis,
                                 *([None] * (leaf.ndim - 2)))
        if leaf.ndim >= 1 and leaf.shape[0] == z.axis_size:
            return PartitionSpec(z.axis, *([None] * (leaf.ndim - 1)))
        return PartitionSpec()

    # a param leaf is stage-stacked iff its master carries the extra
    # stage dim — judged leafwise so no key bookkeeping can drift
    def param_spec(p_leaf, m_leaf):
        del p_leaf
        if m_leaf.ndim >= 3:
            return PartitionSpec(axis)
        return PartitionSpec()

    return base.replace(
        params=jax.tree.map(param_spec, state.params,
                            state.opt_state.master),
        opt_state=jax.tree.map(opt_spec, state.opt_state))


def pipeline_state_shardings(state: Any, *, mesh=None,
                             axis: str = PIPE_AXIS) -> Any:
    """``NamedSharding`` tree for :func:`pipeline_state_specs` —
    ``jax.device_put`` target after :func:`stage_local_zero`, and the
    :class:`~apex_tpu.resilience.ResilientCheckpointer` restore target
    (orbax restores onto the target's shardings, so a resumed run
    lands back on the stage shards)."""
    from apex_tpu.core import mesh as mesh_lib

    mesh = mesh or mesh_lib.get_mesh()
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        pipeline_state_specs(state, axis=axis),
        is_leaf=lambda x: isinstance(x, PartitionSpec))


# --------------------------------------------------------- the train step

def sync_grad_overflow(grads: Any, axis: str = PIPE_AXIS) -> Any:
    """Make the loss-scale step-or-skip decision pipe-global.

    The ZeRO ``apply_gradients`` syncs overflow over the *data* axis
    (``pmin``), but a non-finite gradient born in one stage's backward
    is invisible to the other stages — they would step while the
    poisoned stage skips, desynchronizing the pipeline.  This poisons
    every rank's grads with NaN whenever ANY pipe rank saw a
    non-finite value, so the dynamic-loss-scale backoff fires on all
    stages together.  No-op (plus one scalar ``pmin``) when all grads
    are finite.
    """
    finite = lax.pmin(all_finite(grads).astype(jnp.int32), axis)
    poison = jnp.where(finite > 0, jnp.float32(0), jnp.float32(jnp.nan))
    return jax.tree.map(
        lambda g: g + poison.astype(g.dtype)
        if jnp.issubdtype(jnp.asarray(g).dtype, jnp.floating) else g,
        grads)


def run_1f1b(
    stage_fn: Any,
    loss_fn: Any,
    stage_params: Any,
    microbatches: jnp.ndarray,
    *,
    axis: str = PIPE_AXIS,
    skip_dead_ticks: Optional[bool] = None,
    loss_params: Any = None,
    return_input_cotangents: bool = False,
):
    """The 1F1B schedule + its cross-rank reductions, for use *inside*
    a multi-axis ``shard_map`` body (the composed dp × pipe step).

    The engine driver
    (:func:`~apex_tpu.transformer.pipeline_parallel.schedules.
    forward_backward_pipelining_without_interleaving`) owns its own
    ``shard_map`` over ``{pipe}`` — it cannot host the ZeRO
    choreography, which needs the data axis manual in the *same* body.
    ``run_1f1b`` is the driver's inner half: call it where both axes
    are already manual, with this rank's ``stage_params`` (local
    stage-stacked leaves, leading dim 1) and the *replicated*
    ``(M, mb, ...)`` microbatch stack.  Returns ``(loss, grads)`` —
    loss replicated over ``axis`` (mean over microbatches), grads
    matching ``stage_params`` — plus the ``aux`` dict when
    ``loss_params`` / ``return_input_cotangents`` close the
    embedding/head gradients (``loss_params_grads`` summed over ranks;
    ``input_cotangents`` ``(M, mb, ...)`` replicated).
    """
    m = microbatches.shape[0]
    out = spmd_pipeline_1f1b(
        stage_fn, loss_fn, stage_params, microbatches, axis=axis,
        skip_dead_ticks=skip_dead_ticks, loss_params=loss_params,
        return_input_cotangents=return_input_cotangents)
    loss_local, grads_local = out[0], out[1]
    # loss_local is the per-microbatch sum on rank p-1, 0 elsewhere
    loss = lax.psum(loss_local, axis) / m
    params_local = jax.tree.map(
        lambda a: a[0] if a.ndim else a, stage_params)
    # restore the stripped stacked-stage axis (ndim leaves carried the
    # split stage dim; 0-d leaves were replicated scalars whose grad
    # is the sum of every stage's contribution)
    grads = jax.tree.map(
        lambda g, a: g[None] if a.ndim else lax.psum(g, axis),
        grads_local, params_local)
    if loss_params is None and not return_input_cotangents:
        return loss, grads
    extras = out[2]
    aux = {}
    if loss_params is not None:
        # fired on the last rank only; psum = the sum
        aux["loss_params_grads"] = jax.tree.map(
            lambda g: lax.psum(g, axis), extras["loss_params_grads"])
    if return_input_cotangents:
        # live on rank 0; masked psum = broadcast over the ring
        cts = extras["input_cotangents"]
        aux["input_cotangents"] = lax.psum(
            jnp.where(lax.axis_index(axis) == 0, cts,
                      jnp.zeros_like(cts)), axis)
    return loss, grads, aux


def _plain_state_specs(state: Any, num_stages: int,
                       axis: str = PIPE_AXIS) -> Any:
    """Spec tree for a staged NON-zero train state: stage-stacked
    leaves (leading dim == ``num_stages`` — params AND the optimizer
    moments initialized from them) go ``P(axis)``; everything else
    (step counters, loss-scale scalars) replicates.  The zero-mode
    equivalent with exact master bookkeeping is
    :func:`pipeline_state_specs`."""
    p = int(num_stages)

    def spec(leaf):
        # static shape metadata only — placement is decided before
        # any trace, on concrete state leaves
        if leaf.ndim and leaf.shape[0] == p:
            return PartitionSpec(axis)
        return PartitionSpec()

    return jax.tree.map(spec, state)


def wrap_pipeline_step(
    body: Any,
    *,
    state: Any,
    mesh,
    batch_specs: Sequence[Any],
    extra_out_specs: Sequence[Any] = (PartitionSpec(),),
    axis: str = PIPE_AXIS,
    data_axis: str = DATA_AXIS,
    donate: bool = True,
):
    """Wrap a pipeline train-step body into the jitted dp × pipe
    ``shard_map`` executable.

    ``body(state, *batch) -> (new_state, *extras)`` runs with **both**
    ``data_axis`` and ``axis`` manual (each present in ``mesh``) and
    the state bound to :func:`pipeline_state_specs` on the way in and
    out — inside it, call :func:`run_1f1b` for the schedule,
    :func:`sync_grad_overflow` on the assembled grads, then
    ``state.apply_gradients`` (whose ZeRO reduce-scatter/all-gather
    now runs stage-locally over the data axis).  Tensor axes in
    ``mesh`` stay GSPMD-managed, so TP stage bodies compose.
    ``extra_out_specs`` covers the non-state outputs (default: one
    replicated scalar — the loss).  The state buffer is donated
    (rebind it from the step's output, never reread the input).

    The executable is **microbatch-shape keyed**: one trace covers
    warmup, steady state and drain (the 1F1B tick table is a single
    ``lax.scan`` over microbatch-invariant shapes), so a training loop
    holds exactly one trace — the zero-retrace budget the chaos soak
    asserts.

    A plain (non-ZeRO) staged state is accepted too: its
    stage-stacked leaves take the :func:`_plain_state_specs`
    placement (the body must then mean the grads over ``data_axis``
    itself — there is no reduce-scatter to do it).
    """
    specs = (pipeline_state_specs(state, axis=axis)
             if getattr(state, "zero", None) is not None
             else _plain_state_specs(state, mesh.shape[axis], axis))
    # pipe (and data, for the ZeRO collectives) go manual; tensor axes
    # remain GSPMD-managed so TP layers compose.  Size-1 axes (the
    # planner's emitted mesh carries every library axis, degenerate
    # ones at 1) count as manual too — nothing is sharded over them,
    # and folding them in lets the common dp × pipe(×1×1) case take
    # the full-manual spelling below.  When the manual set covers the
    # whole mesh, omit the partial-manual axis_names subset entirely.
    manual = frozenset(
        a for a in mesh.axis_names
        if a in (data_axis, axis) or mesh.shape[a] == 1)
    kw = {} if manual == set(mesh.axis_names) else {"axis_names": manual}
    return jax.jit(
        jax.shard_map(
            body, mesh=mesh,
            in_specs=(specs,) + tuple(batch_specs),
            out_specs=(specs,) + tuple(extra_out_specs),
            check_vma=False, **kw),
        donate_argnums=(0,) if donate else ())
