"""Minimal data-parallel training — the reference's
``examples/simple/distributed/distributed_data_parallel.py``.

The reference launches one process per GPU and wraps the model in
``apex.parallel.DistributedDataParallel``; gradients all-reduce during
backward.  TPU-native: one process, a ``Mesh`` over all devices, batch
sharded on the ``data`` axis — jit inserts the gradient ``psum``.

``--zero {0,1,2}`` (ISSUE 11) swaps the replicated optimizer for the
ZeRO-sharded one (``apex_tpu.parallel.distributed_optim``): fp32
masters and Adam/SGD moments shard over the ``data`` axis instead of
being hand-replicated on every device, gradients reduce-scatter
(stage 2; stage 1 all-reduces then slices), and the updated params
all-gather in the compute dtype.  ``--zero-int8`` additionally puts
the grad sync on the int8 quantized wire.  The state placement comes
from ``zero_shardings`` — which is also the checkpoint-restore
target, so ``--ckpt-dir`` resume lands the shards exactly where a
fresh run puts them.

The loop runs under ``apex_tpu.resilience.ResilientLoop`` — with
``--ckpt-dir`` it survives kill -TERM (final checkpoint + clean exit)
and auto-resumes on relaunch; without, the wrapper is a near-free
pass-through (the ``resilience_overhead`` bench leg quantifies it).

``--plan auto`` (ISSUE 15) stops hand-picking the layout entirely:
the ZeRO stage and wire dtype come from ``apex_tpu.plan()`` over a
parameter-count profile of the net (data-parallel only — the planner
knows nothing about an arbitrary flax module's insides).  An explicit
``--zero`` still wins.

``--plan auto --layers N`` (ISSUE 20) swaps the net for a stacked
residual-MLP ``N`` layers deep so the planner can also enumerate
**pipeline** degrees; ``--hbm-gb`` sets the per-chip feasibility
budget.  Tighten it until every dp/ZeRO layout busts and the winner
is a ``dp × pipe`` layout, which this path adopts end-to-end:
``stage_split`` by the planned degree → stage-local ZeRO → the
plan's own ``state_shardings`` placement →
``parallel.pipeline.wrap_pipeline_step`` running 1F1B over the
planned mesh with ``plan.microbatches`` microbatches per step.

  python examples/simple/distributed.py [--zero 2] [--ckpt-dir /tmp/d]
  python examples/simple/distributed.py --plan auto
  python examples/simple/distributed.py --plan auto --layers 8 \\
      --hbm-gb 0.001   # tiny budget: only pipelined layouts fit
"""

from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import amp, initialize_mesh
from apex_tpu.optim import fused_sgd
from apex_tpu.parallel import ZeroConfig, zero_shardings, zero_state_specs
from apex_tpu.resilience import ResilientCheckpointer, ResilientLoop


class Net(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Dense(128)(x))
        return nn.Dense(1)(x)


def _drive(args, state, train_step, data, mesh):
    """The shared resilient training loop: both the DP/ZeRO path and
    the planned-pipeline path end here."""
    def loop_step(state, batch):
        state, loss = train_step(state, *batch)
        return state, {"loss": loss}

    def show(step, row):
        if step % 10 == 0 or step == args.steps:
            print(f"step {step:3d}  loss {row['loss']:.5f}")

    from apex_tpu.utils import MetricsWriter
    loop = ResilientLoop(
        loop_step,
        checkpointer=(ResilientCheckpointer(args.ckpt_dir, keep=2)
                      if args.ckpt_dir else None),
        checkpoint_every=20,
        scalars_of=lambda aux: {"loss": aux["loss"]},
        metrics=MetricsWriter(sink=show))
    with mesh:
        state, report = loop.run(state, lambda s: data, args.steps)
    print(f"steps_run {report.steps_run}  "
          f"resumed_from {report.resumed_from}  "
          f"preempted {report.preempted}")


def _run_planned_stack(args, ndev):
    """``--plan auto --layers N``: let the planner pick dp × pipe ×
    ZeRO for a stacked residual-MLP, then adopt whatever it emits —
    the same recipe works for a pure-dp winner (``pipe == 1``
    degenerates cleanly) and a pipelined one."""
    import dataclasses

    import apex_tpu
    from apex_tpu.parallel import pipeline as pl
    from apex_tpu.plan import DEFAULT_HW

    hid = 64
    r = np.random.default_rng(0)
    stacked = (
        jnp.asarray(r.normal(size=(args.layers, hid, hid)) * 0.3,
                    jnp.float32),
        jnp.asarray(r.normal(size=(args.layers, hid)) * 0.1,
                    jnp.float32),
        jnp.asarray(r.normal(size=(args.layers, hid, hid)) * 0.3,
                    jnp.float32),
    )
    n_params = sum(x.size for x in jax.tree.leaves(stacked))
    hw = (dataclasses.replace(DEFAULT_HW,
                              hbm_bytes=args.hbm_gb * 2**30)
          if args.hbm_gb else None)
    planned = apex_tpu.plan(
        apex_tpu.plan.generic_profile(n_params, dtype_bytes=4,
                                      num_layers=args.layers),
        devices=ndev, objective="train", hw=hw,
        microbatches=args.microbatches)
    lay = planned.layout
    print(f"plan: auto -> {lay.describe()} "
          f"({planned.score['value']:.0f} samples/s/chip modeled, "
          f"{len(planned.alternatives)} alternatives scored)")
    pipe, m = max(lay.pipe, 1), max(planned.microbatches, 1)
    if pipe > 1:
        print(f"pipeline: {pipe} stages (layers "
              f"{planned.stage_assignment}), {m} microbatches/step, "
              f"modeled bubble "
              f"{planned.score.get('bubble_fraction', 0.0):.3f}")
    else:
        print("planned layout is not pipelined — tighten --hbm-gb "
              "to make the dp/ZeRO layouts infeasible")

    # adopt: stage partition -> (stage-local) ZeRO -> planned placement
    staged = {"stages": pl.stage_split(stacked, pipe)}
    state = amp.initialize(None, staged,
                           fused_sgd(0.05, momentum=0.9),
                           opt_level="O0", zero=planned.zero)
    if planned.zero is not None:
        state = pl.stage_local_zero(state, num_stages=pipe)
    state = jax.device_put(state, planned.state_shardings(state))

    def layer_apply(x, wb):
        w1, b1, w2 = wb
        h = jnp.tanh(x @ w1 + b1)
        return x + h @ w2, None

    def stage_fn(stage_params, x):
        x, _ = jax.lax.scan(layer_apply, x, stage_params)
        return x

    def body(state, x, y):
        def loss_fn(out, i):
            yl = jax.lax.dynamic_index_in_dim(y, i, 0, keepdims=False)
            # loss reduction anchored in fp32, like every loss here
            d = (out - yl).astype(jnp.float32)
            return jnp.mean(d * d)

        loss, grads = pl.run_1f1b(stage_fn, loss_fn,
                                  state.params["stages"], x)
        grads = pl.sync_grad_overflow({"stages": grads})
        if planned.zero is None:
            # no ZeRO reduce-scatter to sync the replicas — mean the
            # grads over data here
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(g, "data"), grads)
        new_state, _ = state.apply_gradients(grads=grads)
        return new_state, jax.lax.pmean(loss, "data")

    train_step = pl.wrap_pipeline_step(
        body, state=state, mesh=planned.mesh,
        batch_specs=(planned.data_spec, planned.data_spec))

    mb = 8
    A = jnp.asarray(r.normal(size=(hid, hid)) * 0.5, jnp.float32)
    X = jnp.asarray(r.normal(size=(lay.dp * m, mb, hid)), jnp.float32)
    Y = jnp.tanh(X @ A)
    sharding = NamedSharding(planned.mesh, planned.data_spec)
    X, Y = jax.device_put(X, sharding), jax.device_put(Y, sharding)
    _drive(args, state, train_step, (X, Y), planned.mesh)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", default=None,
                    help="rolling checkpoints + auto-resume here")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--zero", type=int, default=None,
                    choices=(0, 1, 2),
                    help="ZeRO stage: 0 = replicated optimizer state, "
                         "1 = sharded state + all-reduce grads, "
                         "2 = sharded state + reduce-scatter grads "
                         "(unset + --plan auto = planner's choice)")
    ap.add_argument("--zero-int8", action="store_true",
                    help="int8 quantized wire for the ZeRO grad sync")
    ap.add_argument("--plan", choices=("auto",), default=None,
                    help="auto = route the ZeRO/wire layout choice "
                         "through apex_tpu.plan() (explicit --zero "
                         "still wins)")
    ap.add_argument("--layers", type=int, default=0,
                    help="with --plan auto: use a stacked residual-MLP "
                         "this many layers deep so the planner can "
                         "also enumerate pipeline degrees (ISSUE 20)")
    ap.add_argument("--hbm-gb", type=float, default=None,
                    help="per-chip HBM feasibility budget in GB for "
                         "the planner (tiny fractions are fine for "
                         "the CPU demo — tighten until only pipelined "
                         "layouts fit)")
    ap.add_argument("--microbatches", type=int, default=8,
                    help="1F1B microbatches per step for planned "
                         "pipeline layouts")
    args = ap.parse_args()
    from apex_tpu.utils import enable_compile_cache

    enable_compile_cache()
    if args.zero_int8 and not args.zero:
        ap.error("--zero-int8 needs --zero 1 or 2 (the int8 wire is "
                 "the ZeRO grad sync's dtype)")
    # multi-host: pick up MASTER_ADDR/RANK/WORLD_SIZE (the reference
    # launcher's env contract) if set; single-host no-op
    from apex_tpu.parallel import init_distributed
    init_distributed()
    ndev = len(jax.devices())
    if args.plan == "auto" and args.layers:
        _run_planned_stack(args, ndev)
        return
    mesh = initialize_mesh(data_parallel_size=-1)
    print(f"mesh: {ndev} device(s) on the 'data' axis")

    net = Net()
    params = net.init(jax.random.PRNGKey(0), jnp.zeros((1, 16)))["params"]
    zero = None
    if args.plan == "auto" and args.zero is None:
        # route the layout choice through the planner (ISSUE 15): a
        # parameter-count profile is all an arbitrary flax net can
        # offer, so the decision space is dp × ZeRO stage × wire — the
        # emitted ZeroConfig is committed exactly like a hand-set one
        import apex_tpu

        n_params = sum(x.size for x in jax.tree.leaves(params))
        planned = apex_tpu.plan(
            apex_tpu.plan.generic_profile(n_params), devices=ndev,
            objective="train")
        zero = planned.zero
        print(f"plan: auto -> {planned.layout.describe()} "
              f"({planned.score['value']:.0f} samples/s/chip modeled, "
              f"{len(planned.alternatives)} alternatives scored)")
    elif args.zero:
        zero = ZeroConfig(
            axis="data", stage=args.zero,
            reduce_dtype="int8" if args.zero_int8 else None,
            axis_size=ndev)
    state = amp.initialize(
        lambda p, x: net.apply({"params": p}, x), params,
        fused_sgd(0.05, momentum=0.9), opt_level="O0", zero=zero)

    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(64 * ndev, 16)), jnp.float32)
    Y = jnp.sum(X[:, :4], axis=1, keepdims=True)
    sharding = NamedSharding(mesh, P("data"))
    X, Y = jax.device_put(X, sharding), jax.device_put(Y, sharding)

    if zero is not None:
        # sharded masters + optimizer state, replicated params — the
        # committed placement doubles as the checkpoint-restore target
        state = jax.device_put(state, zero_shardings(state, mesh=mesh))
        shard_bytes = sum(
            int(np.prod(l.sharding.shard_shape(l.shape))) * l.dtype.itemsize
            for l in jax.tree.leaves(state.opt_state))
        wire = ("int8" if zero.reduce_dtype == "int8"
                else "fp32" if zero.reduce_dtype is None
                else str(jnp.dtype(zero.reduce_dtype)))
        print(f"zero: stage {zero.stage} over {ndev}-way 'data' axis, "
              f"reduce_dtype={wire}, "
              f"optimizer-state shard {shard_bytes} B/device "
              f"(~1/{ndev} of replicated)")
        specs = zero_state_specs(state)

        # the step runs fully-manual inside shard_map: per-replica
        # grads go straight to apply_gradients, which owns the ZeRO
        # reduce-scatter / shard-local update / param all-gather
        def zero_step(state, x, y):
            def loss_fn(p):
                pred = state.apply_fn(p, x).astype(jnp.float32)
                return jnp.mean((pred - y) ** 2)
            loss, grads = jax.value_and_grad(loss_fn)(state.params)
            new_state, _ = state.apply_gradients(grads=grads)
            return new_state, jax.lax.pmean(loss, "data")

        train_step = jax.jit(jax.shard_map(
            zero_step, mesh=mesh,
            in_specs=(specs, P("data"), P("data")),
            out_specs=(specs, P()), check_vma=False),
            donate_argnums=(0,))
    else:
        # committed-replicated carry so a checkpoint-restored state
        # (which lands on its target's placement) matches the
        # fresh-run placement
        state = jax.device_put(state, NamedSharding(mesh, P()))

        # donate the threaded state; X/Y are reused across the loop
        @functools.partial(jax.jit, donate_argnums=(0,))
        def train_step(state, x, y):
            def loss_fn(p):
                # loss reduction anchored in fp32 (the convention every
                # model loss here follows): under a half-dtype net the
                # MSE mean would otherwise accumulate in bf16
                pred = state.apply_fn(p, x).astype(jnp.float32)
                return jnp.mean((pred - y) ** 2)
            loss, grads = jax.value_and_grad(loss_fn)(state.params)
            new_state, _ = state.apply_gradients(grads=grads)
            return new_state, loss

    _drive(args, state, train_step, (X, Y), mesh)


if __name__ == "__main__":
    main()
