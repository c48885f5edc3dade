"""Tensor + sequence-parallel GPT training on a mesh.

The ``apex.transformer`` workflow (BASELINE.json configs[3], GPT-2-TP)
rebuilt TPU-native: one jit, weights sharded over the ``tensor`` axis by
their ``nn.with_partitioning`` specs, batch over ``data``, sequence
parallelism as activation sharding — XLA inserts the same collectives
the reference's mappings hand-code (SURVEY.md §3.4).

``--pp N`` adds pipeline parallelism: the transformer body is stacked
into stages with ``build_model`` (reference:
``pipeline_parallel/utils.py``) and pipelined with microbatches over the
``pipe`` axis; embedding/head run outside the pipelined region, as in
Megatron's stage-embedding special-casing.

Runs anywhere:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/transformer_tp.py --tp 2 --dp 4 --steps 5
  ... python examples/transformer_tp.py --tp 2 --pp 2 --dp 2 --steps 5
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import amp, initialize_mesh
from apex_tpu.models import GPTConfig, GPTModel, gpt_loss_fn
from apex_tpu.optim import fused_adam
from apex_tpu.transformer import broadcast_data


def run_pipelined(args):  # graftlint: hot-step
    """tp×pp×dp: transformer body pipelined via build_model stages."""
    import numpy as np

    from apex_tpu.core.mesh import PIPE_AXIS
    from apex_tpu.models import TransformerConfig, ParallelTransformerLayer
    from apex_tpu.transformer.pipeline_parallel import (
        build_model, spmd_pipeline)

    mesh = initialize_mesh(tensor_model_parallel_size=args.tp,
                           pipeline_model_parallel_size=args.pp,
                           data_parallel_size=args.dp)
    m = 2
    if args.batch_size % m or args.batch_size < m:
        raise SystemExit(
            f"--batch-size {args.batch_size} must be a positive "
            f"multiple of the microbatch count ({m}) under --pp")
    seq, mb = args.seq_len, args.batch_size // m
    cfg = TransformerConfig(
        vocab_size=1024, hidden_size=256, num_layers=1, num_heads=2,
        max_seq_len=seq, sequence_parallel=(args.tp > 1), causal=True,
        dtype=jnp.bfloat16)
    layer = ParallelTransformerLayer(cfg)
    x0 = jnp.zeros((mb, seq, cfg.hidden_size), jnp.float32)
    stage_fn, stages, stage_spec = build_model(
        layer, num_layers=args.pp * 2, pipeline_model_parallel_size=args.pp,
        rng=jax.random.PRNGKey(0), sample_input=x0)

    def pipe_forward(p, ids):
        h = jnp.take(p["embed"], ids, axis=0)
        mbs = h.reshape(m, mb, seq, cfg.hidden_size)

        @jax.shard_map(mesh=mesh, in_specs=(P(PIPE_AXIS), P()),
                       out_specs=P(), axis_names={PIPE_AXIS})
        def run(stages_local, mbs_local):
            return spmd_pipeline(stage_fn, stages_local, mbs_local)

        outs = run(p["stages"], mbs).reshape(m * mb, seq, cfg.hidden_size)
        return outs @ p["head"]

    with jax.set_mesh(mesh):
        embed = jax.random.normal(
            jax.random.PRNGKey(1), (cfg.vocab_size, cfg.hidden_size)) * 0.02
        head = jax.random.normal(
            jax.random.PRNGKey(2), (cfg.hidden_size, cfg.vocab_size)) * 0.02
        params = {"embed": embed, "stages": stages, "head": head}
        half = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
        state = amp.initialize(pipe_forward, params, fused_adam(1e-3),
                               opt_level=args.opt_level, half_dtype=half)
        # stage leaves pipe(+tensor)-sharded per build_model's spec
        new_params = dict(state.params)
        new_params["stages"] = jax.tree.map(
            lambda sp, l: jax.device_put(l, NamedSharding(mesh, sp)),
            stage_spec, state.params["stages"],
            is_leaf=lambda v: isinstance(v, P))
        state = state.replace(params=new_params)

        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(
            0, cfg.vocab_size, size=(m * mb, seq + 1)), jnp.int32)
        inputs = jax.device_put(ids[:, :-1], NamedSharding(mesh, P("data")))
        labels = jax.device_put(ids[:, 1:], NamedSharding(mesh, P("data")))

        # the old state is dead once the new one returns — donate it so
        # params/opt-state don't hold two copies of HBM across the step
        # (inputs/labels are reused every step and must NOT be donated)
        @functools.partial(jax.jit, donate_argnums=(0,))
        def train_step(state, inputs, labels):
            def loss_fn(p_):
                logits = pipe_forward(state.policy.cast_to_compute(p_),
                                      inputs)
                loss = gpt_loss_fn(logits.astype(jnp.float32), labels)
                return state.scale_loss(loss), loss

            grads, loss = jax.grad(loss_fn, has_aux=True)(state.params)
            new_state, _ = state.apply_gradients(grads=grads)
            return new_state, loss

        for step in range(args.steps):
            t0 = time.perf_counter()
            state, loss = train_step(state, inputs, labels)
            # stop the clock on device completion, not on the loss
            # readback — float(loss) inside the timed region bills the
            # d2h transfer to the step and stalls the next dispatch
            jax.block_until_ready(loss)
            dt = time.perf_counter() - t0
            # graftlint: unsharded(loss fetched for logging only, after the timed region closes)
            print(f"step {step:3d}  loss {float(loss):.4f}  "
                  f"({dt * 1e3:,.0f} ms)")


def main():  # graftlint: hot-step
    p = argparse.ArgumentParser()
    p.add_argument("--tp", type=int, default=2)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--dp", type=int, default=-1)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--opt-level", default="O2")
    args = p.parse_args()
    from apex_tpu.utils import enable_compile_cache

    enable_compile_cache()

    if args.pp > 1:
        run_pipelined(args)
        return

    mesh = initialize_mesh(tensor_model_parallel_size=args.tp,
                           data_parallel_size=args.dp)
    cfg = GPTConfig.tiny(sequence_parallel=True,
                         max_seq_len=args.seq_len,
                         dtype=jnp.bfloat16)
    model = GPTModel(cfg)

    with mesh:
        tokens = jnp.zeros((args.batch_size, args.seq_len), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        state = amp.initialize(
            lambda p_, ids: model.apply({"params": p_}, ids),
            params, fused_adam(1e-3), opt_level=args.opt_level,
            half_dtype=jnp.bfloat16)

        key = jax.random.PRNGKey(1)
        ids = jax.random.randint(
            key, (args.batch_size, args.seq_len + 1), 0, cfg.vocab_size,
            jnp.int32)
        batch = broadcast_data(
            ["inputs", "labels"],
            {"inputs": ids[:, :-1], "labels": ids[:, 1:]}, jnp.int32)

        # donate the threaded state (batch tensors are reused per step)
        @functools.partial(jax.jit, donate_argnums=(0,))
        def train_step(state, inputs, labels):
            def loss_fn(p_):
                logits = state.apply_fn(p_, inputs)
                loss = gpt_loss_fn(logits, labels)
                return state.scale_loss(loss), loss
            grads, loss = jax.grad(loss_fn, has_aux=True)(
                state.compute_params())
            new_state, finite = state.apply_gradients(grads=grads)
            return new_state, loss

        for step in range(args.steps):
            t0 = time.perf_counter()
            state, loss = train_step(state, batch["inputs"],
                                     batch["labels"])
            # the tok/s figure must time the device work alone: block
            # for completion, then read the loss off the clock
            jax.block_until_ready(loss)
            dt = time.perf_counter() - t0
            tok_s = args.batch_size * args.seq_len / dt
            # graftlint: unsharded(loss fetched for logging only, after the timed region closes)
            print(f"step {step:3d}  loss {float(loss):.4f}  "
                  f"tok/s {tok_s:,.0f}")


if __name__ == "__main__":
    main()
