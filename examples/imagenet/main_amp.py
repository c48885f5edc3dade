"""ImageNet training with mixed precision + data parallelism.

Mirror of the reference's ``examples/imagenet/main_amp.py`` (ResNet-50,
amp O1/O2, FusedSGD, apex DDP / SyncBatchNorm) rebuilt TPU-native:
``PrecisionPolicy`` instead of monkey-patched amp, GSPMD data
parallelism (grads ``psum`` over the mesh) instead of bucketed NCCL
allreduce, SyncBatchNorm via cross-replica Welford ``psum``.

Runs on any JAX backend.  Data: ``--data file.npz`` (arrays
``images`` NHWC float and ``labels`` int) trains on real data;
``--synthetic-learnable`` generates class-conditional synthetic images
so convergence is demonstrable without a dataset (loss falls, accuracy
rises — printed per step); the default is random synthetic throughput
mode, as in the reference's no-dataset dry runs.

O1 here is the real per-op interceptor (``amp.o1.o1_intercept`` over a
dtype-None model — conv/dense run bf16, BN/softmax fp32), not a whole-
model cast; O2/O3 cast the model via the precision policy.

  python examples/imagenet/main_amp.py --opt-level O1 --steps 30 \
      --batch-size 64 --image-size 64 --synthetic-learnable
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu import amp, initialize_mesh
from apex_tpu.models.resnet import ResNet, ResNetConfig
from apex_tpu.optim import fused_sgd


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--opt-level", default="O2",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--image-size", type=int, default=64)
    p.add_argument("--num-classes", type=int, default=100)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--sync-bn", action="store_true",
                   help="SyncBatchNorm over the data axis")
    p.add_argument("--fused-bn", action="store_true",
                   help="fused BN(+add+ReLU) kernels "
                        "(apex_tpu.ops.batch_norm; docs/perf_resnet.md)")
    p.add_argument("--stem", default="conv", choices=["conv", "s2d"],
                   help="'s2d' = MLPerf space-to-depth stem (needs an "
                        "even image size)")
    p.add_argument("--arch", default="resnet50",
                   choices=["resnet18", "resnet50"])
    p.add_argument("--data", default=None, metavar="FILE.npz",
                   help="npz with arrays images (NHWC) + labels (int)")
    p.add_argument("--synthetic-learnable", action="store_true",
                   help="class-conditional synthetic data so training "
                        "demonstrably converges (prints accuracy)")
    p.add_argument("--ckpt-dir", default=None,
                   help="run under apex_tpu.resilience.ResilientLoop: "
                        "rolling hash-verified checkpoints here, "
                        "auto-resume, SIGTERM → final checkpoint + "
                        "clean exit, NaN rewind (docs/resilience.md)")
    p.add_argument("--ckpt-every", type=int, default=50,
                   help="checkpoint cadence (steps) for --ckpt-dir")
    return p.parse_args()


def main():  # graftlint: hot-step
    args = parse_args()
    from apex_tpu.utils import enable_compile_cache

    enable_compile_cache()
    mesh = initialize_mesh(data_parallel_size=-1)  # all devices → DP

    if args.data:
        # the model head must match the dataset: peek at the labels
        # before building the config
        args.num_classes = int(np.load(args.data)["labels"].max()) + 1
    stages = (3, 4, 6, 3) if args.arch == "resnet50" else (2, 2, 2, 2)
    # O1: model stays dtype-None (modules promote with fp32 params) and
    # the per-op interceptor routes convs/dense to bf16, norms/losses
    # to fp32 — the reference's O1, not a whole-model cast
    dtype = (None if args.opt_level == "O1"
             else jnp.bfloat16 if args.opt_level in ("O2", "O3")
             else jnp.float32)
    cfg = ResNetConfig(
        stage_sizes=stages, num_classes=args.num_classes,
        bn_axis_names=("data",) if args.sync_bn else None,
        dtype=dtype, fused_bn=args.fused_bn, stem=args.stem)
    model = ResNet(cfg)

    rng = np.random.default_rng(0)
    shape = (args.batch_size, args.image_size, args.image_size, 3)
    if args.data:
        blob = np.load(args.data)
        raw = blob["images"][: args.batch_size]
        if raw.dtype == np.uint8:      # shards ship uint8 pixels
            raw = raw.astype(np.float32) / 255.0
        images = jnp.asarray(raw, jnp.float32)
        labels = jnp.asarray(blob["labels"][: args.batch_size])
    elif args.synthetic_learnable:
        # class-conditional means: each class is a distinct low-freq
        # pattern + noise, so a working train step must separate them
        labels_np = rng.integers(0, args.num_classes,
                                 size=(args.batch_size,))
        protos = rng.normal(size=(args.num_classes, 8, 8, 3))
        pats = np.repeat(np.repeat(
            protos[labels_np], args.image_size // 8, 1),
            args.image_size // 8, 2)
        images = jnp.asarray(
            pats + 0.5 * rng.normal(size=shape), jnp.float32)
        labels = jnp.asarray(labels_np)
    else:
        images = jnp.asarray(rng.normal(size=shape), jnp.float32)
        labels = jnp.asarray(
            rng.integers(0, args.num_classes, size=(args.batch_size,)))

    variables = model.init(jax.random.PRNGKey(0), images[:2], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    def apply_fn(p, x, bs):
        if args.opt_level == "O1":
            from apex_tpu.amp import o1
            with o1.o1_intercept(jnp.bfloat16):
                return model.apply({"params": p, "batch_stats": bs}, x,
                                   train=True, mutable=["batch_stats"])
        return model.apply({"params": p, "batch_stats": bs}, x,
                           train=True, mutable=["batch_stats"])

    state = amp.initialize(
        apply_fn, params,
        fused_sgd(args.lr, momentum=args.momentum,
                  weight_decay=args.weight_decay),
        opt_level=args.opt_level)

    batch_sharding = NamedSharding(mesh, P("data"))
    images = jax.device_put(images, batch_sharding)
    labels = jax.device_put(labels, batch_sharding)
    # commit the carry replicated over the mesh: a fresh (uncommitted)
    # state composes with the sharded batch implicitly, but a state
    # RESTORED from a checkpoint comes back committed to its target's
    # placement — so the target must already be the placement the step
    # expects (docs/resilience.md, "restore places like the target")
    replicated = NamedSharding(mesh, P())
    state = jax.device_put(state, replicated)
    batch_stats = jax.device_put(batch_stats, replicated)

    # state and batch_stats are replaced every step — donate both so the
    # old copies' HBM is reused (x/y are the same arrays each step and
    # must stay undonated)
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(state, batch_stats, x, y):
        def loss_fn(p):
            logits, mut = state.apply_fn(p, x, batch_stats)
            logits = logits.astype(jnp.float32)
            onehot = jax.nn.one_hot(y, args.num_classes)
            loss = -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits) * onehot, axis=-1))
            acc = jnp.mean(jnp.argmax(logits, -1) == y)
            return state.scale_loss(loss), (loss, acc,
                                            mut["batch_stats"])
        grads, (loss, acc, new_bs) = jax.grad(
            loss_fn, has_aux=True)(state.compute_params())
        new_state, finite = state.apply_gradients(grads=grads)
        return new_state, new_bs, loss, acc, finite

    with mesh:
        if args.ckpt_dir:
            # preemption-safe path: the reference's kill-and-come-back
            # workflow (save model+optimizer+amp together, restore,
            # keep training), with the dying part handled too
            from apex_tpu.resilience import (
                ResilientCheckpointer, ResilientLoop)

            def loop_step(carry, batch):
                st, bs = carry
                st, bs, loss, acc, finite = train_step(st, bs, *batch)
                return (st, bs), {"loss": loss, "acc": acc,
                                  "finite": finite}

            loop = ResilientLoop(
                loop_step,
                checkpointer=ResilientCheckpointer(args.ckpt_dir,
                                                   keep=3),
                checkpoint_every=args.ckpt_every,
                finite_of=lambda aux: aux["finite"])
            (state, batch_stats), report = loop.run(
                (state, batch_stats),
                lambda step: (images, labels), args.steps)
            print(f"resilient loop: resumed_from={report.resumed_from} "
                  f"steps_run={report.steps_run} "
                  f"preempted={report.preempted} "
                  f"rewinds={report.rewinds} "
                  f"checkpoints={report.checkpoints_saved}")
            return

        for step in range(args.steps):
            t0 = time.perf_counter()
            state, batch_stats, loss, acc, finite = train_step(
                state, batch_stats, images, labels)
            # time the device work alone — reading the metrics inside
            # the window bills three d2h transfers to imgs/s
            jax.block_until_ready(loss)
            dt = time.perf_counter() - t0
            # graftlint: unsharded(metrics fetched once for logging, off the clock — one transfer, not three)
            loss, acc, finite = jax.device_get((loss, acc, finite))
            print(f"step {step:4d}  loss {float(loss):.4f}  "
                  f"acc {float(acc):.3f}  finite {bool(finite)}  "
                  f"imgs/s {args.batch_size / dt:9.1f}")


if __name__ == "__main__":
    main()
