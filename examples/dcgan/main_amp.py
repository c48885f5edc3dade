"""DCGAN with mixed precision — two models, two optimizers, one scaler
regime.

Mirror of the reference's ``examples/dcgan/main_amp.py``, whose point is
amp with *multiple* models/optimizers/losses (``amp.initialize`` taking
lists).  Functionally here: two independent ``MixedPrecisionTrainState``s
(G and D), each with its own dynamic loss scale, trained adversarially
on synthetic data.

  python examples/dcgan/main_amp.py --steps 10
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from apex_tpu import amp
from apex_tpu.optim import fused_adam


class Generator(nn.Module):
    feat: int = 32

    @nn.compact
    def __call__(self, z):
        x = nn.Dense(4 * 4 * self.feat * 4)(z)
        x = x.reshape(z.shape[0], 4, 4, self.feat * 4)
        for mult in (2, 1):
            x = nn.ConvTranspose(self.feat * mult, (4, 4), (2, 2),
                                 padding="SAME")(x)
            x = nn.relu(nn.GroupNorm(num_groups=8)(x))
        x = nn.ConvTranspose(3, (4, 4), (2, 2), padding="SAME")(x)
        return jnp.tanh(x)


class Discriminator(nn.Module):
    feat: int = 32

    @nn.compact
    def __call__(self, x):
        for mult in (1, 2, 4):
            x = nn.Conv(self.feat * mult, (4, 4), (2, 2),
                        padding="SAME")(x)
            x = nn.leaky_relu(x, 0.2)
        return nn.Dense(1)(x.reshape(x.shape[0], -1))


def bce_logits(logits, target):
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * target
        + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def main():  # graftlint: hot-step
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--zdim", type=int, default=64)
    p.add_argument("--opt-level", default="O1")
    p.add_argument("--data", default=None, metavar="FILE.npz",
                   help="npz with an `images` array (NHWC, 32x32, "
                        "uint8 or float) as the real distribution; "
                        "default: synthetic noise images")
    args = p.parse_args()
    from apex_tpu.utils import enable_compile_cache

    enable_compile_cache()

    gen, disc = Generator(), Discriminator()
    key = jax.random.PRNGKey(0)
    z0 = jnp.zeros((2, args.zdim))
    g_params = gen.init(key, z0)["params"]
    d_params = disc.init(key, jnp.zeros((2, 32, 32, 3)))["params"]

    g_state = amp.initialize(
        lambda p_, z: gen.apply({"params": p_}, z), g_params,
        fused_adam(2e-4, b1=0.5), opt_level=args.opt_level)
    d_state = amp.initialize(
        lambda p_, x: disc.apply({"params": p_}, x), d_params,
        fused_adam(2e-4, b1=0.5), opt_level=args.opt_level)

    rng = np.random.default_rng(0)
    if args.data:
        raw = np.load(args.data)["images"]
        if raw.shape[1:] != (32, 32, 3):
            raise ValueError(
                f"dcgan expects (N, 32, 32, 3) images, got {raw.shape}")
        if raw.shape[0] < args.batch_size:
            # D must see as many reals as fakes per step
            print(f"# shard has {raw.shape[0]} images < batch-size "
                  f"{args.batch_size}; clamping batch size")
            args.batch_size = raw.shape[0]
        raw = raw[: args.batch_size]
        if raw.dtype == np.uint8:
            raw = raw.astype(np.float32) / 255.0
        # map into the generator's tanh range
        real = jnp.asarray(raw * 2.0 - 1.0, jnp.float32)
    else:
        real = jnp.asarray(
            rng.normal(size=(args.batch_size, 32, 32, 3)), jnp.float32)

    @jax.jit
    def step(g_state, d_state, z):
        fake = g_state.apply_fn(g_state.compute_params(), z)

        def d_loss_fn(dp):
            d_real = d_state.apply_fn(dp, real)
            d_fake = d_state.apply_fn(dp, jax.lax.stop_gradient(fake))
            loss = bce_logits(d_real, 1.0) + bce_logits(d_fake, 0.0)
            return d_state.scale_loss(loss), loss
        d_grads, d_loss = jax.grad(d_loss_fn, has_aux=True)(
            d_state.compute_params())
        d_state, _ = d_state.apply_gradients(grads=d_grads)

        def g_loss_fn(gp):
            fake = g_state.apply_fn(gp, z)
            loss = bce_logits(d_state.apply_fn(
                d_state.compute_params(), fake), 1.0)
            return g_state.scale_loss(loss), loss
        g_grads, g_loss = jax.grad(g_loss_fn, has_aux=True)(
            g_state.compute_params())
        g_state, _ = g_state.apply_gradients(grads=g_grads)
        return g_state, d_state, g_loss, d_loss

    for i in range(args.steps):
        z = jax.random.normal(jax.random.PRNGKey(i),
                              (args.batch_size, args.zdim))
        g_state, d_state, g_loss, d_loss = step(g_state, d_state, z)
        # graftlint: unsharded(demo logging — both losses ride one fetch instead of two)
        g_loss, d_loss = jax.device_get((g_loss, d_loss))
        print(f"step {i:3d}  G {float(g_loss):.4f}  D {float(d_loss):.4f}")


if __name__ == "__main__":
    main()
