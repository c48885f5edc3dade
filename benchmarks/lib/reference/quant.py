"""Matrix products for the references, and the lower precision that
serves as their control.

``matmul`` / ``einsum`` with ``lower=None`` are float32 at ``highest``
precision (on a TPU the default float32 product rounds its operands to
bfloat16).  ``lower="float8_e4m3fn"`` is the precision step below the
bfloat16 that the configurations state, as an fp8 training or serving
path would compute: both operands rounded to e4m3 with one scale per
tensor, and in the backward pass the incoming gradient rounded to e5m2
the same way before the two transposed products."""

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
GRAD_DTYPE = {"float8_e4m3fn": "float8_e5m2"}


def round_to(x, lower):
    """``x`` rounded to ``lower`` (one scale per tensor for fp8) and
    back to float32."""
    if lower is None:
        return x
    dt = jnp.dtype(lower)
    if dt == jnp.bfloat16:
        return x.astype(dt).astype(x.dtype)
    top = float(jnp.finfo(dt).max)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dt).astype(x.dtype) / scale


def lowered(op, lower):
    """``op(a, b)`` on operands rounded to ``lower``; its gradient is
    ``op``'s own at the rounded operands, for the rounded cotangent."""
    if lower is None:
        return op

    @jax.custom_vjp
    def f(a, b):
        return op(round_to(a, lower), round_to(b, lower))

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        _, vjp = jax.vjp(op, round_to(a, lower), round_to(b, lower))
        return vjp(round_to(g, GRAD_DTYPE.get(lower, lower)))

    f.defvjp(fwd, bwd)
    return f


def matmul(a, b, lower=None):
    return lowered(functools.partial(jnp.matmul, precision=HIGHEST),
                   lower)(a, b)


def einsum(spec, a, b, lower=None):
    return lowered(functools.partial(jnp.einsum, spec, precision=HIGHEST),
                   lower)(a, b)
