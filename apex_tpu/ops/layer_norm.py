"""Fused LayerNorm / RMSNorm — Pallas TPU kernels with custom VJP.

Reference: ``apex/normalization/fused_layer_norm.py`` +
``csrc/layer_norm_cuda.cpp`` / ``csrc/layer_norm_cuda_kernel.cu``
(FusedLayerNorm, FusedRMSNorm, Mixed variants) and
``apex/contrib/layer_norm`` (FastLayerNorm).  The reference fuses the
row statistics + normalize + affine into one CUDA kernel (fwd and bwd).

TPU design: one Pallas kernel per pass, gridded over row blocks held in
VMEM; statistics computed in fp32 on the VPU regardless of input dtype
(the reference promotes the same way).  The backward's dx is a second
Pallas kernel using saved (mean, rstd); the parameter grads dγ/dβ are
cross-row reductions left to XLA (they lower to efficient full-array
reductions and fuse with surrounding ops).

- "Mixed" variants (fp32 params with half activations) need no special
  kernel: pass half ``x`` with fp32 ``weight`` — compute is fp32 either
  way and the output takes ``x.dtype``.
- ``memory_efficient=True`` (reference: recompute in bwd instead of
  saving) ≙ wrapping the call in ``jax.checkpoint``; the stats here are
  (N,1) scalars-per-row, already tiny.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import pick_block_rows, resolve_impl

__all__ = [
    "fused_layer_norm",
    "fused_rms_norm",
    "layer_norm_reference",
    "rms_norm_reference",
]


# --------------------------------------------------------------------- #
# XLA reference compositions (golden semantics; CPU/GPU fallback)
# --------------------------------------------------------------------- #
def layer_norm_reference(x, weight=None, bias=None, eps: float = 1e-5):
    """Eager jnp composition matching torch.nn.functional.layer_norm."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(jnp.square(xc), axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_reference(x, weight=None, eps: float = 1e-5):
    """Eager jnp composition of RMSNorm (Zhang & Sennrich)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(x.dtype)


# --------------------------------------------------------------------- #
# Pallas kernels
# --------------------------------------------------------------------- #
def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mu_ref, rs_ref, *,
                   eps: float, rms: bool):
    x = x_ref[:].astype(jnp.float32)
    if rms:
        mu = jnp.zeros((x.shape[0], 1), jnp.float32)
        var = jnp.mean(jnp.square(x), axis=1, keepdims=True)
    else:
        mu = jnp.mean(x, axis=1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mu) * rstd
    y = xhat * w_ref[:].astype(jnp.float32)
    if b_ref is not None:
        y = y + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mu_ref[:] = mu
    rs_ref[:] = rstd


def _ln_bwd_dx_kernel(dy_ref, x_ref, w_ref, mu_ref, rs_ref, dx_ref, *,
                      rms: bool):
    """dx for layer norm:  dx = rstd * (wdy - mean(wdy) - xhat*mean(wdy*xhat))
    (the mean(wdy) term drops for RMSNorm)."""
    dy = dy_ref[:].astype(jnp.float32)
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    mu = mu_ref[:]
    rstd = rs_ref[:]
    xhat = (x - mu) * rstd
    wdy = dy * w
    c2 = jnp.mean(wdy * xhat, axis=1, keepdims=True)
    if rms:
        dx = (wdy - xhat * c2) * rstd
    else:
        c1 = jnp.mean(wdy, axis=1, keepdims=True)
        dx = (wdy - c1 - xhat * c2) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _run_ln_fwd(x2d, w2d, b2d, eps, rms, interpret, block_rows=None):
    n, h = x2d.shape
    br = block_rows or pick_block_rows(n, h, op="layer_norm",
                                       dtype=x2d.dtype)
    grid = (pl.cdiv(n, br),)
    kernel = functools.partial(_ln_fwd_kernel, eps=eps, rms=rms)
    in_specs = [
        pl.BlockSpec((br, h), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM),
    ]
    args = [x2d, w2d]
    if b2d is None:
        kernel = functools.partial(_ln_fwd_kernel_nobias, eps=eps, rms=rms)
    else:
        in_specs.append(
            pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM))
        args.append(b2d)
    # the scope names the kernel in HLO metadata and profiler traces
    with jax.named_scope("layer_norm_fwd"):
        y, mu, rstd = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((br, h), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((br, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((br, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n, h), x2d.dtype),
                jax.ShapeDtypeStruct((n, 1), jnp.float32),
                jax.ShapeDtypeStruct((n, 1), jnp.float32),
            ],
            interpret=interpret,
        )(*args)
    return y, mu, rstd


def _ln_fwd_kernel_nobias(x_ref, w_ref, y_ref, mu_ref, rs_ref, *,
                          eps: float, rms: bool):
    _ln_fwd_kernel(x_ref, w_ref, None, y_ref, mu_ref, rs_ref,
                   eps=eps, rms=rms)


def _run_ln_bwd_dx(dy2d, x2d, w2d, mu, rstd, rms, interpret):
    n, h = x2d.shape
    br = pick_block_rows(n, h, op="layer_norm", dtype=x2d.dtype)
    grid = (pl.cdiv(n, br),)
    kernel = functools.partial(_ln_bwd_dx_kernel, rms=rms)
    # the scope names the kernel in HLO metadata and profiler traces
    with jax.named_scope("layer_norm_bwd"):
        dx = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((br, h), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((br, h), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, h), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((br, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((br, 1), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((br, h), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((n, h), x2d.dtype),
            interpret=interpret,
        )(dy2d, x2d, w2d, mu, rstd)
    return dx


# --------------------------------------------------------------------- #
# custom-vjp wrappers
# --------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln_pallas(x2d, w2d, b2d, eps, rms, interpret):
    y, _, _ = _run_ln_fwd(x2d, w2d, b2d, eps, rms, interpret)
    return y


def _ln_pallas_fwd(x2d, w2d, b2d, eps, rms, interpret):
    y, mu, rstd = _run_ln_fwd(x2d, w2d, b2d, eps, rms, interpret)
    return y, (x2d, w2d, mu, rstd, None if b2d is None else True)


def _ln_pallas_bwd(eps, rms, interpret, res, dy):
    x2d, w2d, mu, rstd, has_bias = res
    dx = _run_ln_bwd_dx(dy, x2d, w2d, mu, rstd, rms, interpret)
    # parameter grads: cross-row reductions — XLA territory.
    dyf = dy.astype(jnp.float32)
    xhat = (x2d.astype(jnp.float32) - mu) * rstd
    dw = jnp.sum(dyf * xhat, axis=0, keepdims=True).astype(w2d.dtype)
    db = (jnp.sum(dyf, axis=0, keepdims=True).astype(w2d.dtype)
          if has_bias else None)
    return dx, dw, db


_ln_pallas.defvjp(_ln_pallas_fwd, _ln_pallas_bwd)


# --------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------- #
def _normalize_call(x, weight, bias, eps, rms, implementation):
    h = x.shape[-1]
    # Pallas path needs a lane-aligned hidden size; otherwise XLA.
    impl = resolve_impl(implementation, pallas_ok=(h % 128 == 0))
    if impl == "xla":
        if rms:
            return rms_norm_reference(x, weight, eps=eps)
        return layer_norm_reference(x, weight, bias, eps=eps)

    interpret = impl == "pallas_interpret"
    orig_shape = x.shape
    x2d = x.reshape(-1, h)
    if weight is None:
        weight = jnp.ones((h,), x.dtype)
    w2d = weight.reshape(1, h)
    b2d = None
    if not rms and bias is not None:
        b2d = bias.reshape(1, h)
    y = _ln_pallas(x2d, w2d, b2d, float(eps), rms, interpret)
    return y.reshape(orig_shape)


def fused_layer_norm(x, weight=None, bias=None, *, eps: float = 1e-5,
                     implementation: Optional[str] = None):
    """Fused layer norm over the last axis (apex ``FusedLayerNorm``).

    ``weight``/``bias`` may be ``None`` (elementwise_affine=False
    upstream).  Statistics in fp32; output in ``x.dtype``; grads flow
    through a fused Pallas backward on TPU.
    """
    return _normalize_call(x, weight, bias, eps, False, implementation)


def fused_rms_norm(x, weight=None, *, eps: float = 1e-5,
                   implementation: Optional[str] = None):
    """Fused RMSNorm over the last axis (apex ``FusedRMSNorm``)."""
    return _normalize_call(x, weight, None, eps, True, implementation)
