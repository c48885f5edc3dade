"""Fused BatchNorm (+ optional residual-add + ReLU) — NHWC Pallas kernels.

Reference: ``apex/contrib/groupbn`` (``bn.cu``/``batch_norm.h``: the
MLPerf-ResNet NHWC BatchNorm with fused add+ReLU epilogues) and
``apex/parallel/optimized_sync_batchnorm`` (cross-process stats).

Why this exists (round-5 calibration, BASELINE.md "Round-5 ResNet
roofline calibration"): the resnet50 legs run at ~0.49 of their own
analytic achievable-traffic bound because the XLA program moves ≈2.2×
the architecture-mandated bytes — BN normalize, residual-add and ReLU
each materialize as separate HBM passes, and the BN backward re-reads
x/dy once per statistic.  The fused op collapses those:

- **fwd** — one partial-sums pass over x (Σx, Σx² per channel; the
  *same* partials SyncBN ``psum``s across the data axes), then ONE
  normalize pass applying scale/shift + residual-add + ReLU in a
  single read of x / write of y (vs XLA's separate stat-reduce,
  normalize, and add/ReLU sweeps).
- **bwd** — one reduction pass computing BOTH backward statistics
  (Σdz, Σdz·x̂) plus dγ/dβ in a single read of (dy, x), then one pass
  writing dx (and the residual cotangent, which is free — it equals
  the post-ReLU dz already in registers).  XLA's autodiff of the
  composition re-reads the activation per reduction and materializes
  x̂ and the ReLU mask.

Cross-replica (SyncBN) support: pass ``axis_names`` — the per-channel
partial sums from the fused reduction are ``psum``'d between the two
passes (forward *and* backward), so the multi-device leg shares the
single-pass kernels; per-device traffic is identical to local BN plus
two (C,)-sized collectives.  dγ/dβ stay *local* sums, matching what
autodiff-of-``psum`` produces, so DDP's grad all-reduce yields
bit-identical parameter gradients to the unfused module.

The jnp composition (``batch_norm_reference``) is the golden semantics
and the CPU/GPU fallback; the ``custom_vjp`` wraps BOTH paths so the
fused single-pass backward structure holds even where the Pallas
kernels don't run.  Kernel envelope: channels a multiple of 64 (≤2048)
and a row count with an 8-aligned divisor — everything else (odd
channel counts included) dispatches to the reference.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import resolve_impl

__all__ = [
    "batch_norm_train",
    "batch_norm_inference",
    "batch_norm_reference",
]

_ACTS = (None, "relu")


# --------------------------------------------------------------------- #
# XLA reference composition (golden semantics; CPU/GPU fallback)
# --------------------------------------------------------------------- #
def _bound_axes(axis_names) -> Tuple[str, ...]:
    """Keep only mesh axes actually bound in the current trace."""
    if not axis_names:
        return ()
    out = []
    for a in axis_names:
        try:
            lax.axis_size(a)
            out.append(a)
        except (NameError, KeyError):
            continue
    return tuple(out)


def _apply_epilogue(y, residual, act):
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    return y


def batch_norm_reference(x, weight=None, bias=None, *, eps: float = 1e-5,
                         residual=None, act: Optional[str] = None,
                         axis_names=()):
    """Eager jnp train-mode BN(+add+ReLU): returns ``(y, mean, var)``.

    ``x``: channels-last ``(N, ..., C)``; stats reduce over every
    leading dim (and over ``axis_names`` mesh axes via ``psum`` when
    bound).  ``var`` is the biased batch variance (normalization
    semantics; Bessel-correct it yourself for torch-style running
    stats).  Golden semantics for :func:`batch_norm_train`.
    """
    if act not in _ACTS:
        raise ValueError(f"unknown act {act!r}")
    axes = _bound_axes(axis_names)
    reduce_dims = tuple(range(x.ndim - 1))
    n_local = 1
    for d in reduce_dims:
        n_local *= x.shape[d]
    xf = x.astype(jnp.float32)
    s1 = jnp.sum(xf, axis=reduce_dims)
    s2 = jnp.sum(jnp.square(xf), axis=reduce_dims)
    n = float(n_local)
    if axes:
        s1 = lax.psum(s1, axes)
        s2 = lax.psum(s2, axes)
        for a in axes:
            n *= lax.axis_size(a)
    mean = s1 / n
    var = jnp.maximum(s2 / n - jnp.square(mean), 0.0)
    y = (xf - mean) * lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    y = _apply_epilogue(y, residual, act)
    return y.astype(x.dtype), mean, var


def batch_norm_inference(x, mean, var, weight=None, bias=None, *,
                         eps: float = 1e-5, residual=None,
                         act: Optional[str] = None):
    """Eval-mode BN over given (running) stats, + optional add/ReLU.

    A pure elementwise affine — XLA fuses it into one pass on every
    backend, so there is no Pallas variant (and autodiff through it is
    already single-pass).  Math matches
    ``apex_tpu.parallel.SyncBatchNorm``'s eval path bit-for-bit.
    """
    if act not in _ACTS:
        raise ValueError(f"unknown act {act!r}")
    y = (x.astype(jnp.float32) - mean) * lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    y = _apply_epilogue(y, residual, act)
    return y.astype(x.dtype)


# --------------------------------------------------------------------- #
# Pallas kernels — grid over row blocks of the (R, C) flattened input
# --------------------------------------------------------------------- #
def _bn_reduce_kernel(x_ref, s1_ref, s2_ref):
    """Partial per-channel Σx / Σx² (the sums SyncBN psums)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s1_ref[:] = jnp.zeros_like(s1_ref)
        s2_ref[:] = jnp.zeros_like(s2_ref)

    x = x_ref[:].astype(jnp.float32)
    s1_ref[:] += jnp.sum(x, axis=0, keepdims=True)
    s2_ref[:] += jnp.sum(x * x, axis=0, keepdims=True)


def _bn_apply_kernel(x_ref, res_ref, sc_ref, sh_ref, y_ref, *,
                     relu: bool, has_res: bool):
    """One read/one write: y = act(x·scale + shift (+ residual))."""
    z = x_ref[:].astype(jnp.float32) * sc_ref[:] + sh_ref[:]
    if has_res:
        z = z + res_ref[:].astype(jnp.float32)
    if relu:
        z = jnp.maximum(z, 0.0)
    y_ref[:] = z.astype(y_ref.dtype)


def _relu_mask(x, y_ref, sc_ref, sh_ref):
    """The ReLU-chain mask.  Without a residual the pre-activation is
    the per-channel affine ``x·scale + shift`` of the x block already
    in VMEM, so the mask is recomputed for free; with a residual the
    affine alone can't determine the sign, so the saved output y
    (``y > 0 ⟺ pre-act > 0`` a.e.) is read instead."""
    if y_ref is not None:
        return y_ref[:].astype(jnp.float32) > 0.0
    return x * sc_ref[:] + sh_ref[:] > 0.0


def _bn_bwd_reduce_kernel(dy_ref, x_ref, y_ref, sc_ref, sh_ref,
                          mc_ref, rc_ref, s1_ref, s2_ref, *,
                          relu: bool):
    """Single pass over (dy, x) for BOTH backward statistics:
    s1 = Σdz, s2 = Σdz·x̂ (dz = dy·1[pre-act>0] under the ReLU
    epilogue).  s1/s2 double as dβ/dγ (local sums) and — psum'd — as
    the dx coefficients, so no second reduction sweep exists."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s1_ref[:] = jnp.zeros_like(s1_ref)
        s2_ref[:] = jnp.zeros_like(s2_ref)

    x = x_ref[:].astype(jnp.float32)
    dz = dy_ref[:].astype(jnp.float32)
    if relu:
        dz = dz * _relu_mask(x, y_ref, sc_ref, sh_ref)
    xhat = (x - mc_ref[:]) * rc_ref[:]
    s1_ref[:] += jnp.sum(dz, axis=0, keepdims=True)
    s2_ref[:] += jnp.sum(dz * xhat, axis=0, keepdims=True)


def _bn_bwd_dx_kernel(dy_ref, x_ref, y_ref, sc_ref, sh_ref, mc_ref,
                      rc_ref, a_ref, b_ref, c_ref, dx_ref, dres_ref, *,
                      relu: bool, has_res: bool):
    """dx (+ the free residual cotangent) in one pass:
    dx = a·dz + b + x̂·c with per-channel (a, b, c) precomputed from
    the psum'd statistics; dres = dz is already in registers."""
    x = x_ref[:].astype(jnp.float32)
    dz = dy_ref[:].astype(jnp.float32)
    if relu:
        dz = dz * _relu_mask(x, y_ref, sc_ref, sh_ref)
    if has_res:
        dres_ref[:] = dz.astype(dres_ref.dtype)
    xhat = (x - mc_ref[:]) * rc_ref[:]
    dx_ref[:] = (a_ref[:] * dz + b_ref[:] + xhat * c_ref[:]).astype(
        dx_ref.dtype)


def _pick_rows(r_total: int, c: int) -> Optional[int]:
    """Largest 8-multiple divisor of the row count whose fp32 block
    keeps ~4 co-resident buffers inside a ~4 MB VMEM budget (None: no
    legal block).  A measured autotune entry (op="batch_norm") takes
    precedence when it divides the row count."""
    from apex_tpu.ops import autotune

    budget = max(8, (1024 * 1024) // max(1, c * 4))
    hit = autotune.cached_block_rows("batch_norm", c, "float32")
    best = None
    for br in range(8, min(r_total, budget) + 1, 8):
        if r_total % br == 0:
            best = br
            if hit and br >= hit:
                return br
    return best


_SEQ = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _row_spec(br, c):
    return pl.BlockSpec((br, c), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


def _vec_spec(c):
    return pl.BlockSpec((1, c), lambda i: (0, 0),
                        memory_space=pltpu.VMEM)


def _bn_reduce_call(x2, br, interpret):
    r, c = x2.shape
    return pl.pallas_call(
        _bn_reduce_kernel,
        grid=(r // br,),
        in_specs=[_row_spec(br, c)],
        out_specs=[_vec_spec(c), _vec_spec(c)],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32)] * 2,
        # the (1, C) outputs accumulate across row blocks — pin the
        # grid sequential so a parallel-dims default can't break it
        compiler_params=_SEQ,
        interpret=interpret,
    )(x2)


def _bn_apply_call(x2, res2, scale, shift, relu, br, interpret):
    r, c = x2.shape
    has_res = res2 is not None

    def kernel(*refs):
        if has_res:
            x_ref, res_ref, sc_ref, sh_ref, y_ref = refs
        else:
            x_ref, sc_ref, sh_ref, y_ref = refs
            res_ref = None
        _bn_apply_kernel(x_ref, res_ref, sc_ref, sh_ref, y_ref,
                         relu=relu, has_res=has_res)

    in_specs = [_row_spec(br, c)] * (2 if has_res else 1) \
        + [_vec_spec(c), _vec_spec(c)]
    args = ((x2, res2) if has_res else (x2,)) + (scale, shift)
    return pl.pallas_call(
        kernel,
        grid=(r // br,),
        in_specs=in_specs,
        out_specs=_row_spec(br, c),
        out_shape=jax.ShapeDtypeStruct((r, c), x2.dtype),
        compiler_params=_SEQ,
        interpret=interpret,
    )(*args)


def _bwd_inputs(dy2, x2, y2, scsh, mc, rc, br, c):
    """Shared (args, in_specs, ref-unpacker) for the two bwd kernels:
    row blocks (dy, x[, y]) then per-channel vectors ([sc, sh], mc,
    rc)."""
    has_y = y2 is not None
    has_scsh = scsh is not None
    args = (dy2, x2) + ((y2,) if has_y else ())
    in_specs = [_row_spec(br, c)] * len(args)
    if has_scsh:
        args += scsh
        in_specs += [_vec_spec(c)] * 2
    args += (mc, rc)
    in_specs += [_vec_spec(c)] * 2

    def unpack(ins):
        it = iter(ins)
        dy_ref, x_ref = next(it), next(it)
        y_ref = next(it) if has_y else None
        sc_ref = next(it) if has_scsh else None
        sh_ref = next(it) if has_scsh else None
        mc_ref, rc_ref = next(it), next(it)
        return (dy_ref, x_ref, y_ref, sc_ref, sh_ref, mc_ref, rc_ref,
                tuple(it))

    return args, in_specs, unpack


def _bn_bwd_reduce_call(dy2, x2, y2, scsh, mc, rc, relu, br,
                        interpret):
    r, c = x2.shape
    args, in_specs, unpack = _bwd_inputs(dy2, x2, y2, scsh, mc, rc,
                                         br, c)

    def kernel(*refs):
        (dy_ref, x_ref, y_ref, sc_ref, sh_ref, mc_ref, rc_ref,
         rest) = unpack(refs[:len(args)])
        s1_ref, s2_ref = refs[len(args):]
        _bn_bwd_reduce_kernel(dy_ref, x_ref, y_ref, sc_ref, sh_ref,
                              mc_ref, rc_ref, s1_ref, s2_ref,
                              relu=relu)

    return pl.pallas_call(
        kernel,
        grid=(r // br,),
        in_specs=in_specs,
        out_specs=[_vec_spec(c), _vec_spec(c)],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32)] * 2,
        compiler_params=_SEQ,
        interpret=interpret,
    )(*args)


def _bn_bwd_dx_call(dy2, x2, y2, scsh, mc, rc, a, b, cc, relu,
                    has_res, br, interpret):
    r, c = x2.shape
    args, in_specs, unpack = _bwd_inputs(dy2, x2, y2, scsh, mc, rc,
                                         br, c)
    args += (a, b, cc)
    in_specs += [_vec_spec(c)] * 3

    def kernel(*refs):
        (dy_ref, x_ref, y_ref, sc_ref, sh_ref, mc_ref, rc_ref,
         rest) = unpack(refs[:len(args)])
        a_ref, b_ref, c_ref = rest
        outs = refs[len(args):]
        dx_ref = outs[0]
        dres_ref = outs[1] if has_res else None
        _bn_bwd_dx_kernel(dy_ref, x_ref, y_ref, sc_ref, sh_ref, mc_ref,
                          rc_ref, a_ref, b_ref, c_ref, dx_ref,
                          dres_ref, relu=relu, has_res=has_res)

    out_specs = [_row_spec(br, c)] * (2 if has_res else 1)
    out_shape = [jax.ShapeDtypeStruct((r, c), x2.dtype)] \
        * (2 if has_res else 1)
    out = pl.pallas_call(
        kernel,
        grid=(r // br,),
        in_specs=in_specs,
        out_specs=out_specs if has_res else out_specs[0],
        out_shape=out_shape if has_res else out_shape[0],
        compiler_params=_SEQ,
        interpret=interpret,
    )(*args)
    return out if has_res else (out, None)


# --------------------------------------------------------------------- #
# custom_vjp core — wraps BOTH the Pallas and the jnp path, so the
# single-pass backward structure holds on every backend
# --------------------------------------------------------------------- #
class _Spec(NamedTuple):
    eps: float
    act: Optional[str]
    axes: Tuple[str, ...]
    impl: str                # "pallas" | "xla"
    br: Optional[int]
    interpret: bool
    has_res: bool


def _global_count(r_local: int, axes) -> float:
    n = float(r_local)
    for a in axes:
        n *= lax.axis_size(a)
    return n


def _psum_stacked(rows, axes):
    """One psum over stacked (k, C) per-channel partials (a single
    tiny collective instead of k)."""
    stacked = jnp.stack(rows)
    if axes:
        stacked = lax.psum(stacked, axes)
    return tuple(stacked)


def _fwd_compute(spec: _Spec, x2, w2, b2, res2):
    r, c = x2.shape
    if spec.impl == "pallas":
        s1, s2 = _bn_reduce_call(x2, spec.br, spec.interpret)
    else:
        xf = x2.astype(jnp.float32)
        s1 = jnp.sum(xf, axis=0, keepdims=True)
        s2 = jnp.sum(jnp.square(xf), axis=0, keepdims=True)
    s1, s2 = _psum_stacked((s1, s2), spec.axes)
    n = _global_count(r, spec.axes)
    mean = s1 / n
    var = jnp.maximum(s2 / n - jnp.square(mean), 0.0)
    rstd = lax.rsqrt(var + spec.eps)
    scale = rstd * w2.astype(jnp.float32)
    shift = b2.astype(jnp.float32) - mean * scale
    if spec.impl == "pallas":
        y = _bn_apply_call(x2, res2, scale, shift, spec.act == "relu",
                           spec.br, spec.interpret)
    else:
        z = x2.astype(jnp.float32) * scale + shift
        if spec.has_res:
            z = z + res2.astype(jnp.float32)
        if spec.act == "relu":
            z = jnp.maximum(z, 0.0)
        y = z.astype(x2.dtype)
    return y, mean, var, rstd, scale, shift


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bn_core(spec: _Spec, x2, w2, b2, res2):
    y, mean, var = _fwd_compute(spec, x2, w2, b2, res2)[:3]
    return y, mean, var


def _bn_core_fwd(spec, x2, w2, b2, res2):
    y, mean, var, rstd, scale, shift = _fwd_compute(spec, x2, w2, b2,
                                                    res2)
    # The ReLU chain's mask: without a residual the pre-activation is
    # the per-channel affine of x (already read in both bwd passes),
    # so only the tiny (1, C) scale/shift are saved and the bwd never
    # touches y; with a residual the affine can't determine the sign,
    # so y is saved instead (>0 ⟺ pre-act >0 a.e.).  Either way the
    # pre-activation and the residual are never materialized.
    y_res = y if (spec.act == "relu" and spec.has_res) else None
    scsh = ((scale, shift)
            if (spec.act == "relu" and not spec.has_res) else None)
    return (y, mean, var), (x2, w2, mean, rstd, y_res, scsh)


def _bn_core_bwd(spec, residuals, cots):
    x2, w2, mean, rstd, y2, scsh = residuals
    dy, dmean_ext, dvar_ext = cots
    r, c = x2.shape
    relu = spec.act == "relu"

    def mask_of(xf):
        if y2 is not None:
            return y2.astype(jnp.float32) > 0.0
        return xf * scsh[0] + scsh[1] > 0.0

    if spec.impl == "pallas":
        s1, s2 = _bn_bwd_reduce_call(dy, x2, y2, scsh, mean, rstd,
                                     relu, spec.br, spec.interpret)
    else:
        xf = x2.astype(jnp.float32)
        dz = dy.astype(jnp.float32)
        if relu:
            dz = dz * mask_of(xf)
        xhat = (xf - mean) * rstd
        s1 = jnp.sum(dz, axis=0, keepdims=True)
        s2 = jnp.sum(dz * xhat, axis=0, keepdims=True)
    # dγ/dβ: LOCAL sums (DDP's grad all-reduce supplies the global
    # combine — identical to autodiff of the psum'd composition)
    dw = s2.astype(w2.dtype)
    db = s1.astype(w2.dtype)
    # dx coefficients need the GLOBAL sums (+ the mean/var output
    # cotangents, normally symbolic zeros — batch_stats ride as aux)
    g1, g2, gm, gv = _psum_stacked(
        (s1, s2,
         jnp.asarray(dmean_ext, jnp.float32).reshape(1, c),
         jnp.asarray(dvar_ext, jnp.float32).reshape(1, c)),
        spec.axes)
    n = _global_count(r, spec.axes)
    wf = w2.astype(jnp.float32)
    a = rstd * wf
    bcoef = (gm - a * g1) / n
    ccoef = (2.0 * gv / rstd - a * g2) / n
    if spec.impl == "pallas":
        dx, dres = _bn_bwd_dx_call(dy, x2, y2, scsh, mean, rstd, a,
                                   bcoef, ccoef, relu, spec.has_res,
                                   spec.br, spec.interpret)
    else:
        xf = x2.astype(jnp.float32)
        dz = dy.astype(jnp.float32)
        if relu:
            dz = dz * mask_of(xf)
        xhat = (xf - mean) * rstd
        dx = (a * dz + bcoef + xhat * ccoef).astype(x2.dtype)
        dres = dz.astype(x2.dtype) if spec.has_res else None
    return dx, dw, db, dres


_bn_core.defvjp(_bn_core_fwd, _bn_core_bwd)


# --------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------- #
def batch_norm_train(x, weight=None, bias=None, *, eps: float = 1e-5,
                     residual=None, act: Optional[str] = None,
                     axis_names=(), implementation: Optional[str] = None):
    """Fused train-mode BatchNorm(+residual-add+ReLU) over an NHWC (or
    any ``(N, ..., C)`` channels-last) tensor.

    Returns ``(y, mean, var)`` — ``mean``/``var`` are the fp32 batch
    statistics (biased variance), for the caller's running-stats
    update.  ``residual`` (same shape/dtype as ``x``) is added after
    the affine, before ``act``; its cotangent comes out of the fused
    backward for free.  ``act``: None | "relu".

    ``axis_names``: mesh axes to ``psum`` the per-channel partial
    Σx/Σx² over (SyncBatchNorm semantics) — unbound axes are ignored,
    so the same module code runs inside and outside ``shard_map``.

    Forward and backward each touch the activation in exactly two
    passes (one reduction, one map) on both the Pallas and the XLA
    path; the backward's two statistics, dγ and dβ all come out of the
    single reduction.  Dispatch follows ``apex_tpu.ops._dispatch``
    (``implementation=`` / ``APEX_TPU_OPS_IMPL``); shapes outside the
    kernel envelope (channels not a multiple of 64, C > 2048, or no
    8-aligned row-block divisor) fall back to the XLA path, which the
    golden tests pin to :func:`batch_norm_reference` semantics.
    """
    if act not in _ACTS:
        raise ValueError(f"unknown act {act!r}")
    if residual is not None and residual.shape != x.shape:
        raise ValueError(
            f"residual shape {residual.shape} != x shape {x.shape}")
    c = x.shape[-1]
    r_total = int(np.prod(x.shape[:-1]))
    br = _pick_rows(r_total, c)
    pallas_ok = (c % 64 == 0 and c <= 2048 and br is not None)
    impl = resolve_impl(implementation, pallas_ok=pallas_ok)
    if impl != "xla" and not pallas_ok:
        raise ValueError(
            f"batch_norm implementation={implementation!r} requested "
            f"but the shape is outside the kernel envelope (need "
            f"C % 64 == 0, C <= 2048, and an 8-aligned divisor of the "
            f"row count; got C={c}, rows={r_total})")
    axes = _bound_axes(axis_names)
    spec = _Spec(
        eps=float(eps), act=act, axes=axes,
        impl="xla" if impl == "xla" else "pallas",
        br=br, interpret=impl == "pallas_interpret",
        has_res=residual is not None)
    x2 = x.reshape(r_total, c)
    res2 = None if residual is None else residual.reshape(r_total, c)
    w2 = (weight if weight is not None
          else jnp.ones((c,), jnp.float32)).reshape(1, c)
    b2 = (bias if bias is not None
          else jnp.zeros((c,), jnp.float32)).reshape(1, c)
    y2, mean, var = _bn_core(spec, x2, w2, b2, res2)
    return y2.reshape(x.shape), mean.reshape(c), var.reshape(c)
