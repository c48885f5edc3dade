"""MXU dot_general-form probe — measures, on the real chip, the rate of
every matmul orientation the flash-attention kernels could use at
head_dim=64, to ground the d=64 redesign in hardware facts rather than
folklore.

Context (VERDICT round 3, missing #1): the long-context legs run at
11-20% of roofline because d=64 half-fills the MXU.  The 128-deep
systolic array gives a hard 50% utilization cap to any matmul whose
CONTRACTION dim is 64 (each output element is a 64-term dot product —
half the array depth is idle by construction, and block-diagonal
head-packing just moves the waste into multiply-by-zero).  But the
OUTPUT-dim waste (N=64 in P@V, dS@K, Pᵀ@dO, dSᵀ@Q) is removable by
computing the transposed output (N becomes bq/bk, M=64): whether that
pays depends on how Mosaic lowers non-NN dot_general forms, which this
probe measures.

Forms probed (all bf16 operands, f32 accumulation, 512-tiles):
  nn_full   (512,512)@(512,512)             reference full-rate
  nn_qk     (512,64)@(64,512)    K=64       current QKᵀ   (cap: 50%)
  nn_pv     (512,512)@(512,64)   N=64       current P@V   (cap: 50%)
  tn_pv     dg((512,64),(512,512),c0/c0)    proposed accᵀ += Vᵀ@Pᵀ form
  tn_dq     same shape class                proposed dqᵀ  += Kᵀ@dSᵀ
  nt_dv     dg((512,64),(512,512),c0/c1)    proposed dvᵀ  += dOᵀ@P
  nn_T      (512,64)ᵀ-free: k@qᵀ M=512,K=64 transposed-score form
  xpose     (512,64) -> (64,512) transpose  per-step relayout cost

Usage: python tools/mxu_probe.py   (on the chip; idle machine)
"""

from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

S, D = 512, 64

# a jitted call carries a FIXED dispatch+fetch cost, so each form
# runs enough grid steps to put ~0.5 s of real work on the clock, and
# the measured trivial-call overhead is subtracted
_G_BY_FORM = {  # steps sized for ~0.5s assuming ~100 TFLOP/s
    "nn_full": 1 << 18, "nn_qk": 1 << 20, "nn_pv": 1 << 20,
    "tn": 1 << 20, "nt": 1 << 20, "nn_T": 1 << 20, "xpose": 1 << 20,
}


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, form, n_steps):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    a = a_ref[:]
    b = b_ref[:]
    f32 = jnp.float32
    if form == "nn_full":          # (S,S)@(S,S)
        r = jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                preferred_element_type=f32)
    elif form == "nn_qk":          # (S,D)@(D,S): K=64
        r = jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                preferred_element_type=f32)
    elif form == "nn_pv":          # (S,S)@(S,D): N=64
        r = jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                preferred_element_type=f32)
    elif form == "tn":             # dg((S,D),(S,S), c0/c0) -> (D,S)
        r = jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                                preferred_element_type=f32)
    elif form == "nt":             # dg((S,D),(S,S), c0/c1) -> (D,S)
        r = jax.lax.dot_general(a, b, (((0,), (1,)), ((), ())),
                                preferred_element_type=f32)
    elif form == "nn_T":           # (S,D)@(D,S) M=S,K=64 (k@qT)
        r = jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                preferred_element_type=f32)
    elif form == "xpose":          # relayout cost probe
        r = jnp.transpose(a).astype(f32)        # (S,D) -> (D,S)
    else:
        raise ValueError(form)
    acc_ref[:] += r

    @pl.when(g == n_steps - 1)
    def _():
        o_ref[:] = acc_ref[:]


_SHAPES = {
    # form: (a_shape, b_shape, out_shape, useful_flops_per_step)
    "nn_full": ((S, S), (S, S), (S, S), 2 * S * S * S),
    "nn_qk": ((S, D), (D, S), (S, S), 2 * S * S * D),
    "nn_pv": ((S, S), (S, D), (S, D), 2 * S * S * D),
    "tn": ((S, D), (S, S), (D, S), 2 * S * S * D),
    "nt": ((S, D), (S, S), (D, S), 2 * S * S * D),
    "nn_T": ((S, D), (D, S), (S, S), 2 * S * S * D),
    "xpose": ((S, D), (D, S), (D, S), 0),
}


def _overhead():
    """Fixed per-call dispatch+fetch cost (subtracted)."""
    triv = jax.jit(lambda x: x + 1)
    x = jnp.float32(0)
    jax.device_get(triv(x))
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.device_get(triv(x))
        dts.append(time.perf_counter() - t0)
    return min(dts)


def probe(form, overhead):
    a_shape, b_shape, out_shape, flops = _SHAPES[form]
    g_steps = _G_BY_FORM[form]
    a = jax.random.normal(jax.random.PRNGKey(0), a_shape, jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(1), b_shape, jnp.bfloat16)
    fn = pl.pallas_call(
        functools.partial(_kernel, form=form, n_steps=g_steps),
        grid=(g_steps,),
        in_specs=[
            pl.BlockSpec(a_shape, lambda g: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(b_shape, lambda g: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(out_shape, lambda g: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM(out_shape, jnp.float32)],
    )
    jfn = jax.jit(fn)
    out = jfn(a, b)
    jax.device_get(out.ravel()[0])              # waits for the run
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = jfn(a, b)
        jax.device_get(out.ravel()[0])
        dts.append(time.perf_counter() - t0)
    dt = (min(dts) - overhead) / g_steps
    return {
        "form": form,
        "ns_per_step": round(dt * 1e9, 1),
        "tflops": round(flops / dt / 1e12, 2) if flops else None,
        "windows_ms_total": [round(d * 1e3) for d in dts],
    }


def main():
    forms = sys.argv[1:] or list(_SHAPES)
    overhead = _overhead()
    print(json.dumps({"call_overhead_ms": round(overhead * 1e3, 1)}))
    for f in forms:
        print(json.dumps(probe(f, overhead)))


if __name__ == "__main__":
    main()
