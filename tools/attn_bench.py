"""Isolated flash-attention kernel benchmark (real chip).

Measures fwd-only and fwd+bwd wall time and useful-TFLOP/s of
``apex_tpu.ops.attention.fused_attention`` at given (b, s, h, d) —
the harness behind BASELINE.md's long-context kernel-rate numbers.

Flop accounting (causal): each of the 9 tile matmuls (fwd: QKᵀ, PV;
dq: S-recompute, dP, dQ; dkv: S-recompute, dP, dV, dK) does
2·b·h·s²·d·0.5 flops; fwd-only = 2 matmuls.  Rates are *useful* flops
(recomputes counted, padding not) per second.

Keeps the fixed per-call dispatch+fetch cost off the clock by
iterating inside one jit (lax.scan) and subtracting the measured
trivial-call overhead.

Usage:
    python tools/attn_bench.py [s=32768] [d=64] [h=8] [hk=0] [b=1]
                               [iters=8] [window=0]
(``hk``: GQA kv heads, 0 = MHA; flops are counted per q-head, so GQA
rates are directly comparable with MHA rows.)
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp


def _overhead():
    triv = jax.jit(lambda x: x + 1)
    x = jnp.float32(0)
    jax.device_get(triv(x))
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.device_get(triv(x))
        dts.append(time.perf_counter() - t0)
    return min(dts)


def measure(fn, args, iters, overhead, windows=3):  # graftlint: hot-step
    @jax.jit
    def many(q, *rest):
        def body(c, _):
            # thread the carry into q so the call is NOT loop-invariant
            # (XLA hoists an invariant body out of the scan, measuring
            # nothing); scale keeps the perturbation numerically inert
            out = fn(q + c * jnp.bfloat16(1e-8), *rest)
            # fold a scalar from EVERY output leaf into the carry —
            # an unused leaf's entire producing kernel is DCE'd
            acc = jnp.bfloat16(0)
            for lf in jax.tree.leaves(out):
                acc = acc + lf.ravel()[0].astype(jnp.bfloat16)
            return acc, None

        c, _ = jax.lax.scan(body, jnp.bfloat16(0), None, length=iters)
        return c

    out = many(*args)
    jax.device_get(out)  # graftlint: unsharded(warmup barrier — compile before the timed windows)
    dts = []
    for _ in range(windows):
        t0 = time.perf_counter()
        # graftlint: unsharded(the fetch IS the measurement barrier; its cost is subtracted as `overhead`)
        jax.device_get(many(*args))
        dts.append(time.perf_counter() - t0)
    return (min(dts) - overhead) / iters


def main():
    kw = dict(s=32768, d=64, h=8, hk=0, b=1, iters=8, window=0)
    for a in sys.argv[1:]:
        k, v = a.split("=")
        kw[k] = int(v)
    s, d, h, b, iters = (kw[k] for k in ("s", "d", "h", "b", "iters"))
    window = kw["window"] or None
    hk = kw["hk"] or h                   # GQA: fewer kv heads

    from apex_tpu.ops.attention import fused_attention

    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d),
                          jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hk, d),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hk, d),
                          jnp.bfloat16)

    def fwd(q, k, v):
        return fused_attention(q, k, v, causal=True, window=window,
                               implementation="pallas")

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            o = fused_attention(q, k, v, causal=True, window=window,
                                implementation="pallas")
            return jnp.sum(o.astype(jnp.float32) ** 2)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    overhead = _overhead()
    dt_f = measure(fwd, (q, k, v), iters, overhead)
    dt_fb = measure(fwd_bwd, (q, k, v), iters, overhead)
    # useful (visible) softmax positions: causal triangle, or the band
    # (window > s executes full attention — clamp so flops stay honest)
    w = min(window or s, s)
    pairs = (w - 1) * w / 2 + (s - w + 1) * w     # sum_q min(q+1, w)
    unit = 2 * b * h * pairs * d                  # one tile-matmul
    print(json.dumps({
        "b": b, "s": s, "h": h, "hk": hk, "d": d, "window": window,
        "call_overhead_ms": round(overhead * 1e3, 1),
        "fwd_ms": round(dt_f * 1e3, 2),
        "fwd_tflops": round(2 * unit / dt_f / 1e12, 2),
        "fwd_bwd_ms": round(dt_fb * 1e3, 2),
        "fwd_bwd_tflops": round(9 * unit / dt_fb / 1e12, 2),
    }))


if __name__ == "__main__":
    main()
