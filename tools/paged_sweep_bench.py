"""Time one ``paged_decode_fused`` call on the chip and split its cost
into a part per row and a part per sweep chunk.

    python3 tools/paged_sweep_bench.py [--out paged_sweep.json]
                                       [--iters 200]

Geometries: Falcon-H1-34B (4 kv heads, 5 query heads a kv head) and
Mistral-7B (8 kv heads, 4 a kv head); head width 128, pages of 16
tokens, bf16 pool, RoPE on.  Shapes: 64 rows x 512 keys, 16 rows x
2048 keys, 64 rows x 128 keys, the row's new key counted: 4, 16 and 1
sweep chunks of 128 keys a row.  A call costs ``c0 + rows x (a + k x
chunks)``: the first two shapes hold the same rows x chunks and give
``a``, the first and the third hold the same rows and give ``k``.

Each shape is timed as ``iters`` calls inside one jit (a scan that
carries the pools, so every call writes its row in place, as a serving
step does), the best of three windows, less the cost of a trivial
call.  Bytes are the live K/V read once plus the new rows, q and o
(``benchmarks/lib/counts.py::paged_decode``'s count) over 819 GB/s.
Refuses to run off a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

HBM_GBS = 819.0                        # TPU v5e (Google Cloud, "TPU v5e")
GEOMETRIES = {"falcon_h1": (4, 5), "mistral": (8, 4)}   # kv heads, rep
SHAPES = [(64, 512), (16, 2048), (64, 128)]             # rows, keys
D, BS, T = 128, 16, 128                # head width, page, sweep chunk


def _trivial_call_s():
    triv = jax.jit(lambda x: x + 1)
    x = jnp.float32(0)
    jax.device_get(triv(x))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        jax.device_get(triv(x))
        best = min(best, time.perf_counter() - t0)
    return best


def _case(hk, rep, rows, keys, seed=0):
    from apex_tpu.ops.rope import rope_cos_sin

    rng = np.random.default_rng(seed)
    pages = keys // BS
    nb = rows * pages + 1                 # block 0 is the null page
    mb = 2048 // BS
    tables = np.zeros((rows, mb), np.int32)
    tables[:, :pages] = (1 + rng.permutation(rows * pages)).reshape(
        rows, pages)
    lengths = np.full((rows,), keys - 1, np.int32)
    bf = jnp.bfloat16
    kp = jnp.asarray(rng.normal(size=(hk, nb, BS, D)), bf)
    vp = jnp.asarray(rng.normal(size=(hk, nb, BS, D)), bf)
    q = jnp.asarray(rng.normal(size=(rows, 1, hk * rep, D)), bf)
    nk = jnp.asarray(rng.normal(size=(rows, 1, hk, D)), bf)
    nv = jnp.asarray(rng.normal(size=(rows, 1, hk, D)), bf)
    cos, sin = rope_cos_sin(2048, D)
    pc = lengths[:, None]
    rope = (jnp.asarray(cos[pc][:, :, None, :]),
            jnp.asarray(sin[pc][:, :, None, :]))
    return (q, nk, nv, kp, vp, jnp.asarray(tables), jnp.asarray(lengths),
            *rope)


def time_call(args, iters, trivial_s):
    from apex_tpu.ops.paged_attention import paged_decode_fused

    @jax.jit
    def many(q, nk, nv, kp, vp, bt, ln, cos, sin):
        def body(carry, _):
            acc, kp, vp = carry
            o, kp, vp = paged_decode_fused(
                q + acc * jnp.bfloat16(1e-8), nk, nv, kp, vp, bt, ln,
                max_seq_len=2048, cos_b=cos, sin_b=sin,
                implementation="pallas")
            return (o.ravel()[0], kp, vp), None

        (acc, kp, vp), _ = jax.lax.scan(
            body, (jnp.bfloat16(0), kp, vp), None, length=iters)
        return acc, kp[0, 0, 0, 0], vp[0, 0, 0, 0]

    jax.device_get(many(*args))                  # compile and warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.device_get(many(*args))
        best = min(best, time.perf_counter() - t0)
    return (best - trivial_s) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="paged_sweep.json")
    ap.add_argument("--iters", type=int, default=200)
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"paged_sweep_bench times the chip; found {dev.platform}")
    import apex_tpu

    trivial_s = _trivial_call_s()
    result = {"device_kind": dev.device_kind,
              "apex_tpu": apex_tpu.__file__, "trivial_call_us":
              trivial_s * 1e6, "geometries": {}}
    for name, (hk, rep) in GEOMETRIES.items():
        calls = {}
        for rows, keys in SHAPES:
            sec = time_call(_case(hk, rep, rows, keys), a.iters, trivial_s)
            nbytes = (2 * hk * D * (rows * keys + rows)
                      + 2 * hk * rep * D * rows) * 2
            calls[f"{rows}x{keys}"] = {
                "us": sec * 1e6, "roofline_pct":
                100 * nbytes / (HBM_GBS * 1e9) / sec}
            print(name, rows, keys, json.dumps(calls[f"{rows}x{keys}"]),
                  flush=True)
        u = {k: v["us"] for k, v in calls.items()}
        a_row = (u["64x512"] - u["16x2048"]) / 48
        k_chunk = (u["64x512"] - u["64x128"]) / (64 * 3)
        chunk_bytes = 2 * hk * T * D * 2
        result["geometries"][name] = {
            "calls": calls, "a_us_per_row": a_row,
            "k_us_per_chunk": k_chunk,
            "c0_us": u["64x128"] - 64 * (a_row + k_chunk),
            "chunk_at_bandwidth_us": chunk_bytes / (HBM_GBS * 1e9) * 1e6}
        print(name, json.dumps(result["geometries"][name]), flush=True)
    with open(a.out, "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
