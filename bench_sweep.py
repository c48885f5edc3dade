"""Ad-hoc perf sweep for the north-star config (O2 path only).

Usage: BENCH_BATCH=32 BENCH_REMAT_POLICY=dots_with_no_batch_dims_saveable
       python bench_sweep.py
Fresh process per config (each starts from an empty HBM).  Every row
names the device it ran on.
"""

import json
import os

import bench


def main():
    import jax.numpy as jnp

    from apex_tpu.utils import enable_compile_cache

    enable_compile_cache()
    cfg_kw = {
        "remat": os.environ.get("BENCH_REMAT", "1") == "1",
        "remat_policy": os.environ.get("BENCH_REMAT_POLICY",
                                       "nothing_saveable"),
        "dtype": jnp.bfloat16,
    }
    for knob in ("attention_block_q", "attention_block_k",
                 "remat_skip_every"):
        v = os.environ.get("BENCH_" + knob.upper())
        if v:
            cfg_kw[knob] = int(v)
    n_steps = int(os.environ.get("BENCH_STEPS", "20"))
    k_windows = max(1, int(os.environ.get("BENCH_WINDOWS", "2")))
    state, step, _probes, batch, b = bench._build(
        cfg_kw, "O2", jnp.bfloat16, fused=True)
    dt, dts, loss, finite, _ = bench._measure_step(
        state, step, batch, n_steps, k_windows)
    print(json.dumps({
        "batch": b,
        "remat_policy": cfg_kw["remat_policy"] if cfg_kw["remat"] else None,
        "step_ms": round(dt * 1e3, 2),
        "window_ms": [round(d * 1e3, 2) for d in dts],
        "samples_per_sec": round(b / dt, 2),
        "finite": finite,
        **bench.device_fields(),
    }))


if __name__ == "__main__":
    main()
