"""Run the kernel test subset on the REAL TPU chip and record the
result as a repo artifact (round-4 verdict weak #3: interpret-mode CI
cannot catch Mosaic-only miscompiles — e.g. the round-3 GroupNorm
sequential-grid assumption — so each round records one on-chip pass).

The subset is the Pallas-kernel golden suites (attention / layer norm /
ops / optim incl. the fp8-Adam kernel) — the tests whose CPU runs go
through interpret mode and therefore prove nothing about Mosaic
compilation.  Distributed/mesh suites stay CPU-only (one real chip).

Usage:  python tools/onchip_tests.py chiprun_out/ONCHIP_r{N}.json
(the record's path is required, and an existing record is never
overwritten).  One process per chip: the pytest child holds it while it
runs, and this parent imports jax only after the child has exited.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

SUBSET = [
    "tests/test_attention.py",
    "tests/test_batch_norm.py",    # fused BN(+add+ReLU) kernels (ISSUE 3)
    # paged-attention decode kernel (ISSUE 5): scalar-prefetch block
    # tables + the DMA-skip clamp are exactly what interpret mode
    # cannot prove — the gather path must run on the real chip.  The
    # quantized twin (ISSUE 8) adds the int8/fp8 page DMA + the
    # per-row page-scale blocks — Mosaic must compile the in-register
    # dequant and the 1-byte tiles for real
    "tests/test_paged_attention.py",
    # prefix-shared CoW pages + speculative decoding (ISSUE 7): the
    # refcount/trie accounting and the drafted-step verify rollback
    # must hold against REAL pool pages — on chip a leaked or
    # double-freed page corrupts a co-tenant's KV instead of a numpy
    # shadow, and the spec_step executable must Mosaic-compile at its
    # 1+K width.  TestQuantizedKV (ISSUE 8) additionally pins the
    # quantize-on-write scatter + scale reset against real HBM pages
    "tests/test_paged_serving.py",
    # fused decode epilogue (ISSUE 14): the one-pass sampling kernel
    # must Mosaic-compile for real (radix descents, in-kernel threefry
    # replay, VMEM scratch) and its key-for-key chain identity to
    # sample_dynamic must hold on-chip where the COMPILED kernel — not
    # interpret mode — draws the tokens
    "tests/test_fused_sampling.py",
    "tests/test_layer_norm.py",
    "tests/test_ops.py",
    "tests/test_optim.py",
    # resilience layer (ISSUE 4): checkpoint atomicity/manifests and
    # the fault/rewind/preempt machinery against the REAL TPU runtime
    # — interpret-mode CPU proves nothing about on-chip donation,
    # device_get snapshots, or orbax sharded writes
    "tests/test_resilience.py",
    # serving fleet (ISSUE 6): the router/breaker unit tier plus the
    # chaos soaks (replica kill + drain) — on chip the kill path
    # abandons REAL device buffers and migration re-prefills on a
    # survivor's live pool, which CPU timing cannot exercise honestly
    "tests/test_fleet.py",
    # graftlint v2 runtime twin (ISSUE 9): the lock sanitizer's own
    # unit tier, and the chaos soaks below run the real stack under
    # strict instrumentation — on chip the worker/supervisor timing is
    # the honest interleaving the order recorder is meant to observe
    "tests/test_lockcheck.py",
    # graftlint v3 runtime twin (ISSUE 10): the numerics sanitizer's
    # unit tier — on chip the fp16 downcast-overflow and underflow
    # paths run against real MXU/VPU rounding, not the CPU emulation
    "tests/test_numcheck.py",
    # graftlint v4 runtime twin (ISSUE 16): the placement sanitizer's
    # unit tier — on chip the declared-vs-actual comparisons run
    # against REAL committed shardings (not the virtual CPU mesh) and
    # the transfer windows see real device->host DMA, not zero-copy
    "tests/test_shardcheck.py",
    # ZeRO-1/2 (ISSUE 11): the reduce-scatter/all-gather choreography,
    # the int8 wire leg and the sharded-checkpoint placement must run
    # against REAL ICI collectives and per-device HBM — the virtual
    # CPU mesh proves the math, not the placement or the wire
    "tests/test_zero.py",
    # tensor-parallel paged serving (ISSUE 13): the shard_map'ed paged
    # kernel (per-chip head slices, replicated block tables), the
    # sharded pool/scale placement fixed point behind the 5×1 retrace
    # budgets, and the TP↔single-chip token identity must hold against
    # REAL per-chip HBM pools and ICI all-reduces — the virtual CPU
    # mesh proves the math, not the placement or the wire
    "tests/test_tp_serving.py",
    # the planner (ISSUE 15): pure host-side arithmetic, but the
    # autotune-adoption seam reads the chip's REAL cache entries and
    # the emitted placements commit onto real devices — cheap to run,
    # catches a planner/engine key drift on the hardware that matters
    "tests/test_plan.py",
    # pipeline parallelism (ISSUE 20): the 1F1B schedule's ppermute
    # ring, the stage-local ZeRO placement and the single-trace budget
    # must hold against REAL ICI neighbor links and per-chip HBM — the
    # virtual CPU mesh proves the schedule math, not the wire or the
    # per-stage residency
    "tests/test_pipeline.py",
    "tests/test_chaos.py",
]


def main():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if len(sys.argv) != 2:
        sys.exit(__doc__)
    record = os.path.abspath(sys.argv[1])
    if os.path.exists(record):
        sys.exit(f"{record} exists — a record is never overwritten; "
                 f"name the next one")
    env = dict(os.environ)
    env["APEX_TPU_TEST_PLATFORM"] = os.environ.get(
        "APEX_TPU_TEST_PLATFORM", "tpu")
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", *SUBSET, "-q"],
        cwd=root, env=env, capture_output=True, text=True,
        timeout=7200)
    dt = time.time() - t0
    result_line, m, failed = _parse_summary(proc.stdout or "")
    # only now: the child that held the chip has exited
    import jax

    out = {
        "artifact": "on-chip kernel test pass",
        "platform_env": env["APEX_TPU_TEST_PLATFORM"],
        "result_line": result_line,
        "passed": int(m.group(1)) if m else 0,
        "failed": int(failed.group(1)) if failed else 0,
        "returncode": proc.returncode,
        "wall_seconds": round(dt, 1),
        "jax": jax.__version__,
        "libtpu": _libtpu_version(),
        "date": time.strftime("%Y-%m-%d"),
        "subset": SUBSET,
    }
    os.makedirs(os.path.dirname(record), exist_ok=True)
    with open(record, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))
    if proc.returncode != 0:
        print(proc.stdout[-3000:], file=sys.stderr)
        sys.exit(1)


def _parse_summary(stdout: str):
    """Find pytest's ``N passed``/``N failed`` summary in the output tail.

    On green runs pytest -q prints the summary line above trailing
    warnings-summary / coverage chatter, so parsing only the very last
    line recorded 0/0 for successful passes.  Scan bottom-up (no line
    cap: a long tail must not push the summary out of reach; the
    count patterns cannot false-match ordinary test output) for the
    first line with a pass/fail/error count.
    """
    lines = stdout.strip().splitlines()
    for line in reversed(lines):
        m = re.search(r"(\d+) passed", line)
        failed = re.search(r"(\d+) (?:failed|error)", line)
        if m or failed:
            return line, m, failed
    return (lines[-1] if lines else ""), None, None


def _libtpu_version():
    try:
        import importlib.metadata as md

        return md.version("libtpu")
    except Exception:
        return None


if __name__ == "__main__":
    main()
