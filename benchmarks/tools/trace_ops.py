"""The builder's longer look at the trace a ``--trace 1`` run left (the
result line keeps ten operations): every operation over a thousandth of
the busy time, with how often it ran, and the programs' run times.

    python3 benchmarks/tools/trace_ops.py <cell> <tag>

Reads ``.bench_trace/<cell>`` with ``lib/trace.py``; writes
``chiprun_out/<tag>.json`` and prints it.  Never touches jax's devices,
so it may run after the traced run in the same chip call."""

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    from lib import trace

    cell, tag = sys.argv[1:3]
    root = os.path.join(os.path.dirname(__file__), "..", "..")
    s = trace.reduce_dir(os.path.join(root, ".bench_trace", cell))
    floor = 1e-3 * s["busy_s"]
    ops = sorted(((trace.short_name(n), r["count"], r["self_s"],
                   r["total_s"]) for n, r in s["ops"].items()
                  if r["self_s"] >= floor), key=lambda x: -x[2])
    out = {"busy_s": s["busy_s"], "window_s": s["window_s"],
           "ops": [[n, c, round(a, 6), round(b, 6)] for n, c, a, b in ops],
           "modules": {k: [len(v), statistics.median(v), sum(v)]
                       for k, v in s["modules"].items()},
           "idle_gaps": s["idle_gaps"][:12]}
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/{tag}.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out)[:20000])


if __name__ == "__main__":
    main()
