"""The builder's own measuring; the driver runs ``run.py`` itself.

    python3 benchmarks/tools/measure.py runs <tag> <cell>:<seed>:<seconds>:<trace> ...
    python3 benchmarks/tools/measure.py readings <cell> <seconds> <kind> <seed>[,<key>=<number>...] ... [--rehearse]

``runs``: each run in a process of its own, as the driver makes them
(this parent never touches jax); every result line is kept in
``chiprun_out/<tag>.jsonl``.

``readings``: the numbers that ``correct`` compares, over several seeds
in ONE process, kept in ``chiprun_out/readings-<unix time>.jsonl`` (a
chip call brings back whole files, so one name would be overwritten).  ``kind`` is
``program``, ``control``, a fault's name, or ``control+<fault>`` (the
fault planted in the reference put in the program's place).  ``key=number`` lays that
traffic parameter over the cell's for the one run: a rate sweep is
``readings <cell> 40 program 7,rate_per_s=0.9 7,rate_per_s=1.2 ...``.
A control or fault that crashes or gives no number has failed and sets
no upper reading."""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def runs(tag, specs):
    out = open(f"chiprun_out/{tag}.jsonl", "a")
    for spec in specs:
        cell, seed, seconds, trace = spec.split(":")
        cmd = [sys.executable, "benchmarks/run.py", "--workload", cell,
               "--seed", seed, "--seconds", seconds, "--trace", trace]
        t0 = time.time()
        p = subprocess.run(cmd, capture_output=True, text=True)
        wall = time.time() - t0
        last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
        try:
            line = json.loads(last)
        except ValueError:
            line = None
        rec = {"cell": cell, "seed": int(seed), "seconds": float(seconds),
               "trace": int(trace), "rc": p.returncode, "wall_s": wall,
               "line": line, "stderr_tail": p.stderr[-3000:]}
        out.write(json.dumps(rec) + "\n")
        out.flush()
        if line is None:
            print(spec, "rc", p.returncode, "NO LINE", p.stderr[-1500:])
            continue
        m = {k: round(v["value"], 4) for k, v in line["metrics"].items()}
        c = {k: float(f"{v['value']:.3g}") for k, v in line["checks"].items()}
        print(spec, f"rc={p.returncode} wall={wall:.0f}s "
              f"correct={line['correct']} att={line['attempted']} "
              f"fail={line['failed']} "
              f"peak={line['device']['memory_peak_bytes'] / 2**30:.2f}GiB",
              {k: line["device"].get(k) for k in ("busy_s", "window_s")
               if k in line["device"]}, m, c, flush=True)
        for k, rows in (line.get("breakdown") or {}).items():
            print("   ", k, [[n[:50], round(s, 4)] for n, s in rows[:6]])


def readings(cell, seconds, kind, items, rehearse):
    import run

    out = open(f"chiprun_out/readings-{int(time.time())}.jsonl", "a")
    for item in items:
        seed, *pairs = item.split(",")
        over = {k: float(v) if "." in v else int(v)
                for k, v in (p.split("=") for p in pairs)}
        rec = {"cell": cell, "kind": kind, "seed": int(seed),
               "seconds": float(seconds), "override": over,
               "rehearse": bool(rehearse)}
        try:
            line = run.run_cell(
                ["--workload", cell, "--seed", seed, "--seconds", seconds,
                 "--trace", "0"] + rehearse,
                fault=next((k for k in kind.split("+")
                            if k not in ("program", "control")), None),
                control="control" in kind.split("+"),
                override={"traffic_params": over} if over else None)
            rec.update(
                correct=line["correct"], attempted=line["attempted"],
                failed=line["failed"], notes=line["notes"],
                metrics={k: v["value"] for k, v in line["metrics"].items()},
                checks={k: v["value"] for k, v in line["checks"].items()})
        except Exception as e:                      # noqa: BLE001
            rec["error"] = repr(e)[:500]
        print(json.dumps(rec), flush=True)
        out.write(json.dumps(rec) + "\n")
        out.flush()


def main():
    args = [a for a in sys.argv[1:] if a != "--rehearse"]
    os.makedirs("chiprun_out", exist_ok=True)
    if args[0] == "runs":
        runs(args[1], args[2:])
    elif args[0] == "readings":
        readings(args[1], args[2], args[3], args[4:],
                 ["--rehearse"] if "--rehearse" in sys.argv else [])
    else:
        sys.exit(__doc__)


if __name__ == "__main__":
    main()
