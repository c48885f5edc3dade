"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This file knows no cell, configuration, driver, traffic mix or metric by
name.  ``BENCHMARK.json`` (one directory up) names them; each is a file
of its own, found by that name:

    workloads/<cell>.json   driver, traffic generator and its parameters
    configs/<config>.json   published sizes, cuts, deployment settings
    drivers/<driver>.py     ``run(ctx) -> dict``: sets the system up from
                            the seed, drives the window, checks the output
    traffic/<generator>.py  ``generate(params, seed, seconds, ...)``
    metrics/<metric>.json   the per-layer metric's reader and arguments
    readers/<reader>.py     ``read(args, run) -> number or None``

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end with ``--trace 0``,
per-layer with ``--trace 1``), ``device``, with a trace ``breakdown``,
and last ``checks``: every number compared beside its limit.  Off a TPU
the command fails before doing any work; ``--rehearse`` runs the cell's
tiny ``rehearse`` sizes on whatever platform there is, reports that
platform and sets ``rehearse`` in ``device``, so a rehearsal is never
read as a chip run."""

import time

T_START = time.perf_counter()

import argparse
import importlib.util
import json
import pathlib
import sys
import threading

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_json(kind, name):
    return json.loads((HERE / kind / f"{name}.json").read_text())


def load_module(kind, name):
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def merged(base, over):
    """``base`` with ``over``'s keys laid on top, dicts merged."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(
            v, dict) and isinstance(out.get(k), dict) else v
    return out


class Tracer:
    """The profiler over a part of the window, started and stopped from
    a thread of its own so that the load generator is not held up."""

    def __init__(self, out_dir):
        self.out_dir = str(out_dir)
        self.t_on = self.t_off = None
        self._timers = []

    def schedule(self, start_after_s, duration_s):
        for delay, fn in ((start_after_s, self._on),
                          (start_after_s + duration_s, self.stop)):
            t = threading.Timer(delay, fn)
            t.daemon = True
            t.start()
            self._timers.append(t)

    def _on(self):
        import shutil

        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)   # keep one trace

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.t_on = time.perf_counter()

    def stop(self):
        import jax

        if self.t_on is not None and self.t_off is None:
            self.t_off = time.perf_counter()
            jax.profiler.stop_trace()

    def finish(self):
        """Wait for the trace to be written; returns its directory or
        None where no trace was taken."""
        for t in self._timers:
            t.join()
        self.stop()
        return self.out_dir if self.t_on is not None else None


class Context:
    """What a driver gets: the cell, its configuration, the run's
    arguments, and the harness's clock, tracer and memory reading."""

    def __init__(self, args, cell, config, device, n_devices, fault,
                 control):
        self.cell, self.config = cell, config
        self.name = args.workload
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.fault, self.control = fault, control
        self.device, self.n_devices = device, n_devices
        self.t_window = None
        self.marks = {}                  # set-up's parts, for the notes
        self.memory_peak_bytes = None
        self.tracer = Tracer(ROOT / ".bench_trace" / self.name) \
            if self.trace else None

    def load(self, kind, name):
        return load_module(kind, name)

    def mark(self, name):
        """Seconds since the process started, kept under ``name``."""
        self.marks[name] = round(time.perf_counter() - T_START, 2)

    def annotate(self, name):
        import jax

        return jax.profiler.TraceAnnotation(f"bench/{name}")

    def open_window(self):
        """Set-up ends here.  Returns the window's start on the host
        clock; with ``--trace 1`` the profiler covers the part of the
        window that the cell's ``trace`` entry names."""
        self.t_window = time.perf_counter()
        if self.tracer is not None:
            tr = self.cell.get("trace", {})
            dur = min(float(tr.get("seconds", 3.0)), self.seconds)
            start = min(float(tr.get("start_share", 0.5)) * self.seconds,
                        self.seconds - dur)
            self.tracer.schedule(max(start, 0.0), dur)
        return self.t_window

    def read_memory_peak(self):
        """Peak bytes on the fullest chip.  ``peak_bytes_in_use`` alone
        leaves out the programs' temporaries on this runtime, which it
        counts under ``peak_bytes_reserved`` (PERF.md)."""
        import jax

        peak = 0
        for d in jax.devices()[: self.cell["chips"]]:
            st = d.memory_stats() or {}
            peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                       + int(st.get("peak_bytes_reserved", 0)))
        self.memory_peak_bytes = peak
        return peak


def applies(metric, cell_name, reported):
    """Does ``metric`` of BENCHMARK.json belong to this cell?"""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def run_cell(argv, fault=None, control=False, need_chip=True, override=None):
    """One run; returns the result line as a dict.  ``fault`` and
    ``control`` are for the benchmark's own tests and readings: they
    break the timed path underneath or put the lower-precision
    reference in its place (see the drivers); ``override`` lays keys
    over the cell's file, for the builder's readings (a rate sweep)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform; reports the "
                         "platform it ran on")
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {w["name"]: w for w in bench["workloads"]}.get(args.workload)
    if entry is None:
        sys.exit(f"benchmark: no cell {args.workload!r} in BENCHMARK.json")
    cell = merged(load_json("workloads", args.workload), entry)
    config = load_json("configs", entry["config"])
    cell = merged(cell, override or {})
    if args.rehearse:
        cell = merged(cell, cell.get("rehearse", {}))
        config = merged(config, config.get("rehearse", {}))

    import jax

    devices = jax.devices()
    device = devices[0]
    if need_chip and device.platform != "tpu" and not args.rehearse:
        sys.exit(f"benchmark: jax found no TPU (platform "
                 f"{device.platform!r}); nothing was run")
    if need_chip and len(devices) < cell["chips"] and not args.rehearse:
        sys.exit(f"benchmark: cell {args.workload!r} needs "
                 f"{cell['chips']} chips, jax found {len(devices)}")

    from apex_tpu.utils import enable_compile_cache

    enable_compile_cache()
    # every program of a run goes to the cache, the small ones too, so
    # that a second run's set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    ctx = Context(args, cell, config, device, len(devices), fault, control)
    ctx.mark("devices")
    out = load_module("drivers", cell["driver"]).run(ctx)
    if ctx.memory_peak_bytes is None:
        ctx.read_memory_peak()

    e2e = dict(out["end_to_end"], setup_s=ctx.t_window - T_START)
    info = {"platform": device.platform, "kind": device.device_kind,
            "count": len(devices),
            "memory_peak_bytes": ctx.memory_peak_bytes}
    if args.rehearse:
        info["rehearse"] = True          # tiny sizes: never a chip run
    line = {"correct": None, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": {}, "device": info}
    if not ctx.trace:
        for m in bench["end_to_end"]:
            if applies(m, args.workload, e2e) and m["name"] in e2e:
                line["metrics"][m["name"]] = {
                    "value": e2e[m["name"]], "unit": m["unit"]}
    else:
        from lib import peaks, trace as trace_lib

        trace_dir = ctx.tracer.finish()
        summary = trace_lib.reduce_dir(trace_dir, cell["chips"])
        info["busy_s"], info["window_s"] = summary["busy_s"], \
            summary["window_s"]
        line["breakdown"] = {
            k: [list(x) for x in summary[k][:10] if x[1] > 0]
            for k in ("device_ops", "idle_gaps")}
        run = {"facts": out.get("facts", {}), "trace": summary,
               "config": config, "cell": cell, "end_to_end": e2e,
               "peaks": peaks.peaks_for(device.device_kind)
               if device.platform == "tpu" else None,
               "traced": (ctx.tracer.t_on - ctx.t_window,
                          ctx.tracer.t_off - ctx.t_window)}
        for m in bench["per_layer"]:
            if not applies(m, args.workload, e2e):
                continue
            spec = load_json("metrics", m["name"])
            value = load_module("readers", spec["reader"]).read(
                spec.get("args", {}), run)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
    checks = out["checks"]
    line["correct"] = bool(checks) and all(
        c["value"] <= c["limit"] for c in checks.values())
    # beside the contract's keys, for the reader of a log: the set-up
    # time of a traced run too, and the driver's notes on the comparison
    line["notes"] = dict(out.get("notes", {}), setup_s=e2e["setup_s"],
                         setup_marks=ctx.marks)
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return line


def main():
    line = run_cell(sys.argv[1:])
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
