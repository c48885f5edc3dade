"""Reduction of a profiler trace (``*.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  A TPU trace
has one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops`` line holds
one event per executed HLO operation (a ``while`` or a called
computation encloses the events of its body) and whose ``XLA Modules``
line holds one event per executed program; the ``/host:CPU`` plane has
one line per host thread, with the runtime's spans and the benchmark's
own ``bench/...`` annotations.  All share one clock.

``reduce_dir`` returns:

``busy_s``      seconds in which an operation ran, the union of the op
                intervals, averaged over the chips used
``window_s``    first to last event of the whole trace
``ops``         per op (its HLO text): count, total and self seconds (self = not
                covered by an enclosed event), and a ``label``: the name
                with the event's text attributes, which carry the
                ``jax.named_scope`` path and the kernel's name
``modules``     per program name: the list of its runs' seconds
``device_ops``  [[name, self seconds]] largest first
``idle_gaps``   [[what the host was doing, seconds]] largest first: each
                idle gap of the device goes to the innermost host span
                that covers most of it

On a CPU (the rehearsal) the XLA client's threads stand in for a device
so that the same code runs; its numbers mean nothing."""

import bisect
import glob
import os
import re

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
CPU_DEVICE_LINES = ("tf_XLAPjRtCpuClient", "tf_XLAEigen", "tf_XLATfrtCpuClient")
GAPS_CONSIDERED = 400


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def self_times(events):
    """``events``: (start, end, name).  Yields (name, self ns): the
    event's time not covered by events it encloses."""
    stack = []                      # [end, name, self]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            top = stack.pop()
            yield top[1], top[2]
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    while stack:
        top = stack.pop()
        yield top[1], top[2]


def short_name(text):
    """``%fusion.37 = (f32[30528,1024]{...}, ...) fusion(...)`` ->
    ``fusion (f32[30528,1024]{...``: the instruction without its number
    and the head of what it makes.  Unrolled layers give every layer an
    instruction of its own; under this name they add up, and two
    programs' ``while`` stay apart by what they carry."""
    name, _, rest = text.partition(" = ")
    name = re.sub(r"\.\d+$", "", name.lstrip("%"))
    return (name + " " + rest[:48]).strip()


def _label(event):
    parts = [event.name]
    for k, v in event.stats:
        if isinstance(v, str) and v:
            parts.append(v)
    return " ".join(parts)


def reduce_file(path, chips=1):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_lines, module_lines, host_lines = [], [], []
    planes = list(data.planes)
    tpu = [p for p in planes if p.name.startswith("/device:")
           and "SparseCore" not in p.name]
    for p in tpu[:chips]:
        for ln in p.lines:
            if ln.name == OPS_LINE:
                device_lines.append(ln)
            elif ln.name == MODULES_LINE:
                module_lines.append(ln)
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            if not tpu and ln.name.startswith(CPU_DEVICE_LINES):
                device_lines.append(ln)
            else:
                host_lines.append(ln)

    t_first, t_last = float("inf"), float("-inf")
    ops, busy_total, merged_all = {}, 0.0, []
    for ln in device_lines:
        evs = []
        for e in ln.events:
            if e.duration_ns <= 0:
                continue
            s, t = e.start_ns, e.start_ns + e.duration_ns
            evs.append((s, t, e.name))
            rec = ops.get(e.name)
            if rec is None:
                rec = ops[e.name] = {"count": 0, "total_s": 0.0,
                                     "self_s": 0.0, "label": _label(e)}
            rec["count"] += 1
            rec["total_s"] += e.duration_ns * 1e-9
        for name, ns in self_times(evs):
            ops[name]["self_s"] += ns * 1e-9
        merged = union([(s, t) for s, t, _ in evs])
        busy_total += sum(t - s for s, t in merged) * 1e-9
        if not merged_all:
            merged_all = merged          # gaps are read on the first chip
        if merged:
            t_first = min(t_first, merged[0][0])
            t_last = max(t_last, merged[-1][1])

    modules = {}
    for ln in module_lines:
        for e in ln.events:
            modules.setdefault(e.name, []).append(e.duration_ns * 1e-9)

    host = []                            # per line: sorted (start, end, name)
    for ln in host_lines:
        evs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                     for e in ln.events if e.duration_ns > 0)
        if evs:
            host.append((ln.name, evs, [s for s, _, _ in evs]))
            t_first = min(t_first, evs[0][0])
            t_last = max(t_last, max(t for _, t, _ in evs))
    window_ns = max(0.0, t_last - t_first) if t_first < t_last else 0.0

    gaps = []
    edges = [[t_first, t_first]] + merged_all + [[t_last, t_last]]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    blame = {}
    for a, b in gaps[:GAPS_CONSIDERED]:
        best = None                      # (duration, name): innermost
        for _, evs, starts in host:
            i = bisect.bisect_right(starts, b)
            for s, t, name in reversed(evs[max(0, i - 64):i]):
                cover = min(t, b) - max(s, a)
                if cover >= 0.5 * (b - a) and name != "bench/window":
                    if best is None or t - s < best[0]:
                        best = (t - s, name)
        name = best[1] if best else "no host span"
        blame[name] = blame.get(name, 0.0) + (b - a) * 1e-9
    rest = sum(b - a for a, b in gaps[GAPS_CONSIDERED:]) * 1e-9
    if rest:
        blame["shorter gaps"] = rest

    by_short = {}
    for n, r in ops.items():
        by_short[short_name(n)] = by_short.get(short_name(n), 0.0) \
            + r["self_s"]
    return {
        "busy_s": busy_total / max(1, len(tpu[:chips])), "window_s": window_ns * 1e-9,
        "ops": ops, "modules": modules,
        "device_ops": sorted(by_short.items(), key=lambda x: -x[1]),
        "idle_gaps": sorted(([n, s] for n, s in blame.items()),
                            key=lambda x: -x[1]),
    }


def reduce_dir(trace_dir, chips=1):
    return reduce_file(find_xplane(trace_dir), chips)


def events_matching(summary, patterns):
    """(events, distinct instructions, total seconds) of the ops whose
    label holds every substring of ``patterns``."""
    n = names = 0
    total = 0.0
    for rec in summary["ops"].values():
        if all(p in rec["label"] for p in patterns):
            n += rec["count"]
            names += 1
            total += rec["total_s"]
    return n, names, total
