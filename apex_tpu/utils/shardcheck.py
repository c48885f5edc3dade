"""Runtime placement sanitizer — the dynamic oracle behind graftlint's
sharding pass.

``tools/graftlint``'s sharding rules catch SPMD placement bugs
*statically*: unbound collective axis names, ``P`` specs naming axes
the mesh in scope doesn't have, out_specs claiming replication over
shard-divergent bodies, host syncs inside ``# graftlint: hot-step``
functions, donated buffers read after the donating call (see
``docs/graftlint.md``).  This module is the matching *runtime*
tripwire, the way :mod:`apex_tpu.utils.lockcheck` backs the
concurrency rules and :mod:`apex_tpu.utils.numcheck` the precision
rules: the declared placement contracts — ``paged_pool_shardings`` for
a tensor-parallel paged engine's pool, replicated slot state,
``zero_shardings`` / planner-emitted specs for a ZeRO train state —
are compared against what the compiled executables actually return.

Two seams:

- **declared vs actual output shardings** — :func:`instrument` wraps
  an engine's step entry points (the ``retrace_guard``-wrapped
  ``_step`` / ``_decode`` / ``_prefill`` / ``_spec`` / ``_admit`` /
  ``_release``); after each call the output leaves' ``.sharding`` is
  checked against the engine's committed placement (pool sharded on
  the ``tensor`` axis per :func:`~apex_tpu.serving.cache.
  paged_pool_shardings`, slot state replicated).  A silent fallback to
  replication — the classic TP seam failure, a missing constraint that
  XLA "helpfully" papers over — shows up as a mismatch here even
  though every numeric is correct.  :func:`wrap_step` does the same
  for a free-standing train step against an explicit declared tree
  (the ZeRO soak passes ``zero_shardings(state, mesh=mesh)``).
- **device→host transfer accounting** — a :mod:`jax.monitoring`
  listener counts transfer-shaped events (and their ``num_bytes``
  metadata when present) and attributes any that land while an
  instrumented step executes.  A step function is pure device work by
  contract — the engines' single per-step host sync happens *after*
  the step returns — so a transfer inside the step window is recorded
  as a violation in strict mode.  (CPU zero-copies defeat
  ``jax.transfer_guard``, so the event seam is the portable one;
  tests inject synthetic events through the same listener.)

Violations are recorded, never raised at the fault site —
``assert_clean()`` raises :class:`ShardCheckError` at soak end, the
lockcheck/numcheck contract.  ``strict=None`` follows
``APEX_TPU_SHARDCHECK=strict`` (the chaos-smoke CI setting); default
non-strict is observe-only (site histograms, transfer counters, no
violations).

Usage (the chaos soaks)::

    from apex_tpu.utils import shardcheck

    shardcheck.reset()
    shardcheck.instrument(server, strict=True)   # engines, in place
    ... run the soak ...
    shardcheck.assert_clean()
    shardcheck.uninstrument()

Instrumentation is per-object (it swaps instance attributes, like the
lock sanitizer) and idempotent; ``uninstrument()`` restores every
wrapped step and removes the monitoring listener.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import jax

__all__ = [
    "ShardCheckError",
    "instrument",
    "uninstrument",
    "wrap_step",
    "env_strict",
    "reports",
    "reset",
    "assert_clean",
    "summary",
    "site_shardings",
]

_ENV = "APEX_TPU_SHARDCHECK"


class ShardCheckError(AssertionError):
    """Raised by :func:`assert_clean` when the sanitizer has reports."""


def env_strict() -> bool:
    """True when ``APEX_TPU_SHARDCHECK=strict`` (the chaos-smoke CI
    job's setting)."""
    return os.environ.get(_ENV, "").strip().lower() == "strict"


# ---------------------------------------------------------------- recorder

class _Recorder:
    """Process-wide stats + violation log (one lock, tiny sections)."""

    def __init__(self):
        self._mutex = threading.Lock()
        # site -> {"checked": n, "mismatched": m, "calls": c}
        self.sites: Dict[str, Dict[str, int]] = {}
        self.d2h_events = 0
        self.d2h_bytes = 0
        # site -> transfer events attributed to that step window
        self.transfer_sites: Dict[str, int] = {}
        self.violations: List[str] = []
        self._reported: Set[Tuple] = set()

    def report(self, key: Tuple, message: str) -> None:
        # one report per distinct site — a soak loop hitting the same
        # breach a thousand times is one finding
        with self._mutex:
            if key in self._reported:
                return
            self._reported.add(key)
            self.violations.append(message)


_recorder = _Recorder()
_strict = False
_listening = False
# thread-local stack of instrumented-step site names currently running
_window = threading.local()
#: (owner __dict__, attr name, original callable)
_originals: List[Tuple[dict, str, Any]] = []


def reports() -> List[str]:
    """Every violation recorded since the last :func:`reset`."""
    with _recorder._mutex:
        return list(_recorder.violations)


def reset() -> None:
    """Clear histograms, counters and the violation log (test
    isolation).  Instrumentation, if installed, keeps recording into
    fresh state."""
    with _recorder._mutex:
        _recorder.sites.clear()
        _recorder.d2h_events = 0
        _recorder.d2h_bytes = 0
        _recorder.transfer_sites.clear()
        _recorder.violations.clear()
        _recorder._reported.clear()


def assert_clean() -> None:
    """Raise :class:`ShardCheckError` listing every recorded violation
    (no-op when clean) — the soak's closing assertion."""
    found = reports()
    if found:
        listing = "\n  ".join(found)
        raise ShardCheckError(
            f"shardcheck: {len(found)} violation(s):\n  {listing}")


def site_shardings() -> Dict[str, Dict[str, int]]:
    """Per-site placement-check tallies (leaves checked / mismatched /
    step calls observed)."""
    with _recorder._mutex:
        return {site: dict(stats)
                for site, stats in _recorder.sites.items()}


def summary() -> Dict[str, Any]:
    """One-shot placement summary for soak reports: per-site check
    tallies, transfer-event counts (total and attributed to step
    windows), and the violation count."""
    with _recorder._mutex:
        return {
            "sites": {s: dict(st) for s, st in _recorder.sites.items()},
            "d2h_events": _recorder.d2h_events,
            "d2h_bytes": _recorder.d2h_bytes,
            "transfer_sites": dict(_recorder.transfer_sites),
            "violations": len(_recorder.violations),
        }


# ---------------------------------------------------- transfer accounting

_TRANSFER_MARKERS = ("transfer", "device_to_host", "d2h")


def _window_stack() -> List[str]:
    stack = getattr(_window, "stack", None)
    if stack is None:
        stack = _window.stack = []
    return stack


def _on_monitoring_event(event: str, **kwargs: Any) -> None:
    name = event.lower()
    if not any(m in name for m in _TRANSFER_MARKERS):
        return
    nbytes = 0
    for k in ("num_bytes", "bytes", "size"):
        v = kwargs.get(k)
        if isinstance(v, (int, float)):
            nbytes = int(v)
            break
    stack = _window_stack()
    site = stack[-1] if stack else None
    with _recorder._mutex:
        _recorder.d2h_events += 1
        _recorder.d2h_bytes += nbytes
        if site is not None:
            _recorder.transfer_sites[site] = \
                _recorder.transfer_sites.get(site, 0) + 1
    if site is not None and _strict:
        _recorder.report(
            ("transfer", site),
            f"device→host transfer during `{site}`: the step "
            f"executables are pure device work by contract (the single "
            f"per-step host sync happens after the step returns) — an "
            f"in-step transfer means a value escaped the mesh "
            f"mid-step (event {event!r}"
            + (f", {nbytes} B" if nbytes else "") + ")")


def _on_monitoring_duration(event: str, duration: float,
                            **kwargs: Any) -> None:
    del duration
    _on_monitoring_event(event, **kwargs)


def _install_listener() -> None:
    global _listening
    if _listening:
        return
    jax.monitoring.register_event_listener(_on_monitoring_event)
    jax.monitoring.register_event_duration_secs_listener(
        _on_monitoring_duration)
    _listening = True


def _remove_listener() -> None:
    global _listening
    if not _listening:
        return
    jax.monitoring.unregister_event_listener(_on_monitoring_event)
    jax.monitoring.unregister_event_duration_listener(
        _on_monitoring_duration)
    _listening = False


# ------------------------------------------------------ placement compare

def _equivalent(actual: Any, expected: Any, ndim: int) -> Optional[bool]:
    """True/False when comparable; None when either side can't say
    (no sharding on the leaf, or incomparable sharding types)."""
    if actual is None or expected is None:
        return None
    try:
        return bool(actual.is_equivalent_to(expected, ndim))
    except Exception:
        pass
    try:
        return bool(expected.is_equivalent_to(actual, ndim))
    except Exception:
        return None


def _as_sharding(entry: Any, mesh: Any) -> Any:
    """A declared entry may be a NamedSharding already or a bare
    PartitionSpec (resolved against ``mesh``)."""
    if isinstance(entry, jax.sharding.PartitionSpec):
        if mesh is None:
            return None
        return jax.sharding.NamedSharding(mesh, entry)
    return entry


def _check_leaves(site: str, declared: Any, actual: Any,
                  mesh: Any) -> None:
    """Compare ``actual``'s leaves against the structurally-matching
    ``declared`` tree of shardings/specs; record mismatches."""
    try:
        pairs = list(zip(
            jax.tree.leaves(
                declared,
                is_leaf=lambda e: isinstance(
                    e, (jax.sharding.Sharding,
                        jax.sharding.PartitionSpec))),
            jax.tree.leaves_with_path(actual)))
    except Exception:                      # pragma: no cover - shape drift
        return
    checked = mismatched = 0
    for entry, (path, leaf) in pairs:
        expected = _as_sharding(entry, mesh)
        got = getattr(leaf, "sharding", None)
        ndim = getattr(leaf, "ndim", None)
        if ndim is None:
            continue
        verdict = _equivalent(got, expected, ndim)
        if verdict is None:
            continue
        checked += 1
        if verdict:
            continue
        mismatched += 1
        if _strict:
            pstr = jax.tree_util.keystr(path)
            _recorder.report(
                ("placement", site, pstr),
                f"placement mismatch at `{site}{pstr}`: declared "
                f"{expected} but the compiled executable returned "
                f"{got} — a missing constraint fell back to a "
                f"different (often fully-replicated) layout the "
                f"declared contract rules out (static twin: the "
                f"sharding pass's spec rules)")
    with _recorder._mutex:
        stats = _recorder.sites.setdefault(
            site, {"calls": 0, "checked": 0, "mismatched": 0})
        stats["checked"] += checked
        stats["mismatched"] += mismatched


def _count_call(site: str) -> None:
    with _recorder._mutex:
        stats = _recorder.sites.setdefault(
            site, {"calls": 0, "checked": 0, "mismatched": 0})
        stats["calls"] += 1


# --------------------------------------------------------------- wrappers

class _StepProxy:
    """Callable wrapper over a step entry point (usually a
    ``tracecheck._GuardedFunction``): times the transfer-attribution
    window around the call, then checks the declared placement of the
    outputs.  Every other attribute (``trace_count``, ``signatures``,
    ``reset`` …) proxies to the wrapped callable, so the engines'
    ``trace_counts`` diagnostics keep working."""

    def __init__(self, inner: Any, site: str,
                 declared_of: Optional[Callable[[Any], Any]],
                 mesh: Any):
        object.__setattr__(self, "_shardcheck_inner", inner)
        object.__setattr__(self, "_shardcheck_site", site)
        object.__setattr__(self, "_shardcheck_declared_of", declared_of)
        object.__setattr__(self, "_shardcheck_mesh", mesh)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        site = self._shardcheck_site
        _count_call(site)
        stack = _window_stack()
        stack.append(site)
        try:
            out = self._shardcheck_inner(*args, **kwargs)
        finally:
            stack.pop()
        declared_of = self._shardcheck_declared_of
        if declared_of is not None:
            declared = declared_of(out)
            if declared is not None:
                _check_leaves(site, declared, out,
                              self._shardcheck_mesh)
        return out

    def __getattr__(self, name: str) -> Any:
        return getattr(
            object.__getattribute__(self, "_shardcheck_inner"), name)

    def __setattr__(self, name: str, value: Any) -> None:
        setattr(object.__getattribute__(self, "_shardcheck_inner"),
                name, value)

    def __repr__(self) -> str:
        return (f"shardcheck({self._shardcheck_inner!r} "
                f"@ {self._shardcheck_site})")


def wrap_step(fn: Callable, *, declared: Any, mesh: Any = None,
              name: str = "step",
              strict: Optional[bool] = None) -> Callable:
    """Wrap a free-standing step callable against an explicit declared
    output-placement tree (``zero_shardings(state, mesh=mesh)``, a
    planner-emitted spec tree, …).  ``declared`` must structurally
    match the step's output (bare ``PartitionSpec`` entries resolve
    against ``mesh``); leaves without a declared sharding are skipped.
    """
    global _strict
    if strict is not None:
        _strict = bool(strict)
    elif env_strict():
        _strict = True
    _install_listener()
    return _StepProxy(fn, name, lambda out: declared, mesh)


# ------------------------------------------------------------- instrument

#: step-attr -> how many leading outputs carry the engine's committed
#: placement (cache pool, then slot state); admit/release return the
#: state alone
_PAGED_STEPS = {"_decode": ("cache", "state"),
                "_prefill": ("cache", "state"),
                "_spec": ("cache", "state"),
                "_admit": ("state",),
                "_release": ("state",)}


def _paged_declared_of(engine: Any, parts: Tuple[str, ...]
                       ) -> Callable[[Any], Any]:
    from apex_tpu.core.mesh import TENSOR_AXIS
    from apex_tpu.serving.cache import paged_pool_shardings

    mesh = engine.mesh
    replicated = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec())

    def declared_of(out: Any) -> Any:
        if parts == ("state",):
            # admit/release: the whole output is the slot state
            return jax.tree.map(lambda _: replicated, out)
        if not isinstance(out, tuple) or len(out) < len(parts):
            return None
        declared: List[Any] = []
        for part, piece in zip(parts, out):
            if part == "cache":
                # the committed pool layout, re-derived from THIS
                # step's output shapes so quantized pools and COW
                # growth stay covered
                declared.append(
                    paged_pool_shardings(piece, mesh, TENSOR_AXIS))
            else:
                declared.append(
                    jax.tree.map(lambda _: replicated, piece))
        return tuple(declared)

    return declared_of


def instrument(obj: Any, *, strict: Optional[bool] = None,
               recurse: int = 2,
               _visited: Optional[Set[int]] = None) -> Any:
    """Wrap ``obj``'s step entry points with the placement recorder;
    returns ``obj``.

    - An engine's guarded step functions are replaced by recording
      proxies.  A tensor-parallel engine (``mesh`` committed)
      gets declared-vs-actual output checks (pool on the ``tensor``
      axis, slot state replicated); a single-chip engine gets
      transfer-window accounting only — there is no multi-device
      placement to verify.
    - ``strict=None`` follows ``APEX_TPU_SHARDCHECK=strict`` (the
      chaos-smoke CI setting); pass ``strict=True`` to force violation
      recording (the chaos soaks do), ``strict=False`` for
      observe-only.
    - ``recurse`` walks that many levels of apex_tpu-owned instance
      attributes (and list/dict elements), so instrumenting an
      ``InferenceServer`` also covers its engine, and a
      ``FleetRouter`` its replicas' engines.

    Idempotent: re-instrumenting is a no-op per step, and objects
    created *after* instrumentation (scale-up replicas) can be
    instrumented as they appear.  Unlike numcheck this wraps at the
    *call* boundary, not trace time, so instrumenting after warmup
    still observes every subsequent step.
    """
    global _strict
    if strict is None:
        strict = env_strict()
    _strict = bool(strict)
    _install_listener()
    if _visited is None:
        _visited = set()
    if id(obj) in _visited:
        return obj
    _visited.add(id(obj))
    d = getattr(obj, "__dict__", None)
    if not isinstance(d, dict):
        return obj
    cls_name = type(obj).__name__
    if cls_name.startswith("_LockChecked"):    # lockcheck composability
        cls_name = cls_name[len("_LockChecked"):]
    mesh = d.get("mesh")
    for attr, value in list(d.items()):
        if isinstance(value, _StepProxy):
            continue
        if not (callable(value) and hasattr(value, "trace_count")):
            continue                    # only the guarded step fns
        site = f"{cls_name}.{attr}"
        if attr not in _PAGED_STEPS:
            continue
        declared_of = None
        if mesh is not None:
            declared_of = _paged_declared_of(obj, _PAGED_STEPS[attr])
        _originals.append((d, attr, value))
        d[attr] = _StepProxy(value, site, declared_of, mesh)
    if recurse > 0:
        children: List[Any] = []
        for value in list(d.values()):
            if isinstance(value, (list, tuple)):
                children.extend(value)
            elif isinstance(value, dict):
                children.extend(value.values())
            else:
                children.append(value)
        for child in children:
            mod = getattr(type(child), "__module__", "") or ""
            if mod.partition(".")[0] == "apex_tpu":
                instrument(child, strict=strict, recurse=recurse - 1,
                           _visited=_visited)
    return obj


def uninstrument() -> None:
    """Restore every wrapped step and remove the monitoring listener
    (recorded stats survive until :func:`reset`)."""
    while _originals:
        d, attr, orig = _originals.pop()
        if isinstance(d.get(attr), _StepProxy):
            d[attr] = orig
    _remove_listener()
