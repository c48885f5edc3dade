"""Runtime trace-hygiene guards — the dynamic oracle behind graftlint.

``tools/graftlint`` catches retrace hazards *statically* (env reads at
trace time, python branching on traced values, cache-defeating jit
signatures — see ``docs/graftlint.md``).  This module is the matching
*runtime* tripwire: it counts how often JAX actually re-traces, so a
test can assert that a train step compiles once and stays compiled.

Two mechanisms, combining the hook-based and wrapper-based approaches:

- a **process-wide trace-event counter** hooked into
  :mod:`jax.monitoring` (the ``/jax/core/compile/jaxpr_trace_duration``
  event fires per jaxpr trace — i.e. on jit cache misses, never on
  hits).  Coarse — nested jaxprs count individually — but it needs no
  cooperation from the code under test:
  ``delta = trace_event_count(); fn(x); assert trace_event_count() == delta``
  proves a call was a cache hit.

- :func:`retrace_guard`, an exact per-function wrapper: it jits the
  wrapped function and counts executions of the *python body* (which
  runs exactly once per trace).  Once the count exceeds ``max_traces``
  the next trace raises :class:`RetraceError` with the offending
  argument signature — turning a silent recompile storm (the classic
  shape-polymorphism / unhashable-static-arg bug) into a loud failure.

Usage::

    from apex_tpu.utils import tracecheck

    step = tracecheck.retrace_guard(train_step, max_traces=2)
    for batch in data:            # raises RetraceError on trace #3
        state, loss = step(state, batch)
    assert step.trace_count == 1  # stable signature -> one compile
"""

from __future__ import annotations

import functools
import threading
from typing import Any, Callable, Optional

__all__ = [
    "RetraceError",
    "retrace_guard",
    "install_trace_counter",
    "trace_event_count",
    "reset_trace_event_count",
]

# The monitoring event jax records once per jaxpr trace (cache misses
# only; a jit cache hit records nothing).  Stable across jax 0.4.x.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"

_lock = threading.Lock()
_state = {"installed": False, "available": False, "events": 0}


def _on_event_duration(event: str, duration_secs: float,
                       **kwargs: Any) -> None:
    if event == _TRACE_EVENT:
        with _lock:
            _state["events"] += 1


def install_trace_counter() -> bool:
    """Register the process-wide trace-event listener (idempotent).

    Returns True if the :mod:`jax.monitoring` hook is active, False if
    the API is unavailable (the counter then stays at 0 and
    :func:`retrace_guard` — which needs no hook — is the fallback).
    """
    with _lock:
        if _state["installed"]:
            return _state["available"]
        _state["installed"] = True
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                _on_event_duration)
            _state["available"] = True
        except Exception:          # pragma: no cover - old/exotic jax
            _state["available"] = False
        return _state["available"]


def trace_event_count() -> int:
    """Jaxpr traces observed since import (or the last reset).

    Counts *jaxpr* traces — one user-level ``jit`` miss typically
    records several (inner jaxprs count too) — so assert on deltas
    ("no new traces"), not absolute values.  Installs the listener on
    first use.
    """
    install_trace_counter()
    with _lock:
        return _state["events"]


def reset_trace_event_count() -> None:
    """Zero the process-wide counter (test isolation)."""
    install_trace_counter()
    with _lock:
        _state["events"] = 0


class RetraceError(RuntimeError):
    """A guarded function exceeded its retrace budget."""


def _describe_args(args: tuple, kwargs: dict) -> str:
    def one(x: Any) -> str:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            return f"{dtype}{list(shape)}"
        r = repr(x)
        return r if len(r) <= 40 else r[:37] + "..."

    parts = [one(a) for a in args]
    parts += [f"{k}={one(v)}" for k, v in sorted(kwargs.items())]
    return ", ".join(parts)


class _GuardedFunction:
    """Callable wrapper returned by :func:`retrace_guard`.

    Attributes: ``trace_count`` (traces so far), ``max_traces``,
    ``signatures`` (arg descriptions of each trace, for the error
    message and post-mortems).  ``reset()`` zeroes the budget *and*
    clears the jit cache, so the guard restarts cleanly.
    """

    def __init__(self, fn: Callable, max_traces: int, name: str,
                 wrap_jit: bool, jit_kwargs: dict):
        if max_traces < 1:
            raise ValueError(f"max_traces must be >= 1, got {max_traces}")
        self._fn = fn
        self._name = name
        self.max_traces = max_traces
        self.trace_count = 0
        self.signatures: list = []
        self._wrap_jit = wrap_jit
        self._jit_kwargs = jit_kwargs
        self._build()
        functools.update_wrapper(self, fn)

    def _build(self) -> None:
        def counted(*args, **kwargs):
            # this body runs exactly once per trace of the jitted
            # wrapper (cache hits replay the compiled executable and
            # never re-enter python)
            sig = _describe_args(args, kwargs)
            if self.trace_count >= self.max_traces:
                # over budget: raise WITHOUT counting or recording —
                # failed traces are never cached, so a caller that
                # catches and retries would otherwise re-enter here
                # per call, growing trace_count/signatures unboundedly
                # and misreporting one extra signature as a storm
                seen = "\n  ".join(self.signatures)
                raise RetraceError(
                    f"{self._name!r} exceeded max_traces="
                    f"{self.max_traces}: signature {sig} would "
                    f"compile from scratch.  Every distinct shape/"
                    f"dtype/static-arg signature is a new trace — a "
                    f"growing signature set is a retrace storm (shape "
                    f"polymorphism, unhashable statics, or trace-time "
                    f"env/config reads).  Signatures already "
                    f"compiled:\n  {seen}")
            self.trace_count += 1
            self.signatures.append(sig)
            try:
                return self._fn(*args, **kwargs)
            except Exception:
                # the trace failed, so jit caches nothing: the budget
                # must not be consumed, or retrying the same call
                # would eventually mask the real error with a
                # spurious RetraceError over duplicate signatures
                self.trace_count -= 1
                self.signatures.pop()
                raise

        # the guard's name is the program's: the compiled module reads
        # ``jit_<name>`` in a profile and the dispatch
        # ``PjitFunction(<name>)``, not ``counted`` for every guard
        counted.__name__ = counted.__qualname__ = \
            self._name.replace(".", "_")
        if self._wrap_jit:
            import jax
            self._wrapped = jax.jit(counted, **self._jit_kwargs)
        else:
            self._wrapped = counted

    def __call__(self, *args, **kwargs):
        return self._wrapped(*args, **kwargs)

    def lower(self, *args, **kwargs):
        """``jax.jit(fn).lower`` of the guarded function (AOT
        inspection; a signature already traced does not trace again)."""
        return self._wrapped.lower(*args, **kwargs)

    def reset(self) -> None:
        """Zero the count and drop the compiled cache."""
        self.trace_count = 0
        self.signatures = []
        self._build()

    def __repr__(self) -> str:
        return (f"retrace_guard({self._name}, traces="
                f"{self.trace_count}/{self.max_traces})")


def retrace_guard(fn: Optional[Callable] = None, *, max_traces: int = 2,
                  name: Optional[str] = None, wrap_jit: bool = True,
                  **jit_kwargs: Any) -> Callable:
    """Wrap ``fn`` so exceeding ``max_traces`` raises :class:`RetraceError`.

    ``fn`` must be the *un-jitted* python function: the guard applies
    ``jax.jit(fn, **jit_kwargs)`` itself (``wrap_jit=False`` skips the
    jit for use under an outer ``jit``/``pmap``, still counting body
    executions).  Works as a decorator with or without arguments::

        @retrace_guard(max_traces=1)
        def train_step(state, batch): ...

    The returned wrapper exposes ``trace_count``, ``max_traces``,
    ``signatures`` and ``reset()``.
    """
    if fn is None:
        return functools.partial(
            retrace_guard, max_traces=max_traces, name=name,
            wrap_jit=wrap_jit, **jit_kwargs)
    if hasattr(fn, "lower") and hasattr(fn, "eval_shape"):
        raise TypeError(
            "retrace_guard needs the un-jitted python function (it "
            "counts python-body executions, which a compiled cache hit "
            "skips); pass the function itself and let the guard jit it")
    return _GuardedFunction(
        fn, max_traces, name or getattr(fn, "__name__", repr(fn)),
        wrap_jit, jit_kwargs)
