"""The span primitive, and thin wrappers of ``jax.profiler``.

:func:`span` is how the program measures itself: one context manager
that is at once an event on the profiler's clock — the clock the device
trace is on, so a device idle gap can be blamed on the host span that
covers it — and a running ``[count, seconds]`` total that a health
probe reads with no profiler session at all.  :class:`SpanTotals` holds
the totals of one owner (the serving worker's engine, scheduler and
server each have theirs; ``InferenceServer.health()["spans"]`` is the
three merged).

The rest wraps ``jax.profiler`` as it is: :func:`trace` captures a
trace of a block (around any interval of a live server: its spans
appear on the worker thread's line), :func:`annotate` is a bare named
range, :func:`save_device_memory_profile` dumps the device memory
profile.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, Optional, Sequence

import jax

__all__ = ["trace", "annotate", "span", "SpanTotals",
           "save_device_memory_profile"]


@contextlib.contextmanager
def trace(log_dir: str, *, create_perfetto_link: bool = False) -> Iterator[None]:
    """Capture a profiler trace of the enclosed block into ``log_dir``
    (view with TensorBoard's profile plugin)."""
    jax.profiler.start_trace(log_dir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Named range visible in profiler timelines (nvtx.range parity).

    Use as context manager or decorator::

        with annotate("fused_adam_step"):
            state = step(state, batch)
    """
    return jax.profiler.TraceAnnotation(name)


class SpanTotals:
    """Cumulative ``[count, seconds]`` of a FIXED tuple of span names.

    One writer (the thread that owns the spans' code), any reader: the
    dict is filled here and never resized, a record's two cells are
    each replaced by one store, so :meth:`snapshot` is safe from any
    thread without a lock (a reader may see a count one ahead of its
    seconds, never a torn value or a ``RuntimeError``).
    """

    def __init__(self, names: Sequence[str]):
        # graftlint: unguarded(fixed key set filled at construction, never resized; one writer thread stores into a record's two cells, readers copy them — no lock needed)
        self._totals: Dict[str, list] = {name: [0, 0.0] for name in names}

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """``{name: {"n": count, "s": seconds}}`` so far."""
        return {name: {"n": rec[0], "s": rec[1]}
                for name, rec in self._totals.items()}


class span:
    """``with span(totals, name, **ids):`` — a
    ``jax.profiler.TraceAnnotation(name, **ids)`` around the block
    (inert with no profiler session) that on exit adds the block's
    ``time.perf_counter()`` seconds and 1 to ``totals[name]`` (always
    on).  ``name`` must be one of ``totals``' names.

    ``since`` is for a span whose name is known only after its first
    work: a ``perf_counter`` reading to count the seconds from, while
    the trace event starts where the name is known.
    """

    __slots__ = ("_rec", "_ann", "_t0")

    def __init__(self, totals: SpanTotals, name: str, *,
                 since: Optional[float] = None, **ids):
        self._rec = totals._totals[name]
        self._ann = jax.profiler.TraceAnnotation(name, **ids)
        self._t0 = since

    def __enter__(self) -> "span":
        self._ann.__enter__()
        if self._t0 is None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        elapsed = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        rec = self._rec
        rec[0] += 1
        rec[1] += elapsed


def save_device_memory_profile(path: str) -> None:
    """Dump the current device memory profile (pprof format)."""
    jax.profiler.save_device_memory_profile(path)
