"""The span primitive, and thin wrappers of ``jax.profiler``.

:func:`span` is how the program measures itself: one context manager
that is at once an event on the profiler's clock — the clock the device
trace is on, so a device idle gap can be blamed on the host span that
covers it — and a running ``[count, seconds]`` total that a health
probe reads with no profiler session at all.  :class:`SpanTotals` holds
the totals of one owner (the serving worker's engine, scheduler and
server each have theirs; ``InferenceServer.health()["spans"]`` is the
three merged) and, beside each total, the same instances by duration in
power-of-two buckets (``health()["span_hist"]``), so that a rare long
instance is not lost in a mean.

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

__all__ = ["trace", "annotate", "span", "SpanTotals", "BUCKETS_MS",
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


#: lower bounds of the duration buckets, in milliseconds: bucket ``k``
#: holds the instances of ``min(int(ms).bit_length(), 11)`` — ``0`` is
#: under 1 ms, ``1024`` is 1024 ms and over
BUCKETS_MS = (0,) + tuple(1 << k for k in range(11))


def _buckets(cells: int) -> tuple:
    """One name's bucket records of ``cells`` cells, indexed by
    ``int(ms).bit_length()``: every index from the last bound's to 63
    is the one open bucket, so that a span's exit takes no ``min``."""
    records = [[0] + [0.0] * (cells - 1) for _ in BUCKETS_MS]
    return tuple(records + [records[-1]] * (64 - len(records)))


class SpanTotals:
    """Cumulative ``[count, seconds]`` of a FIXED tuple of span names,
    and the same by duration: ``len(BUCKETS_MS)`` buckets of ``[count,
    seconds]`` a name.  Names in ``cpu`` also add the thread's CPU
    seconds (``time.thread_time``) to their bucket's third cell — two
    more clock reads a span, so only for a span whose stalls have to be
    told apart: computing (CPU ≈ seconds) or waiting (CPU ≪ seconds).

    One writer (the thread that owns the spans' code), any reader: every
    record and bucket is allocated here and never resized, each cell is
    replaced by one store, so :meth:`snapshot` and :meth:`histogram` are
    safe from any thread without a lock (a reader may see a count one
    ahead of its seconds, never a torn value or a ``RuntimeError``).
    """

    def __init__(self, names: Sequence[str], *, cpu: Sequence[str] = ()):
        # record: [count, seconds, buckets, cpu-timed]
        # graftlint: unguarded(fixed key set and bucket lists filled at construction, never resized; one writer thread stores into their cells, readers copy them — no lock needed)
        self._totals: Dict[str, list] = {
            name: [0, 0.0, _buckets(3 if name in cpu else 2), name in cpu]
            for name in names}

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """``{name: {"n": count, "s": seconds}}`` so far."""
        return {name: {"n": rec[0], "s": rec[1]}
                for name, rec in self._totals.items()}

    def histogram(self) -> Dict[str, Dict[str, Dict[str, float]]]:
        """``{name: {bucket: {"n", "s"[, "cpu"]}}}`` so far.

        ``bucket`` is the lower bound in ms as a string (``"0"``,
        ``"1"``, ``"2"``, … ``"1024"``); ``cpu`` only for the names
        timed on the CPU."""
        out = {}
        for name, rec in self._totals.items():
            out[name] = {str(ms): dict(zip(("n", "s", "cpu"), cell))
                         for ms, cell in zip(BUCKETS_MS, rec[2])}
        return out


class span:
    """``with span(totals, name, **ids):`` — a
    ``jax.profiler.TraceAnnotation(name, **ids)`` around the block
    (inert with no profiler session) that on exit adds the block's
    ``time.perf_counter()`` seconds and 1 to ``totals[name]`` and to its
    duration bucket (always on), and the thread's CPU seconds to the
    bucket where ``totals`` times ``name`` on the CPU.  ``name`` must be
    one of ``totals``' names.

    ``since`` is for a span whose name is known only after its first
    work: a ``perf_counter`` reading to count the seconds from, while
    the trace event starts where the name is known.
    """

    __slots__ = ("_rec", "_ann", "_t0", "_c0")

    def __init__(self, totals: SpanTotals, name: str, *,
                 since: Optional[float] = None, **ids):
        self._rec = totals._totals[name]
        self._ann = jax.profiler.TraceAnnotation(name, **ids)
        self._t0 = since

    def __enter__(self) -> "span":
        self._ann.__enter__()
        if self._t0 is None:
            self._t0 = time.perf_counter()
        self._c0 = time.thread_time() if self._rec[3] else None
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        elapsed = time.perf_counter() - self._t0
        c0 = self._c0
        if c0 is not None:
            cpu = time.thread_time() - c0
        self._ann.__exit__(exc_type, exc, tb)
        rec = self._rec
        rec[0] += 1
        rec[1] += elapsed
        cell = rec[2][int(elapsed * 1e3).bit_length()]
        cell[0] += 1
        cell[1] += elapsed
        if c0 is not None:
            cell[2] += cpu


def save_device_memory_profile(path: str) -> None:
    """Dump the current device memory profile (pprof format)."""
    jax.profiler.save_device_memory_profile(path)
