"""Threaded front-end: submit → handle, streaming tokens, metrics.

:class:`InferenceServer` owns one worker thread that runs the
engine/scheduler loop (JAX dispatch stays single-threaded); client
threads talk to it only through the bounded queue and per-request
:class:`RequestHandle` streams.  Throughput / occupancy / queue-depth
metrics flow through :class:`apex_tpu.utils.metrics.MetricsWriter`
every ``metrics_interval`` steps, tagged with the server's step counter
and drained in order.

Usage::

    server = InferenceServer(model, params, max_slots=4)
    with server:                       # starts (and warms up) the loop
        h = server.submit([1, 2, 3], max_new_tokens=16)
        for tok in h.stream():         # tokens as they decode
            ...
        full = h.result()              # or block for the final list
"""

from __future__ import annotations

import queue as queue_mod
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from apex_tpu.core.mesh import TENSOR_AXIS
from apex_tpu.resilience import faults
from apex_tpu.serving.engine import PagedEngine
from apex_tpu.serving.scheduler import QueueFull, Request, Scheduler
from apex_tpu.utils.metrics import (
    MetricsWriter,
    counters,
    percentile_summary,
)
from apex_tpu.utils.profiler import SpanTotals, span

__all__ = ["InferenceServer", "RequestHandle", "ServerClosed",
           "RequestFailed", "ReplicaDraining"]

_SENTINEL = object()

#: the server's spans: ``step`` is one whole iteration of the worker
#: loop with work (deadline expiry, the scheduler's step, delivery;
#: id ``step``; timed on the CPU too, so that a stalled step tells
#: computing from waiting); ``deliver`` hands the step's tokens to
#: handles and taps (client callbacks run here); ``idle`` is the
#: worker's wait for work, entered only when there is none (one
#: instance a stretch without work);
#: ``follow_up`` the wait for a client's next turn after a step that
#: freed a slot (``FOLLOW_UP_S``).  The two waits lie outside ``step``
SERVE_STEP = "apex/serve/step"
DELIVER = "apex/serve/deliver"
SERVE_IDLE = "apex/serve/idle"
FOLLOW_UP = "apex/serve/follow_up"

#: after a step that finished a request, with an empty queue, the
#: worker waits at most this long for a follow-up request before the
#: next step's admission (a closed-loop client's next turn): a
#: millisecond against a step of tens
FOLLOW_UP_S = 0.001

#: server-side observer a fleet router attaches to a handle:
#: ``tap(token, finished, error)`` — token events carry ``(tok, fin,
#: None)``, the terminal failure carries ``(None, True, exc)``.
Tap = Callable[[Optional[int], bool, Optional[BaseException]], None]


class ServerClosed(RuntimeError):
    """TERMINAL: the server shut down (or its worker died) before the
    request finished — the request will never produce more tokens.
    Also raised by ``submit`` on a stopped server."""


class ReplicaDraining(ServerClosed):
    """TERMINAL *for this replica only*: the server is gracefully
    draining (:meth:`InferenceServer.begin_drain`) and evicted the
    request — its engine slot is released, its streamed prefix is
    intact — so a fleet router can migrate it (``prompt ++ streamed
    tokens``, remaining budget) onto a survivor.  Plain clients
    without a router on top should treat it exactly as
    :class:`ServerClosed`."""


class RequestFailed(RuntimeError):
    """TERMINAL: this one request failed — deadline expired, repeated
    step faults, or an unresumable continuation — while the server
    itself keeps serving.  ``__cause__`` carries the root failure when
    there is one."""


class RequestHandle:
    """Client-side view of one in-flight request.

    Error contract (see ``docs/resilience.md``): :meth:`stream` and
    :meth:`result` raise exactly one of

    - ``TimeoutError`` — RETRYABLE: *no token yet* within ``timeout``.
      The request is still live; call again with the same handle.
    - :class:`RequestFailed` — TERMINAL: this request failed (deadline,
      repeated faults); the server is still serving others.
    - :class:`ServerClosed` — TERMINAL: the server stopped first.

    The terminal error is recorded on the handle *before* clients are
    woken, so a shutdown can never surface as a bare timeout: a reader
    either times out (and may retry) or observes the real terminal
    state — never a timeout that silently means "cancelled".
    """

    def __init__(self, request: Request, tap: Optional[Tap] = None):
        self._request = request
        self._stream: "queue_mod.Queue" = queue_mod.Queue()
        self._done = threading.Event()
        self._error: Optional[BaseException] = None
        # server-side observer (fleet plumbing): installed at
        # construction so no event can slip past it — a fast worker
        # may deliver before submit() even returns
        self._tap = tap

    # ------------------------------------------------------- server side
    def _deliver(self, token: int, finished: bool) -> None:
        self._stream.put(int(token))
        if finished:
            self._stream.put(_SENTINEL)
            self._done.set()
        if self._tap is not None:
            self._tap(int(token), bool(finished), None)

    def _fail(self, error: BaseException) -> None:
        """Terminal failure: record the cause, then wake clients."""
        self._error = error
        self._stream.put(_SENTINEL)
        self._done.set()
        if self._tap is not None:
            self._tap(None, True, error)

    def _cancel(self) -> None:
        self._fail(ServerClosed(
            "server shut down before the request finished"))

    # ------------------------------------------------------- client side
    def stream(self, timeout: Optional[float] = None):
        """Yield tokens as they are produced; ends at eos/budget.

        ``TimeoutError`` means *no token yet* — retryable, resume with
        another ``stream()``/``result()`` call; :class:`RequestFailed`
        and :class:`ServerClosed` are terminal (class docstring has the
        full contract).
        """
        while True:
            try:
                item = self._stream.get(timeout=timeout)
            except queue_mod.Empty:
                raise TimeoutError(
                    f"no token within {timeout}s (request still "
                    f"live — retryable)") from None
            if item is _SENTINEL:
                if self._error is not None:
                    raise self._error
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until finished; returns every produced token.  Same
        error contract as :meth:`stream`: ``TimeoutError`` is
        retryable ("still decoding"), :class:`RequestFailed` /
        :class:`ServerClosed` are terminal."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                "request still decoding (retryable)")
        if self._error is not None:
            raise self._error
        return list(self._request.tokens)

    @property
    def done(self) -> bool:
        return self._done.is_set()

    @property
    def error(self) -> Optional[BaseException]:
        """The terminal error, or ``None`` (also ``None`` while live)."""
        return self._error

    @property
    def tokens_so_far(self) -> List[int]:
        return list(self._request.tokens)


class InferenceServer:
    """Continuous-batching inference server over one model.

    ``submit`` blocks (bounded backpressure) while the queue is full —
    pass ``block=False`` to get :class:`QueueFull` immediately.
    ``shutdown(wait=True)`` serves everything already accepted, then
    stops; ``wait=False`` cancels queued AND in-flight requests (their
    handles raise :class:`ServerClosed`).

    Failure semantics (docs/resilience.md): a retryable
    :class:`~apex_tpu.resilience.faults.TransientError` during a step
    poisons only the slots it names (all active slots when it names
    none) — those tenants are evicted and requeued ONCE, continuing
    from their already-streamed prefix; a second fault (or an
    unresumable continuation) fails just that request with
    :class:`RequestFailed`.  Per-request deadlines are enforced both in
    the queue and mid-decode.  Every accepted request therefore ends in
    exactly one of: tokens delivered to completion, ``RequestFailed``,
    or ``ServerClosed`` — never silently lost, never hung.  Anything
    non-transient still kills the worker and cancels all clients (the
    engine's device state cannot be trusted after an arbitrary
    failure).
    """

    def __init__(self, model, params, *, max_slots: int = 4,
                 prefill_chunk: int = 32, queue_capacity: int = 64,
                 metrics: Optional[MetricsWriter] = None,
                 metrics_interval: int = 32,
                 kv_cache: str = "paged", block_size: int = 0,
                 pool_tokens: Optional[int] = None,
                 admit_headroom: Optional[int] = None,
                 share_prefixes: bool = False,
                 spec_tokens: int = 0, spec_ngram: int = 3,
                 kv_dtype: Optional[str] = None,
                 tp: int = 0, mesh: Optional[Any] = None):
        # kept only because benchmarks/configs/*.json still pass
        # "kv_cache": "paged" (ROADMAP D2b); there is one engine
        if kv_cache != "paged":
            raise ValueError(
                f"kv_cache={kv_cache!r}: the dense slab engine was "
                "removed in PR 34 — PagedEngine is the server's only "
                "engine and 'paged' the only value; the keyword itself "
                "goes once the benchmark's configuration files stop "
                "passing it (ROADMAP D2b), so drop it from the call")
        if tp and mesh is not None:
            # mesh may be a Mesh or an int (the engine accepts
            # both); either way its tensor width must agree with
            # an explicit tp
            mesh_tp = (mesh if isinstance(mesh, int)
                       else dict(mesh.shape).get(TENSOR_AXIS, 1))
            if mesh_tp != tp:
                raise ValueError(
                    f"tp={tp} disagrees with mesh "
                    f"({TENSOR_AXIS} axis {mesh_tp}) — pass one "
                    f"or make them match")
        self.engine = PagedEngine(
            model, params, max_slots=max_slots,
            block_size=block_size, pool_tokens=pool_tokens,
            prefill_chunk=prefill_chunk,
            admit_headroom=admit_headroom,
            share_prefixes=share_prefixes,
            spec_tokens=spec_tokens, spec_ngram=spec_ngram,
            kv_dtype=kv_dtype,
            mesh=(mesh if mesh is not None
                  else (tp if tp and tp > 1 else None)))
        self.scheduler = Scheduler(self.engine,
                                   queue_capacity=queue_capacity)
        self.metrics = metrics
        self.metrics_interval = max(1, int(metrics_interval))
        # identity-keyed handle registry: client threads setitem/pop,
        # the worker get/pops — every touch is one GIL-atomic dict op,
        # it is never iterated, and keys are unique per request
        # graftlint: unguarded(single atomic dict ops per touch, identity keys, never iterated)
        self._handles: dict = {}          # uid -> RequestHandle
        self._wakeup = threading.Condition()
        self._stop = False  # graftlint: guarded-by(_wakeup)
        self._drain_on_stop = True
        self._draining = False
        self._drain_evicted = 0
        self._started_at: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        self._steps = 0
        self._step_attempts = 0
        #: the last step finished a request: wait FOLLOW_UP_S for its
        #: client's next one before the next admission
        self._slot_freed = False
        self._tokens_emitted = 0
        self._window_tokens = 0
        self._window_t0: Optional[float] = None
        self._last_emit_step = -1
        self._requeues = 0
        self._failed_requests = 0
        self._deadline_expired = 0
        # latency telemetry: time-to-first-token per request and
        # per-step decode wall time, bounded reservoirs (p50/p99 ride
        # every metrics emission and the soak summary).  The worker
        # appends while any thread (fleet supervisor SLO probes,
        # clients) snapshots — iterating a deque during an append
        # raises RuntimeError, so both sides hold _lat_lock (the
        # pre-existing race graftlint's concurrency pass flagged)
        self._lat_lock = threading.Lock()
        self._ttft: deque = deque(maxlen=2048)  # graftlint: guarded-by(_lat_lock)
        self._queue_wait: deque = deque(maxlen=2048)  # graftlint: guarded-by(_lat_lock)
        self._step_times: deque = deque(maxlen=4096)  # graftlint: guarded-by(_lat_lock)
        # requests that got their first token, and the seconds from
        # their admission to it (prefill, as the request felt it)
        self._first_tokens = 0
        self._prefill_s = 0.0
        self.spans = SpanTotals((SERVE_STEP, DELIVER, SERVE_IDLE, FOLLOW_UP),
                                cpu=(SERVE_STEP,))
        #: the exception that killed the worker loop, if any — clients
        #: see ServerClosed; the root cause lives here for post-mortems.
        #: Published under _wakeup together with the _stop flip, so a
        #: reader that saw _stop also sees the cause
        self.error: Optional[BaseException] = None  # graftlint: guarded-by(_wakeup)

    # ---------------------------------------------------------- lifecycle
    def start(self, *, warmup: bool = True) -> "InferenceServer":
        if self._thread is not None:
            raise RuntimeError("server already started")
        if warmup:
            self.engine.warmup()
        self._started_at = time.monotonic()
        self._thread = threading.Thread(
            target=self._serve, name="apex-tpu-serving", daemon=True)
        self._thread.start()
        return self

    def shutdown(self, *, wait: bool = True,
                 timeout: Optional[float] = None) -> None:
        if self._thread is None:
            return
        with self._wakeup:
            self._stop = True
            self._drain_on_stop = wait
            self._wakeup.notify_all()
        self._thread.join(timeout)
        self._thread = None

    def begin_drain(self) -> None:
        """Graceful drain, phase 1: stop admitting and evict every
        queued/in-flight request at the next step boundary, failing
        each handle with :class:`ReplicaDraining` so a fleet router
        can migrate it (``prompt ++ streamed tokens`` onto a
        survivor).  The engine releases every slot through the normal
        compiled ``release`` — the pool returns to
        ``blocks_in_use == 0`` — and the worker then idles until
        :meth:`shutdown`.  Without a router on top, clients simply
        observe :class:`ServerClosed` (its base class)."""
        with self._wakeup:
            self._draining = True
            self._wakeup.notify_all()

    @property
    def draining(self) -> bool:
        """True after :meth:`begin_drain` — also in :meth:`health`."""
        return self._draining

    def kill(self, error: Optional[BaseException] = None) -> None:
        """SIGKILL-equivalent death for chaos drills (the
        ``replica.kill`` fault site routes here): the worker stops
        WITHOUT draining and WITHOUT releasing engine state — a real
        SIGKILL takes the host's device memory with it — so the
        pool's accounting is abandoned mid-flight (``blocks_in_use``
        stays nonzero; the replica is dead, not reusable).  Every
        queued and in-flight handle fails with :class:`ServerClosed`;
        a :class:`~apex_tpu.serving.fleet.FleetRouter` migrates them
        onto survivors.  Idempotent; a no-op on a server with no live
        worker (never started, or already shut down cleanly) — there
        is nothing to kill, and fabricating an ``error`` there would
        make ``health()`` report a failure that never happened."""
        with self._wakeup:
            thread = self._thread
            if thread is None:
                return
            if self.error is None:
                self.error = error if error is not None \
                    else RuntimeError("replica killed (chaos drill)")
            self._stop = True
            self._drain_on_stop = False
            self._wakeup.notify_all()
        thread.join()
        self._thread = None

    def __enter__(self) -> "InferenceServer":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> None:
        # propagate client-side errors without hanging on a full drain
        self.shutdown(wait=exc_type is None)

    # ------------------------------------------------------------- intake
    def submit(self, prompt, *, max_new_tokens: int,
               temperature: float = 0.0, top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               eos_id: Optional[int] = None, seed: int = 0,
               deadline: Optional[float] = None,
               block: bool = True,
               timeout: Optional[float] = None,
               tap: Optional[Tap] = None) -> RequestHandle:
        """Enqueue one request; returns its :class:`RequestHandle`.

        ``deadline`` (seconds from acceptance) bounds the request's
        total latency: once expired — whether still queued or
        mid-decode — it fails with :class:`RequestFailed` and its slot
        is freed.  ``timeout`` bounds only this *submission* under
        backpressure (distinct from the deadline).  ``tap`` is fleet
        plumbing: a server-side observer of the handle's events (see
        :data:`Tap`), installed before the request can produce any —
        :class:`~apex_tpu.serving.fleet.FleetRouter` uses it to mirror
        streams and catch migration signals.
        """
        request = Request(
            prompt=np.asarray(prompt, np.int32).reshape(-1),
            max_new_tokens=int(max_new_tokens),
            temperature=float(temperature),
            top_k=top_k, top_p=top_p, eos_id=eos_id, seed=int(seed),
            deadline=None if deadline is None else float(deadline))
        # the handle must be reachable by the worker BEFORE the request
        # enters the queue: run_step doesn't take _wakeup, so a fast
        # worker can admit — even finish — a one-token request between
        # the enqueue and any later registration, and its events would
        # be dropped.  Keyed by object identity (stable pre-enqueue;
        # uid is only assigned inside scheduler.submit).
        handle = RequestHandle(request, tap=tap)
        self._handles[id(request)] = handle
        # distinct from the per-request `deadline`: this bounds only
        # the backpressure wait of THIS submit call
        submit_deadline = None if timeout is None \
            else time.monotonic() + timeout
        try:
            while True:
                with self._wakeup:
                    if self._stop or self._thread is None:
                        raise ServerClosed("server is not running")
                    if self._draining:
                        raise ServerClosed(
                            "server is draining (not admitting)")
                    try:
                        self.scheduler.submit(request)
                        self._wakeup.notify_all()
                        return handle
                    except QueueFull:
                        if not block:
                            raise
                        remaining = None if submit_deadline is None \
                            else submit_deadline - time.monotonic()
                        if remaining is not None and remaining <= 0:
                            raise
                        # woken by the worker after each admission wave
                        self._wakeup.wait(
                            0.05 if remaining is None
                            else min(0.05, remaining))
        except BaseException:
            self._handles.pop(id(request), None)
            raise

    # ------------------------------------------------------------- worker
    def _serve(self) -> None:  # graftlint: thread-entry(serving-worker)
        try:
            while True:
                with self._wakeup:
                    if not self.scheduler.has_work() and not self._stop:
                        with span(self.spans, SERVE_IDLE):
                            while (not self.scheduler.has_work()
                                   and not self._stop):
                                self._wakeup.wait(0.1)
                    if self._stop and (not self._drain_on_stop
                                       or not self.scheduler.has_work()):
                        break
                if self._draining:
                    self._drain_out()
                    continue            # idle until shutdown()
                with span(self.spans, SERVE_STEP, step=self._steps):
                    now = self._serve_step()
                if self._slot_freed:
                    self._await_follow_up()
                if now is not None and self.metrics is not None \
                        and self._steps % self.metrics_interval == 0:
                    self._emit_metrics(now)
        except BaseException as exc:    # noqa: BLE001 — any engine
            # failure (RetraceError, OOM, ...) must not strand clients:
            # record it, flip _stop so submit()/blocking waiters see a
            # closed server, and fall through to the cancel path below.
            # Both published under _wakeup: a reader that observed the
            # stop flag must also observe its cause
            with self._wakeup:
                self.error = exc
                self._stop = True
                self._wakeup.notify_all()
        finally:
            with self._wakeup:
                error = self.error
            # cancel every leftover queued/in-flight handle (normal
            # wait=False shutdown reaches here too; after a full drain
            # there is simply nothing left to cancel)
            for req in self.scheduler.cancel_queued():
                handle = self._handles.pop(id(req), None)
                if handle is not None:
                    handle._cancel()
            for slot, req in enumerate(self.scheduler._slots):
                if req is None:
                    continue
                if error is None:
                    self.engine.release(slot)
                self.scheduler._slots[slot] = None
                handle = self._handles.pop(id(req), None)
                if handle is not None:
                    handle._cancel()
            # the steps still in flight served the handles just
            # cancelled: nobody is left to hand their tokens to
            self.scheduler.discard_in_flight()
            if self.metrics is not None \
                    and self._steps != self._last_emit_step:
                self._emit_metrics(time.monotonic())

    def _serve_step(self) -> Optional[float]:
        """One iteration with work (worker thread): expire deadlines,
        run the scheduler's step, deliver its tokens.  The scheduler
        leaves the NEXT step dispatched where it can, so the chip works
        through the delivery, the wait for a follow-up and the next
        admission; an expiry or a fault recovery between two steps
        evicts under that step, which then emits for nobody.  Returns
        the step's time, or ``None`` where no step completed
        (everything expired, or a transient fault was recovered
        from)."""
        self._expire_deadlines()
        if not self.scheduler.has_work():
            return None                 # everything just expired
        try:
            # injected against the ATTEMPT counter, not self._steps: a
            # faulted attempt doesn't advance the step count, and a
            # step-pinned fault keyed on it would re-fire forever and
            # starve recovery
            attempt = self._step_attempts
            self._step_attempts += 1
            faults.inject("serving.step", step=attempt)
            t_step0 = time.monotonic()
            events = self.scheduler.run_step()
            with self._lat_lock:
                self._step_times.append(time.monotonic() - t_step0)
        except faults.TransientError as exc:
            # a retryable step fault: the raiser guarantees engine
            # state is intact (host-side failure, raised before
            # dispatch), so recovery is slot-local — evict the
            # poisoned tenants, requeue each once
            self._recover_step(exc)
            with self._wakeup:
                self._wakeup.notify_all()
            return None
        for req, exc in self.scheduler.take_admit_failures():
            failure = RequestFailed(
                f"admission failed twice for request "
                f"{req.uid}: {exc}")
            failure.__cause__ = exc
            self._fail_request(req, failure)
        self._steps += 1
        now = time.monotonic()
        if self._window_t0 is None:
            self._window_t0 = now
        with span(self.spans, DELIVER):
            for ev in events:
                self._tokens_emitted += 1
                self._window_tokens += 1
                req = ev.request
                if len(req.tokens) == 1:
                    # first token of this request (requeued
                    # continuations keep their prefix, so this fires
                    # exactly once per request)
                    req.first_token_at = now
                    self._first_tokens += 1
                    self._prefill_s += now - req.admitted_at
                    with self._lat_lock:
                        self._ttft.append(now - req.accepted_at)
                        self._queue_wait.append(
                            req.admitted_at - req.accepted_at)
                handle = self._handles.get(id(req))
                if handle is not None:
                    handle._deliver(ev.token, ev.finished)
                    if ev.finished:
                        self._handles.pop(id(req), None)
        self._slot_freed = any(ev.finished for ev in events)
        with self._wakeup:
            self._wakeup.notify_all()   # queue space freed
        return now

    def _await_follow_up(self) -> None:
        """A slot has just come free and nobody is queued for it: a
        client that was waiting for that last token gets
        ``FOLLOW_UP_S`` to hand in its next request (the submit's
        notify ends the wait at once) before the step runs without it.
        The worker would otherwise keep the interpreter through the
        next admission and the slot would idle a whole step."""
        self._slot_freed = False
        with self._wakeup:
            if not self._stop and self.scheduler.queue_depth == 0:
                with span(self.spans, FOLLOW_UP):
                    self._wakeup.wait(FOLLOW_UP_S)

    def _drain_out(self) -> None:
        """Evict everything for :meth:`begin_drain` (worker thread):
        queued requests are cancelled, active tenants evicted with
        their engine slots released (pages go home), and every handle
        fails with :class:`ReplicaDraining` — the router-visible
        migrate signal.  Not counted as request failures: drain is
        scheduling, not loss."""
        dropped = self.scheduler.cancel_queued()
        dropped += self.scheduler.evict_all()
        for req in dropped:
            self._drain_evicted += 1
            counters.inc("serving.drain_evict")
            handle = self._handles.pop(id(req), None)
            if handle is not None:
                handle._fail(ReplicaDraining(
                    f"request {req.uid} evicted by graceful drain "
                    f"after {len(req.tokens)} streamed tokens"))
        if dropped:
            with self._wakeup:
                self._wakeup.notify_all()

    # ----------------------------------------------------- fault recovery
    def _fail_request(self, req: Request,
                      failure: RequestFailed) -> None:
        """Route a terminal per-request failure to its handle."""
        self._failed_requests += 1
        counters.inc("serving.request_failed")
        handle = self._handles.pop(id(req), None)
        if handle is not None:
            handle._fail(failure)

    def _recover_step(self, exc: "faults.TransientError") -> None:
        """Evict the poisoned slots; requeue each tenant once.

        ``exc.slots`` names the poisoned slots when attribution exists;
        with none, every active slot is suspect (the fault fired before
        any of them stepped).  A tenant already requeued once — or one
        whose continuation no longer fits the context — fails terminally
        with :class:`RequestFailed`; the server itself keeps serving.
        """
        counters.inc("serving.step_fault")
        poisoned = getattr(exc, "slots", None)
        for slot, req in enumerate(list(self.scheduler._slots)):
            if req is None:
                continue
            if poisoned is not None and slot not in poisoned:
                continue
            self.scheduler.evict(slot)
            cause: BaseException = exc
            if req.retries < 1:
                req.retries += 1
                try:
                    self.scheduler.requeue(req)
                    self._requeues += 1
                    counters.inc("serving.requeue")
                    continue
                except ValueError as ve:    # unresumable continuation
                    cause = ve
            failure = RequestFailed(
                f"request {req.uid} evicted by a step fault and not "
                f"requeueable (retries={req.retries}): {cause}")
            failure.__cause__ = cause
            self._fail_request(req, failure)

    def _expire_deadlines(self) -> None:
        """Fail queued AND in-flight requests past their deadline."""
        now = time.monotonic()
        for req in self.scheduler.expire_queued(now):
            self._deadline_expired += 1
            counters.inc("serving.deadline_expired")
            self._fail_request(req, RequestFailed(
                f"request {req.uid} deadline ({req.deadline}s) "
                f"expired in queue"))
        for slot, req in enumerate(list(self.scheduler._slots)):
            if req is None or req.deadline is None:
                continue
            if now - req.accepted_at > req.deadline:
                self.scheduler.evict(slot)
                self._deadline_expired += 1
                counters.inc("serving.deadline_expired")
                self._fail_request(req, RequestFailed(
                    f"request {req.uid} deadline ({req.deadline}s) "
                    f"expired after {len(req.tokens)} tokens"))

    def latency_summary(self) -> Dict[str, float]:
        """p50/p99 of time-to-first-token, of the wait in the queue
        before a request's first admission, and of per-step decode
        latency over the bounded reservoirs (seconds / milliseconds)
        — the soak-summary numbers; also folded into every metrics
        emission."""
        # snapshot under _lat_lock: the worker thread appends
        # concurrently, and iterating a deque during an append raises
        # RuntimeError — list(deque) iterates too, so the snapshot
        # itself must exclude the appender, not just downstream use
        with self._lat_lock:
            ttft = list(self._ttft)
            queue_wait = list(self._queue_wait)
            step_times = list(self._step_times)
        out: Dict[str, float] = {}
        out.update(percentile_summary(
            ttft, "ttft_p50_s", "ttft_p99_s"))
        out.update(percentile_summary(
            queue_wait, "queue_wait_p50_s", "queue_wait_p99_s"))
        out.update(percentile_summary(
            step_times, "step_ms_p50", "step_ms_p99", scale=1e3))
        return out

    def _emit_metrics(self, now: float) -> None:
        dt = max(now - (self._window_t0 or now), 1e-9)
        engine = self.engine
        chips = engine.chips_per_replica
        payload = {
            "tokens_per_sec": self._window_tokens / dt,
            # the Gemma-paper serving protocol reports throughput PER
            # CHIP — a tensor-parallel replica (chips > 1) divides by
            # its mesh width so 1×M and M×1 deployments compare at
            # equal chip count
            "tokens_per_sec_per_chip": self._window_tokens / dt / chips,
            "chips_per_replica": chips,
            "occupancy": self.scheduler.occupancy,
            "queue_depth": self.scheduler.queue_depth,
            "tokens_total": self._tokens_emitted,
            "requeues": self._requeues,
            "failed_requests": self._failed_requests,
            "deadline_expired": self._deadline_expired,
            "preempts": self.scheduler.preempts,
            # pool occupancy gauge: the overcommit dial
            "blocks_in_use": engine.blocks_in_use,
            "blocks_total": engine.blocks_total,
            "live_tokens": engine.live_tokens,
            # prefix-sharing gauges (0 when off)
            "shared_blocks": engine.shared_blocks,
            "cow_forks": engine.cow_forks,
            # pool storage width (8 = quantized int8/fp8 pages) —
            # numeric so any sink can plot/aggregate it; the dtype
            # NAME rides health()
            "kv_bits": engine.kv_bits,
        }
        payload.update(self.latency_summary())
        if engine.spec_tokens:
            # only when drafting is configured — a fleet-mean over
            # spec-disabled replicas' hardwired 0.0 would dilute it
            payload["spec_accept_rate"] = engine.spec_accept_rate
        self.metrics(self._steps, payload)
        self.metrics.drain()
        self._last_emit_step = self._steps
        self._window_tokens = 0
        self._window_t0 = now

    # ------------------------------------------------------------- health
    def health(self) -> Dict[str, Any]:
        """Readiness/liveness probe (cheap; any thread).

        ``status`` is ``"serving"`` (worker alive, accepting),
        ``"stopped"`` (never started, shut down, or stopping), or
        ``"failed"`` (worker died — root cause in ``error``);
        ``ready`` is the single boolean a load balancer should gate on
        — a *draining* replica stays ``status="serving"`` but reports
        ``ready=False`` (and ``draining=True``) so routers stop
        admitting without treating it as a failure.  ``uptime_s`` is
        seconds since :meth:`start`.  Counter fields make the probe
        double as the chaos-soak scoreboard: accepted == completed +
        failed when nothing is lost.  The full field table lives in
        ``docs/serving.md``.
        """
        now = time.monotonic()
        engine = self.engine
        with self._wakeup:
            alive = self._thread is not None and self._thread.is_alive()
            stopping = self._stop
            draining = self._draining
            error = self.error
        if error is not None:
            status = "failed"
        elif not alive or stopping:
            status = "stopped"
        else:
            status = "serving"
        out = {
            "status": status,
            "ready": status == "serving" and not draining,
            "draining": draining,
            "uptime_s": (0.0 if self._started_at is None
                         else now - self._started_at),
            "steps": self._steps,
            "queue_depth": self.scheduler.queue_depth,
            "occupancy": self.scheduler.occupancy,
            "tokens_emitted": self._tokens_emitted,
            "requeues": self._requeues,
            "failed_requests": self._failed_requests,
            "deadline_expired": self._deadline_expired,
            "drain_evicted": self._drain_evicted,
            "preempts": self.scheduler.preempts,
            # the program's own measurement (docs/serving.md): span
            # totals of the worker's three layers, the same by
            # duration, and where requests changed state
            "spans": {**self.spans.snapshot(),
                      **self.scheduler.spans.snapshot(),
                      **engine.spans.snapshot()},
            "span_hist": {**self.spans.histogram(),
                          **self.scheduler.spans.histogram(),
                          **engine.spans.histogram()},
            "admitted": self.scheduler.admitted,
            "queue_wait_s": self.scheduler.queue_wait_s,
            "first_tokens": self._first_tokens,
            "prefill_s": self._prefill_s,
            "compiles": sum(engine.trace_counts.values()),
            "error": None if error is None else repr(error),
            # chips this ONE replica spans (the tensor-parallel
            # degree; 1 on a single chip) — the fleet's capacity math
            # and the per-chip throughput protocol both read it
            "chips_per_replica": engine.chips_per_replica,
            "blocks_in_use": engine.blocks_in_use,
            "blocks_total": engine.blocks_total,
            "live_tokens": engine.live_tokens,
            "shared_blocks": engine.shared_blocks,
            "cow_forks": engine.cow_forks,
            "kv_pages_live": engine.kv_pages_live,
            "kv_write_pages": engine.kv_write_pages,
            # steps dispatched while their predecessor was unfetched
            "steps_ahead": engine.steps_ahead,
            # a mixed step's compact lanes: real ones fed, ones
            # computed, prefilling rows the lane budget held back
            "mixed_lanes_real": engine.mixed_lanes_real,
            "mixed_lanes_computed": engine.mixed_lanes_computed,
            "prefill_rows_deferred": engine.prefill_rows_deferred,
            "kv_dtype": engine.kv_dtype,
            "kv_bits": engine.kv_bits,
        }
        mesh_shape = engine.mesh_shape
        if mesh_shape:
            out["mesh_shape"] = mesh_shape
        if engine.spec_tokens:
            out["spec_accept_rate"] = engine.spec_accept_rate
        if engine.window is not None:
            # pages one window layer's sweeps visited (docs/serving.md)
            out["kv_window_pages"] = engine.kv_window_pages
        if engine.expert_layers:
            # the expert share's load, over steps and expert layers
            out["expert_assignments"] = engine.expert_assignments
            out["expert_load_max"] = engine.expert_load_max
            out["experts_active"] = engine.experts_active
            out["expert_layer_steps"] = engine.expert_layer_steps
        if engine.ssm_state_bytes:
            # recurrent state beside the pages (docs/serving.md)
            out["ssm_state_bytes"] = engine.ssm_state_bytes
            out["ssm_state_resets"] = engine.ssm_state_resets
            out["ssm_positions"] = engine.ssm_positions
        return out

    def prefix_hit_blocks(self, prompt) -> int:
        """Pages of ``prompt``'s prefix already resident in this
        server's trie (0 with sharing off) — the fleet router's
        prefix-affinity key."""
        return self.engine.prefix_hit_blocks(prompt)

    # ---------------------------------------------------------- telemetry
    @property
    def steps(self) -> int:
        return self._steps

    @property
    def tokens_emitted(self) -> int:
        return self._tokens_emitted
