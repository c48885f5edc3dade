"""Continuous batching: bounded FIFO queue + slot-level admission.

The scheduler owns the host-side view the device never needs: which
request occupies which slot, what has been emitted, and who is waiting.
At every step boundary it (1) refills free slots from the queue in FIFO
order — as far as the engine's page pool can take the head request —
then (2) dispatches engine steps (prompt chunks and decoding tenants in
the same batch) until one runs ahead of the one about to be fetched,
where the engine allows it, and (3) collects the oldest and routes
each token it produced to the request that held the slot when that
step was planned, evicting tenants that finished (eos or budget): the
chip runs step n + 1 while the host routes and delivers step n.
Requests never wait
for each other's completion: a 512-token generation and a 3-token one
share the batch, and the short one's slot is re-used the step after it
finishes — the continuous-batching property that fixed-batch
``generate()`` lacks.

Thread-safety: ``submit`` may be called from any thread (the queue has
its own lock); ``run_step`` must be called from the single thread that
owns the engine (``apex_tpu.serving.api.InferenceServer``'s worker).
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import deque
from typing import Deque, List, Optional, Tuple

import numpy as np

from apex_tpu.resilience import faults
from apex_tpu.utils.metrics import counters
from apex_tpu.utils.profiler import SpanTotals, span

__all__ = ["Request", "Scheduler", "QueueFull", "StepEvent"]

#: the scheduler's spans: ``admit`` covers one request's admission
#: (from leaving the queue to owning its slot, ``engine.admit``
#: included; ids ``uid``, ``prompt_len``), ``route`` what follows
#: ``engine.collect()`` (preempt requeues, token routing, releases)
ADMIT = "apex/sched/admit"
ROUTE = "apex/sched/route"


class QueueFull(RuntimeError):
    """The bounded request queue is at capacity."""


@dataclasses.dataclass
class Request:
    """One generation request (host object).

    ``top_k=None``/``0`` disables truncation, ``top_p=None``/``1.0``
    disables the nucleus filter, ``eos_id=None`` disables eos
    stopping, ``seed`` derives the request's private sampling key
    (tokens are a function of the request, not of its co-tenants).
    ``deadline`` (seconds from acceptance, ``None`` = unbounded) is
    enforced by the serving loop: an expired request — queued or
    mid-decode — fails with an explicit terminal error rather than
    occupying a slot forever.

    ``retries`` / ``accepted_at`` are serving-loop bookkeeping: how
    many times this request has been requeued after a transient step
    fault, and when it entered the queue (the deadline epoch).

    The other stamps (``time.monotonic()``, ``-1.0`` until set) say
    when the request changed state, for the client that holds it:
    ``enqueued_at`` — when it last entered the queue (submission, or
    a requeue after a preempt or a fault); ``admitted_at`` — when it
    was first given a slot; ``first_token_at`` — when the serving
    loop handed over its first token.
    """

    prompt: np.ndarray
    max_new_tokens: int
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_id: Optional[int] = None
    seed: int = 0
    deadline: Optional[float] = None
    uid: int = -1                       # assigned by the scheduler
    tokens: List[int] = dataclasses.field(default_factory=list)
    retries: int = 0
    accepted_at: float = -1.0
    enqueued_at: float = -1.0
    admitted_at: float = -1.0
    first_token_at: float = -1.0


@dataclasses.dataclass(frozen=True)
class StepEvent:
    """One token routed to one request at a step boundary."""

    request: Request
    token: int
    finished: bool


class Scheduler:
    """Bounded-queue continuous batcher over one
    :class:`~apex_tpu.serving.engine.PagedEngine`."""

    def __init__(self, engine, *, queue_capacity: int = 64):
        if queue_capacity < 1:
            raise ValueError(
                f"queue_capacity must be >= 1, got {queue_capacity}")
        self.engine = engine
        self.queue_capacity = int(queue_capacity)
        self._queue: Deque[Request] = deque()  # graftlint: guarded-by(_lock)
        self._lock = threading.Lock()
        self._uid = itertools.count()
        # host shadow of slot occupancy — the device active mask is
        # never read back outside step().  Fixed-length: only the
        # serving worker assigns items (never resizes), so a monitor
        # thread's iteration (occupancy/has_work) reads each cell
        # atomically and cannot raise or tear
        # graftlint: unguarded(fixed-size list, item writes by the engine-owning worker only; iteration safe)
        self._slots: List[Optional[Request]] = [None] * engine.max_slots
        # beside each step the engine has in flight, oldest first: the
        # slots' requests when it was planned.  A row of a step's
        # output is routed through THIS table — never through _slots,
        # which an admission, an expiry or an eviction may have changed
        # since — and a slot that is vacated is voided in it.  Only the
        # serving worker appends and pops; a monitor thread's
        # has_work() reads its truth value, one atomic load
        # graftlint: unguarded(appended, popped and cleared by the engine-owning worker only; other threads read its truth value)
        self._flights: Deque[List[Optional[Request]]] = deque()
        self._admit_failures: List[Tuple[Request, BaseException]] = []
        #: block-exhaustion preemptions requeued so far
        self.preempts = 0
        #: admissions so far (re-admissions after a preempt or a fault
        #: included) and the seconds they had waited in the queue
        self.admitted = 0
        self.queue_wait_s = 0.0
        self.spans = SpanTotals((ADMIT, ROUTE))

    # ------------------------------------------------------------ intake
    def submit(self, request: Request) -> Request:
        """Enqueue (FIFO); raises :class:`QueueFull` at capacity and
        ``ValueError`` for requests the engine can never admit (the
        check runs HERE so a doomed request fails at submit time, not
        inside the serving loop)."""
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        self.engine.validate_request(
            prompt.shape[0], request.max_new_tokens,
            request.temperature, request.top_k, request.top_p)
        request.prompt = prompt
        # originals, for fault-recovery requeues: a requeued request is
        # re-admitted with prompt = original ++ tokens-so-far and the
        # remaining budget, both derived from these
        request._prompt0 = prompt                    # type: ignore[attr-defined]
        request._budget0 = int(request.max_new_tokens)  # type: ignore[attr-defined]
        with self._lock:
            if len(self._queue) >= self.queue_capacity:
                raise QueueFull(
                    f"request queue at capacity "
                    f"({self.queue_capacity}); retry after a drain")
            request.uid = next(self._uid)
            request.accepted_at = request.enqueued_at = time.monotonic()
            self._queue.append(request)
        return request

    def requeue(self, request: Request) -> None:
        """Put an already-ACCEPTED request back at the queue's front
        (fault-recovery path — see ``InferenceServer._serve``).

        The request continues where it left off: its next admission
        prefills ``original prompt ++ tokens emitted so far`` with the
        remaining budget, so clients keep their streamed prefix and the
        total token count is unchanged.  Validates the continuation
        (the longer prompt must still fit the context and the pool) —
        a ``ValueError`` here means the request cannot be resumed and
        the caller must fail it terminally.  Bypasses the capacity
        check: accepted requests are never dropped for queue pressure.
        """
        prompt = np.asarray(request._prompt0, np.int32)  # type: ignore[attr-defined]
        if request.tokens:
            prompt = np.concatenate(
                [prompt, np.asarray(request.tokens, np.int32)])
        budget = int(request._budget0) - len(request.tokens)  # type: ignore[attr-defined]
        self.engine.validate_request(
            prompt.shape[0], budget, request.temperature,
            request.top_k, request.top_p)
        request.prompt = prompt
        request.max_new_tokens = budget
        with self._lock:
            request.enqueued_at = time.monotonic()
            self._queue.appendleft(request)

    def expire_queued(self, now: Optional[float] = None) -> List[Request]:
        """Remove and return queued requests whose deadline has passed
        (in-flight expiry is the serving loop's job — it owns the
        engine slots)."""
        now = time.monotonic() if now is None else now
        expired: List[Request] = []
        with self._lock:
            keep: Deque[Request] = deque()
            for req in self._queue:
                if req.deadline is not None \
                        and now - req.accepted_at > req.deadline:
                    expired.append(req)
                else:
                    keep.append(req)
            self._queue = keep
        return expired

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def active_count(self) -> int:
        return sum(r is not None for r in self._slots)

    @property
    def occupancy(self) -> float:
        return self.active_count / self.engine.max_slots

    def has_work(self) -> bool:
        """A tenant, a queued request, or a step still to collect."""
        return (self.active_count > 0 or self.queue_depth > 0
                or bool(self._flights))

    # ------------------------------------------------------------- steps
    def _admit_from_queue(self) -> int:
        """Fill free slots FIFO; returns the number admitted.

        A TRANSIENT failure during one admission (a retryable
        :class:`~apex_tpu.resilience.faults.TransientError`, injected
        or real — the raiser's contract is that engine state is
        untouched) is isolated to that request: it is retried from the
        queue's front once, then recorded terminally on
        ``take_admit_failures`` — either way the other tenants keep
        decoding.  Any other exception propagates (fatal, as before).

        Admission is TOKEN-gated, not just slot-gated: the engine's
        ``can_admit`` must also clear the queue head (free pages must
        cover prompt + decode headroom).  The check stays FIFO — a
        too-big head blocks the queue rather than being overtaken,
        so admission order cannot starve large requests.  Under a
        quantized pool (``kv_dtype="int8"``/``"fp8"``, ISSUE 8) the
        gate needs no extra logic: the engine sizes ``pool_tokens`` in
        QUANTIZED tokens (~2–4× more at equal HBM), so the same
        free-page arithmetic admits the reclaimed capacity as
        occupancy.
        """
        admitted = 0
        for slot, occupant in enumerate(self._slots):
            if occupant is not None:
                continue
            with self._lock:
                if not self._queue:
                    break
                head = self._queue[0]
                # shared-aware token gate: the engine discounts
                # trie-resident prefix pages, so a hot-prompt request
                # admits into capacity sharing reclaimed
                if not self.engine.can_admit(head.prompt.shape[0],
                                             head.max_new_tokens,
                                             prompt=head.prompt):
                    counters.inc("serving.admit_blocked")
                    break
                req = self._queue.popleft()
            with span(self.spans, ADMIT, uid=req.uid,
                      prompt_len=int(req.prompt.shape[0])):
                try:
                    faults.inject("serving.admit")
                    self.engine.admit(
                        slot, req.prompt,
                        max_new_tokens=req.max_new_tokens,
                        temperature=req.temperature,
                        top_k=req.top_k or 0,
                        top_p=req.top_p,
                        eos_id=req.eos_id,
                        seed=req.seed)
                except faults.TransientError as exc:
                    counters.inc("serving.admit_fault")
                    if req.retries < 1:
                        req.retries += 1
                        with self._lock:
                            self._queue.appendleft(req)
                    else:
                        self._admit_failures.append((req, exc))
                    # don't spin on the same request within one
                    # boundary — the retry happens at the next step
                    break
                now = time.monotonic()
                if req.admitted_at < 0:
                    req.admitted_at = now
                self.admitted += 1
                self.queue_wait_s += now - req.enqueued_at
                self._slots[slot] = req
            admitted += 1
        return admitted

    # graftlint: thread-entry(serving-worker)
    def take_admit_failures(self) -> List[Tuple[Request, BaseException]]:
        """Drain requests whose admission failed terminally (the
        serving loop routes these to their handles)."""
        failed, self._admit_failures = self._admit_failures, []
        return failed

    # graftlint: thread-entry(serving-worker)
    def evict(self, slot: int) -> Optional[Request]:
        """Release ``slot`` (its pages go back to the pool) and return its
        tenant — deadline-expiry and fault-recovery path.  Call from
        the engine-owning thread only."""
        req = self._slots[slot]
        if req is None:
            return None
        self.engine.release(slot)
        self._vacate(slot)
        return req

    def _vacate(self, slot: int) -> None:
        """``slot`` has lost its tenant: whatever the steps in flight
        still produce for it is nobody's (knowingly discarded — the
        token was never handed over, and a requeued continuation makes
        it again), and the slot's next tenant never sees it."""
        self._slots[slot] = None
        for tenants in self._flights:
            tenants[slot] = None

    # graftlint: thread-entry(serving-worker)
    def evict_all(self) -> List[Request]:
        """Evict every active tenant and return them in slot order —
        the graceful-drain path (``InferenceServer.begin_drain``).
        Engine rows are released through the same compiled ``release``
        as normal completion, so the pool gets all its pages back
        (``blocks_in_use`` returns to 0 once the queue is also
        cancelled).  The steps in flight served nobody else and are
        dropped unfetched.  Call from the engine-owning thread only."""
        evicted: List[Request] = []
        for slot in range(len(self._slots)):
            req = self.evict(slot)
            if req is not None:
                evicted.append(req)
        self.discard_in_flight()
        return evicted

    # graftlint: thread-entry(serving-worker)
    def discard_in_flight(self) -> None:
        """Drop the steps in flight unfetched: every slot is vacated
        (a drain, a shutdown), so nobody is left to route them to."""
        self._flights.clear()
        self.engine.discard()

    # graftlint: thread-entry(serving-worker)
    def run_step(self) -> List[StepEvent]:
        """One step boundary: admit → dispatch → collect → route/evict.

        Returns the tokens of the step COLLECTED here, the oldest in
        flight (empty when idle); one more step may stay in flight
        behind it, so that the chip works while the caller delivers.
        Call from the engine-owning thread only.

        The chip is kept fed with at most two dispatched steps — the
        one about to be fetched and one ahead of it; the engine refuses
        the one ahead where the step in flight has to be collected
        first (it frees a slot, so that the next request rides the very
        next step; a drafted step; a plan that would preempt —
        :meth:`PagedEngine.dispatch`).

        The engine returns a :class:`~apex_tpu.serving.engine.
        StepOutput`: only ``emitted`` slots route a token (mid-prefill
        tenants compute but emit nothing), and ``preempted`` tenants —
        evicted by the engine for block exhaustion, pages already
        freed — are requeued at the FRONT to continue from their
        streamed prefix (the PR-4 fault-recovery machinery, but
        without spending the request's transient-fault retry budget:
        preemption is scheduling, not failure).
        """
        self._admit_from_queue()
        while len(self._flights) < 2 and self.active_count \
                and self.engine.dispatch():
            self._flights.append(list(self._slots))
        if not self._flights:
            return []
        out = self.engine.collect()
        with span(self.spans, ROUTE):
            events = self._route(out, self._flights[0])
            self._flights.popleft()
            return events

    def _route(self, out, tenants) -> List[StepEvent]:
        """Requeue the step's preempted tenants, route its tokens to
        their requests — ``tenants``, the slots' requests when the step
        was planned —, release the slots that finished."""
        tokens, finished, _emitted, preempted, counts = out
        for slot in preempted:
            req = tenants[slot]
            if req is None:
                continue
            self._vacate(slot)          # engine already freed the slot
            self.preempts += 1
            counters.inc("serving.preempt")
            try:
                self.requeue(req)
            except ValueError as exc:   # unresumable continuation
                self._admit_failures.append((req, exc))
        events: List[StepEvent] = []
        for slot, req in enumerate(tenants):
            if req is None:
                continue
            # a drafted (speculative) step can emit SEVERAL tokens for
            # one slot — route each in order, finishing on the last
            n_emit = int(counts[slot])
            if n_emit == 0:
                continue
            row = tokens[slot]
            for j in range(n_emit):
                tok = int(row[j])
                fin = bool(finished[slot]) and j == n_emit - 1
                req.tokens.append(tok)
                events.append(StepEvent(req, tok, fin))
                if fin:
                    self.engine.release(slot)
                    self._vacate(slot)
        return events

    # graftlint: single-threaded(synchronous convenience for tests/batch scripts; no server thread runs beside it)
    def drain(self) -> List[StepEvent]:
        """Run steps until queue and slots are empty; returns every
        event in emission order (synchronous convenience for tests and
        batch scripts — the threaded server streams instead)."""
        events: List[StepEvent] = []
        while self.has_work():
            events.extend(self.run_step())
        return events

    def cancel_queued(self) -> List[Request]:
        """Drop every not-yet-admitted request (server shutdown path)."""
        with self._lock:
            dropped = list(self._queue)
            self._queue.clear()
        return dropped
