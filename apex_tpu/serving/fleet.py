"""Multi-replica serving fleet: health-gated router, graceful drain,
and replica-kill survival.

One :class:`~apex_tpu.serving.api.InferenceServer` is one host; heavy
traffic needs N replicas that individually fail, drain, and scale
without client-visible loss.  :class:`FleetRouter` is the front door:

- **Routing** — ``submit()`` goes to the least-loaded *routable*
  replica, ranked by the engine's ``blocks_in_use /
  blocks_total`` occupancy gauge, queue depth breaking ties.  A failed
  routing attempt (full queue, closed replica, injected
  ``fleet.route`` fault) retries with capped,
  deterministically-jittered backoff onto the next-best replica before
  surfacing :class:`~apex_tpu.serving.api.RequestFailed`.
- **Health gating** — a supervisor thread probes every replica's
  ``health()`` on an interval, feeding a per-replica
  :class:`CircuitBreaker`: ``healthy`` → ``suspect`` after K
  consecutive probe failures or a step-latency p99 SLO breach →
  ``ejected`` (unroutable) → after a cooldown, ``probation`` (routable
  again, on trial) → ``healthy`` after consecutive good probes — or
  straight back to ``ejected`` on any probation failure.
- **Tenant migration** — a killed or dead replica's in-flight
  requests are requeued onto survivors via the PR-4/5 streamed-prefix
  machinery (``prompt ++ already-streamed tokens``, remaining budget,
  remaining deadline), so generation resumes elsewhere with greedy
  output token-identical to an uninterrupted run and zero
  client-visible loss (the client's :class:`FleetHandle` just keeps
  streaming).
- **Graceful drain** — :meth:`FleetRouter.drain` stops admitting to a
  replica, migrates every queued/active tenant, waits until the
  replica is empty (its paged pool back to ``blocks_in_use == 0``),
  then shuts it down and detaches it.
- **Scaling** — :meth:`FleetRouter.scale_up` builds a fresh replica
  from the factory; :meth:`FleetRouter.scale_down` routes through
  drain so nothing is lost.  With an :class:`AutoscaleConfig`, the
  supervisor drives both from aggregate queue depth and fleet TTFT
  p99 (:func:`scale_decision`).

Three deterministic fault sites plug into the
:class:`~apex_tpu.resilience.faults.FaultPlan` registry —
``fleet.route`` (per routing attempt), ``fleet.probe`` (per health
probe), and ``replica.kill`` (per supervisor tick; ANY raising kind
fired there SIGKILL-equivalently kills the replica) — so chaos runs
replay exactly; see the site table in ``apex_tpu/resilience/faults.py``.

Per-replica metrics aggregate into one fleet view through
:func:`apex_tpu.utils.metrics.namespaced_sink` /
:meth:`~apex_tpu.utils.metrics.MetricsWriter.merge` (no step-tag
collisions).  ``docs/fleet.md`` is the narrative guide; the chaos
acceptance soaks live in ``tests/test_chaos.py``.

Usage::

    factory = lambda: InferenceServer(model, params, max_slots=16)
    router = FleetRouter(factory, replicas=3)
    with router:
        h = router.submit(prompt_tokens, max_new_tokens=256)
        for tok in h.stream():
            ...                     # survives a replica dying mid-way
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
import zlib
from collections import deque
from typing import (
    Any, Callable, Deque, Dict, List, Mapping, Optional, Sequence,
)

import numpy as np

from apex_tpu.resilience import faults
from apex_tpu.serving.api import (
    RequestFailed,
    RequestHandle,
    ServerClosed,
)
from apex_tpu.serving.scheduler import QueueFull
from apex_tpu.utils.metrics import (
    MetricsWriter,
    counters,
    namespaced_sink,
    percentile_summary,
)

__all__ = [
    "FleetRouter",
    "FleetHandle",
    "CircuitBreaker",
    "AutoscaleConfig",
    "load_score",
    "select_replica",
    "route_backoff",
    "scale_decision",
    "HEALTHY",
    "SUSPECT",
    "EJECTED",
    "PROBATION",
]

#: every exception class the fault registry can raise — the fleet
#: sites treat ANY raising kind as the site's failure signal
#: (TransientError and Preempted are deliberately not FaultError
#: subclasses; see resilience.faults)
_INJECTED = (faults.FaultError, faults.TransientError, faults.Preempted)

#: circuit-breaker states (module constants so tests and dashboards
#: can name them without importing the class internals)
HEALTHY = "healthy"
SUSPECT = "suspect"
EJECTED = "ejected"
PROBATION = "probation"


class CircuitBreaker:
    """Per-replica health state machine (the router's gate).

    ::

        healthy --[suspect_after consecutive probe failures,
                   or one step-latency p99 breach]--> suspect
        suspect --[eject_after more consecutive failures]--> ejected
        suspect --[probation_probes consecutive successes]--> healthy
        ejected --[cooldown_s elapsed, via tick()]--> probation
        probation --[probation_probes consecutive successes]--> healthy
        probation --[any failure]--> ejected   (fresh cooldown)

    ``ejected`` is the only unroutable state (:attr:`routable`);
    ``suspect`` and ``probation`` still take traffic — the breaker
    sheds a replica only after repeated evidence, and re-admits it on
    trial rather than all at once.  Time is always passed in
    (``now``), so transitions are a pure function of the event
    sequence — unit-testable without clocks and replayable in chaos
    runs.  Thread-safe: the supervisor records probes while client
    dispatch threads record submit failures.  Every ejection counts
    on ``fleet.ejected``.
    """

    def __init__(self, *, suspect_after: int = 3, eject_after: int = 2,
                 cooldown_s: float = 2.0, probation_probes: int = 2):
        if suspect_after < 1 or eject_after < 1 or probation_probes < 1:
            raise ValueError(
                "suspect_after, eject_after and probation_probes must "
                "all be >= 1")
        if cooldown_s <= 0:
            raise ValueError(f"cooldown_s must be > 0, got {cooldown_s}")
        self.suspect_after = int(suspect_after)
        self.eject_after = int(eject_after)
        self.cooldown_s = float(cooldown_s)
        self.probation_probes = int(probation_probes)
        # transitions happen under _mutex; the router's gate reads the
        # state string unlocked (one atomic load — at worst a probe
        # routes to a replica ejected this instant, which the retry
        # path absorbs)
        # graftlint: unguarded(writes under _mutex; unlocked readers take one atomic str load, staleness absorbed by routing retries)
        self.state = HEALTHY
        # RLock: on_latency_breach re-enters on_failure
        self._mutex = threading.RLock()
        self._fails = 0  # graftlint: guarded-by(_mutex)
        self._oks = 0  # graftlint: guarded-by(_mutex)
        self._ejected_at: Optional[float] = None  # graftlint: guarded-by(_mutex)

    @property
    def routable(self) -> bool:
        """Whether the router may send traffic here (not ejected)."""
        return self.state != EJECTED

    def on_success(self, now: float = 0.0) -> str:
        """Record a good probe; returns the (possibly new) state."""
        del now
        with self._mutex:
            self._fails = 0
            if self.state in (SUSPECT, PROBATION):
                self._oks += 1
                if self._oks >= self.probation_probes:
                    self.state = HEALTHY
                    self._oks = 0
            return self.state

    def on_failure(self, now: float = 0.0) -> str:
        """Record a failed probe; returns the (possibly new) state."""
        with self._mutex:
            self._oks = 0
            if self.state == HEALTHY:
                self._fails += 1
                if self._fails >= self.suspect_after:
                    self.state = SUSPECT
                    self._fails = 0
            elif self.state == SUSPECT:
                self._fails += 1
                if self._fails >= self.eject_after:
                    self._eject(now)
            elif self.state == PROBATION:
                self._eject(now)
            return self.state

    def on_latency_breach(self, now: float = 0.0) -> str:
        """A step-latency p99 SLO breach: a healthy replica turns
        suspect immediately (no K-failure grace — latency is measured
        over a whole percentile window, not one probe); a suspect or
        probation replica counts it like a probe failure."""
        with self._mutex:
            if self.state == HEALTHY:
                self._oks = 0
                self._fails = 0
                self.state = SUSPECT
                return self.state
            return self.on_failure(now)

    # graftlint: requires-lock(_mutex)
    def _eject(self, now: float) -> None:
        # callers hold self._mutex
        self.state = EJECTED
        self._fails = 0
        self._oks = 0
        self._ejected_at = now
        counters.inc("fleet.ejected")

    def tick(self, now: float) -> str:
        """Move an ejected replica into probation once ``cooldown_s``
        has elapsed; call once per supervisor tick."""
        with self._mutex:
            if self.state == EJECTED and self._ejected_at is not None \
                    and now - self._ejected_at >= self.cooldown_s:
                self.state = PROBATION
                self._oks = 0
            return self.state


# --------------------------------------------------------------------- #
# pure routing / scaling math (unit-tested without servers)
# --------------------------------------------------------------------- #
def load_score(health: Mapping[str, Any]) -> float:
    """Least-loaded routing key for one replica ``health()`` dict: the
    pool's ``blocks_in_use / blocks_total`` occupancy; a dict that
    lacks the gauge (a hand-made one) falls back to its slot
    ``occupancy`` — both in [0, 1].  Queue depth breaks ties upstream
    (:func:`select_replica`)."""
    total = health.get("blocks_total") or 0
    if total:
        return float(health.get("blocks_in_use", 0)) / float(total)
    return float(health.get("occupancy", 0.0))


def select_replica(
        healths: Sequence[Optional[Mapping[str, Any]]],
        affinity: Optional[Sequence[int]] = None) -> int:
    """Index of the least-loaded ready replica, or -1 when none is.

    ``healths[i]`` is replica i's ``health()`` dict, or ``None`` for a
    replica the caller already excluded (ejected, draining, dead).
    Ranking: :func:`load_score` ascending, then **prefix affinity**
    descending (``affinity[i]`` = trie-resident prefix pages of the
    request on replica i — a hit replica serves the request without
    recomputing or re-storing the shared prompt's KV), then
    ``queue_depth``, then index (stable under ties).  Affinity is a
    TIE-BREAK below load: it concentrates a hot prompt's tenants
    where its pages live, but never overrides least-loaded placement
    (no hot-prompt replica meltdown); with no ``affinity`` the
    pre-ISSUE-7 ordering is unchanged."""
    best = -1
    best_key = None
    for i, h in enumerate(healths):
        if not h or not h.get("ready"):
            continue
        hit = 0 if affinity is None else int(affinity[i])
        key = (load_score(h), -hit, int(h.get("queue_depth", 0)), i)
        if best_key is None or key < best_key:
            best, best_key = i, key
    return best


def route_backoff(attempt: int, uid: int = 0, *, base: float = 0.01,
                  cap: float = 0.25) -> float:
    """Capped exponential backoff with deterministic jitter.

    ``attempt`` counts retries (1 = first retry).  The raw delay
    ``base * 2**(attempt-1)`` is capped at ``cap``, then jittered into
    ``[raw/2, raw]`` by a hash of ``(uid, attempt)`` — the same
    crc32-into-[0,1) trick the fault registry uses, so a chaos run's
    retry timing replays exactly (no live RNG).  The cap holds after
    jitter: the returned delay never exceeds ``cap``."""
    raw = min(float(cap), float(base) * (2.0 ** max(0, attempt - 1)))
    u = zlib.crc32(f"{uid}:{attempt}".encode()) / 2.0 ** 32
    return raw * (0.5 + 0.5 * u)


@dataclasses.dataclass(frozen=True)
class AutoscaleConfig:
    """Queue-depth + TTFT-p99 scale thresholds (the roadmap's scale
    hooks).  ``scale_up_queue_depth`` — aggregate queued requests
    beyond which the fleet adds a replica; ``ttft_slo_p99_s`` — fleet
    TTFT p99 SLO whose breach also scales up (``None`` disables the
    latency trigger); ``scale_down_queue_depth`` — aggregate depth at
    or below which an idle fleet sheds a replica (through drain, so
    scale-down is loss-free); ``min_replicas``/``max_replicas`` bound
    the fleet; ``cooldown_ticks`` suppresses decisions for that many
    supervisor ticks after any scale action (anti-flap)."""

    scale_up_queue_depth: int = 8
    scale_down_queue_depth: int = 0
    ttft_slo_p99_s: Optional[float] = None
    min_replicas: int = 1
    max_replicas: int = 8
    cooldown_ticks: int = 10


def scale_decision(queue_depth: int, ttft_p99_s: Optional[float],
                   n_replicas: int,
                   cfg: AutoscaleConfig) -> Optional[str]:
    """Pure scale decision: ``"up"``, ``"down"``, or ``None``.

    Scale up when below ``min_replicas``, or when hot (aggregate
    ``queue_depth`` above the up-threshold, or TTFT p99 over its SLO)
    and below ``max_replicas``.  Scale down only when NOT hot, at or
    below the down-threshold, and above ``min_replicas``."""
    if n_replicas < cfg.min_replicas:
        return "up"
    hot = queue_depth > cfg.scale_up_queue_depth or (
        cfg.ttft_slo_p99_s is not None and ttft_p99_s is not None
        and ttft_p99_s > cfg.ttft_slo_p99_s)
    if hot:
        return "up" if n_replicas < cfg.max_replicas else None
    if queue_depth <= cfg.scale_down_queue_depth \
            and n_replicas > cfg.min_replicas:
        return "down"
    return None


# --------------------------------------------------------------------- #
# fleet request bookkeeping
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class _FleetRequest:
    """Router-side record of one request: everything migration needs
    to resume it elsewhere (original prompt, streamed tokens, sampling
    params, remaining budget/deadline) plus where it currently runs."""

    uid: int
    prompt: np.ndarray
    budget: int
    temperature: float
    top_k: Optional[int]
    top_p: Optional[float]
    eos_id: Optional[int]
    seed: int
    deadline: Optional[float]
    accepted_at: float = 0.0
    tokens: List[int] = dataclasses.field(default_factory=list)
    handle: Optional["FleetHandle"] = None
    replica: int = -1
    migrations: int = 0


class FleetHandle(RequestHandle):
    """Client-side view of one *fleet* request — the same streaming
    API and error contract as :class:`~apex_tpu.serving.api.
    RequestHandle` (``TimeoutError`` retryable; ``RequestFailed`` /
    ``ServerClosed`` terminal), with migration invisible: if the
    replica serving this request dies or drains, the stream simply
    pauses while the router requeues it onto a survivor, then resumes
    — ``tokens_so_far``/``result`` return the union of tokens streamed
    across every replica the request visited, each exactly once."""


@dataclasses.dataclass
class _Replica:
    """Router-side record of one replica server."""

    index: int
    server: Any                      # InferenceServer (duck-typed)
    breaker: CircuitBreaker
    writer: Optional[MetricsWriter] = None
    draining: bool = False
    dead: bool = False
    #: fleet uid -> record, for every request currently on this replica
    active: Dict[int, _FleetRequest] = dataclasses.field(
        default_factory=dict)


class FleetRouter:
    """Health-gated front door over a pool of replica
    :class:`~apex_tpu.serving.api.InferenceServer`\\ s.

    ``factory`` builds one (unstarted) replica server; the router owns
    their lifecycle (``start``/``warmup`` on :meth:`start`, shutdown
    on :meth:`shutdown`, plus :meth:`drain`, :meth:`kill_replica`,
    :meth:`scale_up`/:meth:`scale_down` in between).  ``submit``
    mirrors the server's signature (minus backpressure knobs — the
    router retries across replicas instead of blocking on one queue)
    and returns a :class:`FleetHandle`.

    Failure semantics extend the single-server contract
    (``docs/resilience.md``): every accepted request still ends in
    exactly one of completed / ``RequestFailed`` / ``ServerClosed`` —
    but a replica dying (killed, crashed) or draining no longer fails
    its requests: they migrate to survivors and keep streaming, with
    greedy output token-identical to an uninterrupted run.
    ``RequestFailed`` now also covers routing exhaustion (no replica
    accepted after the retry budget) and failed migration (no
    survivor, expired deadline, unresumable continuation).

    The supervisor thread wakes every ``probe_interval`` seconds to
    probe health into each replica's :class:`CircuitBreaker` (with
    ``step_slo_ms`` as the latency-breach threshold, when set), check
    the ``replica.kill`` fault site, process pending migrations, drive
    autoscaling (when ``autoscale`` is set), and aggregate metrics.
    """

    def __init__(self, factory: Optional[Callable[[], Any]] = None, *,
                 replicas: int = 2,
                 servers: Optional[Sequence[Any]] = None,
                 probe_interval: float = 0.25,
                 breaker_factory: Optional[
                     Callable[[], CircuitBreaker]] = None,
                 step_slo_ms: Optional[float] = None,
                 route_retries: int = 3,
                 backoff_base: float = 0.01,
                 backoff_cap: float = 0.25,
                 autoscale: Optional[AutoscaleConfig] = None,
                 metrics: Optional[MetricsWriter] = None,
                 metrics_interval: int = 8):
        if servers is None and factory is None:
            raise ValueError("pass a replica factory or servers=[...]")
        if servers is None and replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if route_retries < 0:
            raise ValueError(
                f"route_retries must be >= 0, got {route_retries}")
        self.factory = factory
        self.probe_interval = float(probe_interval)
        self.step_slo_ms = step_slo_ms
        self.route_retries = int(route_retries)
        self.backoff_base = float(backoff_base)
        self.backoff_cap = float(backoff_cap)
        self.autoscale = autoscale
        self.metrics = metrics
        self.metrics_interval = max(1, int(metrics_interval))
        self._breaker_factory = breaker_factory or CircuitBreaker
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        # append-only replica table (replicas are marked dead, never
        # removed): appends hold _lock; unlocked readers (monitors,
        # _live() on lock-free paths) index or iterate a list that
        # only grows, which CPython reads atomically — at worst a
        # probe misses a replica added this instant
        # graftlint: unguarded(append-only under _lock; unlocked iteration/indexing of a grow-only list is atomic per op)
        self._replicas: List[Optional[_Replica]] = []
        self._requests: Dict[int, _FleetRequest] = {}  # graftlint: guarded-by(_lock)
        self._migq: Deque[int] = deque()  # graftlint: guarded-by(_lock)
        self._pump_lock = threading.Lock()
        self._uid = itertools.count()
        self._route_steps = itertools.count()
        # TTFT reservoir: replica worker taps append (under _cv, which
        # IS _lock) while the supervisor/clients snapshot — unlocked,
        # list(deque)-during-append raises RuntimeError (the
        # pre-existing race graftlint's concurrency pass flagged)
        self._ttft: Deque[float] = deque(maxlen=4096)  # graftlint: guarded-by(_lock)
        self._submitted = 0  # graftlint: guarded-by(_lock)
        self._completed = 0  # graftlint: guarded-by(_lock)
        self._failed = 0  # graftlint: guarded-by(_lock)
        self._migrated = 0  # graftlint: guarded-by(_lock)
        self._tokens_total = 0  # graftlint: guarded-by(_lock)
        self._scale_cooldown = 0  # graftlint: guarded-by(_lock)
        self._running = False
        self._stopping = False
        self._stop_supervisor = False
        self._supervisor: Optional[threading.Thread] = None
        #: last exception a supervisor pass swallowed (the loop itself
        #: must outlive any single bad tick); surfaced in health()
        self.supervisor_error: Optional[BaseException] = None
        if servers is not None:
            for server in servers:
                self._add_replica(server)
        else:
            for _ in range(int(replicas)):
                self._add_replica(self.factory())

    # ---------------------------------------------------------- replicas
    def _add_replica(self, server: Any) -> _Replica:
        rep = _Replica(index=0, server=server,
                       breaker=self._breaker_factory())
        with self._lock:
            rep.index = len(self._replicas)
            self._replicas.append(rep)
        if self.metrics is not None \
                and getattr(server, "metrics", None) is None:
            # route the replica's self-drained emissions into the
            # fleet writer, namespaced — no step-tag collisions.  A
            # server the factory already wired its OWN writer+sink
            # keeps that pipeline untouched: its rows drain
            # server-side to the caller's sink and are deliberately
            # NOT fleet-aggregated (hand the router metrics-less
            # servers to aggregate them) — the fleet view still
            # carries the fleet/ summary rows either way
            rep.writer = MetricsWriter(sink=namespaced_sink(
                f"replica{rep.index}", self.metrics))
            server.metrics = rep.writer
        return rep

    def _live(self) -> List[_Replica]:
        """Replicas that can take traffic-lifecycle actions (not dead,
        not draining) — call with or without the lock held."""
        return [r for r in self._replicas
                if r is not None and not r.dead and not r.draining]

    @property
    def num_replicas(self) -> int:
        """Live (not dead, not draining) replica count."""
        with self._lock:
            return len(self._live())

    def replica(self, index: int) -> Any:
        """The replica server at ``index`` (introspection/tests)."""
        rep = self._replicas[index]
        if rep is None:
            raise ValueError(f"replica {index} was removed")
        return rep.server

    # --------------------------------------------------------- lifecycle
    def start(self, *, warmup: bool = True) -> "FleetRouter":
        """Start every replica (tracing its executables when
        ``warmup``) and the supervisor thread."""
        if self._running:
            raise RuntimeError("fleet already started")
        for rep in self._live():
            rep.server.start(warmup=warmup)
        self._running = True
        self._stopping = False
        self._stop_supervisor = False
        self._supervisor = threading.Thread(
            target=self._supervise, name="apex-tpu-fleet", daemon=True)
        self._supervisor.start()
        return self

    def shutdown(self, *, wait: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Stop the fleet.  ``wait=True`` serves every in-flight
        request to a terminal outcome first (migrations included);
        ``wait=False`` cancels them (:class:`ServerClosed`)."""
        if wait:
            deadline = (None if timeout is None
                        else time.monotonic() + timeout)
            while True:
                self._pump_migrations()
                with self._cv:
                    if not self._requests:
                        break
                    if deadline is not None \
                            and time.monotonic() > deadline:
                        break
                    self._cv.wait(0.05)
        with self._cv:
            self._stopping = True
            self._stop_supervisor = True
            self._cv.notify_all()
        supervisor = self._supervisor
        if supervisor is not None:
            supervisor.join(timeout)
            self._supervisor = None
        for rep in list(self._replicas):
            if rep is not None and not rep.dead:
                rep.server.shutdown(wait=wait)
        # anything still tracked lost its replica without a migration
        # target: fail it explicitly (never silently lost)
        leftovers = []
        with self._cv:
            leftovers = list(self._requests.values())
            self._requests.clear()
            self._migq.clear()
            self._failed += len(leftovers)
        for rec in leftovers:
            rec.handle._fail(ServerClosed(
                "fleet shut down before the request finished"))
        if self.metrics is not None:
            self._emit_metrics()
        self._running = False

    def __enter__(self) -> "FleetRouter":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> None:
        self.shutdown(wait=exc_type is None)

    # ------------------------------------------------------------ intake
    def submit(self, prompt, *, max_new_tokens: int,
               temperature: float = 0.0, top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               eos_id: Optional[int] = None, seed: int = 0,
               deadline: Optional[float] = None) -> FleetHandle:
        """Route one request to the least-loaded routable replica;
        returns its :class:`FleetHandle`.

        Raises :class:`~apex_tpu.serving.api.RequestFailed` when no
        replica accepts within the retry budget (each attempt backs
        off per :func:`route_backoff` and moves to the next-best
        replica), and :class:`ServerClosed` on a stopped fleet.
        ``deadline`` is fleet-scoped: migration forwards the
        *remaining* deadline to the new replica.
        """
        if not self._running or self._stopping:
            raise ServerClosed("fleet is not running")
        rec = _FleetRequest(
            uid=next(self._uid),
            prompt=np.asarray(prompt, np.int32).reshape(-1),
            budget=int(max_new_tokens),
            temperature=float(temperature),
            top_k=top_k, top_p=top_p, eos_id=eos_id, seed=int(seed),
            deadline=None if deadline is None else float(deadline),
            accepted_at=time.monotonic())
        rec.handle = FleetHandle(rec)
        with self._lock:
            self._requests[rec.uid] = rec
            self._submitted += 1
        try:
            self._dispatch(rec)
        except BaseException:
            with self._lock:
                self._requests.pop(rec.uid, None)
                self._submitted -= 1
            raise
        return rec.handle

    # ---------------------------------------------------------- routing
    def _select(self, excluded,
                prompt=None) -> Optional[_Replica]:
        """Least-loaded routable replica (health probed fresh), or
        ``None``.  ``prompt`` (the request's ``original ++ streamed``
        tokens) feeds the prefix-affinity tie-break: a replica whose
        trie already holds the prompt's prefix pages wins ties, so a
        hot system prompt's tenants converge where its KV lives — the
        routing hook PR 6 left open."""
        with self._lock:
            candidates = [r for r in self._live()
                          if r.breaker.routable
                          and r.index not in excluded]
            n = len(self._replicas)
        healths: List[Optional[Dict[str, Any]]] = [None] * n
        affinity = [0] * n
        for rep in candidates:
            try:
                healths[rep.index] = rep.server.health()
            except Exception:               # noqa: BLE001 — a replica
                healths[rep.index] = None   # too broken to probe is
                continue                    # simply not a candidate
            if prompt is not None:
                try:
                    affinity[rep.index] = int(getattr(
                        rep.server, "prefix_hit_blocks",
                        lambda _p: 0)(prompt))
                except Exception:           # noqa: BLE001 — affinity
                    affinity[rep.index] = 0  # is advisory, never fatal
        index = select_replica(healths, affinity)
        return None if index < 0 else self._replicas[index]

    def _dispatch(self, rec: _FleetRequest, *,
                  migration: bool = False) -> None:
        """Place ``rec`` on a replica — first admission and migration
        share this path (a migration's prompt is ``original ++
        streamed tokens`` with the remaining budget/deadline).  Raises
        :class:`RequestFailed` after the retry budget."""
        prompt = rec.prompt
        if rec.tokens:
            prompt = np.concatenate(
                [prompt, np.asarray(rec.tokens, np.int32)])
        budget = rec.budget - len(rec.tokens)
        last: Optional[BaseException] = None
        excluded: set = set()
        attempts = self.route_retries + 1
        for attempt in range(1, attempts + 1):
            if attempt > 1:
                time.sleep(route_backoff(
                    attempt - 1, rec.uid, base=self.backoff_base,
                    cap=self.backoff_cap))
            # recomputed per attempt: backoff slept above is charged
            # against the fleet-scoped deadline, never granted back
            deadline = None
            if rec.deadline is not None:
                remaining = rec.deadline - (time.monotonic()
                                            - rec.accepted_at)
                if migration and remaining <= 0:
                    raise RequestFailed(
                        f"request {rec.uid} deadline ({rec.deadline}s)"
                        f" expired before migration")
                deadline = max(remaining, 0.0)
            try:
                # one deterministic injection per routing attempt
                faults.inject("fleet.route",
                              step=next(self._route_steps))
            except _INJECTED as exc:
                last = exc
                counters.inc("fleet.route_fault")
                continue
            target = self._select(excluded, prompt)
            if target is None:
                # every replica excluded or unroutable — clear the
                # per-round exclusions (a replica may have recovered)
                # and back off
                excluded.clear()
                last = last or ServerClosed("no routable replica")
                continue
            # register BEFORE submitting: a fast worker can stream —
            # even finish — the request before submit() returns, and
            # the tap must find consistent bookkeeping
            with self._lock:
                rec.replica = target.index
                target.active[rec.uid] = rec
            try:
                target.server.submit(
                    prompt, max_new_tokens=budget,
                    temperature=rec.temperature, top_k=rec.top_k,
                    top_p=rec.top_p, eos_id=rec.eos_id, seed=rec.seed,
                    deadline=deadline, block=False,
                    tap=self._tap_for(rec, target.index))
            except QueueFull as exc:
                last = exc
                counters.inc("fleet.route_retry")
                excluded.add(target.index)
                with self._lock:
                    target.active.pop(rec.uid, None)
                continue
            except ServerClosed as exc:
                last = exc
                counters.inc("fleet.route_retry")
                excluded.add(target.index)
                target.breaker.on_failure(time.monotonic())
                with self._lock:
                    target.active.pop(rec.uid, None)
                continue
            except ValueError as exc:       # unresumable continuation
                with self._lock:
                    target.active.pop(rec.uid, None)
                failure = RequestFailed(
                    f"request {rec.uid} not routable: {exc}")
                failure.__cause__ = exc
                raise failure
            return
        counters.inc("fleet.route_failed")
        failure = RequestFailed(
            f"request {rec.uid}: no replica accepted after "
            f"{attempts} routing attempts")
        failure.__cause__ = last
        raise failure

    # --------------------------------------------------- stream plumbing
    def _tap_for(self, rec: _FleetRequest, replica_index: int):
        def tap(token: Optional[int], finished: bool,
                error: Optional[BaseException]) -> None:
            if error is not None:
                self._on_inner_error(rec, replica_index, error)
            else:
                self._on_inner_token(rec, replica_index, token,
                                     finished)
        return tap

    # graftlint: thread-entry(replica-worker)
    def _on_inner_token(self, rec: _FleetRequest, replica_index: int,
                        token: int, finished: bool) -> None:
        """A replica delivered one token (its worker thread): mirror
        it into the fleet handle and record it for migration."""
        first = not rec.tokens
        if first:           # clock read off the per-token hot path;
            # computed before taking _cv so lock-wait is not counted
            ttft = time.monotonic() - rec.accepted_at
        rec.tokens.append(int(token))
        rec.handle._deliver(int(token), bool(finished))
        with self._cv:
            if first:
                self._ttft.append(ttft)
            self._tokens_total += 1
            if finished:
                rep = self._replicas[replica_index]
                if rep is not None:
                    rep.active.pop(rec.uid, None)
                self._requests.pop(rec.uid, None)
                self._completed += 1
                self._cv.notify_all()

    # graftlint: thread-entry(replica-worker)
    def _on_inner_error(self, rec: _FleetRequest, replica_index: int,
                        error: BaseException) -> None:
        """A replica failed this request.  :class:`ServerClosed` (the
        replica died, was killed, or is draining) queues a migration —
        the fleet handle stays open and the stream resumes on a
        survivor; anything else (:class:`RequestFailed`: deadline,
        double transient fault) is terminal and forwarded."""
        migrate = isinstance(error, ServerClosed) and not self._stopping
        with self._cv:
            rep = self._replicas[replica_index]
            if rep is not None:
                rep.active.pop(rec.uid, None)
            if migrate:
                self._migq.append(rec.uid)
                self._cv.notify_all()
                return
            self._requests.pop(rec.uid, None)
            self._failed += 1
            self._cv.notify_all()
        rec.handle._fail(error)

    def _terminal(self, rec: _FleetRequest,
                  error: BaseException) -> None:
        with self._cv:
            self._requests.pop(rec.uid, None)
            self._failed += 1
            self._cv.notify_all()
        rec.handle._fail(error)

    def _pump_migrations(self) -> None:
        """Re-dispatch every queued migration (survivors continue each
        tenant from its streamed prefix).  Serialized; callable from
        the supervisor loop, :meth:`drain`'s wait loop, and
        :meth:`kill_replica` alike."""
        with self._pump_lock:
            while True:
                with self._lock:
                    if not self._migq:
                        return
                    uid = self._migq.popleft()
                    rec = self._requests.get(uid)
                if rec is None or rec.handle.done:
                    continue
                if self._stopping:
                    self._terminal(rec, ServerClosed(
                        "fleet shut down before the request finished"))
                    continue
                try:
                    self._dispatch(rec, migration=True)
                except RequestFailed as exc:
                    self._terminal(rec, exc)
                    continue
                rec.migrations += 1
                counters.inc("fleet.migrated")
                with self._cv:
                    self._migrated += 1
                    self._cv.notify_all()

    # ------------------------------------------------- drain / kill / scale
    def drain(self, index: int, *,
              timeout: Optional[float] = 120.0) -> Any:
        """Gracefully drain replica ``index`` and detach it.

        Stops admitting (router-side exclusion + the server's own
        ``begin_drain``), migrates every queued/in-flight tenant onto
        survivors via the streamed-prefix requeue, waits until the
        replica is empty, then shuts it down.  Loss-free: every active
        tenant finishes elsewhere or fails *explicitly*; the drained
        replica's paged pool is back to ``blocks_in_use == 0``.
        Returns the drained server (detached from the fleet).

        A ``TimeoutError`` leaves the replica draining but NOT wedged:
        ``drain(index)`` again resumes waiting on the same drain (it
        is idempotent up to the shutdown), or ``kill_replica(index)``
        abandons it.
        """
        with self._lock:
            rep = self._replicas[index]
            if rep is None or rep.dead:
                raise ValueError(f"replica {index} is not live")
            resuming = rep.draining
            rep.draining = True
        if not resuming:
            counters.inc("fleet.drain")
            rep.server.begin_drain()
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        while True:
            self._pump_migrations()
            with self._cv:
                pending = [uid for uid, rc in self._requests.items()
                           if rc.replica == index]
                if not rep.active and not pending:
                    break
                if deadline is not None \
                        and time.monotonic() > deadline:
                    raise TimeoutError(
                        f"drain of replica {index} did not complete "
                        f"within {timeout}s ({len(pending)} tenants "
                        f"pending); drain({index}) again to keep "
                        f"waiting, or kill_replica({index})")
                self._cv.wait(0.02)
        rep.server.shutdown(wait=True)
        with self._lock:
            rep.dead = True                  # detached from the fleet
        return rep.server

    def kill_replica(self, index: int) -> None:
        """SIGKILL-equivalent chaos drill on replica ``index``: the
        worker dies without draining or releasing engine state (see
        ``InferenceServer.kill``); every in-flight tenant migrates to
        survivors and resumes from its streamed prefix.  The
        ``replica.kill`` fault site routes here."""
        with self._lock:
            rep = self._replicas[index]
            if rep is None or rep.dead:
                return
            rep.dead = True
        counters.inc("fleet.replica_killed")
        rep.server.kill()
        # the dying worker's handle cancellations queued the
        # migrations — place them now rather than on the next tick
        self._pump_migrations()

    def scale_up(self, *, warmup: bool = True) -> Optional[int]:
        """Add one replica from the factory; returns its index (or
        ``None`` at the autoscale ``max_replicas`` ceiling)."""
        if self.factory is None:
            raise RuntimeError(
                "scale_up needs a replica factory (the router was "
                "built from a fixed server list)")
        if self.autoscale is not None \
                and self.num_replicas >= self.autoscale.max_replicas:
            return None
        server = self.factory()
        if self._running:
            # start (and warm) BEFORE joining the pool: the supervisor
            # probes every pooled replica, and a replica mid-warmup
            # would rack up "stopped" probe failures it never earned
            server.start(warmup=warmup)
        rep = self._add_replica(server)
        counters.inc("fleet.scale_up")
        return rep.index

    def scale_down(self, index: Optional[int] = None, *,
                   timeout: Optional[float] = 120.0) -> Optional[Any]:
        """Remove one replica through :meth:`drain` (loss-free).  With
        no ``index``, the replica with the fewest in-flight tenants
        goes (fewest migrations).  Returns the drained server, or
        ``None`` when the fleet is at its floor."""
        floor = (self.autoscale.min_replicas
                 if self.autoscale is not None else 1)
        with self._lock:
            live = self._live()
            if len(live) <= floor:
                return None
            if index is None:
                index = min(live,
                            key=lambda r: (len(r.active), r.index)
                            ).index
        counters.inc("fleet.scale_down")
        return self.drain(index, timeout=timeout)

    def maybe_scale(self, healths: Optional[
            Dict[int, Dict[str, Any]]] = None) -> Optional[str]:
        """One autoscale evaluation (the supervisor calls this every
        tick; tests may call it directly): aggregate queue depth +
        fleet TTFT p99 through :func:`scale_decision`, honoring the
        anti-flap cooldown.  ``healths`` reuses the tick's probe
        results (by replica index) instead of re-sweeping every
        server.  Returns the action taken."""
        cfg = self.autoscale
        if cfg is None:
            return None
        # finish an in-flight scale-down first: drain is resumable, so
        # the supervisor retries it in probe_interval-bounded slices
        # instead of blocking a whole tick or leaking a draining
        # zombie (draining replicas are invisible to _live(), so
        # nothing else would ever complete them)
        with self._lock:
            draining = [r for r in self._replicas
                        if r is not None and not r.dead and r.draining]
        if draining:
            try:
                self.drain(draining[0].index,
                           timeout=self.probe_interval)
            except TimeoutError:
                pass                       # resumed next tick
            return None
        with self._lock:
            if self._scale_cooldown > 0:
                self._scale_cooldown -= 1
                return None
        depth = sum(h.get("queue_depth", 0)
                    for h in self._healths(healths).values())
        ttft = self.latency_summary().get("ttft_p99_s")
        decision = scale_decision(depth, ttft, self.num_replicas, cfg)
        if decision == "up":
            if self.scale_up() is None:
                return None
        elif decision == "down":
            try:
                if self.scale_down(
                        timeout=self.probe_interval) is None:
                    return None
            except TimeoutError:
                pass       # the draining branch above finishes it
        if decision:
            with self._lock:
                self._scale_cooldown = cfg.cooldown_ticks
        return decision

    # --------------------------------------------------------- supervisor
    def _supervise(self) -> None:  # graftlint: thread-entry(fleet-supervisor)
        tick = 0
        next_tick = time.monotonic()
        while True:
            with self._cv:
                if self._stop_supervisor:
                    break
                wait = next_tick - time.monotonic()
                if wait > 0:
                    self._cv.wait(wait)
                if self._stop_supervisor:
                    break
            now = time.monotonic()
            run_tick = now >= next_tick
            try:
                # completions/errors notify _cv so migrations pump
                # promptly, but the probe/scale/metrics body keeps its
                # own cadence — tick-denominated knobs (breaker
                # streaks, autoscale cooldown, fault-site steps) must
                # count probe_interval beats, not request completions
                self._pump_migrations()
                if run_tick:
                    self._tick(now, tick)
            except Exception as exc:        # noqa: BLE001 — one bad
                # pass (a factory/warmup failure inside autoscale, a
                # drain timeout) must not kill the supervisor: probing
                # and migration pumping are what keep "never silently
                # lost, never hung" true for the whole fleet
                self.supervisor_error = exc
                counters.inc("fleet.supervisor_error")
            finally:
                # advance OUTSIDE the try: a persistently-raising tick
                # (factory that always OOMs, a broken metrics sink)
                # must still consume its beat, or the loop would spin
                # hot at wait<=0 re-firing fault sites at a frozen step
                if run_tick:
                    tick += 1
                    next_tick = now + self.probe_interval

    def _tick(self, now: float, tick: int) -> None:
        """One supervisor pass: ``replica.kill`` fault site, health
        probes through the breakers, dead-replica detection, pending
        migrations, autoscale, metrics.  ``tick`` is the fault-site
        step (shared by every replica probed this pass — pin specs
        with ``step``/``times``)."""
        with self._lock:
            replicas = [r for r in self._replicas
                        if r is not None and not r.dead]
        healths: Dict[int, Dict[str, Any]] = {}
        for rep in replicas:
            if rep.draining:
                continue
            try:
                # ANY raising kind at this site is a kill order
                faults.inject("replica.kill", step=tick)
            except _INJECTED:
                self.kill_replica(rep.index)
                continue
            ok, health = self._probe(rep, tick)
            if health is not None:
                healths[rep.index] = health
            if not ok:
                rep.breaker.on_failure(now)
            elif health is not None and health["status"] == "failed":
                # the worker died on its own — its cancel path already
                # queued the migrations; just mark the body
                with self._lock:
                    rep.dead = True
                counters.inc("fleet.replica_dead")
            else:
                breached = False
                # the latency breach is a HEALTHY→suspect signal only:
                # the p99 window is a trailing reservoir, and a
                # shed/probation replica serves no traffic to refresh
                # it — letting the stale percentile re-fire there
                # would eject a recovered replica forever on zero new
                # evidence (suspect→ejected stays probe-driven)
                if self.step_slo_ms is not None \
                        and rep.breaker.state == HEALTHY:
                    p99 = rep.server.latency_summary().get(
                        "step_ms_p99")
                    breached = p99 is not None and p99 > self.step_slo_ms
                if breached:
                    rep.breaker.on_latency_breach(now)
                else:
                    rep.breaker.on_success(now)
            rep.breaker.tick(now)
        self._pump_migrations()
        self.maybe_scale(healths)
        if self.metrics is not None \
                and tick % self.metrics_interval == 0:
            self._emit_metrics(healths)

    def _probe(self, rep: _Replica, tick: int):
        """One health probe: the ``fleet.probe`` fault site fires
        first (a raising kind counts as a failed probe — exactly how a
        flaky network or hung host looks to the breaker), then the
        replica's ``health()``."""
        try:
            faults.inject("fleet.probe", step=tick)
            health = rep.server.health()
        except _INJECTED:
            counters.inc("fleet.probe_fault")
            return False, None
        except Exception:                   # noqa: BLE001 — a probe
            return False, None              # must never kill the loop
        if health["status"] == "failed":
            return True, health             # dead, not unprobeable
        return bool(health.get("ready")), health

    # ---------------------------------------------------------- telemetry
    def _healths(self, cached: Optional[
            Dict[int, Dict[str, Any]]] = None
            ) -> Dict[int, Dict[str, Any]]:
        """``health()`` per live replica, preferring the tick's cached
        probe results so one supervisor pass sweeps each server once."""
        out: Dict[int, Dict[str, Any]] = {}
        for rep in self._live():
            health = None if cached is None else cached.get(rep.index)
            if health is None:
                try:
                    health = rep.server.health()
                except Exception:           # noqa: BLE001
                    continue
            out[rep.index] = health
        return out

    def _emit_metrics(self, healths: Optional[
            Dict[int, Dict[str, Any]]] = None) -> None:
        """Aggregate one fleet row (replica rows arrive continuously
        through their namespaced sinks) and drain the fleet writer."""
        writer = self.metrics
        if writer is None:
            return
        with self._lock:
            stats = {
                "replicas_live": len(self._live()),
                "in_flight": len(self._requests),
                "submitted": self._submitted,
                "completed": self._completed,
                "failed": self._failed,
                "migrated": self._migrated,
                "tokens_total": self._tokens_total,
            }
        sweep = self._healths(healths).values()
        stats["queue_depth"] = sum(
            int(h.get("queue_depth", 0)) for h in sweep)
        stats["replicas_ready"] = sum(
            bool(h.get("ready")) for h in sweep)
        # prefix-sharing / speculative-decoding merged view: summed
        # page gauges, fleet-mean accept rate (paged replicas only)
        stats["shared_blocks"] = sum(
            int(h.get("shared_blocks", 0)) for h in sweep)
        stats["cow_forks"] = sum(
            int(h.get("cow_forks", 0)) for h in sweep)
        rates = [float(h["spec_accept_rate"]) for h in sweep
                 if "spec_accept_rate" in h]
        if rates:
            stats["spec_accept_rate"] = sum(rates) / len(rates)
        # narrowest KV storage width in the fleet (8 = some replica
        # serves quantized pages); numeric for the metrics pipeline —
        # the dtype NAMES ride health()["kv_dtypes"]
        bits = [int(h["kv_bits"]) for h in sweep if "kv_bits" in h]
        if bits:
            stats["kv_bits_min"] = min(bits)
        # mesh view (ISSUE 13): a replica is no longer one chip — the
        # fleet's capacity is N replicas × M chips, and per-chip
        # throughput must divide by chips_total, not replicas_live
        chips = [int(h.get("chips_per_replica", 1)) for h in sweep]
        stats["chips_per_replica"] = max(chips, default=1)
        stats["chips_total"] = sum(chips)
        stats.update(self.latency_summary())
        writer(writer.advance_step(),
               {f"fleet/{k}": float(v) for k, v in stats.items()})
        writer.drain()

    def latency_summary(self) -> Dict[str, float]:
        """Fleet-level latency percentiles: TTFT over every request
        the router accepted (migration pauses included — the client's
        honest first-token wait), plus the worst per-replica decode
        step p99 (``step_ms_p99_max``)."""
        # snapshot under _lock: replica workers append concurrently,
        # and iterating a deque during an append raises RuntimeError
        with self._lock:
            ttft = list(self._ttft)
        out: Dict[str, float] = {}
        out.update(percentile_summary(
            ttft, "ttft_p50_s", "ttft_p99_s"))
        p99s = []
        for rep in self._live():
            try:
                p99 = rep.server.latency_summary().get("step_ms_p99")
            except Exception:               # noqa: BLE001
                continue
            if p99 is not None:
                p99s.append(p99)
        if p99s:
            out["step_ms_p99_max"] = float(max(p99s))
        return out

    def stats(self) -> Dict[str, int]:
        """Fleet scoreboard (the chaos-soak ledger): ``submitted ==
        completed + failed + in_flight`` at every instant — nothing is
        ever silently lost."""
        with self._lock:
            return {
                "submitted": self._submitted,
                "completed": self._completed,
                "failed": self._failed,
                "in_flight": len(self._requests),
                "migrated": self._migrated,
                "tokens_total": self._tokens_total,
                "replicas_live": len(self._live()),
            }

    def health(self) -> Dict[str, Any]:
        """Fleet readiness probe: ``ready`` when at least one replica
        is routable and ready; ``replicas`` carries each replica's
        breaker state, drain/dead flags, in-flight count, and its own
        ``health()`` dict (for live replicas)."""
        entries = []
        ready = 0
        with self._lock:
            replicas = [r for r in self._replicas if r is not None]
        for rep in replicas:
            entry: Dict[str, Any] = {
                "index": rep.index,
                "breaker": rep.breaker.state,
                "draining": rep.draining,
                "dead": rep.dead,
                "in_flight": len(rep.active),
            }
            if not rep.dead:
                try:
                    health = rep.server.health()
                except Exception:           # noqa: BLE001
                    health = None
                entry["health"] = health
                if health is not None and health.get("ready") \
                        and rep.breaker.routable and not rep.draining:
                    ready += 1
            entries.append(entry)
        sweep = [e.get("health") or {} for e in entries]
        rates = [float(h["spec_accept_rate"]) for h in sweep
                 if "spec_accept_rate" in h]
        out = {
            "status": "serving" if (self._running
                                    and not self._stopping)
            else "stopped",
            "ready": ready > 0 and self._running and not self._stopping,
            "replicas_ready": ready,
            "replicas": entries,
            # fleet-merged prefix-sharing / drafting gauges
            "shared_blocks": sum(
                int(h.get("shared_blocks", 0)) for h in sweep),
            "cow_forks": sum(
                int(h.get("cow_forks", 0)) for h in sweep),
            "spec_accept_rate": (sum(rates) / len(rates)
                                 if rates else 0.0),
            # distinct KV-pool storage dtypes across live replicas
            # (sorted; "none" = an unquantized paged pool) — a mixed
            # fleet mid-rollout legitimately reports several
            "kv_dtypes": sorted({
                str(h.get("kv_dtype") or "none") for h in sweep
                if "kv_bits" in h}),
            # mesh view (ISSUE 13): widest replica + total chips the
            # fleet spans (N replicas × M chips — health gauges stay
            # per-replica, so routing/breakers never changed), plus
            # the distinct per-replica mesh shapes (a mixed fleet
            # mid-resize legitimately reports several)
            "chips_per_replica": max(
                (int(h.get("chips_per_replica", 1)) for h in sweep),
                default=1),
            "chips_total": sum(
                int(h.get("chips_per_replica", 1)) for h in sweep),
            "mesh_shapes": sorted({
                str(h["mesh_shape"]) for h in sweep
                if h.get("mesh_shape")}),
            "supervisor_error": (None if self.supervisor_error is None
                                 else repr(self.supervisor_error)),
        }
        out.update(self.stats())
        return out
