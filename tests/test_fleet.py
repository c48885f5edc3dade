"""Fleet router unit tier (tier-1 — NO real servers).

Everything here runs against pure functions and duck-typed fake
replicas, so the whole file costs milliseconds:

- router selection math: least-loaded by the ``blocks_in_use /
  blocks_total`` gauge (slot ``occupancy`` where a health dict lacks
  it), queue-depth tie break, not-ready/ejected exclusion;
- circuit-breaker transitions: healthy → suspect (K failures or a
  latency-p99 breach) → ejected → probation (cooldown) → healthy, and
  probation's fail-fast re-ejection;
- drain ordering: stop admitting → migrate actives (prompt ++
  streamed tokens, remaining budget) → shutdown, in that order;
- routing backoff: capped, deterministically jittered, and the
  retry-then-``RequestFailed`` contract (incl. the ``fleet.route`` /
  ``fleet.probe`` / ``replica.kill`` fault sites);
- autoscale decisions from queue depth + TTFT p99.

The end-to-end replica-kill/drain soaks with real ``InferenceServer``
replicas live in ``tests/test_chaos.py`` (``-m chaos``).
"""

import itertools
import time

import numpy as np
import pytest

from apex_tpu.resilience import FaultPlan, FaultSpec, active
from apex_tpu.serving import (
    FleetRouter,
    QueueFull,
    ReplicaDraining,
    RequestFailed,
    ServerClosed,
)
from apex_tpu.serving.fleet import (
    EJECTED,
    HEALTHY,
    PROBATION,
    SUSPECT,
    AutoscaleConfig,
    CircuitBreaker,
    load_score,
    route_backoff,
    scale_decision,
    select_replica,
)


class FakeServer:
    """Duck-typed ``InferenceServer``: scripted health gauges,
    recorded lifecycle calls, manually-driven token emission through
    the real tap plumbing."""

    def __init__(self, *, blocks=(0, 16), queue_depth=0, occupancy=0.0,
                 reject=None, prefix_hit=0, kv_dtype=None, kv_bits=None,
                 chips=1, mesh_shape=None):
        self.calls = []
        self.live = {}                  # key -> (prompt, kwargs, tap)
        self._keys = itertools.count()
        self.blocks_in_use, self.blocks_total = blocks
        self.queue_depth = queue_depth
        self.occupancy = occupancy
        self.reject = reject            # exception class raised on submit
        self.prefix_hit = prefix_hit    # scripted trie hit (affinity)
        self.kv_dtype = kv_dtype        # scripted pool storage dtype
        self.kv_bits = kv_bits          # ... and width (None = unreported)
        self.chips = chips              # scripted chips_per_replica
        self.mesh_shape = mesh_shape    # scripted TP mesh shape
        self.running = False
        self.draining = False
        self.metrics = None
        self.metrics_interval = 32

    def prefix_hit_blocks(self, prompt):
        del prompt
        return self.prefix_hit

    # ------------------------------------------------ server surface
    def start(self, *, warmup=True):
        del warmup
        self.running = True
        self.calls.append("start")
        return self

    def health(self):
        out = {
            "status": "serving" if self.running else "stopped",
            "ready": self.running and not self.draining,
            "draining": self.draining,
            "uptime_s": 0.0,
            "queue_depth": self.queue_depth,
            "occupancy": self.occupancy,
        }
        if self.blocks_total:
            out["blocks_in_use"] = self.blocks_in_use
            out["blocks_total"] = self.blocks_total
        if self.kv_bits is not None:
            out["kv_dtype"] = self.kv_dtype
            out["kv_bits"] = self.kv_bits
        out["chips_per_replica"] = self.chips
        if self.mesh_shape:
            out["mesh_shape"] = self.mesh_shape
        return out

    def latency_summary(self):
        return {}

    def submit(self, prompt, *, max_new_tokens, tap=None, **kw):
        if self.reject is not None:
            self.calls.append("reject")
            raise self.reject("scripted rejection")
        key = next(self._keys)
        self.calls.append(("submit",
                           [int(t) for t in np.asarray(prompt).ravel()],
                           int(max_new_tokens)))
        self.live[key] = (np.asarray(prompt), kw, tap)
        return key

    def begin_drain(self):
        self.draining = True
        self.calls.append("begin_drain")
        for key in list(self.live):
            _p, _kw, tap = self.live.pop(key)
            tap(None, True, ReplicaDraining("drain eviction"))

    def kill(self, error=None):
        del error
        self.running = False
        self.calls.append("kill")
        for key in list(self.live):
            _p, _kw, tap = self.live.pop(key)
            tap(None, True, ServerClosed("killed"))

    def shutdown(self, *, wait=True, timeout=None):
        del timeout
        self.running = False
        self.calls.append(("shutdown", wait))

    # --------------------------------------------------- test driver
    def emit(self, key, token, finished=False):
        prompt, kw, tap = self.live[key]
        if finished:
            del self.live[key]
        tap(int(token), bool(finished), None)

    def submits(self):
        return [c for c in self.calls
                if isinstance(c, tuple) and c[0] == "submit"]


def _router(fakes, **kw):
    """A started router over pre-built fakes; the supervisor sleeps
    (long probe interval) so tests drive ticks deterministically."""
    kw.setdefault("probe_interval", 60.0)
    return FleetRouter(servers=fakes, **kw).start()


class TestSelectionMath:
    def test_load_score_prefers_blocks_gauge(self):
        paged = {"ready": True, "blocks_in_use": 4, "blocks_total": 16,
                 "occupancy": 1.0}
        assert load_score(paged) == 0.25     # gauge wins over occupancy
        bare = {"ready": True, "occupancy": 0.5}     # no pool gauge
        assert load_score(bare) == 0.5

    def test_least_loaded_wins(self):
        healths = [
            {"ready": True, "blocks_in_use": 8, "blocks_total": 16},
            {"ready": True, "blocks_in_use": 2, "blocks_total": 16},
            {"ready": True, "blocks_in_use": 12, "blocks_total": 16},
        ]
        assert select_replica(healths) == 1

    def test_queue_depth_breaks_ties_then_index(self):
        healths = [
            {"ready": True, "blocks_in_use": 4, "blocks_total": 16,
             "queue_depth": 3},
            {"ready": True, "blocks_in_use": 4, "blocks_total": 16,
             "queue_depth": 1},
        ]
        assert select_replica(healths) == 1
        healths[0]["queue_depth"] = 1
        assert select_replica(healths) == 0   # full tie -> stable index

    def test_not_ready_and_excluded_skipped(self):
        healths = [
            {"ready": False, "blocks_in_use": 0, "blocks_total": 16},
            None,                              # ejected/draining/dead
            {"ready": True, "blocks_in_use": 15, "blocks_total": 16},
        ]
        assert select_replica(healths) == 2
        assert select_replica([None, {"ready": False}]) == -1
        assert select_replica([]) == -1

    def test_prefix_affinity_breaks_load_ties(self):
        """ISSUE-7 satellite: equal load, the replica whose trie
        already holds the request's prefix wins — before queue depth,
        after load (affinity concentrates a hot prompt, never
        overrides least-loaded)."""
        healths = [
            {"ready": True, "blocks_in_use": 4, "blocks_total": 16,
             "queue_depth": 0},
            {"ready": True, "blocks_in_use": 4, "blocks_total": 16,
             "queue_depth": 2},
        ]
        # tie on load: affinity outranks the lower queue depth
        assert select_replica(healths, affinity=[0, 3]) == 1
        # affinity never overrides a load difference
        healths[1]["blocks_in_use"] = 8
        assert select_replica(healths, affinity=[0, 3]) == 0
        # no affinity info: pre-ISSUE-7 ordering unchanged
        healths[1]["blocks_in_use"] = 4
        assert select_replica(healths) == 0
        assert select_replica(healths, affinity=None) == 0


class TestRouteBackoff:
    def test_cap_holds_for_every_attempt(self):
        for attempt in range(1, 40):
            for uid in range(20):
                d = route_backoff(attempt, uid, base=0.01, cap=0.25)
                assert 0.0 < d <= 0.25

    def test_deterministic_and_jittered(self):
        a = route_backoff(3, uid=7)
        assert a == route_backoff(3, uid=7)      # replayable
        assert a != route_backoff(3, uid=8)      # jitter varies by uid
        assert a != route_backoff(4, uid=7)      # and by attempt
        # jitter stays within [raw/2, raw]
        raw = 0.01 * 2 ** 2
        assert raw / 2 <= a <= raw

    def test_grows_until_cap(self):
        # compare jitter-free upper envelopes
        raws = [min(0.25, 0.01 * 2 ** (a - 1)) for a in range(1, 10)]
        assert raws == sorted(raws)
        assert raws[-1] == 0.25


class TestCircuitBreaker:
    def test_k_failures_then_suspect_then_eject(self):
        br = CircuitBreaker(suspect_after=3, eject_after=2,
                            cooldown_s=1.0, probation_probes=2)
        assert br.state == HEALTHY and br.routable
        assert br.on_failure(0.0) == HEALTHY
        assert br.on_failure(0.0) == HEALTHY
        assert br.on_failure(0.0) == SUSPECT    # K = 3
        assert br.routable                      # suspect still routes
        assert br.on_failure(0.0) == SUSPECT
        assert br.on_failure(0.0) == EJECTED
        assert not br.routable

    def test_success_resets_consecutive_count(self):
        br = CircuitBreaker(suspect_after=3)
        br.on_failure(0.0)
        br.on_failure(0.0)
        br.on_success(0.0)                      # streak broken
        br.on_failure(0.0)
        br.on_failure(0.0)
        assert br.state == HEALTHY

    def test_latency_breach_suspects_immediately(self):
        br = CircuitBreaker(suspect_after=3, eject_after=2)
        assert br.on_latency_breach(0.0) == SUSPECT
        # in suspect, a breach counts like a probe failure
        assert br.on_latency_breach(0.0) == SUSPECT
        assert br.on_latency_breach(0.0) == EJECTED

    def test_cooldown_probation_readmit_and_refail(self):
        br = CircuitBreaker(suspect_after=1, eject_after=1,
                            cooldown_s=2.0, probation_probes=2)
        br.on_failure(10.0)                     # -> suspect
        br.on_failure(10.0)                     # -> ejected at t=10
        assert br.tick(11.0) == EJECTED         # cooldown not elapsed
        assert br.on_success(11.0) == EJECTED   # successes don't skip it
        assert br.tick(12.0) == PROBATION
        assert br.routable                      # on trial
        assert br.on_success(12.5) == PROBATION
        assert br.on_success(13.0) == HEALTHY   # 2 consecutive goods
        # and a probation failure re-ejects with a fresh cooldown
        br.on_failure(13.0)
        br.on_failure(13.0)
        assert br.tick(15.0) == PROBATION
        assert br.on_failure(15.5) == EJECTED
        assert br.tick(16.0) == EJECTED         # fresh cooldown from 15.5

    def test_suspect_heals_back_to_healthy(self):
        br = CircuitBreaker(suspect_after=1, probation_probes=2)
        br.on_failure(0.0)
        assert br.state == SUSPECT
        br.on_success(0.0)
        assert br.on_success(0.0) == HEALTHY

    def test_validation(self):
        with pytest.raises(ValueError):
            CircuitBreaker(suspect_after=0)
        with pytest.raises(ValueError):
            CircuitBreaker(cooldown_s=0.0)


class TestScaleDecision:
    CFG = AutoscaleConfig(scale_up_queue_depth=8,
                          scale_down_queue_depth=0,
                          ttft_slo_p99_s=1.0, min_replicas=1,
                          max_replicas=4)

    def test_queue_depth_triggers_up(self):
        assert scale_decision(9, None, 2, self.CFG) == "up"
        assert scale_decision(8, None, 2, self.CFG) is None

    def test_ttft_slo_breach_triggers_up(self):
        assert scale_decision(0, 2.0, 2, self.CFG) == "up"
        assert scale_decision(0, 0.5, 2, self.CFG) is None \
            or scale_decision(0, 0.5, 2, self.CFG) == "down"

    def test_bounds_respected(self):
        assert scale_decision(99, 9.9, 4, self.CFG) is None   # at max
        assert scale_decision(0, None, 1, self.CFG) is None   # at min
        assert scale_decision(0, None, 3, self.CFG) == "down"
        assert scale_decision(0, None, 0, self.CFG) == "up"   # below min

    def test_hysteresis_band_holds_steady(self):
        # between the down- and up-thresholds nothing changes (no flap)
        cfg = AutoscaleConfig(scale_up_queue_depth=8,
                              scale_down_queue_depth=2,
                              max_replicas=4)
        assert scale_decision(5, None, 2, cfg) is None


class TestRouting:
    def test_least_loaded_replica_gets_the_request(self):
        busy = FakeServer(blocks=(12, 16))
        idle = FakeServer(blocks=(2, 16))
        router = _router([busy, idle])
        h = router.submit([1, 2, 3], max_new_tokens=4)
        assert idle.submits() == [("submit", [1, 2, 3], 4)]
        assert busy.submits() == []
        idle.emit(0, 7)
        idle.emit(0, 9, finished=True)
        assert h.result(timeout=5) == [7, 9]
        assert router.stats()["completed"] == 1
        router.shutdown()

    def test_queue_full_fails_over_to_next_best(self):
        full = FakeServer(blocks=(0, 16), reject=QueueFull)
        backup = FakeServer(blocks=(8, 16))
        router = _router([full, backup])
        router.submit([5], max_new_tokens=2)
        assert full.calls.count("reject") >= 1
        assert backup.submits() == [("submit", [5], 2)]
        router.shutdown(wait=False)

    def test_ejected_replica_is_never_selected(self):
        a = FakeServer(blocks=(0, 16))       # least loaded...
        b = FakeServer(blocks=(9, 16))
        router = _router([a, b])
        router._replicas[0].breaker._eject(0.0)   # ...but tripped
        router.submit([4], max_new_tokens=1)
        assert a.submits() == []
        assert b.submits() == [("submit", [4], 1)]
        router.shutdown(wait=False)

    def test_prefix_affinity_routes_to_the_trie_holder(self):
        """Equal-load replicas: the one whose trie holds the request's
        prefix (``prefix_hit_blocks``) gets the request — and a loaded
        trie holder still loses to a less-loaded cold replica."""
        a = FakeServer(blocks=(4, 16))
        b = FakeServer(blocks=(4, 16), prefix_hit=2)
        router = _router([a, b])
        router.submit([7, 7, 7], max_new_tokens=1)
        assert a.submits() == []
        assert b.submits() == [("submit", [7, 7, 7], 1)]
        b.blocks_in_use = 12                 # now clearly hotter
        router.submit([7, 7, 7], max_new_tokens=1)
        assert a.submits() == [("submit", [7, 7, 7], 1)]
        router.shutdown(wait=False)

    def test_exhausted_retries_surface_request_failed(self):
        fakes = [FakeServer(reject=QueueFull) for _ in range(2)]
        router = _router(fakes, route_retries=2, backoff_base=0.001,
                         backoff_cap=0.004)
        t0 = time.monotonic()
        with pytest.raises(RequestFailed, match="routing attempts"):
            router.submit([1], max_new_tokens=1)
        # capped backoff: 3 attempts never cost more than ~3 caps
        assert time.monotonic() - t0 < 1.0
        assert router.stats()["in_flight"] == 0    # not leaked
        router.shutdown(wait=False)

    def test_route_fault_site_retries_then_succeeds(self):
        fake = FakeServer()
        router = _router([fake], backoff_base=0.001, backoff_cap=0.004)
        plan = FaultPlan([FaultSpec(site="fleet.route",
                                    kind="transient", times=1)])
        with active(plan):
            router.submit([2, 3], max_new_tokens=2)
        assert plan.fire_count(0) == 1
        assert fake.submits() == [("submit", [2, 3], 2)]
        router.shutdown(wait=False)

    def test_submit_on_stopped_fleet_raises(self):
        router = FleetRouter(servers=[FakeServer()])
        with pytest.raises(ServerClosed):
            router.submit([1], max_new_tokens=1)


class TestMigration:
    def test_kill_migrates_with_streamed_prefix(self):
        primary = FakeServer(blocks=(0, 16))
        backup = FakeServer(blocks=(8, 16))
        router = _router([primary, backup])
        h = router.submit([1, 2, 3], max_new_tokens=5)
        primary.emit(0, 11)
        primary.emit(0, 13)
        router.kill_replica(0)
        # the survivor continues from prompt ++ streamed tokens with
        # the REMAINING budget
        assert backup.submits() == [("submit", [1, 2, 3, 11, 13], 3)]
        backup.emit(0, 17)
        backup.emit(0, 19)
        backup.emit(0, 23, finished=True)
        assert h.result(timeout=5) == [11, 13, 17, 19, 23]
        stats = router.stats()
        assert stats["migrated"] == 1
        assert stats["completed"] == 1 and stats["failed"] == 0
        router.shutdown(wait=False)

    def test_migration_without_survivor_fails_explicitly(self):
        only = FakeServer()
        router = _router([only], route_retries=1, backoff_base=0.001,
                         backoff_cap=0.002)
        h = router.submit([9], max_new_tokens=3)
        only.emit(0, 5)
        router.kill_replica(0)
        with pytest.raises(RequestFailed):
            h.result(timeout=5)
        assert router.stats()["failed"] == 1
        assert router.stats()["in_flight"] == 0
        router.shutdown(wait=False)

    def test_replica_request_failed_is_terminal_not_migrated(self):
        a, b = FakeServer(blocks=(0, 16)), FakeServer(blocks=(9, 16))
        router = _router([a, b])
        h = router.submit([1], max_new_tokens=2)
        _p, _kw, tap = a.live.pop(0)
        tap(None, True, RequestFailed("deadline expired"))
        with pytest.raises(RequestFailed, match="deadline"):
            h.result(timeout=5)
        assert b.submits() == []            # no migration for failures
        assert router.stats()["migrated"] == 0
        router.shutdown(wait=False)


class TestDrainOrdering:
    def test_stop_admitting_then_migrate_then_shutdown(self):
        primary = FakeServer(blocks=(0, 16))
        backup = FakeServer(blocks=(8, 16))
        router = _router([primary, backup])
        h1 = router.submit([1, 2], max_new_tokens=4)
        h2 = router.submit([3], max_new_tokens=3)
        primary.emit(0, 7)
        assert len(primary.live) == 2 and backup.submits() == []
        drained = router.drain(0)
        assert drained is primary
        # ordering: admissions happened strictly before begin_drain,
        # and shutdown came after the drain completed
        names = [c if isinstance(c, str) else c[0]
                 for c in primary.calls]
        assert names == ["start", "submit", "submit", "begin_drain",
                         "shutdown"]
        assert primary.calls[-1] == ("shutdown", True)
        # both tenants migrated with their streamed prefixes
        assert backup.submits() == [("submit", [1, 2, 7], 3),
                                    ("submit", [3], 3)]
        # new traffic routes around the drained replica
        router.submit([8], max_new_tokens=1)
        assert backup.submits()[-1] == ("submit", [8], 1)
        backup.emit(0, 1, finished=True)
        backup.emit(1, 2, finished=True)
        backup.emit(2, 3, finished=True)
        assert h1.result(timeout=5) == [7, 1]
        assert h2.result(timeout=5) == [2]
        assert router.stats()["migrated"] == 2
        router.shutdown(wait=False)

    def test_drain_rejects_dead_or_draining_replica(self):
        fake = FakeServer()
        router = _router([fake, FakeServer()])
        router.kill_replica(0)
        with pytest.raises(ValueError, match="not live"):
            router.drain(0)
        router.shutdown(wait=False)

    def test_drain_timeout_is_retryable_not_wedging(self):
        """A drain that times out leaves the replica draining but
        recoverable: drain(index) again resumes the SAME drain (no
        second begin_drain) and completes once the tenants migrate."""
        slowpoke = FakeServer(blocks=(0, 16))
        backup = FakeServer(blocks=(8, 16))
        # begin_drain that does NOT evict yet (a replica mid-step)
        slowpoke.begin_drain = lambda: (
            setattr(slowpoke, "draining", True),
            slowpoke.calls.append("begin_drain"))
        router = _router([slowpoke, backup])
        router.submit([1, 2], max_new_tokens=3)
        with pytest.raises(TimeoutError, match="drain\\(0\\) again"):
            router.drain(0, timeout=0.05)
        # now the worker "catches up" and evicts; the retry resumes
        for key in list(slowpoke.live):
            _p, _kw, tap = slowpoke.live.pop(key)
            tap(None, True, ReplicaDraining("late eviction"))
        drained = router.drain(0)
        assert drained is slowpoke
        assert slowpoke.calls.count("begin_drain") == 1   # resumed
        assert backup.submits() == [("submit", [1, 2], 3)]
        router.shutdown(wait=False)


class TestFaultSites:
    def test_replica_kill_site_kills_one_replica(self):
        a, b = FakeServer(), FakeServer()
        router = _router([a, b])
        plan = FaultPlan([FaultSpec(site="replica.kill",
                                    kind="transient", step=0, times=1)])
        with active(plan):
            router._tick(0.0, 0)
        assert a.calls.count("kill") == 1      # first live replica
        assert b.calls.count("kill") == 0
        assert router._replicas[0].dead
        assert router.num_replicas == 1
        router.shutdown(wait=False)

    def test_probe_faults_drive_breaker_to_ejection_and_back(self):
        fake = FakeServer().start()
        router = FleetRouter(
            servers=[fake],
            breaker_factory=lambda: CircuitBreaker(
                suspect_after=2, eject_after=1, cooldown_s=1.0,
                probation_probes=1))
        breaker = router._replicas[0].breaker
        plan = FaultPlan([FaultSpec(site="fleet.probe",
                                    kind="transient", steps=(0, 1, 2))])
        with active(plan):
            router._tick(0.0, 0)
            router._tick(0.0, 1)
            assert breaker.state == SUSPECT
            router._tick(0.0, 2)
        assert breaker.state == EJECTED and not breaker.routable
        # cooldown elapses -> probation -> healthy on a clean probe
        router._tick(1.5, 3)
        assert breaker.state in (PROBATION, HEALTHY)
        router._tick(1.6, 4)
        assert breaker.state == HEALTHY

    def test_dead_worker_detected_by_probe(self):
        fake = FakeServer().start()
        router = FleetRouter(servers=[fake])

        def failed_health():
            return {"status": "failed", "ready": False,
                    "queue_depth": 0, "occupancy": 0.0}
        fake.health = failed_health
        router._tick(0.0, 0)
        assert router._replicas[0].dead


class TestAutoscale:
    def _fleet(self, cfg, n=1):
        built = []

        def factory():
            fake = FakeServer()
            fake.start()           # factory replicas join mid-flight
            built.append(fake)
            return fake
        router = FleetRouter(
            factory, replicas=n, probe_interval=60.0, autoscale=cfg)
        for rep in router._replicas:    # pre-built fakes: mark running
            rep.server.running = True
        return router, built

    def test_queue_pressure_scales_up_with_cooldown(self):
        cfg = AutoscaleConfig(scale_up_queue_depth=4,
                              scale_down_queue_depth=0,
                              max_replicas=3, cooldown_ticks=2)
        router, built = self._fleet(cfg)
        router._replicas[0].server.queue_depth = 10
        assert router.maybe_scale() == "up"
        assert router.num_replicas == 2
        # anti-flap: the next cooldown_ticks evaluations are no-ops
        assert router.maybe_scale() is None
        assert router.maybe_scale() is None
        assert router.maybe_scale() == "up"
        assert router.num_replicas == 3
        # at max_replicas the decision is suppressed entirely
        assert router.maybe_scale() is None

    def test_idle_fleet_scales_down_through_drain(self):
        cfg = AutoscaleConfig(scale_up_queue_depth=4,
                              scale_down_queue_depth=0,
                              min_replicas=1, cooldown_ticks=0)
        router, built = self._fleet(cfg, n=2)
        assert router.maybe_scale() == "down"
        assert router.num_replicas == 1
        drained = [r for r in router._replicas if r.dead]
        assert len(drained) == 1
        calls = drained[0].server.calls
        assert "begin_drain" in calls
        assert ("shutdown", True) in calls
        # floor respected
        assert router.maybe_scale() is None

    def test_scale_up_without_factory_raises(self):
        router = FleetRouter(servers=[FakeServer()])
        with pytest.raises(RuntimeError, match="factory"):
            router.scale_up()


class TestFleetHealth:
    def test_scoreboard_shape_and_ledger(self):
        a, b = FakeServer(blocks=(0, 16)), FakeServer(blocks=(4, 16))
        router = _router([a, b])
        h1 = router.submit([1], max_new_tokens=2)
        health = router.health()
        assert health["status"] == "serving" and health["ready"]
        assert health["replicas_ready"] == 2
        assert [e["breaker"] for e in health["replicas"]] \
            == [HEALTHY, HEALTHY]
        assert health["submitted"] == 1
        assert health["in_flight"] == 1
        # the ledger balances at every instant
        assert health["submitted"] == health["completed"] \
            + health["failed"] + health["in_flight"]
        a.emit(0, 3)
        a.emit(0, 4, finished=True)
        assert h1.result(timeout=5) == [3, 4]
        health = router.health()
        assert health["completed"] == 1 and health["in_flight"] == 0
        router.shutdown()
        assert not router.health()["ready"]

    def test_kv_dtype_merged_view(self):
        """ISSUE-8 fleet view: health() lists the DISTINCT pool
        storage dtypes across live replicas (a mixed fleet mid-rollout
        legitimately reports several; 'none' = unquantized paged), and
        the metrics row carries the narrowest width as
        fleet/kv_bits_min."""
        from apex_tpu.utils import MetricsWriter

        a = FakeServer(blocks=(0, 16), kv_dtype="int8", kv_bits=8)
        b = FakeServer(blocks=(0, 16), kv_dtype=None, kv_bits=32)
        c = FakeServer()                     # reports no kv fields
        rows = []
        writer = MetricsWriter(sink=lambda s, m: rows.append((s, m)))
        router = _router([a, b, c], metrics=writer)
        health = router.health()
        assert health["kv_dtypes"] == ["int8", "none"]
        router._emit_metrics()
        merged = {}
        for _, m in rows:
            merged.update(m)
        assert merged.get("fleet/kv_bits_min") == 8.0
        router.shutdown(wait=False)

    def test_chips_merged_view(self):
        """ISSUE-13 fleet view: a replica is no longer one chip — the
        merged health() carries the widest replica
        (``chips_per_replica``), the fleet's total chip count
        (``chips_total`` = N×M capacity math), and the distinct
        per-replica mesh shapes; the metrics row mirrors the numeric
        two.  Health gauges stay per-replica, so routing and breakers
        never changed."""
        from apex_tpu.utils import MetricsWriter

        a = FakeServer(blocks=(0, 16), chips=2,
                       mesh_shape={"tensor": 2})
        b = FakeServer(blocks=(0, 16), chips=2,
                       mesh_shape={"tensor": 2})
        c = FakeServer(blocks=(0, 16))       # single-chip replica
        rows = []
        writer = MetricsWriter(sink=lambda s, m: rows.append((s, m)))
        router = _router([a, b, c], metrics=writer)
        health = router.health()
        assert health["chips_per_replica"] == 2
        assert health["chips_total"] == 5
        assert health["mesh_shapes"] == ["{'tensor': 2}"]
        router._emit_metrics()
        merged = {}
        for _, m in rows:
            merged.update(m)
        assert merged.get("fleet/chips_per_replica") == 2.0
        assert merged.get("fleet/chips_total") == 5.0
        router.shutdown(wait=False)


class TestFleetLatencySummarySnapshotRace:
    """Regression twin of the server-side fix (ISSUE 9, flagged by the
    graftlint concurrency pass): replica worker taps append to the
    router's ``_ttft`` reservoir (via ``_on_inner_token``, under
    ``_cv`` — which wraps ``_lock``) while the supervisor and clients
    snapshot it in ``latency_summary()``.  Iterating a deque during an
    append raises ``RuntimeError``; the snapshot now happens under
    ``_lock``.  The hammer fails within milliseconds unlocked."""

    def test_snapshot_survives_concurrent_tap_appends(self):
        import threading
        from collections import deque

        router = FleetRouter.__new__(FleetRouter)
        router._lock = threading.Lock()
        router._cv = threading.Condition(router._lock)
        router._ttft = deque(maxlen=4096)
        router._replicas = []               # no live replicas: p99s skip
        for i in range(512):
            with router._cv:
                router._ttft.append(0.01 * i)
        stop = threading.Event()
        errors = []

        def tap_thread():                   # _on_inner_token's append path
            i = 0
            try:
                while not stop.is_set():
                    with router._cv:
                        router._ttft.append(0.01 * (i % 11))
                    i += 1
            except BaseException as exc:    # pragma: no cover
                errors.append(exc)

        t = threading.Thread(target=tap_thread)
        t.start()
        try:
            deadline = time.monotonic() + 0.8
            while time.monotonic() < deadline:
                out = router.latency_summary()
                assert set(out) == {"ttft_p50_s", "ttft_p99_s"}
        finally:
            stop.set()
            t.join()
        assert errors == []
