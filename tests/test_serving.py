"""The serving stack above the engine (apex_tpu.serving).

``tests/test_paged_serving.py`` holds the engine's own contracts
(parity with ``generate()`` across page and chunk boundaries, the
zero-retrace soak, the allocator, preemption, sharing, drafting,
quantized pages).  Here:

- what the engine and the scheduler refuse (a sliding-window model no
  longer), a
  request that can never fit, a full queue, bad sampling parameters, a
  second shape through a guarded executable;
- the threaded ``InferenceServer``: the default construction builds
  the paged engine and serves ``generate()``'s tokens, streams, emits
  metrics, shuts down, survives (or reports) a worker crash, drains
  and dies as the fleet router expects;
- the handle's error contract, and the key sets of ``health()`` and of
  the metrics payload.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.models import GPTConfig, GPTModel, LlamaConfig, LlamaModel, generate
from apex_tpu.serving import (
    InferenceServer,
    PagedEngine,
    QueueFull,
    Request,
    Scheduler,
)
from apex_tpu.utils import MetricsWriter
from apex_tpu.utils.tracecheck import RetraceError


def _tiny_gpt():
    cfg = GPTConfig.tiny(position_embedding="learned",
                         scan_layers=True)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return model, {"params": params["params"]}


@pytest.fixture(scope="module")
def gpt():
    return _tiny_gpt()


def _engine(gpt, **kw):
    model, params = gpt
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_chunk", 4)
    return PagedEngine(model, params, **kw)


def _server(gpt, **kw):
    """A server over the tiny model: pages of 8 and chunks of 4, so a
    short prompt still crosses a chunk boundary."""
    model, params = gpt
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_chunk", 4)
    return InferenceServer(model, params, **kw)


class TestEngineValidation:
    def test_sliding_window_model_is_admitted(self):
        """The engine refused windowed models until the paged reads
        took a window (ISSUE 37); tests/test_paged_serving.py serves
        one against generate()."""
        cfg = LlamaConfig.tiny(sliding_window=5, scan_layers=False)
        model = LlamaModel(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
        engine = PagedEngine(model, {"params": params["params"]},
                             max_slots=2, block_size=8)
        assert engine.window == 5
        assert engine._paged_model.cfg.sliding_window == 5

    def test_oversized_request_rejected_at_submit(self, gpt):
        model, _ = gpt
        S = model.cfg.max_seq_len
        sched = Scheduler(_engine(gpt, max_slots=1, pool_tokens=32))
        # fits the context, but the whole pool could never hold it
        with pytest.raises(ValueError, match="pool"):
            sched.submit(Request(prompt=np.zeros(30, np.int32),
                                 max_new_tokens=10))
        with pytest.raises(ValueError, match="max_seq_len"):
            sched.submit(Request(prompt=np.zeros(8, np.int32),
                                 max_new_tokens=S))
        with pytest.raises(ValueError, match="empty prompt"):
            sched.submit(Request(prompt=np.zeros(0, np.int32),
                                 max_new_tokens=1))
        with pytest.raises(ValueError, match="top_k"):
            sched.submit(Request(prompt=np.zeros(4, np.int32),
                                 max_new_tokens=2,
                                 temperature=1.0,
                                 top_k=model.cfg.vocab_size + 1))
        assert sched.queue_depth == 0

    def test_queue_capacity_bounded(self, gpt):
        sched = Scheduler(_engine(gpt, max_slots=1), queue_capacity=2)
        for _ in range(2):
            sched.submit(Request(prompt=np.zeros(4, np.int32),
                                 max_new_tokens=1))
        with pytest.raises(QueueFull):
            sched.submit(Request(prompt=np.zeros(4, np.int32),
                                 max_new_tokens=1))

    def test_guard_raises_on_forced_retrace(self, gpt):
        """The guard is live, not decorative: a second feed width
        through the decode executable, beyond its budget of one trace,
        must raise RetraceError (this is what a shape leak in
        production would look like)."""
        engine = _engine(gpt, max_slots=1)
        engine.warmup()
        ones = np.ones((1,), np.int32)
        off = np.zeros((1,), bool)
        with pytest.raises(RetraceError):
            engine._decode(engine._variables, engine.cache,
                           engine.state, engine._packed(
                               np.zeros((1, 2), np.int32), ones, off, off))
        assert engine.trace_counts["decode_step"] == 1


class TestTopPSampling:
    @pytest.mark.slow
    def test_top_p_one_matches_disabled(self, gpt):
        # [slow: two engine builds ≈ 8 s; the fast tier covers the
        # exact-no-op contract at the sample_dynamic level below and
        # mixes top_p=1.0 traffic through the zero-retrace soak]
        """top_p=1.0 and top_p=None are the same program AND the same
        tokens (the disabled nucleus filter is an exact no-op in
        sample_dynamic, not an epsilon approximation)."""
        model, _ = gpt
        rng = np.random.default_rng(23)
        prompt = rng.integers(0, model.cfg.vocab_size,
                              size=(6,)).astype(np.int32)

        def run(top_p):
            sched = Scheduler(_engine(gpt, max_slots=1))
            req = sched.submit(Request(
                prompt=prompt, max_new_tokens=6, temperature=0.9,
                top_p=top_p, seed=5))
            sched.drain()
            return list(req.tokens)

        assert run(None) == run(1.0)

    def test_dynamic_nucleus_restricts_tokens(self):
        """sample_dynamic with a per-slot top_p must only emit tokens
        from each row's nucleus; disabled rows are exact no-ops."""
        from apex_tpu.serving.engine import sample_dynamic

        rng = np.random.default_rng(3)
        V = 32
        logits = jnp.asarray(rng.normal(size=(2, V)) * 3.0,
                             jnp.float32)
        temp = jnp.asarray([0.8, 0.8], jnp.float32)
        top_k = jnp.zeros((2,), jnp.int32)
        top_p = jnp.asarray([0.6, 0.0], jnp.float32)
        probs = np.asarray(jax.nn.softmax(logits / 0.8, axis=-1))[0]
        order = np.argsort(-probs)
        cum = np.cumsum(probs[order])
        nucleus = set(order[:int(np.searchsorted(cum, 0.6)) + 1]
                      .tolist())
        seen0, seen1 = set(), set()
        for i in range(200):
            keys = np.stack([np.asarray([i, 1], np.uint32),
                             np.asarray([i, 2], np.uint32)])
            out = sample_dynamic(logits, jnp.asarray(keys), temp,
                                 top_k, top_p, V)
            seen0.add(int(out[0]))
            seen1.add(int(out[1]))
        assert seen0 <= nucleus, (seen0, nucleus)
        # the disabled row samples from the full distribution — it
        # must escape the nucleus at least once across 200 draws
        assert any(t not in nucleus for t in seen1)

    def test_top_p_validation_at_submit(self, gpt):
        sched = Scheduler(_engine(gpt, max_slots=1))
        with pytest.raises(ValueError, match="top_p"):
            sched.submit(Request(prompt=np.zeros(4, np.int32),
                                 max_new_tokens=2, temperature=1.0,
                                 top_p=1.5))


class TestInferenceServer:
    def test_default_server_is_paged_and_matches_generate(self, gpt):
        """``InferenceServer(model, params)``, no keyword: the engine
        the benchmark measures, serving generate()'s tokens."""
        model, params = gpt
        rng = np.random.default_rng(29)
        # longer than the default chunk and the default page
        prompt = rng.integers(0, model.cfg.vocab_size,
                              size=(37,)).astype(np.int32)
        ref = np.asarray(generate(
            model, params, jnp.asarray(prompt[None]),
            max_new_tokens=5))[0, 37:]
        with InferenceServer(model, params) as server:
            assert type(server.engine) is PagedEngine
            got = server.submit(
                prompt, max_new_tokens=5).result(timeout=300)
            assert server.engine.trace_counts == {
                "decode_step": 1, "prefill_step": 1, "admit": 1,
                "release": 1}
        np.testing.assert_array_equal(np.asarray(got), ref)
        assert server.health()["blocks_in_use"] == 0

    def test_prefill_chunk_left_unset_is_the_engines_default(self, gpt):
        model, params = gpt
        engine = InferenceServer(model, params).engine
        assert engine._chunk == PagedEngine(model, params)._chunk == 32
        assert engine.pool_tokens == 4 * model.cfg.max_seq_len
        with pytest.raises(ValueError, match="prefill_chunk"):
            InferenceServer(model, params, prefill_chunk=0)

    def test_streaming_and_metrics(self, gpt):
        model, _ = gpt
        rows = []
        writer = MetricsWriter(sink=lambda s, m: rows.append((s, m)))
        server = _server(gpt, max_slots=2, metrics=writer,
                         metrics_interval=2)
        rng = np.random.default_rng(13)
        with server:
            h1 = server.submit(
                rng.integers(0, model.cfg.vocab_size, size=(3,)),
                max_new_tokens=4)
            h2 = server.submit(
                rng.integers(0, model.cfg.vocab_size, size=(6,)),
                max_new_tokens=3, temperature=0.8, seed=4)
            streamed = list(h1.stream(timeout=300))
            assert streamed == h1.result(timeout=300)
            assert len(streamed) == 4
            assert len(h2.result(timeout=300)) == 3
        assert rows, "metrics never emitted"
        steps = [s for s, _ in rows]
        assert steps == sorted(steps)
        for _, m in rows:
            assert {"tokens_per_sec", "occupancy",
                    "queue_depth"} <= set(m)
            assert 0.0 <= m["occupancy"] <= 1.0

    def test_health_and_metrics_key_sets(self, gpt):
        """What a dashboard, the fleet router and the benchmark's
        per-layer metrics read by name — as literals, so a rename or a
        key that goes missing fails here.  ``mesh_shape`` (tensor-
        parallel replicas) and the three ``ssm_*`` (recurrent state)
        are pinned in test_tp_serving.py / test_falcon_h1.py."""
        rows = []
        writer = MetricsWriter(sink=lambda s, m: rows.append(m))
        server = _server(gpt, max_slots=2, spec_tokens=2,
                         metrics=writer, metrics_interval=1)
        with server:
            server.submit(np.arange(1, 8, dtype=np.int32),
                          max_new_tokens=3).result(timeout=300)
            health = server.health()
        assert set(health) == {
            "status", "ready", "draining", "uptime_s", "steps",
            "queue_depth", "occupancy", "tokens_emitted", "requeues",
            "failed_requests", "deadline_expired", "drain_evicted",
            "preempts", "spans", "admitted", "queue_wait_s",
            "first_tokens", "prefill_s", "compiles", "error",
            "chips_per_replica", "blocks_in_use", "blocks_total",
            "live_tokens", "shared_blocks", "cow_forks",
            "kv_pages_live", "kv_write_pages", "steps_ahead", "kv_dtype",
            "kv_bits",
            "spec_accept_rate"}         # the last: spec_tokens > 0 only
        assert set(health["spans"]) == {
            "apex/serve/step", "apex/serve/deliver",
            "apex/sched/admit", "apex/sched/route",
            "apex/engine/step_prefill", "apex/engine/step_decode",
            "apex/engine/step_spec", "apex/engine/plan",
            "apex/engine/dispatch", "apex/engine/fetch",
            "apex/engine/commit"}
        merged = {}
        for row in rows:
            merged.update(row)
        assert set(merged) == {
            "tokens_per_sec", "tokens_per_sec_per_chip",
            "chips_per_replica", "occupancy", "queue_depth",
            "tokens_total", "requeues", "failed_requests",
            "deadline_expired", "preempts", "ttft_p50_s", "ttft_p99_s",
            "queue_wait_p50_s", "queue_wait_p99_s", "step_ms_p50",
            "step_ms_p99", "blocks_in_use", "blocks_total",
            "live_tokens", "shared_blocks", "cow_forks", "kv_bits",
            "spec_accept_rate"}
        assert server.engine.trace_counts == {
            "decode_step": 1, "prefill_step": 1, "spec_step": 1,
            "admit": 1, "release": 1}
        # without drafting the accept rate is absent, not 0.0
        plain = _server(gpt, max_slots=1)
        assert "spec_accept_rate" not in plain.health()
        assert "spec_step" not in plain.engine.trace_counts

    def test_greedy_parity_through_server(self, gpt):
        model, params = gpt
        rng = np.random.default_rng(17)
        prompt = rng.integers(0, model.cfg.vocab_size,
                              size=(5,)).astype(np.int32)
        ref = np.asarray(generate(
            model, params, jnp.asarray(prompt[None]),
            max_new_tokens=5))[0, 5:]
        with _server(gpt, max_slots=2) as server:
            got = server.submit(
                prompt, max_new_tokens=5).result(timeout=300)
        np.testing.assert_array_equal(np.asarray(got), ref)

    def test_preempted_request_keeps_its_stream_and_its_chain(self, gpt):
        """Two tenants overcommit a pool that cannot hold both: the
        engine preempts the younger, the server requeues it, and both
        clients still read generate()'s chain off their handles —
        ``preempts`` in health() is what the benchmark's
        ``preempts.serve`` reads."""
        model, params = gpt
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, model.cfg.vocab_size,
                                size=(n,)).astype(np.int32)
                   for n in (20, 22)]
        budgets = (30, 28)
        server = _server(gpt, max_slots=2, pool_tokens=64,
                         admit_headroom=0)
        with server:
            handles = [server.submit(p, max_new_tokens=n)
                       for p, n in zip(prompts, budgets)]
            got = [h.result(timeout=300) for h in handles]
            health = server.health()
        assert health["preempts"] >= 1
        assert health["failed_requests"] == 0 == health["requeues"]
        assert health["blocks_in_use"] == 0
        for p, n, toks in zip(prompts, budgets, got):
            ref = np.asarray(generate(
                model, params, jnp.asarray(p[None]),
                max_new_tokens=n))[0, len(p):]
            np.testing.assert_array_equal(np.asarray(toks), ref)

    def test_shutdown_without_drain_cancels(self, gpt):
        from apex_tpu.serving import ServerClosed

        server = _server(gpt, max_slots=1)
        server.start(warmup=False)
        h = server.submit(np.zeros(3, np.int32), max_new_tokens=200)
        server.shutdown(wait=False, timeout=60)
        with pytest.raises((ServerClosed, TimeoutError)):
            h.result(timeout=60)

    def test_worker_crash_cancels_clients(self, gpt):
        """An engine failure inside the serving loop must not strand
        clients: handles raise ServerClosed, submit refuses, and the
        root cause is preserved on server.error."""
        from apex_tpu.serving import ServerClosed

        server = _server(gpt, max_slots=1)
        boom = RuntimeError("engine exploded")

        def exploding_step():
            raise boom

        server.scheduler.run_step = exploding_step
        server.start(warmup=False)
        h = server.submit(np.zeros(3, np.int32), max_new_tokens=4)
        with pytest.raises(ServerClosed):
            h.result(timeout=60)
        with pytest.raises(ServerClosed):
            server.submit(np.zeros(2, np.int32), max_new_tokens=1)
        assert server.error is boom
        server.shutdown(timeout=60)

    def test_submit_after_shutdown_raises(self, gpt):
        from apex_tpu.serving import ServerClosed

        server = _server(gpt, max_slots=1)
        server.start(warmup=False)
        server.shutdown()
        with pytest.raises(ServerClosed):
            server.submit(np.zeros(2, np.int32), max_new_tokens=1)


class TestHandleErrorContract:
    """RequestHandle.stream/result error classes (docs/resilience.md):
    TimeoutError = retryable "no token yet"; ServerClosed /
    RequestFailed = terminal.  A shutdown race must never surface as a
    bare timeout."""

    def test_timeout_is_retryable_not_terminal(self, gpt):
        server = _server(gpt, max_slots=1)
        server.start(warmup=False)      # first token needs a compile
        try:
            h = server.submit(np.zeros(3, np.int32), max_new_tokens=3)
            with pytest.raises(TimeoutError, match="retryable"):
                h.result(timeout=1e-4)
            # the request was NOT terminated by that timeout: the same
            # handle still completes
            assert len(h.result(timeout=300)) == 3
            assert h.error is None
        finally:
            server.shutdown(timeout=60)

    def test_shutdown_surfaces_terminal_not_timeout(self, gpt):
        from apex_tpu.serving import ServerClosed

        server = _server(gpt, max_slots=1)
        server.start(warmup=False)
        h = server.submit(np.zeros(3, np.int32), max_new_tokens=200)
        # wait=False cancels in-flight requests; after the worker has
        # joined, the handle MUST report the terminal ServerClosed even
        # with a tiny timeout — the old shutdown race surfaced here as
        # a bare TimeoutError
        server.shutdown(wait=False, timeout=120)
        with pytest.raises(ServerClosed):
            h.result(timeout=0.001)
        with pytest.raises(ServerClosed):
            list(h.stream(timeout=0.001))
        assert isinstance(h.error, ServerClosed)

    def test_deadline_failure_is_request_failed(self, gpt):
        from apex_tpu.serving import RequestFailed

        server = _server(gpt, max_slots=1)
        with server:
            h = server.submit(np.zeros(3, np.int32),
                              max_new_tokens=100, deadline=1e-4)
            with pytest.raises(RequestFailed, match="deadline"):
                h.result(timeout=300)
            # the failure is per-request: the server keeps serving
            h2 = server.submit(np.zeros(2, np.int32), max_new_tokens=2)
            assert len(h2.result(timeout=300)) == 2
            assert server.health()["ready"]


class TestDrainKillAndHealthFields:
    """Replica-lifecycle plumbing for the fleet router
    (docs/serving.md health table, docs/fleet.md): graceful drain
    evicts with ReplicaDraining and releases the engine; kill abandons
    the engine and cancels with ServerClosed; health() carries
    draining / uptime_s / queue_depth.  [one server per test — warmup
    dominates, so the assertions are batched along each lifecycle]"""

    def test_drain_lifecycle_health_fields_and_eviction(self, gpt):
        from apex_tpu.serving import ReplicaDraining, ServerClosed

        server = _server(gpt, max_slots=1)
        server.start(warmup=False)      # executables compile on demand
        h = server.submit(np.zeros(3, np.int32), max_new_tokens=200)
        for _ in h.stream(timeout=300):
            break                       # mid-decode, prefix streamed
        health = server.health()
        assert health["draining"] is False
        assert health["uptime_s"] >= 0.0
        assert "queue_depth" in health and "drain_evicted" in health
        server.begin_drain()
        with pytest.raises(ReplicaDraining):
            h.result(timeout=300)
        # the migrate signal is a ServerClosed subclass: plain clients
        # need no special case — and the streamed prefix survives
        assert isinstance(h.error, ServerClosed)
        assert len(h.tokens_so_far) >= 1
        health = server.health()
        assert health["draining"] is True and server.draining
        # still alive, but a load balancer must stop routing here
        assert health["status"] == "serving"
        assert health["ready"] is False
        assert health["drain_evicted"] == 1
        # the drain released the slot: every page is back in the pool
        assert health["blocks_in_use"] == 0
        assert health["blocks_total"] == server.engine.blocks_total
        with pytest.raises(ServerClosed, match="draining"):
            server.submit(np.zeros(3, np.int32), max_new_tokens=1)
        server.shutdown(timeout=60)

    def test_kill_cancels_clients_and_reports_failed(self, gpt):
        from apex_tpu.serving import ServerClosed

        server = _server(gpt, max_slots=1)
        server.start(warmup=False)
        h = server.submit(np.zeros(3, np.int32), max_new_tokens=200)
        server.kill()
        with pytest.raises(ServerClosed):
            h.result(timeout=300)
        health = server.health()
        assert health["status"] == "failed" and not health["ready"]
        assert server.error is not None
        server.kill()                           # idempotent
        server.shutdown()                       # and shutdown-safe

    def test_kill_mid_decode_abandons_the_pool(self, gpt):
        """A kill takes the device memory with it: nothing is released,
        so the dead replica's pool still counts its tenant's pages —
        the router migrates from the streamed prefix, never from the
        engine."""
        from apex_tpu.serving import ServerClosed

        server = _server(gpt, max_slots=1)
        server.start(warmup=False)
        h = server.submit(np.arange(1, 12, dtype=np.int32),
                          max_new_tokens=200)
        for _ in h.stream(timeout=300):
            break                       # mid-decode: pages are held
        server.kill()
        with pytest.raises(ServerClosed):
            h.result(timeout=300)
        assert len(h.tokens_so_far) >= 1
        health = server.health()
        assert health["status"] == "failed"
        assert health["blocks_in_use"] >= 2     # 11 + tokens > one page
        assert health["live_tokens"] >= 11       # the whole prompt is in


class TestLatencySummarySnapshotRace:
    """Regression for a real pre-existing cross-thread race the
    graftlint concurrency pass flagged (ISSUE 9): the worker thread
    appends to the ``_ttft``/``_step_times`` reservoirs while any
    thread (fleet supervisor SLO probes, clients) snapshots them in
    ``latency_summary()`` — and iterating a deque during an append
    raises ``RuntimeError``.  Both sides now hold ``_lat_lock``; this
    hammer fails within milliseconds on the unlocked code."""

    def test_snapshot_survives_concurrent_appends(self):
        import threading
        import time as _time
        from collections import deque

        srv = InferenceServer.__new__(InferenceServer)
        srv._lat_lock = threading.Lock()
        srv._ttft = deque(maxlen=2048)
        srv._queue_wait = deque(maxlen=2048)
        srv._step_times = deque(maxlen=4096)
        for i in range(512):                    # pre-fill: long iteration
            with srv._lat_lock:
                srv._ttft.append(0.01 * i)
                srv._step_times.append(0.002)
        stop = threading.Event()
        errors = []

        def worker():
            i = 0
            try:
                while not stop.is_set():
                    with srv._lat_lock:         # the worker's append path
                        srv._ttft.append(0.01 * (i % 7))
                        srv._step_times.append(0.002 + 1e-5 * (i % 3))
                    i += 1
            except BaseException as exc:        # pragma: no cover
                errors.append(exc)

        t = threading.Thread(target=worker)
        t.start()
        try:
            deadline = _time.monotonic() + 0.8
            while _time.monotonic() < deadline:
                out = srv.latency_summary()
                assert set(out) == {"ttft_p50_s", "ttft_p99_s",
                                    "step_ms_p50", "step_ms_p99"}
                # (an empty queue-wait reservoir adds no keys)
        finally:
            stop.set()
            t.join()
        assert errors == []
