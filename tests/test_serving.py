"""Continuous-batching serving engine (apex_tpu.serving).

Correctness contracts under test:
- greedy decode through the slotted engine is TOKEN-IDENTICAL to the
  fixed-batch ``generate()`` loop for the same prompts;
- a steady-state soak interleaving admissions/evictions across >= 3
  prompt-length buckets with heterogeneous sampling params triggers
  ZERO retraces after warmup (asserted both via the process-wide
  trace-event counter and the engine's own ``retrace_guard`` budgets,
  which would raise ``RetraceError`` on any excess trace);
- a request's sampled tokens depend on its own seed, not on its
  co-tenants (per-slot rng);
- the threaded ``InferenceServer`` streams tokens, emits metrics, and
  shuts down cleanly.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.models import GPTConfig, GPTModel, LlamaConfig, LlamaModel, generate
from apex_tpu.serving import (
    Engine,
    InferenceServer,
    QueueFull,
    Request,
    Scheduler,
)
from apex_tpu.serving import cache as slot_cache
from apex_tpu.utils import MetricsWriter, tracecheck
from apex_tpu.utils.tracecheck import RetraceError


def _tiny_gpt():
    cfg = GPTConfig.tiny(position_embedding="learned",
                         scan_layers=True)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return model, {"params": params["params"]}


def _tiny_llama():
    cfg = LlamaConfig.tiny(scan_layers=True)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return model, {"params": params["params"]}


@pytest.fixture(scope="module")
def gpt():
    return _tiny_gpt()


@pytest.fixture(scope="module")
def llama():
    return _tiny_llama()


def _prompts(rng, vocab, lengths):
    return [rng.integers(0, vocab, size=(L,)).astype(np.int32)
            for L in lengths]


class TestSlotCache:
    def test_pool_shapes_and_reset(self, gpt):
        model, _ = gpt
        from apex_tpu.models.generate import cache_shapes

        shapes = cache_shapes(model, 1)
        pool = slot_cache.stacked_zeros(shapes, 3)
        flat = jax.tree.leaves(pool)
        per_slot = jax.tree.leaves(shapes)
        assert all(p.shape == (3,) + tuple(s.shape)
                   for p, s in zip(flat, per_slot))
        # write then reset roundtrips to zeros
        one = jax.tree.map(
            lambda s: jnp.ones(s.shape, s.dtype), shapes)
        pool = slot_cache.write_slot(pool, 1, one)
        assert all(float(jnp.sum(jnp.abs(leaf[1].astype(jnp.float32))))
                   > 0 for leaf in jax.tree.leaves(pool))
        pool = slot_cache.reset_slot(pool, 1)
        assert all(float(jnp.sum(jnp.abs(leaf.astype(jnp.float32))))
                   == 0 for leaf in jax.tree.leaves(pool))

    def test_rewind_targets_only_index_leaves(self, gpt):
        model, _ = gpt
        from apex_tpu.models.generate import init_cache

        cache = init_cache(model, 1)
        cache = jax.tree.map(
            lambda x: x + jnp.ones_like(x), cache)
        out = slot_cache.rewind_index_leaves(cache, 7)
        flat = jax.tree_util.tree_flatten_with_path(out)[0]
        saw_index = 0
        for path, leaf in flat:
            name = slot_cache._leaf_name(path)
            if name in ("cache_index", "position_index"):
                saw_index += 1
                assert np.all(np.asarray(leaf) == 7), name
            else:
                assert np.all(np.asarray(leaf) == 1), name
        assert saw_index >= 2       # per-layer cache_index + model pos

    def test_sliding_window_cache_rejected(self):
        cfg = LlamaConfig.tiny(sliding_window=5, scan_layers=False)
        model = LlamaModel(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
        with pytest.raises(ValueError, match="ring-buffer"):
            Engine(model, {"params": params["params"]},
                   max_slots=2, prompt_buckets=(8,))


class TestEngineValidation:
    def test_bucket_exceeding_max_seq_len_rejected(self, gpt):
        model, params = gpt
        S = model.cfg.max_seq_len
        with pytest.raises(ValueError, match="bucket"):
            Engine(model, params, prompt_buckets=(S,))

    def test_oversized_request_rejected_at_submit(self, gpt):
        model, params = gpt
        engine = Engine(model, params, max_slots=1,
                        prompt_buckets=(8,))
        sched = Scheduler(engine)
        with pytest.raises(ValueError, match="bucket"):
            sched.submit(Request(prompt=np.zeros(9, np.int32),
                                 max_new_tokens=1))
        with pytest.raises(ValueError, match="max_seq_len"):
            sched.submit(Request(
                prompt=np.zeros(8, np.int32),
                max_new_tokens=model.cfg.max_seq_len))
        with pytest.raises(ValueError, match="top_k"):
            sched.submit(Request(prompt=np.zeros(4, np.int32),
                                 max_new_tokens=2,
                                 temperature=1.0,
                                 top_k=model.cfg.vocab_size + 1))

    def test_queue_capacity_bounded(self, gpt):
        model, params = gpt
        engine = Engine(model, params, max_slots=1,
                        prompt_buckets=(8,))
        sched = Scheduler(engine, queue_capacity=2)
        for _ in range(2):
            sched.submit(Request(prompt=np.zeros(4, np.int32),
                                 max_new_tokens=1))
        with pytest.raises(QueueFull):
            sched.submit(Request(prompt=np.zeros(4, np.int32),
                                 max_new_tokens=1))


class TestGreedyParity:
    # [the llama twin is slow-marked: ~17s of CPU compile for the same
    # dense-engine property the gpt twin pins in tier-1; it still runs
    # under -m slow and in the on-chip pass]
    @pytest.mark.l0
    @pytest.mark.parametrize("which", [
        "gpt", pytest.param("llama", marks=pytest.mark.slow)])
    def test_engine_matches_generate(self, which, request):
        """Mixed-length greedy requests through 2 slots must reproduce
        generate()'s token chains exactly — including requests that
        queue behind the first wave (continuous refill)."""
        model, params = request.getfixturevalue(which)
        rng = np.random.default_rng(3)
        prompts = _prompts(rng, model.cfg.vocab_size,
                           (3, 5, 8, 4, 11))
        budgets = [6, 3, 5, 7, 4]
        engine = Engine(model, params, max_slots=2,
                        prompt_buckets=(4, 8, 16))
        sched = Scheduler(engine)
        reqs = [sched.submit(Request(prompt=p, max_new_tokens=n))
                for p, n in zip(prompts, budgets)]
        sched.drain()
        for p, n, r in zip(prompts, budgets, reqs):
            ref = np.asarray(generate(
                model, params, jnp.asarray(p[None]),
                max_new_tokens=n))[0, len(p):]
            np.testing.assert_array_equal(
                np.asarray(r.tokens), ref,
                err_msg=f"{which} prompt_len={len(p)} n={n}")

    def test_chunked_prefill_engine_matches_generate(self, gpt):
        """The engine's prefill rides the same chunked path as
        generate(prefill_chunk=...): forcing small chunks must not
        change the greedy token chain."""
        model, params = gpt
        rng = np.random.default_rng(19)
        prompt = rng.integers(0, model.cfg.vocab_size,
                              size=(11,)).astype(np.int32)
        ref = np.asarray(generate(
            model, params, jnp.asarray(prompt[None]),
            max_new_tokens=4))[0, 11:]
        engine = Engine(model, params, max_slots=1,
                        prompt_buckets=(16,), prefill_chunk=4)
        sched = Scheduler(engine)
        req = sched.submit(Request(prompt=prompt, max_new_tokens=4))
        sched.drain()
        np.testing.assert_array_equal(np.asarray(req.tokens), ref)

    def test_eos_stops_early_and_matches_generate(self, gpt):
        model, params = gpt
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, model.cfg.vocab_size,
                              size=(5,)).astype(np.int32)
        n = 8
        ref = np.asarray(generate(
            model, params, jnp.asarray(prompt[None]),
            max_new_tokens=n))[0, 5:]
        eos = int(ref[2])            # force a stop three tokens in
        engine = Engine(model, params, max_slots=1,
                        prompt_buckets=(8,))
        sched = Scheduler(engine)
        req = sched.submit(Request(prompt=prompt, max_new_tokens=n,
                                   eos_id=eos))
        sched.drain()
        got = np.asarray(req.tokens)
        # engine stops AT the produced eos; generate's chain up to the
        # first eos must match token for token
        first = int(np.argmax(ref == eos))
        np.testing.assert_array_equal(got, ref[:first + 1])
        assert got[-1] == eos and len(got) < n


class TestTopPSampling:
    @pytest.mark.slow
    def test_top_p_one_matches_disabled(self, gpt):
        # [slow: two engine builds ≈ 8 s; the fast tier covers the
        # exact-no-op contract at the sample_dynamic level below and
        # mixes top_p=1.0 traffic through the zero-retrace soak]
        """top_p=1.0 and top_p=None are the same program AND the same
        tokens (the disabled nucleus filter is an exact no-op in
        sample_dynamic, not an epsilon approximation)."""
        model, params = gpt
        rng = np.random.default_rng(23)
        prompt = rng.integers(0, model.cfg.vocab_size,
                              size=(6,)).astype(np.int32)

        def run(top_p):
            engine = Engine(model, params, max_slots=1,
                            prompt_buckets=(8,))
            sched = Scheduler(engine)
            req = sched.submit(Request(
                prompt=prompt, max_new_tokens=6, temperature=0.9,
                top_p=top_p, seed=5))
            sched.drain()
            return list(req.tokens)

        assert run(None) == run(1.0)

    def test_dynamic_nucleus_restricts_tokens(self, gpt):
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
        model, params = gpt
        engine = Engine(model, params, max_slots=1,
                        prompt_buckets=(8,))
        sched = Scheduler(engine)
        with pytest.raises(ValueError, match="top_p"):
            sched.submit(Request(prompt=np.zeros(4, np.int32),
                                 max_new_tokens=2, temperature=1.0,
                                 top_p=1.5))


class TestSamplingDeterminism:
    def test_tokens_independent_of_cotenants(self, gpt):
        """A sampled request carries its own rng (seeded at admission):
        running alone or beside other traffic must not change its
        tokens."""
        model, params = gpt
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, model.cfg.vocab_size,
                              size=(6,)).astype(np.int32)

        def run(extra_traffic):
            engine = Engine(model, params, max_slots=2,
                            prompt_buckets=(8,))
            sched = Scheduler(engine)
            req = sched.submit(Request(
                prompt=prompt, max_new_tokens=5, temperature=0.9,
                top_k=20, seed=123))
            if extra_traffic:
                for i in range(3):
                    sched.submit(Request(
                        prompt=rng.integers(
                            0, model.cfg.vocab_size,
                            size=(4 + i,)).astype(np.int32),
                        max_new_tokens=4, temperature=1.3, seed=i))
            sched.drain()
            return list(req.tokens)

        assert run(False) == run(True)


class TestSoakZeroRetraces:
    def test_steady_state_soak(self, gpt):
        """The acceptance soak: >= 3 prompt-length buckets, mixed
        temperatures / top_k / top_p / eos / budgets, admissions and
        evictions interleaving across 14 requests through 3 slots —
        zero jaxpr traces after warmup.  The engine's retrace_guards
        (budget: decode_step/admit/release = 1, prefill = #buckets)
        raise RetraceError on any excess trace, and the process-wide
        trace-event counter cross-checks the whole soak.  Nucleus
        (top_p) traffic rides the same executable as everything else
        (the ISSUE-3 plumbing contract: per-slot device-array
        params, budgets unchanged)."""
        model, params = gpt
        engine = Engine(model, params, max_slots=3,
                        prompt_buckets=(4, 8, 16))
        sched = Scheduler(engine)
        engine.warmup()
        assert engine.trace_counts == {
            "decode_step": 1, "prefill": 3, "admit": 1, "release": 1}

        rng = np.random.default_rng(11)
        before = tracecheck.trace_event_count()
        cases = [
            (3, 4, 0.0, None, None, None),
            (7, 3, 0.8, 20, None, None),
            (12, 5, 1.2, 5, None, 0.9), (2, 6, 0.0, None, 17, None),
            (8, 2, 0.5, None, None, 0.5),
            (16, 4, 0.0, None, None, None),
            (5, 3, 1.0, 50, 3, 0.95), (4, 5, 0.0, None, None, None),
            (9, 4, 0.7, 10, None, None), (1, 2, 0.0, None, None, None),
            (13, 3, 1.5, 2, None, 1.0), (6, 6, 0.0, None, 900, None),
            (11, 2, 0.9, None, None, 0.7),
            (8, 4, 0.0, None, None, None),
        ]
        reqs = []
        for i, (L, n, t, k, eos, p) in enumerate(cases):
            reqs.append(sched.submit(Request(
                prompt=rng.integers(0, model.cfg.vocab_size,
                                    size=(L,)).astype(np.int32),
                max_new_tokens=n, temperature=t, top_k=k, top_p=p,
                eos_id=eos, seed=i)))
        events = sched.drain()
        assert tracecheck.trace_event_count() == before, (
            "steady-state soak retraced after warmup")
        assert engine.trace_counts == {
            "decode_step": 1, "prefill": 3, "admit": 1, "release": 1}
        # every request produced tokens and respected its budget
        for (L, n, t, k, eos, p), r in zip(cases, reqs):
            assert 1 <= len(r.tokens) <= n
            if eos is None:
                assert len(r.tokens) == n
        assert len(events) == sum(len(r.tokens) for r in reqs)

    def test_unbucketable_prompt_raises_not_retraces(self, gpt):
        model, params = gpt
        engine = Engine(model, params, max_slots=1,
                        prompt_buckets=(4,))
        with pytest.raises(ValueError, match="bucket"):
            engine.admit(0, np.zeros(5, np.int32), max_new_tokens=1)

    def test_guard_raises_on_forced_retrace(self, gpt):
        """The guard is live, not decorative: bypassing the bucketer
        with a second prefill shape beyond the budget must raise
        RetraceError (this is what a shape leak in production would
        look like)."""
        model, params = gpt
        engine = Engine(model, params, max_slots=1,
                        prompt_buckets=(4,))
        engine.warmup()
        with pytest.raises(RetraceError):
            engine._prefill(engine._variables,
                            jnp.zeros((1, 6), jnp.int32), np.int32(6))


class TestInferenceServer:
    def test_streaming_and_metrics(self, gpt):
        model, params = gpt
        rows = []
        writer = MetricsWriter(sink=lambda s, m: rows.append((s, m)))
        server = InferenceServer(
            model, params, max_slots=2, prompt_buckets=(4, 8),
            metrics=writer, metrics_interval=2)
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

    def test_greedy_parity_through_server(self, gpt):
        model, params = gpt
        rng = np.random.default_rng(17)
        prompt = rng.integers(0, model.cfg.vocab_size,
                              size=(5,)).astype(np.int32)
        ref = np.asarray(generate(
            model, params, jnp.asarray(prompt[None]),
            max_new_tokens=5))[0, 5:]
        with InferenceServer(model, params, max_slots=2,
                             prompt_buckets=(8,)) as server:
            got = server.submit(
                prompt, max_new_tokens=5).result(timeout=300)
        np.testing.assert_array_equal(np.asarray(got), ref)

    def test_shutdown_without_drain_cancels(self, gpt):
        from apex_tpu.serving import ServerClosed

        model, params = gpt
        server = InferenceServer(model, params, max_slots=1,
                                 prompt_buckets=(4,))
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

        model, params = gpt
        server = InferenceServer(model, params, max_slots=1,
                                 prompt_buckets=(4,))
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

        model, params = gpt
        server = InferenceServer(model, params, max_slots=1,
                                 prompt_buckets=(4,))
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
        model, params = gpt
        server = InferenceServer(model, params, max_slots=1,
                                 prompt_buckets=(4,))
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

        model, params = gpt
        server = InferenceServer(model, params, max_slots=1,
                                 prompt_buckets=(4,))
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

        model, params = gpt
        server = InferenceServer(model, params, max_slots=1,
                                 prompt_buckets=(4,))
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

        model, params = gpt
        server = InferenceServer(model, params, max_slots=1,
                                 prompt_buckets=(4,))
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
        with pytest.raises(ServerClosed, match="draining"):
            server.submit(np.zeros(3, np.int32), max_new_tokens=1)
        server.shutdown(timeout=60)

    def test_kill_cancels_clients_and_reports_failed(self, gpt):
        from apex_tpu.serving import ServerClosed

        model, params = gpt
        server = InferenceServer(model, params, max_slots=1,
                                 prompt_buckets=(4,))
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
