"""The program's own measurement (ISSUE 29): the span primitive, the
spans at the serving path's layer boundaries, the stamps and counters
where requests change state, and the names on programs and kernels.

Contracts under test:
- ``utils.profiler.span`` adds its block's seconds and 1 to a fixed
  ``SpanTotals`` record with no profiler session, and is a
  ``TraceAnnotation`` on the profiler's clock with one;
- ``InferenceServer.health()["spans"]`` holds every span of the three
  layers, their counts add up to the steps taken and a child's seconds
  never exceed its parent's;
- ``admitted`` / ``queue_wait_s`` / ``first_tokens`` / ``prefill_s`` /
  ``compiles`` count what they say, and every request's stamps are in
  order;
- ``health()`` stays safe from another thread while the worker steps;
- a guarded program is named by its guard, a kernel by its scope;
- ``steps_ahead`` counts the steps dispatched over an unfetched one,
  never more than the steps taken, and none where every step frees a
  slot (ISSUE 38);
- ``kv_write_pages`` counts the pages a mixed step's chunk write
  touches and nothing for a width-1 step, and warm-up traces the same
  executables as before the chunk write was a kernel (ISSUE 36);
- ``docs/serving.md`` and ``PERF.md`` spell every name as the code does.
"""

import glob
import pathlib
import re
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu import utils
from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.ops import fused_attention, fused_layer_norm
from apex_tpu.serving import (
    InferenceServer,
    PagedEngine,
    Request,
    Scheduler,
)
from apex_tpu.serving import api, engine as engine_mod, scheduler
from apex_tpu.utils import tracecheck
from apex_tpu.utils.profiler import SpanTotals, span

ROOT = pathlib.Path(__file__).resolve().parents[1]

ENGINE_STEPS = (engine_mod.STEP_PREFILL, engine_mod.STEP_DECODE,
                engine_mod.STEP_SPEC)
ENGINE_PARTS = (engine_mod.PLAN, engine_mod.DISPATCH, engine_mod.FETCH,
                engine_mod.COMMIT)
SPANS = ((api.SERVE_STEP, api.DELIVER, scheduler.ADMIT, scheduler.ROUTE)
         + ENGINE_STEPS + ENGINE_PARTS)
FIELDS = ("spans", "admitted", "queue_wait_s", "first_tokens",
          "prefill_s", "compiles", "kv_write_pages", "steps_ahead")


@pytest.fixture(scope="module")
def gpt():
    cfg = GPTConfig.tiny(position_embedding="learned", scan_layers=True)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return model, {"params": params["params"]}


def _prompts(model, sizes, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, model.cfg.vocab_size, size=(n,)).astype(
        np.int32) for n in sizes]


def _moved(after, before, name, field):
    return after["spans"][name][field] - before["spans"][name][field]


# ------------------------------------------------------------ primitive
class TestSpanPrimitive:
    def test_totals_count_without_a_profiler_session(self):
        totals = SpanTotals(("a", "b"))
        assert totals.snapshot() == {"a": {"n": 0, "s": 0.0},
                                     "b": {"n": 0, "s": 0.0}}
        for _ in range(3):
            with span(totals, "a", uid=7):
                time.sleep(0.002)
        snap = totals.snapshot()
        assert snap["a"]["n"] == 3 and snap["a"]["s"] >= 0.006
        assert snap["b"] == {"n": 0, "s": 0.0}

    def test_a_span_that_raises_is_still_counted(self):
        totals = SpanTotals(("a",))
        with pytest.raises(ZeroDivisionError):
            with span(totals, "a"):
                1 / 0
        assert totals.snapshot()["a"]["n"] == 1

    def test_names_are_fixed_at_construction(self):
        totals = SpanTotals(("a",))
        with pytest.raises(KeyError):
            span(totals, "never_declared")
        assert list(totals.snapshot()) == ["a"]

    def test_since_counts_from_an_earlier_reading(self):
        totals = SpanTotals(("a",))
        t0 = time.perf_counter()
        time.sleep(0.005)
        with span(totals, "a", since=t0):
            pass
        assert totals.snapshot()["a"]["s"] >= 0.005


# ------------------------------------------------- the serving path
class TestServerSpans:
    def _serve(self, server, prompts, budget=5):
        handles = [server.submit(p, max_new_tokens=budget)
                   for p in prompts]
        for h in handles:
            assert len(h.result(timeout=300)) == budget
        return [h._request for h in handles]

    def test_health_spans_add_up_after_a_drained_run(self, gpt):
        model, params = gpt
        server = InferenceServer(
            model, params, max_slots=2, block_size=8,
            prefill_chunk=4, pool_tokens=256)
        prompts = _prompts(model, (3, 9, 14, 6, 11))
        with server:
            before = server.health()
            requests = self._serve(server, prompts)
            after = server.health()
            # a second identical batch replays compiled programs
            self._serve(server, prompts)
            again = server.health()
            summary = server.latency_summary()
            counts = dict(server.engine.trace_counts)
        assert set(after["spans"]) == set(SPANS)
        steps = after["steps"] - before["steps"]
        assert steps > 0
        moved = {n: _moved(after, before, n, "n") for n in SPANS}
        secs = {n: _moved(after, before, n, "s") for n in SPANS}
        assert moved[api.SERVE_STEP] == steps
        assert moved[api.DELIVER] == steps
        assert moved[scheduler.ROUTE] == steps
        assert sum(moved[n] for n in ENGINE_STEPS) == steps
        assert moved[engine_mod.STEP_PREFILL] > 0
        assert moved[engine_mod.STEP_DECODE] > 0
        assert moved[engine_mod.STEP_SPEC] == 0       # drafting is off
        for part in ENGINE_PARTS:
            assert moved[part] == steps
        # some of those steps were dispatched over an unfetched one
        assert 0 < after["steps_ahead"] - before["steps_ahead"] < steps
        assert moved[scheduler.ADMIT] == len(prompts)
        # a child's seconds never exceed its parent's
        engine_s = sum(secs[n] for n in ENGINE_STEPS)
        assert 0 < sum(secs[p] for p in ENGINE_PARTS) <= engine_s
        inside = engine_s + secs[scheduler.ADMIT] + secs[scheduler.ROUTE] \
            + secs[api.DELIVER]
        assert inside <= secs[api.SERVE_STEP]
        # requests changing state
        assert after["admitted"] - before["admitted"] == len(prompts)
        assert after["first_tokens"] - before["first_tokens"] \
            == len(prompts)
        assert after["queue_wait_s"] >= before["queue_wait_s"] >= 0
        assert after["prefill_s"] > before["prefill_s"] >= 0
        for r in requests:
            assert 0 < r.accepted_at <= r.enqueued_at <= r.admitted_at \
                <= r.first_token_at
        # compilations: what the guards counted, none in steady state
        assert after["compiles"] == sum(counts.values()) == 4
        assert again["compiles"] == after["compiles"]
        assert {"queue_wait_p50_s", "queue_wait_p99_s"} <= set(summary)
        assert 0 <= summary["queue_wait_p50_s"] \
            <= summary["queue_wait_p99_s"] <= summary["ttft_p99_s"]

    def test_no_step_runs_ahead_where_every_step_frees_a_slot(self, gpt):
        """Budget 1, one-chunk prompts: each step is some request's
        last, so each is collected before the next is planned — and
        the spans still count every device step once."""
        model, params = gpt
        server = InferenceServer(
            model, params, max_slots=2, block_size=8,
            prefill_chunk=16, pool_tokens=256)
        with server:
            before = server.health()
            self._serve(server, _prompts(model, (3, 9, 14, 6, 11)),
                        budget=1)
            after = server.health()
        steps = after["steps"] - before["steps"]
        assert steps >= 3
        assert after["steps_ahead"] == before["steps_ahead"] == 0
        assert sum(_moved(after, before, n, "n")
                   for n in ENGINE_STEPS) == steps
        for part in ENGINE_PARTS:
            assert _moved(after, before, part, "n") == steps

    def test_preempt_readmissions_are_counted(self, gpt):
        model, params = gpt
        server = InferenceServer(
            model, params, max_slots=2, block_size=8,
            prefill_chunk=4, pool_tokens=64, admit_headroom=0)
        p1, p2 = _prompts(model, (20, 22), seed=7)
        with server:
            h1 = server.submit(p1, max_new_tokens=30)
            h2 = server.submit(p2, max_new_tokens=28)
            assert len(h1.result(timeout=300)) == 30
            assert len(h2.result(timeout=300)) == 28
            health = server.health()
        assert health["preempts"] >= 1
        assert health["admitted"] == 2 + health["preempts"]
        assert health["first_tokens"] == 2
        assert health["spans"][scheduler.ADMIT]["n"] == health["admitted"]
        for r in (h1._request, h2._request):
            # a requeue moves enqueued_at, never the first admission
            assert r.accepted_at <= r.admitted_at <= r.first_token_at
            assert r.enqueued_at >= r.accepted_at

    def test_a_drafted_step_is_named_by_its_program(self, gpt):
        model, params = gpt
        engine = PagedEngine(model, params, max_slots=2, block_size=8,
                             prefill_chunk=4, pool_tokens=256,
                             spec_tokens=2, spec_ngram=2)
        # every drafting step finds a draft, whatever the context
        engine._drafter = lambda context, cap, ngram: \
            np.zeros((cap,), np.int32)
        sched = Scheduler(engine)
        engine.warmup()
        before = engine.spans.snapshot()
        sched.submit(Request(prompt=np.arange(6, dtype=np.int32),
                             max_new_tokens=8))
        sched.drain()
        after = engine.spans.snapshot()
        moved = {n: after[n]["n"] - before[n]["n"] for n in after}
        assert moved[engine_mod.STEP_SPEC] > 0
        assert moved[engine_mod.STEP_PREFILL] == 2       # 6 tokens by 4
        assert sum(moved[n] for n in ENGINE_STEPS) \
            == moved[engine_mod.PLAN] == moved[engine_mod.COMMIT]
        assert sched.admitted == 1 and sched.queue_wait_s >= 0

    def test_health_from_another_thread_never_raises(self, gpt):
        model, params = gpt
        server = InferenceServer(
            model, params, max_slots=2, block_size=8,
            prefill_chunk=4, pool_tokens=256)
        stop = threading.Event()
        errors, reads = [], [0]

        def monitor():
            try:
                while not stop.is_set():
                    health = server.health()
                    for rec in health["spans"].values():
                        assert rec["n"] >= 0 and rec["s"] >= 0.0
                    server.latency_summary()
                    reads[0] += 1
            except BaseException as exc:        # pragma: no cover
                errors.append(exc)

        with server:
            t = threading.Thread(target=monitor)
            t.start()
            try:
                self._serve(server, _prompts(model, (5, 12, 7, 9)),
                            budget=12)
            finally:
                stop.set()
                t.join()
        assert errors == [] and reads[0] > 0


# --------------------------------------------- on the profiler's clock
def test_trace_holds_the_engine_spans_nested_on_one_line(gpt, tmp_path):
    from jax.profiler import ProfileData

    model, params = gpt
    engine = PagedEngine(model, params, max_slots=2, block_size=8,
                         prefill_chunk=4, pool_tokens=128)
    sched = Scheduler(engine)
    engine.warmup()
    with utils.profiler.trace(str(tmp_path)):
        req = sched.submit(Request(
            prompt=np.arange(9, dtype=np.int32), max_new_tokens=4))
        sched.drain()
    path = glob.glob(str(
        tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                    dict(e.stats))
                   for e in line.events if e.name.startswith("apex/")]
            if evs:
                lines.append(evs)
    assert len(lines) == 1, "the spans of one thread lie on one line"
    evs = lines[0]
    names = {e[2] for e in evs}
    assert names >= set(ENGINE_PARTS) | {
        engine_mod.STEP_PREFILL, engine_mod.STEP_DECODE,
        scheduler.ADMIT, scheduler.ROUTE}
    # a step is two events of its name, one a half: the dispatch half
    # (plan, dispatch) and, once the step before it is collected, the
    # collect half (fetch, commit)
    halves = [e for e in evs if e[2] in ENGINE_STEPS]
    parts = [e for e in evs if e[2] in ENGINE_PARTS]
    assert len(parts) == 2 * len(halves)
    for s, t, name, _ in parts:
        assert sum(a <= s and t <= b for a, b, _, _ in halves) == 1, name
    held = []
    for a, b, name, _ in sorted(halves):
        mine = tuple(e[2] for e in sorted(parts)
                     if a <= e[0] and e[1] <= b)
        assert mine in (ENGINE_PARTS[:2], ENGINE_PARTS[2:])
        held.append((name, mine == ENGINE_PARTS[:2]))
    # every step is dispatched once and collected once, in that order
    # and under one name, and at most two are in flight
    queue = []
    for name, dispatched in held:
        if dispatched:
            queue.append(name)
            assert len(queue) <= 2
        else:
            assert queue.pop(0) == name
    assert queue == []
    assert any(a and b for (_, a), (_, b) in zip(held, held[1:])), \
        "no step was dispatched ahead of the one before it"
    admit = [e for e in evs if e[2] == scheduler.ADMIT]
    assert len(admit) == 1
    ids = {k: str(v) for k, v in admit[0][3].items()}
    assert ids.get("uid") == str(req.uid)
    assert ids.get("prompt_len") == "9"


# ----------------------------------------------------- the chunk write
def test_a_mixed_step_counts_the_pages_its_write_touches(gpt):
    """``kv_write_pages``: pages spanned by ``[cursor, cursor +
    n_tokens)`` of every live row of a step wider than one token; a
    width-1 step writes inside its attention kernel and counts none."""
    model, params = gpt
    engine = PagedEngine(model, params, max_slots=3, block_size=8,
                         prefill_chunk=16, pool_tokens=256)
    engine.admit(0, np.arange(1, 14, dtype=np.int32), max_new_tokens=8)
    engine.step()           # 13 lanes from position 0: pages 0 and 1
    assert engine.kv_write_pages == 2
    engine.step()           # width 1
    engine.step()
    assert engine.kv_write_pages == 2
    # a second tenant's 21-token prompt rides two mixed steps beside
    # the first one's decode lane (one page each step)
    engine.admit(1, np.arange(1, 22, dtype=np.int32), max_new_tokens=2)
    engine.step()           # row 0: 1 page; row 1: positions 0..15, 2
    assert engine.kv_write_pages == 2 + 1 + 2
    engine.step()           # row 0: 1 page; row 1: positions 16..20, 1
    assert engine.kv_write_pages == 5 + 1 + 1
    before = engine.kv_write_pages
    engine.step()           # width 1 again
    assert engine.kv_write_pages == before
    assert engine.kv_pages_live > 0


@pytest.mark.parametrize("spec_tokens", [0, 2])
def test_warmup_traces_the_same_executables_as_ever(gpt, spec_tokens):
    """The chunk write rides the mixed and the drafted step: warm-up
    traces each guarded program once, and the engine holds no guarded
    program beside the five."""
    model, params = gpt
    engine = PagedEngine(model, params, max_slots=2, block_size=8,
                         prefill_chunk=4, pool_tokens=64,
                         spec_tokens=spec_tokens)
    engine.warmup()
    want = {"decode_step": 1, "prefill_step": 1, "admit": 1,
            "release": 1}
    if spec_tokens:
        want["spec_step"] = 1
    assert engine.trace_counts == want
    guarded = {name for name, held in vars(engine).items()
               if hasattr(held, "trace_count")}
    assert guarded == {"_decode", "_prefill", "_spec", "_admit",
                       "_release"}


# -------------------------------------------------------------- names
def test_retrace_guard_names_the_module_after_the_guard():
    guarded = tracecheck.retrace_guard(lambda x: x + 1, name="a.b")
    assert "module @jit_a_b " in guarded.lower(jnp.ones(3)).as_text()
    plain = tracecheck.retrace_guard(lambda x: x + 1, name="decode_step")
    assert "module @jit_decode_step " in plain.lower(
        jnp.ones(3)).as_text()


@pytest.mark.parametrize("guard,module", [
    ("_decode", "jit_serving_decode_step"),
    ("_prefill", "jit_serving_prefill_step"),
    ("_admit", "jit_serving_admit"),
    ("_release", "jit_serving_release"),
])
def test_paged_engine_programs_are_told_apart(gpt, guard, module):
    model, params = gpt
    engine = PagedEngine(model, params, max_slots=2, block_size=8,
                         prefill_chunk=4, pool_tokens=64)
    assert getattr(engine, guard)._wrapped.__name__ == module[4:]


@pytest.mark.parametrize("scope", [
    "attention.fwd", "attention.bwd_dq", "attention.bwd_dkv",
    "layer_norm_fwd", "layer_norm_bwd"])
def test_kernel_scopes_reach_the_lowered_text(scope):
    def loss(q, k, v, w, b):
        o = fused_attention(q, k, v, implementation="pallas_interpret")
        y = fused_layer_norm(o.reshape(-1, 128), w, b,
                             implementation="pallas_interpret")
        return jnp.sum(y.astype(jnp.float32))

    q = jnp.ones((1, 128, 2, 64), jnp.bfloat16)
    w = jnp.ones((128,), jnp.float32)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        q, q, q, w, w).as_text(debug_info=True)
    # ``jit(f)/jvp(attention.fwd)/pallas_call``,
    # ``.../transpose(jvp(attention.bwd_dq))/pallas_call``
    assert re.search(rf"[/(]{re.escape(scope)}\)*/pallas_call", text)


# --------------------------------------------------------------- docs
@pytest.mark.parametrize("doc", ["docs/serving.md", "PERF.md"])
@pytest.mark.parametrize("name", SPANS + FIELDS + (
    "enqueued_at", "admitted_at", "first_token_at"))
def test_documents_spell_the_names_as_the_code_does(doc, name):
    assert f"`{name}`" in (ROOT / doc).read_text()
