"""One serving step in flight (ISSUE 38): ``PagedEngine.dispatch`` /
``collect`` and the scheduler loop that keeps the chip one step ahead
of the host's fetch.

Contracts under test:
- **the same work**: a scheduler driven with a step in flight hands
  every request exactly the tokens ``engine.step()`` gives in lock-step
  (greedy and sampled rows, every chunking, slots reused) and, for
  greedy rows, ``generate()``'s — for a GQA transformer, a model with
  recurrent state and a model with an expert share, whose counters
  read what lock-step reads;
- **when not to run ahead**: a step that frees a slot, a drafted step
  and a plan that would preempt are collected first;
- **hazards**: a row that finished by EOS under a step in flight emits
  nothing there; an evicted slot's row in flight is nobody's, and the
  slot's next tenant never sees it; a preempted request continues from
  every token it streamed; drain, shutdown and kill leave no handle
  open and no token lost or doubled; an injected step fault recovers.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import (AfmoeConfig, AfmoeModel, FalconH1Config,
                             FalconH1Model, LlamaConfig, LlamaModel,
                             generate)
from apex_tpu.resilience.faults import FaultPlan, FaultSpec, active
from apex_tpu.serving import (InferenceServer, PagedEngine, Request,
                              Scheduler)
from apex_tpu.serving import engine as engine_mod
from apex_tpu.serving.api import ReplicaDraining, ServerClosed

CHUNK = 8
f32 = jnp.float32


def _init(model):
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    return {"params": params["params"]}


def _llama():
    model = LlamaModel(LlamaConfig.tiny(
        vocab_size=256, hidden_size=64, ffn_hidden_size=128,
        max_seq_len=128, dtype=f32, param_dtype=f32))
    return model, _init(model)


def _falcon_h1():
    model = FalconH1Model(FalconH1Config.tiny(dtype=f32, param_dtype=f32))
    return model, _init(model)


def _afmoe():
    model = AfmoeModel(AfmoeConfig.tiny(dtype=f32, param_dtype=f32))
    return model, _init(model)


MODELS = {"llama": _llama, "falcon_h1": _falcon_h1, "afmoe": _afmoe}


@pytest.fixture(scope="module")
def llama():
    return _llama()


def _requests(model, seed=3):
    """Prompts of 1, chunk, chunk + 1 and 2 x chunk + 3 tokens and a
    few between, greedy and temperature / top-k rows mixed: more
    requests than slots, budgets of several steps."""
    rng = np.random.default_rng(seed)
    sizes = (1, CHUNK, CHUNK + 1, 2 * CHUNK + 3, 5, 12, 3)
    budgets = (4, 7, 3, 6, 9, 5, 8)
    sampling = ((0.0, None), (0.8, 20), (0.0, None), (1.1, 5),
                (0.0, None), (0.7, None), (0.0, None))
    return [dict(prompt=rng.integers(0, model.cfg.vocab_size,
                                     size=(n,)).astype(np.int32),
                 max_new_tokens=b, temperature=t, top_k=k, seed=i)
            for i, (n, b, (t, k)) in enumerate(
                zip(sizes, budgets, sampling))]


def lock_step(engine, requests):
    """The reference loop: FIFO admission into free slots, then ONE
    ``engine.step()`` whose output is routed before the next is
    planned."""
    waiting = list(enumerate(requests))
    slots = [None] * engine.max_slots
    tokens = [[] for _ in requests]
    while waiting or any(i is not None for i in slots):
        for slot, held in enumerate(slots):
            if held is None and waiting:
                i, kw = waiting.pop(0)
                engine.admit(slot, **kw)
                slots[slot] = i
        out = engine.step()
        for slot, i in enumerate(slots):
            if i is None:
                continue
            tokens[i].extend(
                int(t) for t in out.tokens[slot, :out.counts[slot]])
            if out.finished[slot]:
                engine.release(slot)
                slots[slot] = None
    return tokens


def in_flight(engine, requests):
    """The same requests through a scheduler, a step in flight."""
    sched = Scheduler(engine)
    handles = [sched.submit(Request(**kw)) for kw in requests]
    sched.drain()
    assert not sched.has_work() and engine.in_flight == 0
    return [list(h.tokens) for h in handles]


def greedy_reference(model, params, kw):
    """``generate()``'s chain; for a model the dense cache refuses
    (recurrent state, an expert share) the chain of the full forward,
    no cache and no state carried, one position at a time."""
    n = kw["max_new_tokens"]
    if isinstance(model, LlamaModel):
        return [int(t) for t in np.asarray(generate(
            model, params, jnp.asarray(kw["prompt"][None]),
            max_new_tokens=n))[0, -n:]]
    seq = [int(t) for t in kw["prompt"]]
    for _ in range(n):
        ids = np.zeros((1, 32), np.int32)
        ids[0, :len(seq)] = seq
        logits = _forward(model)(params, jnp.asarray(ids))
        seq.append(int(jnp.argmax(logits[0, len(seq) - 1])))
    return seq[-n:]


@functools.lru_cache(maxsize=None)
def _forward(model):
    return jax.jit(model.apply)


def _step_counts(engine):
    snap = engine.spans.snapshot()
    return {k: snap[k]["n"] for k in (engine_mod.STEP_PREFILL,
                                      engine_mod.STEP_DECODE,
                                      engine_mod.STEP_SPEC)}


COUNTERS = ("kv_pages_live", "kv_write_pages", "ssm_state_resets",
            "ssm_positions", "expert_assignments", "expert_load_max",
            "experts_active", "expert_layer_steps")


# ------------------------------------------------------- the same work
@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_step_in_flight_serves_lock_steps_tokens(name):
    model, params = MODELS[name]()
    engine = PagedEngine(model, params, max_slots=3, block_size=8,
                         prefill_chunk=CHUNK, pool_tokens=256)
    requests = _requests(model)

    def counted(run):
        before = ({c: getattr(engine, c) for c in COUNTERS},
                  _step_counts(engine))
        tokens = run(engine, requests)
        assert engine.blocks_in_use == 0
        moved = {c: getattr(engine, c) - before[0][c] for c in COUNTERS}
        steps = {k: n - before[1][k]
                 for k, n in _step_counts(engine).items()}
        return tokens, moved, steps

    want, want_moved, want_steps = counted(lock_step)
    assert engine.steps_ahead == 0           # step() never runs ahead
    got, got_moved, got_steps = counted(in_flight)
    assert got == want
    # no request sets an EOS: every finish is foreseen, the plans are
    # lock-step's plans one for one, and so is every counter — the
    # recurrent state's resets and the experts' loads among them
    assert got_steps == want_steps and got_moved == want_moved
    if name == "falcon_h1":
        assert got_moved["ssm_state_resets"] >= len(requests)
    if name == "afmoe":
        assert got_moved["expert_assignments"] > 0
    assert 0 < engine.steps_ahead < sum(got_steps.values())
    for kw, tokens in zip(requests, got):
        assert len(tokens) == kw["max_new_tokens"]
        if kw["temperature"] == 0.0:
            assert tokens == greedy_reference(model, params, kw)
    assert engine.trace_counts == {"decode_step": 1, "prefill_step": 1,
                                   "admit": 1, "release": 1}


# ------------------------------------------------- when not to run ahead
def test_a_step_that_frees_a_slot_is_collected_first(llama):
    """A row whose dispatched emissions reach its budget: the step
    behind it is refused until it is collected, so the slot's next
    tenant rides the very next step."""
    model, params = llama
    engine = PagedEngine(model, params, max_slots=2, block_size=8,
                         prefill_chunk=CHUNK, pool_tokens=128)
    engine.admit(0, np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
    assert engine.dispatch()                 # the prompt: 1st token
    assert engine.dispatch()                 # ahead: 2nd token
    assert engine.in_flight == 2 and engine.steps_ahead == 1
    assert not engine.dispatch()             # depth is one
    assert engine.collect().counts[0] == 1
    assert engine.dispatch()                 # 3rd token: the budget
    assert engine.collect().counts[0] == 1
    assert not engine.dispatch()             # a slot comes free
    out = engine.collect()
    assert out.finished[0] and engine.in_flight == 0
    assert engine.steps_ahead == 2
    with pytest.raises(RuntimeError, match="no step is in flight"):
        engine.collect()
    engine.release(0)
    assert engine.blocks_in_use == 0


def test_step_is_lock_step_and_refuses_a_pipeline(llama):
    model, params = llama
    engine = PagedEngine(model, params, max_slots=1, block_size=8,
                         prefill_chunk=CHUNK, pool_tokens=64)
    engine.admit(0, np.arange(1, 4, dtype=np.int32), max_new_tokens=4)
    assert engine.step().counts[0] == 1 and engine.in_flight == 0
    assert engine.dispatch()
    with pytest.raises(RuntimeError, match="uncollected"):
        engine.step()
    engine.discard()
    assert engine.in_flight == 0
    engine.release(0)
    assert engine.blocks_in_use == 0


def test_every_step_frees_a_slot_at_budget_one(llama):
    """Budget 1, one-chunk prompts: every step is some request's last,
    so none is dispatched ahead — and none is lost."""
    model, params = llama
    engine = PagedEngine(model, params, max_slots=2, block_size=8,
                         prefill_chunk=CHUNK, pool_tokens=128)
    requests = [dict(prompt=np.full((n,), n, np.int32), max_new_tokens=1)
                for n in (3, 8, 5, 1, 7)]
    got = in_flight(engine, requests)
    assert [len(t) for t in got] == [1] * len(requests)
    assert engine.steps_ahead == 0
    assert sum(_step_counts(engine).values()) == 3      # 2 + 2 + 1


def test_a_drafted_step_never_runs_ahead(llama):
    """``spec_tokens > 0``: the served tokens and the acceptance are
    lock-step's, and the only steps dispatched ahead are mixed steps
    (drafts are looked up in the tokens of the step in flight)."""
    model, params = llama
    engine = PagedEngine(model, params, max_slots=2, block_size=8,
                         prefill_chunk=4, pool_tokens=256,
                         spec_tokens=3, spec_ngram=2)
    rng = np.random.default_rng(5)
    pattern = rng.integers(0, model.cfg.vocab_size, size=(6,))
    requests = [dict(prompt=np.tile(pattern, 3).astype(np.int32)[:n],
                     max_new_tokens=b)
                for n, b in ((18, 12), (11, 9), (14, 10))]
    want = lock_step(engine, requests)
    rate = (engine.spec_proposed, engine.spec_accepted)
    assert rate[0] > 0
    ahead = []
    dispatch = engine.dispatch

    def spy():
        behind = engine.in_flight
        done = dispatch()
        if done and behind:
            ahead.append(engine._flights[-1].kind)
        return done

    engine.dispatch = spy
    assert in_flight(engine, requests) == want
    assert (engine.spec_proposed, engine.spec_accepted) \
        == (2 * rate[0], 2 * rate[1])
    assert ahead and set(ahead) == {engine_mod.STEP_PREFILL}
    assert engine.steps_ahead == len(ahead)
    assert _step_counts(engine)[engine_mod.STEP_SPEC] > 0
    for kw, tokens in zip(requests, want):
        assert tokens == greedy_reference(model, params, kw)


def test_a_plan_that_preempts_waits_for_the_step_in_flight(llama):
    """Pool exhaustion: the preempted request is requeued from its
    streamed prefix, which holds every token dispatched before the
    preempting plan — its final tokens are ``generate()``'s, none
    doubled, none missing."""
    model, params = llama
    engine = PagedEngine(model, params, max_slots=2, block_size=8,
                         pool_tokens=64, prefill_chunk=4,
                         admit_headroom=0)
    rng = np.random.default_rng(7)
    requests = [dict(prompt=rng.integers(
        0, model.cfg.vocab_size, size=(n,)).astype(np.int32),
        max_new_tokens=b) for n, b in ((20, 30), (22, 28))]
    preempting = []
    dispatch = engine.dispatch

    def spy():
        behind = engine.in_flight
        done = dispatch()
        if done and engine._flights[-1].preempted:
            preempting.append(behind)
        return done

    engine.dispatch = spy
    sched = Scheduler(engine)
    handles = [sched.submit(Request(**kw)) for kw in requests]
    sched.drain()
    assert sched.preempts >= 1 and engine.steps_ahead > 0
    assert preempting and set(preempting) == {0}
    for kw, h in zip(requests, handles):
        assert list(h.tokens) == greedy_reference(model, params, kw)
    assert engine.blocks_in_use == 0


# ---------------------------------------------------------------- hazards
def test_a_row_finished_by_eos_emits_nothing_under_the_step_in_flight(
        llama):
    """The host cannot foresee an EOS: the row is planned once more in
    the step already in flight.  The device gates that emission, the
    fetched mask says so, and the slot's next tenant — admitted under
    that very step — gets only its own tokens."""
    model, params = llama
    rng = np.random.default_rng(11)
    pa, pb = (rng.integers(0, model.cfg.vocab_size, size=(n,)).astype(
        np.int32) for n in (5, 7))
    ref_a = greedy_reference(model, params,
                             dict(prompt=pa, max_new_tokens=8))
    eos = ref_a[2]
    first = ref_a.index(eos)
    engine = PagedEngine(model, params, max_slots=1, block_size=8,
                         prefill_chunk=CHUNK, pool_tokens=128)
    engine.admit(0, pa, max_new_tokens=8, eos_id=eos)
    assert engine.dispatch() and engine.dispatch()
    got = []
    while True:
        out = engine.collect()
        got.extend(int(t) for t in out.tokens[0, :out.counts[0]])
        if out.finished[0]:
            break
        assert engine.dispatch()
    assert got == ref_a[:first + 1]
    # the step planned before the host knew is still in flight
    assert engine.in_flight == 1
    engine.release(0)
    engine.admit(0, pb, max_new_tokens=3)
    assert engine.dispatch()                 # B's prompt, behind it
    stale = engine.collect()
    assert stale.counts[0] == 0 and not stale.finished[0]
    got_b = []
    while True:
        out = engine.collect()
        got_b.extend(int(t) for t in out.tokens[0, :out.counts[0]])
        if out.finished[0]:
            break
        engine.dispatch()
    assert got_b == greedy_reference(
        model, params, dict(prompt=pb, max_new_tokens=3))
    engine.release(0)
    assert engine.blocks_in_use == 0

    # and through the scheduler: A stops at its EOS, C takes A's slot
    requests = [dict(prompt=pa, max_new_tokens=8, eos_id=eos),
                dict(prompt=pb, max_new_tokens=6),
                dict(prompt=pb[::-1].copy(), max_new_tokens=5)]
    engine2 = PagedEngine(model, params, max_slots=2, block_size=8,
                          prefill_chunk=CHUNK, pool_tokens=128)
    got = in_flight(engine2, requests)
    assert got[0] == ref_a[:first + 1]
    for kw, tokens in zip(requests[1:], got[1:]):
        assert tokens == greedy_reference(model, params, kw)
    assert engine2.blocks_in_use == 0 and engine2.steps_ahead > 0


def test_an_evicted_slots_row_in_flight_is_nobodys(llama):
    """Deadline expiry / ``evict`` under a step in flight, then a new
    tenant in the same slot: the old request keeps what was routed to
    it and gets nothing more, the new one gets only its own tokens,
    the pool drains."""
    model, params = llama
    rng = np.random.default_rng(13)
    pa, pb = (rng.integers(0, model.cfg.vocab_size, size=(n,)).astype(
        np.int32) for n in (6, 9))
    engine = PagedEngine(model, params, max_slots=1, block_size=8,
                         prefill_chunk=CHUNK, pool_tokens=128)
    sched = Scheduler(engine)
    ra = sched.submit(Request(prompt=pa, max_new_tokens=20))
    for _ in range(4):
        sched.run_step()
    assert engine.in_flight == 1             # A's 5th token, unfetched
    streamed = list(ra.tokens)
    assert len(streamed) == 4
    assert sched.evict(0) is ra
    assert engine.blocks_in_use == 0
    rb = sched.submit(Request(prompt=pb, max_new_tokens=5))
    sched.drain()
    assert list(ra.tokens) == streamed
    assert streamed == greedy_reference(
        model, params, dict(prompt=pa, max_new_tokens=20))[:4]
    assert list(rb.tokens) == greedy_reference(
        model, params, dict(prompt=pb, max_new_tokens=5))
    assert engine.blocks_in_use == 0 and not sched.has_work()


def _serve_refs(model, params, prompts, budget):
    return [greedy_reference(model, params,
                             dict(prompt=p, max_new_tokens=budget))
            for p in prompts]


def _server(model, params, **kw):
    return InferenceServer(model, params, max_slots=2, block_size=8,
                           prefill_chunk=CHUNK, pool_tokens=256, **kw)


def _wait_for_tokens(handles, n, timeout=120.0):
    t0 = time.monotonic()
    while any(len(h.tokens_so_far) < n for h in handles):
        assert time.monotonic() - t0 < timeout, "no tokens streamed"
        time.sleep(0.002)


@pytest.mark.parametrize("how", ["shutdown_wait", "begin_drain", "kill"])
def test_stopping_with_a_step_in_flight_loses_and_doubles_nothing(
        llama, how):
    model, params = llama
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, model.cfg.vocab_size, size=(n,)).astype(
        np.int32) for n in (5, 11, 3)]
    budget = 40
    refs = _serve_refs(model, params, prompts, budget)
    server = _server(model, params).start()
    handles = [server.submit(p, max_new_tokens=budget) for p in prompts]
    _wait_for_tokens(handles[:2], 3)
    assert server.health()["steps_ahead"] > 0
    if how == "shutdown_wait":
        server.shutdown(wait=True, timeout=300)
        for h, ref in zip(handles, refs):
            assert h.done and h.result(timeout=0) == ref
    else:
        if how == "begin_drain":
            server.begin_drain()
            want = ReplicaDraining
        else:
            server.kill()
            want = ServerClosed
        for h, ref in zip(handles, refs):
            with pytest.raises(want):
                h.result(timeout=120)
            assert h.done
            # what was streamed is a prefix of the request's chain: a
            # continuation from it loses and doubles nothing
            assert list(h.tokens_so_far) == ref[:len(h.tokens_so_far)]
        if how == "begin_drain":
            assert server.health()["blocks_in_use"] == 0
        server.shutdown(wait=False, timeout=300)
    assert not server.scheduler.has_work()
    assert server.engine.in_flight == 0
    assert server._handles == {}
    if how != "kill":
        assert server.engine.blocks_in_use == 0


def test_an_injected_step_fault_recovers_with_a_step_in_flight(llama):
    """``serving.step`` fires before the scheduler's step while the
    last one is still in flight: its tenants are evicted (their rows in
    flight are nobody's), requeued once, and finish with the tokens of
    an undisturbed run."""
    model, params = llama
    rng = np.random.default_rng(19)
    prompts = [rng.integers(0, model.cfg.vocab_size, size=(n,)).astype(
        np.int32) for n in (4, 10, 7)]
    budget = 12
    refs = _serve_refs(model, params, prompts, budget)
    plan = FaultPlan([FaultSpec(site="serving.step", kind="transient",
                                steps=(5,))])
    with active(plan):
        with _server(model, params) as server:
            handles = [server.submit(p, max_new_tokens=budget)
                       for p in prompts]
            got = [h.result(timeout=300) for h in handles]
            health = server.health()
    assert got == refs
    assert health["requeues"] >= 1 and health["failed_requests"] == 0
    assert health["blocks_in_use"] == 0
    assert health["steps_ahead"] > 0
    assert health["tokens_emitted"] == len(prompts) * budget
