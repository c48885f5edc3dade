"""The serving engine (apex_tpu.serving.PagedEngine).

Correctness contracts under test:

- greedy decode through the engine is TOKEN-IDENTICAL to
  ``generate()`` for prompt lengths straddling every boundary that
  matters (page size, chunk size, and their multiples), and for
  requests that queue behind a first wave and refill its slots;
- a steady-state soak of mixed chunked-prefill + decode traffic with
  heterogeneous sampling params triggers ZERO retraces after warmup at
  the EXACT documented budget — decode_step/prefill_step/admit/release
  = 1 each (every prompt length rides one mixed-step shape);
- the block allocator: fragmentation-tolerant reuse, atomic
  exhaustion, double-free detection, the reserved null page;
- token-budget admission (free pages must cover prompt + headroom)
  and block-exhaustion preemption that requeues the evicted tenant to
  continue from its streamed prefix — with the greedy chain still
  token-identical end to end;
- eviction releases pages (deadline/fault paths reuse the same
  release), sampled chains are a function of the request's own seed,
  and the server surfaces TTFT / step-latency percentiles and the
  blocks-occupancy gauge;
- copy-on-write prefix sharing (ISSUE 7): refcounted page sharing of
  trie-matched prompt prefixes, CoW fork at whole-prompt hits, exact
  ``blocks_in_use`` accounting under sharing, shared-aware admission,
  and greedy chains token-identical with sharing on;
- speculative decoding (ISSUE 7): the prompt-lookup drafter, the
  one-application K-token verify, acceptance-invariant greedy AND
  sampled chains, the accept-rate gauge, and the 5-executable /
  zero-retrace budget with drafting on;
- quantized KV pages (ISSUE 8): ``kv_dtype="int8"``/``"fp8"`` pool
  storage with per-(kv_head, page) amax scales — the ≥1.9× equal-HBM
  capacity default, scale reset on page reuse (deterministic replay on
  a dirty pool), sharing/CoW/spec riding quantized pages
  token-identically to an unshared quantized run, the 5×1 trace budget
  with quantization on, kv_dtype/kv_bits in health()+metrics, the
  "auto" pair pickup from the autotune table, and (slow tier) ≥95%
  greedy token agreement vs ``generate()`` on a trained proxy.
  ``kv_dtype=None`` byte-identity is pinned by this whole module: every
  other test here runs the default unquantized pool through the same
  code path.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.models import GPTConfig, GPTModel, LlamaConfig, LlamaModel, generate
from apex_tpu.serving import (
    BlockAllocator,
    BlockExhausted,
    InferenceServer,
    PagedEngine,
    PrefixTrie,
    Request,
    Scheduler,
    chain_digests,
    prompt_lookup_draft,
)
from apex_tpu.serving import cache as slot_cache
from apex_tpu.utils import MetricsWriter, tracecheck


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


class TestBlockAllocator:
    def test_null_page_reserved_and_sizes(self):
        alloc = BlockAllocator(9, 4)
        assert alloc.blocks_total == 8
        assert alloc.tokens_total == 32
        got = alloc.alloc(8)
        assert 0 not in got and len(set(got)) == 8
        assert alloc.blocks_free == 0

    def test_fragmented_interleave_reuses_everything(self):
        """Interleaved alloc/free in awkward sizes: a paged pool has
        no fragmentation — any n <= free succeeds regardless of WHICH
        pages were returned."""
        alloc = BlockAllocator(17, 8)
        a = alloc.alloc(5)
        b = alloc.alloc(7)
        alloc.free(a[1:4])          # punch holes
        c = alloc.alloc(3)          # reuses the holes
        assert set(c) == set(a[1:4])
        alloc.free(b)
        alloc.free(c)
        alloc.free([a[0], a[4]])
        assert alloc.blocks_free == alloc.blocks_total == 16
        assert set(alloc.alloc(16)) == set(range(1, 17))

    def test_exhaustion_is_atomic(self):
        alloc = BlockAllocator(5, 2)
        alloc.alloc(3)
        with pytest.raises(BlockExhausted):
            alloc.alloc(2)
        # the failed alloc took nothing
        assert alloc.blocks_free == 1
        assert alloc.alloc(1)

    def test_double_free_and_bad_range_raise(self):
        alloc = BlockAllocator(5, 2)
        got = alloc.alloc(2)
        alloc.free(got)
        with pytest.raises(ValueError, match="double free"):
            alloc.free([got[0]])
        with pytest.raises(ValueError, match="range"):
            alloc.free([0])

    def test_blocks_for(self):
        assert slot_cache.blocks_for(1, 8) == 1
        assert slot_cache.blocks_for(8, 8) == 1
        assert slot_cache.blocks_for(9, 8) == 2


class TestGreedyParityAcrossBoundaries:
    # [the llama twin is slow-marked: ~40s of CPU compile for the same
    # engine property the gpt twin pins in tier-1 (GQA decode parity
    # is separately tier-1-covered by test_generate's incremental
    # suites); it still runs under -m slow and in the on-chip pass]
    @pytest.mark.l0
    @pytest.mark.parametrize("which", [
        "gpt", pytest.param("llama", marks=pytest.mark.slow)])
    @pytest.mark.parametrize("slots,chunk,lengths,budgets", [
        (3, 4, (7, 8, 9, 3, 4, 5, 15, 16, 17, 23),
         (6, 3, 5, 7, 4, 8, 3, 5, 6, 4)),
        (2, 16, (3, 5, 8, 4, 11), (6, 3, 5, 7, 4)),
    ], ids=["boundaries", "queued_refill"])
    def test_engine_matches_generate(self, which, slots, chunk,
                                     lengths, budgets, request):
        """block_size=8.  ``boundaries`` (chunk 4): prompt lengths
        straddle the page boundary (7/8/9), the chunk boundary
        (3/4/5), their common multiples (15/16/17) and a multi-page
        prompt (23).  ``queued_refill`` (chunk 16, every prompt one
        chunk): five requests through two slots, three of them queued
        behind the first wave and admitted as slots come free.  Every
        chain must reproduce generate() exactly."""
        model, params = request.getfixturevalue(which)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, model.cfg.vocab_size,
                                size=(L,)).astype(np.int32)
                   for L in lengths]
        engine = PagedEngine(model, params, max_slots=slots,
                             block_size=8, prefill_chunk=chunk)
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
        assert engine.blocks_in_use == 0

    def test_tenant_near_max_seq_len_survives_cotenant_prefill(self):
        """Regression (review finding): a tenant decoding within one
        chunk of max_seq_len rides a WIDE mixed step when a co-tenant
        chunk-prefills; its pad positions past max_seq_len must land
        in the null page, NOT wrap into its last live block (the old
        clamp overwrote visible K/V and flipped late greedy tokens)."""
        import dataclasses

        cfg = dataclasses.replace(
            GPTConfig.tiny(position_embedding="learned",
                           scan_layers=True), max_seq_len=16)
        model = GPTModel(cfg)
        params = {"params": model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 4), jnp.int32))["params"]}
        rng = np.random.default_rng(5)
        pa = rng.integers(0, cfg.vocab_size, size=(2,)).astype(np.int32)
        ref_a = np.asarray(generate(
            model, params, jnp.asarray(pa[None]),
            max_new_tokens=14))[0, 2:]          # fills the cache: 2+14=16
        engine = PagedEngine(model, params, max_slots=2, block_size=8,
                             prefill_chunk=4)
        sched = Scheduler(engine)
        ra = sched.submit(Request(prompt=pa, max_new_tokens=14))
        for _ in range(10):                     # decode A near the end
            sched.run_step()
        pb = rng.integers(0, cfg.vocab_size,
                          size=(10,)).astype(np.int32)
        rb = sched.submit(Request(prompt=pb, max_new_tokens=2))
        sched.drain()
        np.testing.assert_array_equal(np.asarray(ra.tokens), ref_a)
        ref_b = np.asarray(generate(
            model, params, jnp.asarray(pb[None]),
            max_new_tokens=2))[0, 10:]
        np.testing.assert_array_equal(np.asarray(rb.tokens), ref_b)

    @pytest.mark.parametrize("plen", [9, 5], ids=[
        "across_a_page", "inside_a_page"])
    def test_eos_stops_early_and_matches_generate(self, gpt, plen):
        model, params = gpt
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, model.cfg.vocab_size,
                              size=(plen,)).astype(np.int32)
        n = 8
        ref = np.asarray(generate(
            model, params, jnp.asarray(prompt[None]),
            max_new_tokens=n))[0, plen:]
        eos = int(ref[2])            # force a stop three tokens in
        engine = PagedEngine(model, params, max_slots=1, block_size=8,
                             prefill_chunk=4)
        sched = Scheduler(engine)
        req = sched.submit(Request(prompt=prompt, max_new_tokens=n,
                                   eos_id=eos))
        sched.drain()
        got = np.asarray(req.tokens)
        # the engine stops AT the produced eos; generate's chain up to
        # the first eos must match token for token
        first = int(np.argmax(ref == eos))
        np.testing.assert_array_equal(got, ref[:first + 1])
        assert got[-1] == eos and len(got) < n


class TestSoakZeroRetraces:
    @pytest.mark.parametrize("chunk", [4, 32], ids=[
        "chunked_prompts", "whole_prompts"])
    def test_mixed_chunked_prefill_decode_soak(self, gpt, chunk):
        """The acceptance soak: prefill admissions interleave with
        steady decode across 14 requests / 3 slots, mixed
        temperature / top_k / top_p / eos / budgets — zero jaxpr
        traces after warmup, and the guards pin the budget to the
        documented constants: decode_step = prefill_step = admit =
        release = 1.  At chunk 4 the longer prompts take several
        steps; at the default 32 every prompt is one chunk.  Nucleus
        (top_p) traffic rides the same executable as everything else:
        per-slot device-array params, budgets unchanged."""
        model, params = gpt
        engine = PagedEngine(model, params, max_slots=3, block_size=8,
                             prefill_chunk=chunk)
        sched = Scheduler(engine)
        engine.warmup()
        assert engine.trace_counts == {
            "decode_step": 1, "prefill_step": 1, "admit": 1,
            "release": 1}

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
            "steady-state paged soak retraced after warmup")
        assert engine.trace_counts == {
            "decode_step": 1, "prefill_step": 1, "admit": 1,
            "release": 1}
        for (L, n, t, k, eos, p), r in zip(cases, reqs):
            assert 1 <= len(r.tokens) <= n
            if eos is None:
                assert len(r.tokens) == n
        assert len(events) == sum(len(r.tokens) for r in reqs)
        assert engine.blocks_in_use == 0


class TestTokenBudgetAdmission:
    def test_can_admit_gates_on_free_pages(self, gpt):
        model, params = gpt
        engine = PagedEngine(model, params, max_slots=4, block_size=8,
                             pool_tokens=64, prefill_chunk=4,
                             admit_headroom=8)
        # empty pool: plenty of room
        assert engine.can_admit(16, 8)
        # occupy almost everything via a long tenant
        engine.admit(0, np.zeros(40, np.int32), max_new_tokens=8)
        while engine._tenants[0] is not None \
                and engine._tenants[0].fed < 40:
            engine.step()
        assert engine.blocks_in_use >= 5
        # 3 free pages (24 tokens) left: 18+8 tokens of prompt +
        # headroom need a 4th page — blocked; 16+8 exactly fits
        assert not engine.can_admit(18, 8)
        assert engine.can_admit(16, 8)
        engine.release(0)
        assert engine.blocks_in_use == 0

    def test_request_bigger_than_pool_rejected_at_submit(self, gpt):
        model, params = gpt
        engine = PagedEngine(model, params, max_slots=1, block_size=8,
                             pool_tokens=32, prefill_chunk=4)
        sched = Scheduler(engine)
        with pytest.raises(ValueError, match="pool"):
            sched.submit(Request(prompt=np.zeros(30, np.int32),
                                 max_new_tokens=10))
        # and the usual envelope checks still apply
        with pytest.raises(ValueError, match="max_seq_len"):
            engine.validate_request(8, model.cfg.max_seq_len)
        with pytest.raises(ValueError, match="top_k"):
            engine.validate_request(4, 2,
                                    top_k=model.cfg.vocab_size + 1)

    def test_occupied_slot_rejected(self, gpt):
        model, params = gpt
        engine = PagedEngine(model, params, max_slots=1, block_size=8,
                             prefill_chunk=4)
        engine.admit(0, np.zeros(4, np.int32), max_new_tokens=2)
        with pytest.raises(ValueError, match="occupied"):
            engine.admit(0, np.zeros(4, np.int32), max_new_tokens=2)


class TestPreemption:
    def test_exhaustion_preempts_requeues_and_stays_token_identical(
            self, gpt):
        """Two tenants overcommit a pool that cannot hold both live
        sequences: the youngest is preempted (pages freed), requeued,
        and continues from its streamed prefix — both greedy chains
        still match generate() token for token, and the pool drains
        to zero."""
        model, params = gpt
        engine = PagedEngine(model, params, max_slots=2, block_size=8,
                             pool_tokens=64, prefill_chunk=4,
                             admit_headroom=0)
        sched = Scheduler(engine)
        engine.warmup()
        rng = np.random.default_rng(7)
        p1 = rng.integers(0, model.cfg.vocab_size,
                          size=(20,)).astype(np.int32)
        p2 = rng.integers(0, model.cfg.vocab_size,
                          size=(22,)).astype(np.int32)
        r1 = sched.submit(Request(prompt=p1, max_new_tokens=30))
        r2 = sched.submit(Request(prompt=p2, max_new_tokens=28))
        sched.drain()
        assert sched.preempts >= 1
        for p, n, r in ((p1, 30, r1), (p2, 28, r2)):
            ref = np.asarray(generate(
                model, params, jnp.asarray(p[None]),
                max_new_tokens=n))[0, len(p):]
            np.testing.assert_array_equal(np.asarray(r.tokens), ref)
        assert engine.blocks_in_use == 0
        # recovery replays compiled programs — budgets untouched
        assert engine.trace_counts == {
            "decode_step": 1, "prefill_step": 1, "admit": 1,
            "release": 1}

    def test_eviction_releases_blocks(self, gpt):
        """scheduler.evict (the deadline/fault path) returns every
        page to the pool."""
        model, params = gpt
        engine = PagedEngine(model, params, max_slots=2, block_size=8,
                             prefill_chunk=4)
        sched = Scheduler(engine)
        sched.submit(Request(prompt=np.zeros(12, np.int32),
                             max_new_tokens=50))
        for _ in range(6):
            sched.run_step()
        assert engine.blocks_in_use >= 2
        assert sched.active_count == 1
        sched.evict(0)
        assert engine.blocks_in_use == 0
        assert sched.active_count == 0


class TestSamplingDeterminism:
    @pytest.mark.parametrize("chunk", [4, 32], ids=[
        "chunked_prompts", "whole_prompts"])
    def test_tokens_independent_of_cotenants(self, gpt, chunk):
        """A sampled request's chain is a function of its own seed —
        co-tenant traffic (and the chunked prefill it causes) must not
        perturb it: the k-th produced token always consumes the k-th
        rng split (emission-gated rng advance)."""
        model, params = gpt
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, model.cfg.vocab_size,
                              size=(6,)).astype(np.int32)

        def run(extra_traffic):
            engine = PagedEngine(model, params, max_slots=2,
                                 block_size=8, prefill_chunk=chunk)
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


class TestBatchedAdmission:
    """Admissions wait on the host and reach the device together, in
    one call of the ``admit`` executable at the next step's dispatch
    (a burst of arrivals used to cost a call each)."""

    def test_admit_slots_is_admit_slot_row_by_row(self):
        rows = {0: (5, 9, 0, -1, 123, 0.0, 0.0),
                2: (7, 3, 20, 11, 2 ** 32 - 1, 0.9, 0.95),
                3: (1, 1, 4, -1, 0, 1.3, 0.0)}
        one = slot_cache.init_slot_state(5)
        # row 1 holds an older tenant neither way may disturb
        one = slot_cache.admit_slot(one, 1, 2, 6, 0.5, 3, 0.5, 4,
                                    np.uint32(77))
        many = one
        ints = np.zeros((6, 5), np.int32)
        floats = np.zeros((2, 5), np.float32)
        for slot, (tok, budget, top_k, eos, seed, temp, top_p) in \
                rows.items():
            one = slot_cache.admit_slot(
                one, slot, tok, budget, np.float32(temp), top_k,
                np.float32(top_p), eos, np.uint32(seed))
            ints[:, slot] = (1, tok, budget, top_k, eos,
                             np.array(seed, np.uint32).view(np.int32))
            floats[:, slot] = (temp, top_p)
        many = jax.jit(slot_cache.admit_slots)(many, ints, floats)
        for name, a, b in zip(one._fields, one, many):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)

    def test_a_burst_costs_one_call_and_a_withdrawn_tenant_none(
            self, gpt):
        model, params = gpt
        engine = PagedEngine(model, params, max_slots=4, block_size=8,
                             prefill_chunk=4, pool_tokens=128)
        calls = {"admit": 0, "release": 0}
        for name in calls:
            inner = getattr(engine, "_" + name)

            def counted(*a, _inner=inner, _name=name):
                calls[_name] += 1
                return _inner(*a)

            setattr(engine, "_" + name, counted)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, model.cfg.vocab_size,
                                size=(n,)).astype(np.int32)
                   for n in (3, 6, 9)]
        for slot, prompt in enumerate(prompts):
            engine.admit(slot, prompt, max_new_tokens=4)
        assert calls["admit"] == 0          # nothing has reached the device
        engine.admit(3, prompts[0], max_new_tokens=4)
        engine.release(3)                   # withdrawn before any step
        assert calls == {"admit": 0, "release": 0}
        chains = [[] for _ in prompts]
        live = set(range(3))
        while live:
            out = engine.step()
            for slot in sorted(live):
                chains[slot].extend(
                    int(t) for t in out.tokens[slot, :out.counts[slot]])
                if out.finished[slot]:
                    engine.release(slot)
                    live.discard(slot)
        assert calls == {"admit": 1, "release": 3}
        for prompt, chain in zip(prompts, chains):
            want = generate(model, params, jnp.asarray(prompt[None]),
                            max_new_tokens=4)
            assert chain == [int(t) for t in
                             np.asarray(want)[0, prompt.size:]]


class TestPackedDispatch:
    """Every host array handed to a step is a transfer of its own, so a
    step of any kind is handed ONE: block tables, cursors, feed,
    ``n_tokens`` and the flag rows in one int32 array that the step
    takes apart again."""

    @pytest.mark.parametrize("spec", [0, 2])
    def test_a_step_is_handed_one_host_array(self, gpt, spec):
        model, params = gpt
        engine = PagedEngine(model, params, max_slots=3, block_size=8,
                             prefill_chunk=4, pool_tokens=128,
                             spec_tokens=spec)
        seen = []
        for name in ("_decode", "_prefill", "_spec"):
            inner = getattr(engine, name)

            def recorded(*a, _inner=inner, _name=name):
                seen.append((_name, a[3:]))
                return _inner(*a)

            setattr(engine, name, recorded)
        prompt = np.tile(np.arange(1, 4, dtype=np.int32), 3)
        engine.admit(1, prompt, max_new_tokens=6)
        chain = []
        while True:
            out = engine.step()
            chain.extend(int(t) for t in out.tokens[1, :out.counts[1]])
            if out.finished[1]:
                break
        want = generate(model, params, jnp.asarray(prompt[None]),
                        max_new_tokens=6)
        assert chain == [int(t) for t in np.asarray(want)[0, prompt.size:]]
        slots, pages = engine._tables.shape
        widths = {"_decode": (1, 2), "_prefill": (4, 2),
                  "_spec": (1 + spec, 1)}
        assert {name for name, _ in seen} >= {"_decode", "_prefill"}
        for name, host in seen:
            (packed,) = host
            w, flags = widths[name]
            assert isinstance(packed, np.ndarray)
            assert packed.dtype == np.int32
            assert packed.shape == (slots * (pages + 2 + w + flags),)
        # the layout: tables, cursors, feed, n_tokens, then the flags
        feed = np.arange(slots * 4, dtype=np.int32).reshape(slots, 4)
        n = np.array([4, 2, 1], np.int32)
        on, off = np.ones(slots, bool), np.zeros(slots, bool)
        packed = engine._packed(feed, n, on, off)
        parts = np.split(packed, np.cumsum(
            [slots * pages, slots, slots * 4, slots, slots]))
        for got, want in zip(parts, (engine._tables, engine._cursors,
                                     feed, n, on, off)):
            np.testing.assert_array_equal(got, want.reshape(-1))


class TestPagedServer:
    def test_streaming_parity_metrics_and_gauges(self, gpt):
        model, params = gpt
        rng = np.random.default_rng(13)
        prompt = rng.integers(0, model.cfg.vocab_size,
                              size=(9,)).astype(np.int32)
        ref = np.asarray(generate(
            model, params, jnp.asarray(prompt[None]),
            max_new_tokens=5))[0, 9:]
        rows = []
        writer = MetricsWriter(sink=lambda s, m: rows.append((s, m)))
        server = InferenceServer(
            model, params, max_slots=2, block_size=8,
            prefill_chunk=4, metrics=writer, metrics_interval=2)
        with server:
            h1 = server.submit(prompt, max_new_tokens=5)
            h2 = server.submit(
                rng.integers(0, model.cfg.vocab_size, size=(6,)),
                max_new_tokens=3, temperature=0.8, seed=4)
            got = h1.result(timeout=300)
            assert len(h2.result(timeout=300)) == 3
            health = server.health()
        np.testing.assert_array_equal(np.asarray(got), ref)
        # the occupancy gauge and latency percentiles ride health +
        # every metrics emission
        assert health["blocks_total"] == server.engine.blocks_total
        assert health["blocks_in_use"] == 0
        assert health["preempts"] == 0
        assert rows, "metrics never emitted"
        merged = {}
        for _, m in rows:
            merged.update(m)
        assert {"tokens_per_sec", "occupancy", "queue_depth",
                "blocks_in_use", "blocks_total", "ttft_p50_s",
                "ttft_p99_s", "step_ms_p50",
                "step_ms_p99"} <= set(merged)
        assert merged["ttft_p50_s"] > 0
        summary = server.latency_summary()
        assert summary["ttft_p99_s"] >= summary["ttft_p50_s"]

    def test_a_follow_up_request_rides_the_very_next_step(
            self, gpt, monkeypatch):
        """After a step that finished a request the worker waits (at
        most ``FOLLOW_UP_S``) for the client's next request: it is
        admitted by the next step, not the one after.  The wait is
        made long here so that only the submit's notify can end it."""
        import threading

        from apex_tpu.serving import api

        monkeypatch.setattr(api, "FOLLOW_UP_S", 30.0)
        model, params = gpt
        rng = np.random.default_rng(5)
        server = InferenceServer(
            model, params, max_slots=2, block_size=8,
            prefill_chunk=4)
        done, seen = threading.Event(), {}

        def last_token(token, finished, error):
            if finished:
                seen["finished_at_step"] = server._steps
                done.set()

        def first_token(token, finished, error):
            seen.setdefault("first_at_step", server._steps)

        with server:
            server.submit(rng.integers(0, model.cfg.vocab_size, size=(5,)),
                          max_new_tokens=3, tap=last_token)
            assert done.wait(300)
            t0 = time.monotonic()
            # one chunk: its first token comes out of its first step
            follow = server.submit(
                rng.integers(0, model.cfg.vocab_size, size=(3,)),
                max_new_tokens=2, tap=first_token)
            assert len(follow.result(timeout=300)) == 2
            waited = time.monotonic() - t0
        assert seen["first_at_step"] == seen["finished_at_step"] + 1
        assert waited < 20.0          # the notify ended the wait

    def test_invalid_kv_cache_rejected(self, gpt):
        """One engine, one value: anything but "paged" is refused, and
        "dense" is told where its engine went."""
        model, params = gpt
        with pytest.raises(ValueError, match="kv_cache"):
            InferenceServer(model, params, kv_cache="sparse")
        with pytest.raises(ValueError,
                           match="dense slab engine was removed in PR 34"):
            InferenceServer(model, params, kv_cache="dense")
        server = InferenceServer(model, params, kv_cache="paged")
        assert type(server.engine) is PagedEngine


class TestTrafficModel:
    def test_serving_traffic_model_scales_with_live_tokens(self):
        """The analytic per-step KV traffic model (bench_configs):
        dense bytes pinned at max_seq_len, paged bytes ∝ live pages;
        the paged pool footprint is sized in tokens."""
        import bench_configs as bc

        cfg = dict(num_layers=4, kv_heads=2, head_dim=64,
                   max_seq_len=2048, dtype_bytes=2, slots=8,
                   block_size=16)
        small = bc._serving_traffic_model(live_tokens=128, **cfg)
        big = bc._serving_traffic_model(live_tokens=512, **cfg)
        for out in (small, big):
            assert {"dense_kv_read_bytes_per_step",
                    "paged_kv_read_bytes_per_step",
                    "dense_pool_bytes", "paged_pool_tokens"} <= set(out)
        # dense per-step reads are live-independent; paged scale ~4x
        assert small["dense_kv_read_bytes_per_step"] \
            == big["dense_kv_read_bytes_per_step"]
        ratio = (big["paged_kv_read_bytes_per_step"]
                 / small["paged_kv_read_bytes_per_step"])
        assert 3.5 <= ratio <= 4.5
        assert small["paged_kv_read_bytes_per_step"] \
            < small["dense_kv_read_bytes_per_step"]

    def test_quantized_kv_capacity_and_read_bytes(self):
        """ISSUE-8 keys: at int8 the same HBM holds >= 1.9x the tokens
        (scales INCLUDED — from 2-byte storage it lands just under
        2.0x, the scale tax), per-step quantized reads count the scale
        traffic, and kv_dtype=None leaves the dict unchanged."""
        import bench_configs as bc

        cfg = dict(num_layers=4, kv_heads=2, head_dim=64,
                   max_seq_len=2048, dtype_bytes=2, slots=8,
                   block_size=16, live_tokens=256)
        plain = bc._serving_traffic_model(**cfg)
        quant = bc._serving_traffic_model(**cfg, kv_dtype="int8")
        assert "kv_dtype" not in plain
        mult = quant["quantized_capacity_multiplier"]
        assert 1.9 <= mult < 2.0       # bf16 -> int8, scale tax real
        assert quant["paged_pool_tokens_at_equal_hbm"] \
            >= 1.9 * quant["paged_pool_tokens"]
        # quantized reads: half the page bytes plus the scale scalars
        assert quant["paged_kv_read_bytes_per_step_quantized"] \
            > quant["paged_kv_read_bytes_per_step"] // 2
        assert quant["paged_kv_read_bytes_per_step_quantized"] \
            < quant["paged_kv_read_bytes_per_step"]
        # unchanged keys stay byte-identical with the flag off
        assert {k: v for k, v in quant.items()
                if k in plain} == plain
        with pytest.raises(ValueError, match="kv_dtype"):
            bc._serving_traffic_model(**cfg, kv_dtype="int4")


class TestRefcountedAllocator:
    def test_incref_defers_free_and_counts_sharing(self):
        alloc = BlockAllocator(9, 4)
        a = alloc.alloc(2)
        assert alloc.refcount(a[0]) == 1
        assert alloc.incref(a[0]) == 2
        assert alloc.shared_blocks == 1
        assert alloc.blocks_saved == 1
        # first free decrements; the page stays allocated
        assert alloc.free([a[0]]) == []
        assert alloc.blocks_in_use == 2
        assert alloc.shared_blocks == 0
        # last reference frees for real, and is reported
        assert alloc.free([a[0]]) == [a[0]]
        assert alloc.blocks_in_use == 1
        assert alloc.free([a[1]]) == [a[1]]
        assert alloc.blocks_in_use == 0

    def test_double_free_still_raises_under_refcounts(self):
        alloc = BlockAllocator(5, 2)
        got = alloc.alloc(1)
        alloc.free(got)
        with pytest.raises(ValueError, match="double free"):
            alloc.free(got)

    def test_incref_of_free_page_raises(self):
        alloc = BlockAllocator(5, 2)
        got = alloc.alloc(1)
        alloc.free(got)
        with pytest.raises(ValueError, match="not allocated"):
            alloc.incref(got[0])


class TestPrefixTrie:
    def test_chain_digests_identify_whole_prefixes(self):
        a = np.arange(20, dtype=np.int32)
        b = a.copy()
        b[10] += 1                       # diverge inside block 1
        da, db = chain_digests(a, 8), chain_digests(b, 8)
        assert len(da) == len(db) == 2   # only FULL blocks hash
        assert da[0] == db[0]
        assert da[1] != db[1]
        # chaining: same block tokens after a divergent block differ
        c = np.concatenate([b[:8], a[8:]])
        dc = chain_digests(c, 8)
        assert dc[0] == da[0] and dc[1] == da[1]

    def test_register_match_forget(self):
        trie = PrefixTrie()
        d = chain_digests(np.arange(24, dtype=np.int32), 8)
        assert trie.register(d[0], 5)
        assert trie.register(d[1], 9)
        assert not trie.register(d[0], 7)    # first writer wins
        assert trie.match(d) == [5, 9]       # longest-prefix hit
        trie.forget(9)
        assert trie.match(d) == [5]
        assert not trie.holds_block(9) and trie.holds_block(5)
        trie.forget(9)                       # idempotent no-op
        assert len(trie) == 1


class TestPromptLookupDraft:
    def test_ngram_continuation_found(self):
        ctx = np.array([1, 2, 3, 4, 1, 2, 3], np.int32)
        np.testing.assert_array_equal(
            prompt_lookup_draft(ctx, 3), [4, 1, 2])

    def test_most_recent_match_and_fallback(self):
        # trailing [5] occurs twice: the LATER continuation wins
        ctx = np.array([5, 7, 0, 5, 9, 5], np.int32)
        np.testing.assert_array_equal(
            prompt_lookup_draft(ctx, 2, max_ngram=3), [9, 5])
        # no match anywhere -> empty (row decodes undrafted)
        assert prompt_lookup_draft(
            np.array([1, 2, 3], np.int32), 4).size == 0

    def test_k_caps_the_proposal(self):
        ctx = np.array([1, 2, 1, 2], np.int32)
        assert prompt_lookup_draft(ctx, 1).size == 1


class TestPrefixSharing:
    def test_shared_prefix_parity_gauges_and_refcounts(self, gpt):
        """Two tenants share a two-page prompt prefix: the second
        admission maps the first's pages (blocks_in_use grows by the
        PRIVATE tail only), both greedy chains match generate(), and
        the pool drains to zero."""
        model, params = gpt
        rng = np.random.default_rng(31)
        pref = rng.integers(0, model.cfg.vocab_size,
                            size=(16,)).astype(np.int32)
        pa = np.concatenate([pref, rng.integers(
            0, model.cfg.vocab_size, size=(3,)).astype(np.int32)])
        pb = np.concatenate([pref, rng.integers(
            0, model.cfg.vocab_size, size=(5,)).astype(np.int32)])
        engine = PagedEngine(model, params, max_slots=2, block_size=8,
                             prefill_chunk=4, share_prefixes=True)
        sched = Scheduler(engine)
        ra = sched.submit(Request(prompt=pa, max_new_tokens=6))
        for _ in range(6):               # A past prefill, still live
            sched.run_step()
        assert engine.trie_blocks == 2   # A's full prompt blocks
        use_before = engine.blocks_in_use
        rb = sched.submit(Request(prompt=pb, max_new_tokens=6))
        sched.run_step()
        # B's two prefix pages are MAPPED, not allocated
        assert engine.shared_blocks == 2
        assert engine.blocks_saved == 2
        assert engine.blocks_in_use <= use_before + 1
        assert engine.cow_forks == 0     # divergent tail: no fork
        sched.drain()
        for p, r in ((pa, ra), (pb, rb)):
            ref = np.asarray(generate(
                model, params, jnp.asarray(p[None]),
                max_new_tokens=6))[0, len(p):]
            np.testing.assert_array_equal(np.asarray(r.tokens), ref)
        assert engine.blocks_in_use == 0
        assert engine.shared_blocks == 0

    def test_whole_prompt_hit_cow_forks_and_stays_identical(self, gpt):
        """Page-boundary prompt fully resident in the trie: the last
        matched block is CoW-forked (re-derived private) so the
        re-fed final prompt token never writes a shared page — greedy
        output identical for both tenants."""
        model, params = gpt
        rng = np.random.default_rng(37)
        prompt = rng.integers(0, model.cfg.vocab_size,
                              size=(16,)).astype(np.int32)  # 2 pages
        engine = PagedEngine(model, params, max_slots=2, block_size=8,
                             prefill_chunk=4, share_prefixes=True)
        sched = Scheduler(engine)
        ra = sched.submit(Request(prompt=prompt, max_new_tokens=8))
        for _ in range(5):
            sched.run_step()
        rb = sched.submit(Request(prompt=prompt.copy(),
                                  max_new_tokens=8))
        sched.run_step()
        assert engine.cow_forks == 1
        assert engine.shared_blocks == 1     # block 0 shared, 1 forked
        sched.drain()
        ref = np.asarray(generate(
            model, params, jnp.asarray(prompt[None]),
            max_new_tokens=8))[0, 16:]
        np.testing.assert_array_equal(np.asarray(ra.tokens), ref)
        np.testing.assert_array_equal(np.asarray(rb.tokens), ref)
        assert engine.blocks_in_use == 0

    def test_can_admit_discounts_trie_resident_prefix(self, gpt):
        """Shared-aware token gate: a request whose prefix is resident
        admits into capacity that would block an unshared twin — the
        reclaimed pool converts into admitted occupancy."""
        model, params = gpt
        rng = np.random.default_rng(41)
        pref = rng.integers(0, model.cfg.vocab_size,
                            size=(16,)).astype(np.int32)
        engine = PagedEngine(model, params, max_slots=3, block_size=8,
                             pool_tokens=48, prefill_chunk=4,
                             admit_headroom=8, share_prefixes=True)
        sched = Scheduler(engine)
        sched.submit(Request(prompt=np.concatenate(
            [pref, rng.integers(0, model.cfg.vocab_size,
                                size=(2,)).astype(np.int32)]),
            max_new_tokens=4))
        for _ in range(6):
            sched.run_step()
        # 3 of 6 pages held; a fresh 18+8-token request needs 4 pages
        # -> blocked unshared, admitted when 2 pages are trie hits
        fresh = rng.integers(0, model.cfg.vocab_size,
                             size=(18,)).astype(np.int32)
        shared = np.concatenate([pref, fresh[:2]])
        assert not engine.can_admit(18, 8, prompt=fresh)
        assert engine.can_admit(18, 8, prompt=shared)
        assert engine.prefix_hit_blocks(shared) == 2
        assert engine.prefix_hit_blocks(fresh) == 0

    def test_preempt_requeue_reshares_and_drains(self, gpt):
        """Preemption under sharing: refcounts decrement (never
        double-free), the requeued continuation re-matches surviving
        trie pages, greedy chains stay identical, pool drains to 0."""
        model, params = gpt
        rng = np.random.default_rng(43)
        pref = rng.integers(0, model.cfg.vocab_size,
                            size=(16,)).astype(np.int32)
        p1 = np.concatenate([pref, rng.integers(
            0, model.cfg.vocab_size, size=(4,)).astype(np.int32)])
        p2 = np.concatenate([pref, rng.integers(
            0, model.cfg.vocab_size, size=(6,)).astype(np.int32)])
        engine = PagedEngine(model, params, max_slots=2, block_size=8,
                             pool_tokens=64, prefill_chunk=4,
                             admit_headroom=0, share_prefixes=True)
        sched = Scheduler(engine)
        r1 = sched.submit(Request(prompt=p1, max_new_tokens=28))
        r2 = sched.submit(Request(prompt=p2, max_new_tokens=26))
        sched.drain()
        assert sched.preempts >= 1
        for p, n, r in ((p1, 28, r1), (p2, 26, r2)):
            ref = np.asarray(generate(
                model, params, jnp.asarray(p[None]),
                max_new_tokens=n))[0, len(p):]
            np.testing.assert_array_equal(np.asarray(r.tokens), ref)
        assert engine.blocks_in_use == 0


class TestSpeculativeDecoding:
    def test_greedy_parity_across_boundaries_with_spec_on(self, gpt):
        """Draft/verify on: greedy chains must reproduce generate()
        exactly at page-boundary (8/16), chunk-boundary (4) and
        straddling prompt lengths — lookup-friendly (repetitive) and
        lookup-hostile (random) prompts alike."""
        model, params = gpt
        rng = np.random.default_rng(47)
        prompts = [np.tile(rng.integers(
            0, model.cfg.vocab_size, size=(4,)).astype(np.int32), 4)]
        for L in (4, 7, 8, 9, 16, 17):
            prompts.append(rng.integers(
                0, model.cfg.vocab_size, size=(L,)).astype(np.int32))
        engine = PagedEngine(model, params, max_slots=2, block_size=8,
                             prefill_chunk=4, spec_tokens=3)
        sched = Scheduler(engine)
        reqs = [sched.submit(Request(prompt=p, max_new_tokens=6))
                for p in prompts]
        sched.drain()
        for p, r in zip(prompts, reqs):
            ref = np.asarray(generate(
                model, params, jnp.asarray(p[None]),
                max_new_tokens=6))[0, len(p):]
            np.testing.assert_array_equal(
                np.asarray(r.tokens), ref,
                err_msg=f"prompt_len={len(p)}")
        assert engine.spec_proposed > 0      # drafting actually ran
        assert engine.blocks_in_use == 0

    def test_sampled_chains_are_acceptance_invariant(self, gpt):
        """temperature>0: the k-th produced token always consumes the
        k-th rng split, so the SAME seeded chain comes out with
        drafting off, with an ORACLE drafter (every draft accepted —
        multi-token emissions), and with a hostile drafter (every
        draft rejected — pure rollback)."""
        model, params = gpt
        rng = np.random.default_rng(53)
        prompt = np.tile(rng.integers(
            0, model.cfg.vocab_size, size=(5,)).astype(np.int32), 3)

        def run(k, drafter=None):
            engine = PagedEngine(model, params, max_slots=1,
                                 block_size=8, prefill_chunk=4,
                                 spec_tokens=k)
            if drafter is not None:
                engine._drafter = drafter
            sched = Scheduler(engine)
            req = sched.submit(Request(
                prompt=prompt, max_new_tokens=7, temperature=0.9,
                top_k=20, seed=123))
            sched.drain()
            assert engine.blocks_in_use == 0
            return (list(req.tokens), engine.spec_proposed,
                    engine.spec_accepted)

        base, _, _ = run(0)

        def oracle(context, k, ngram):
            # proposes the chain the model is about to sample
            pos = context.size - prompt.size
            return np.asarray(base[pos:pos + k], np.int32)

        def hostile(context, k, ngram):
            tok = (int(context[-1]) + 1) % model.cfg.vocab_size
            return np.full((k,), tok, np.int32)

        toks, proposed, accepted = run(3, oracle)
        assert toks == base
        assert proposed > 0 and accepted > 0   # multi-emit steps ran
        toks, proposed, accepted = run(3, hostile)
        assert toks == base
        assert proposed > 0                    # rollbacks ran

    def test_eos_inside_accepted_run_stops_exactly(self, gpt):
        """An accepted draft that samples eos mid-run must truncate
        the emission at eos — byte-for-byte the sequential stop."""
        model, params = gpt
        rng = np.random.default_rng(59)
        prompt = np.tile(rng.integers(
            0, model.cfg.vocab_size, size=(3,)).astype(np.int32), 4)
        ref = np.asarray(generate(
            model, params, jnp.asarray(prompt[None]),
            max_new_tokens=8))[0, len(prompt):]
        eos = int(ref[3])
        engine = PagedEngine(model, params, max_slots=1, block_size=8,
                             prefill_chunk=4, spec_tokens=4)
        sched = Scheduler(engine)
        req = sched.submit(Request(prompt=prompt, max_new_tokens=8,
                                   eos_id=eos))
        sched.drain()
        got = np.asarray(req.tokens)
        first = int(np.argmax(ref == eos))
        np.testing.assert_array_equal(got, ref[:first + 1])
        assert got[-1] == eos
        assert engine.blocks_in_use == 0

    def test_soak_sharing_and_spec_zero_retraces_at_budget(self, gpt):
        """The ISSUE-7 acceptance soak: mixed shared/unshared AND
        drafted/undrafted traffic with heterogeneous sampling params
        — zero retraces after warmup at the documented budget of FIVE
        executables (decode/prefill/spec/admit/release = 1 each), and
        the accept-rate gauge moves."""
        model, params = gpt
        engine = PagedEngine(model, params, max_slots=3, block_size=8,
                             prefill_chunk=4, share_prefixes=True,
                             spec_tokens=3)
        sched = Scheduler(engine)
        engine.warmup()
        budget = {"decode_step": 1, "prefill_step": 1, "spec_step": 1,
                  "admit": 1, "release": 1}
        assert engine.trace_counts == budget

        rng = np.random.default_rng(61)
        pref = rng.integers(0, model.cfg.vocab_size,
                            size=(16,)).astype(np.int32)
        before = tracecheck.trace_event_count()
        reqs = []
        for i in range(10):
            if i % 2 == 0:      # hot-prompt traffic (shared, lookupy)
                prompt = np.concatenate([pref, rng.integers(
                    0, model.cfg.vocab_size,
                    size=(1 + i // 2,)).astype(np.int32)])
            else:               # cold random traffic
                prompt = rng.integers(
                    0, model.cfg.vocab_size,
                    size=(3 + i,)).astype(np.int32)
            t, k, p = [(0.0, None, None), (0.8, 20, None),
                       (1.2, 5, 0.9)][i % 3]
            reqs.append(sched.submit(Request(
                prompt=prompt, max_new_tokens=3 + i % 4,
                temperature=t, top_k=k, top_p=p, seed=i)))
        sched.drain()
        assert tracecheck.trace_event_count() == before, (
            "sharing+spec soak retraced after warmup")
        assert engine.trace_counts == budget
        for r in reqs:
            assert len(r.tokens) == r._budget0
        assert engine.spec_proposed > 0
        assert 0.0 <= engine.spec_accept_rate <= 1.0
        assert engine.blocks_in_use == 0
        assert engine.shared_blocks == 0

    def test_server_knobs_and_gauges(self, gpt):
        """InferenceServer plumbs the knobs through and surfaces the
        new gauges in health() and metrics emissions."""
        model, params = gpt
        rng = np.random.default_rng(67)
        pref = rng.integers(0, model.cfg.vocab_size,
                            size=(16,)).astype(np.int32)
        rows = []
        writer = MetricsWriter(sink=lambda s, m: rows.append((s, m)))
        server = InferenceServer(
            model, params, max_slots=2, block_size=8,
            prefill_chunk=4, share_prefixes=True, spec_tokens=3,
            metrics=writer, metrics_interval=2)
        prompt = np.tile(pref[:4], 4).astype(np.int32)
        ref = np.asarray(generate(
            model, params, jnp.asarray(prompt[None]),
            max_new_tokens=6))[0, len(prompt):]
        with server:
            h1 = server.submit(prompt, max_new_tokens=6)
            h2 = server.submit(np.concatenate([pref, pref[:1]]),
                               max_new_tokens=4)
            got = h1.result(timeout=300)
            h2.result(timeout=300)
            health = server.health()
            assert server.prefix_hit_blocks(pref) >= 0
        np.testing.assert_array_equal(np.asarray(got), ref)
        assert {"shared_blocks", "cow_forks",
                "spec_accept_rate"} <= set(health)
        assert health["blocks_in_use"] == 0
        merged = {}
        for _, m in rows:
            merged.update(m)
        assert {"shared_blocks", "cow_forks",
                "spec_accept_rate"} <= set(merged)


class TestQuantizedKV:
    """ISSUE 8: int8/fp8 paged KV pool with per-(kv_head, page) amax
    scales riding the cache beside the block table."""

    def test_kv_dtype_validation_is_loud(self, gpt):
        model, params = gpt
        import dataclasses

        from apex_tpu.models import GPTConfig

        with pytest.raises(ValueError, match="paged"):
            dataclasses.replace(model.cfg, kv_dtype="int8")
        with pytest.raises(ValueError, match="kv_dtype"):
            dataclasses.replace(
                model.cfg, kv_cache="paged", kv_block_size=8,
                kv_pool_blocks=4, kv_dtype="int4")
        with pytest.raises(ValueError, match="kv_dtype"):
            InferenceServer(model, params, kv_dtype="int4")
        with pytest.raises(ValueError, match="kv_dtype"):
            PagedEngine(model, params, kv_dtype="int4")

    def test_equal_hbm_default_pool_capacity_at_least_1p9x(self, gpt):
        """The quantized engine's default pool converts the byte
        budget of max_slots × max_seq_len unquantized tokens into
        quantized tokens, SCALES INCLUDED:
        ≥1.9× the unquantized token capacity at int8 (~3.9× here —
        the fp32 test model stores 4-byte K/V unquantized)."""
        model, params = gpt
        base = PagedEngine(model, params, max_slots=2, block_size=8)
        quant = PagedEngine(model, params, max_slots=2, block_size=8,
                            kv_dtype="int8")
        assert quant.kv_bits == 8 and base.kv_bits == 32
        ratio = quant.pool_tokens / base.pool_tokens
        assert ratio >= 1.9, ratio
        # ... and the scale overhead was actually charged: the pool is
        # strictly smaller than a scale-free itemsize conversion
        assert quant.pool_tokens < base.pool_tokens * 4
        # an EXPLICIT pool_tokens is never silently rescaled
        pinned = PagedEngine(model, params, max_slots=2, block_size=8,
                             pool_tokens=64, kv_dtype="int8")
        assert pinned.pool_tokens == 64

    def test_page_reuse_resets_scales_deterministically(self, gpt):
        """Replay the same request on a DIRTY pool (pages + scales
        left by a released tenant): the first write of each reused
        page resets its scale, so the second chain is token-identical
        to the first — stale scales never leak into fresh tenants."""
        model, params = gpt
        rng = np.random.default_rng(71)
        engine = PagedEngine(model, params, max_slots=2, block_size=8,
                             prefill_chunk=4, kv_dtype="int8")
        sched = Scheduler(engine)
        prompts = [rng.integers(0, model.cfg.vocab_size,
                                size=(L,)).astype(np.int32)
                   for L in (7, 12)]

        def wave():
            reqs = [sched.submit(Request(prompt=p, max_new_tokens=6))
                    for p in prompts]
            sched.drain()
            assert engine.blocks_in_use == 0
            return [list(r.tokens) for r in reqs]

        first = wave()
        assert wave() == first

    def test_pad_lane_content_never_touches_page_scales(self, gpt):
        """Mixed-step pad lanes (>= the row's chunk_lens) route to the
        null page: live page scales AND codes are bitwise invariant to
        pad content.  Without the routing, a pad lane's K/V amax would
        scatter-MAX into the row's current page scale and stick
        forever (the running amax is monotone), so a tenant's page
        codes would depend on what garbage happened to ride beside it
        — breaking the scales-are-a-pure-function-of-the-row's-tokens
        invariant that shared/CoW pages rely on."""
        model, params = gpt
        import dataclasses

        from apex_tpu.models.generate import apply_decode, cache_shapes
        cfg = dataclasses.replace(
            model.cfg, kv_cache="paged", kv_block_size=8,
            kv_pool_blocks=6, kv_dtype="int8")
        paged = type(model)(cfg=cfg)
        shapes = cache_shapes(paged, 1)
        base = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            shapes)
        mb = slot_cache.blocks_for(cfg.max_seq_len, 8)
        tables = np.zeros((1, mb), np.int32)
        tables[0, 0] = 1                 # one live page for the row

        def leaves(tree, name):
            return [np.asarray(leaf) for path, leaf
                    in jax.tree_util.tree_flatten_with_path(tree)[0]
                    if slot_cache._leaf_name(path) == name]

        def run(pad_id):
            # 2 real tokens + 2 pad lanes of width-4 mixed step
            ids = np.full((1, 4), pad_id, np.int32)
            ids[0, :2] = (3, 5)
            cache = slot_cache.set_paged_leaves(
                base, tables, np.zeros((1,), np.int32),
                np.array([2], np.int32))
            logits, cache = apply_decode(
                paged, params, cache, jnp.asarray(ids))
            return np.asarray(logits[:, :2]), cache

        ref_logits, ref_cache = run(0)
        got_logits, got_cache = run(int(model.cfg.vocab_size) - 1)
        np.testing.assert_array_equal(got_logits, ref_logits)
        for name in ("key_scales", "value_scales"):
            for ref, got in zip(leaves(ref_cache, name),
                                leaves(got_cache, name)):
                # every page but the null page (0) is bitwise pinned
                np.testing.assert_array_equal(got[..., 1:],
                                              ref[..., 1:])
        for name in ("paged_key", "paged_value"):
            for ref, got in zip(leaves(ref_cache, name),
                                leaves(got_cache, name)):
                np.testing.assert_array_equal(got[..., 1:, :, :],
                                              ref[..., 1:, :, :])

    def test_sharing_cow_and_spec_ride_quantized_pages(self, gpt):
        """Shared prefix pages, a CoW fork, and drafted steps on the
        int8 pool: a tenant reading pages another tenant wrote must
        emit the SAME chain as running alone on a fresh quantized
        engine with the same knobs (prefill chunking and drafting are
        deterministic per row, so page codes and scales are a pure
        function of the row's own token/draft history — co-tenants
        never touch them), and the pool drains with refcounts
        balanced.  The solo twin keeps spec ON: under quantization a
        REJECTED draft's amax legitimately stays in the page's
        monotone running scale (write-then-attend writes draft K/V
        before acceptance is known), so spec-on and spec-off quantized
        chains agree only within the accuracy band, not bitwise — the
        documented drift class of rescale-on-append."""
        model, params = gpt
        rng = np.random.default_rng(73)
        pref = rng.integers(0, model.cfg.vocab_size,
                            size=(16,)).astype(np.int32)
        pa = np.concatenate([pref, rng.integers(
            0, model.cfg.vocab_size, size=(3,)).astype(np.int32)])
        pb = np.concatenate([pref, rng.integers(
            0, model.cfg.vocab_size, size=(5,)).astype(np.int32)])

        solo_eng = PagedEngine(model, params, max_slots=1,
                               block_size=8, prefill_chunk=4,
                               spec_tokens=3, kv_dtype="int8")
        solo_sched = Scheduler(solo_eng)

        def solo(prompt, n):
            # ONE reused engine (compile budget): the pool drains
            # between waves and scale reset handles the dirty pages
            r = solo_sched.submit(Request(prompt=prompt,
                                          max_new_tokens=n))
            solo_sched.drain()
            assert solo_eng.blocks_in_use == 0
            return list(r.tokens)

        engine = PagedEngine(model, params, max_slots=2, block_size=8,
                             prefill_chunk=4, share_prefixes=True,
                             spec_tokens=3, kv_dtype="int8")
        sched = Scheduler(engine)
        # budget large enough that A (multi-token spec emissions) is
        # still LIVE when B arrives — a freed tenant's last-ref pages
        # leave the trie with it
        ra = sched.submit(Request(prompt=pa, max_new_tokens=14))
        for _ in range(6):               # A past prefill, still live
            sched.run_step()
        assert engine.trie_blocks == 2
        rb = sched.submit(Request(prompt=pb, max_new_tokens=6))
        sched.run_step()
        assert engine.shared_blocks == 2     # B mapped A's prefix
        # whole-prompt trie hit (16 = exactly 2 pages): CoW-forks the
        # last matched block on the quantized pool
        rc = sched.submit(Request(prompt=pref.copy(),
                                  max_new_tokens=6))
        sched.drain()
        assert engine.cow_forks >= 1
        assert list(ra.tokens) == solo(pa, 14)
        assert list(rb.tokens) == solo(pb, 6)
        assert list(rc.tokens) == solo(pref, 6)
        assert engine.spec_proposed > 0
        assert engine.blocks_in_use == 0
        assert engine.shared_blocks == 0

    def test_soak_quantized_sharing_spec_zero_retraces_at_budget(
            self, gpt):
        """The ISSUE-8 trace-discipline soak: quantization on TOP of
        sharing + drafting + heterogeneous sampling stays at exactly
        FIVE executables × 1 trace with zero retraces after warmup —
        the scale maintenance lives inside the existing step
        executables, it adds none."""
        model, params = gpt
        engine = PagedEngine(model, params, max_slots=3, block_size=8,
                             prefill_chunk=4, share_prefixes=True,
                             spec_tokens=3, kv_dtype="int8")
        sched = Scheduler(engine)
        engine.warmup()
        budget = {"decode_step": 1, "prefill_step": 1, "spec_step": 1,
                  "admit": 1, "release": 1}
        assert engine.trace_counts == budget

        rng = np.random.default_rng(79)
        pref = rng.integers(0, model.cfg.vocab_size,
                            size=(16,)).astype(np.int32)
        before = tracecheck.trace_event_count()
        reqs = []
        for i in range(8):
            if i % 2 == 0:
                prompt = np.concatenate([pref, rng.integers(
                    0, model.cfg.vocab_size,
                    size=(1 + i // 2,)).astype(np.int32)])
            else:
                prompt = rng.integers(
                    0, model.cfg.vocab_size,
                    size=(3 + i,)).astype(np.int32)
            t, k, p = [(0.0, None, None), (0.8, 20, None),
                       (1.2, 5, 0.9)][i % 3]
            reqs.append(sched.submit(Request(
                prompt=prompt, max_new_tokens=3 + i % 4,
                temperature=t, top_k=k, top_p=p, seed=i)))
        sched.drain()
        assert tracecheck.trace_event_count() == before, (
            "quantized sharing+spec soak retraced after warmup")
        assert engine.trace_counts == budget
        for r in reqs:
            assert len(r.tokens) == r._budget0
        assert engine.blocks_in_use == 0

    def test_server_surfaces_kv_dtype_in_health_and_metrics(self, gpt):
        model, params = gpt
        rows = []
        writer = MetricsWriter(sink=lambda s, m: rows.append((s, m)))
        server = InferenceServer(
            model, params, max_slots=2, block_size=8,
            prefill_chunk=4, kv_dtype="int8", metrics=writer,
            metrics_interval=2)
        with server:
            h = server.submit(np.arange(1, 9, dtype=np.int32),
                              max_new_tokens=5)
            h.result(timeout=300)
            health = server.health()
        assert health["kv_dtype"] == "int8"
        assert health["kv_bits"] == 8
        merged = {}
        for _, m in rows:
            merged.update(m)
        assert merged.get("kv_bits") == 8.0
        # unquantized servers report the storage width of the compute
        # dtype and kv_dtype None
        server2 = InferenceServer(
            model, params, max_slots=1, block_size=8,
            prefill_chunk=4)
        with server2:
            h2 = server2.health()
        assert h2["kv_dtype"] is None and h2["kv_bits"] == 32

    def test_kv_dtype_auto_adopts_tuned_pair(self, gpt, tmp_path,
                                             monkeypatch):
        """block_size=0 + kv_dtype='auto' adopts the joint
        (block_size, kv_dtype) winner from the autotune table; with
        nothing cached it stays unquantized at the default block."""
        model, params = gpt
        monkeypatch.setenv("APEX_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at.json"))
        from apex_tpu.ops import autotune

        autotune.clear_cache()
        try:
            cold = PagedEngine(model, params, max_slots=1,
                               block_size=0, kv_dtype="auto")
            assert cold.kv_dtype is None and cold.block_size == 16
            autotune._store(
                autotune._key("paged_attention_pair",
                              int(model.cfg.head_dim),
                              str(jnp.dtype(model.cfg.dtype)),
                              kv_heads=int(model.cfg.kv_heads)),
                [8, "int8"])
            warm = PagedEngine(model, params, max_slots=1,
                               block_size=0, kv_dtype="auto")
            assert warm.kv_dtype == "int8" and warm.block_size == 8
            assert warm.kv_bits == 8
            # an explicit block size opts OUT of the joint pair (the
            # caller overrode the tuner): auto resolves to unquantized
            expl = PagedEngine(model, params, max_slots=1,
                               block_size=8, kv_dtype="auto")
            assert expl.kv_dtype is None
        finally:
            autotune.clear_cache()


@pytest.mark.slow
class TestQuantizedAccuracySlow:
    """The ISSUE-8 accuracy acceptance on a TRAINED proxy (a random
    init's near-tied logits flip under any perturbation and measure
    nothing): ≥95% greedy token agreement vs ``generate()`` over a
    multi-request soak horizon with kv_dtype='int8'."""

    def test_greedy_token_agreement_at_least_95pct(self):
        import jax as _jax

        from apex_tpu.models import GPTConfig, GPTModel, gpt_loss_fn

        cfg = GPTConfig.tiny(position_embedding="learned",
                             scan_layers=True)
        model = GPTModel(cfg)
        rng = np.random.default_rng(0)
        period = 24
        cyc = rng.permutation(min(cfg.vocab_size, 256))[:period] \
            .astype(np.int32)
        tparams = model.init(_jax.random.PRNGKey(0),
                             jnp.zeros((1, 4), jnp.int32))["params"]

        def cyc_batch(bs, L):
            phases = rng.integers(0, period, size=bs)
            idx = (phases[:, None] + np.arange(L + 1)) % period
            return jnp.asarray(cyc[idx])

        @_jax.jit
        def sgd_step(p, ids, lr):
            def loss_fn(p):
                logits = model.apply({"params": p}, ids[:, :-1],
                                     deterministic=True)
                return gpt_loss_fn(logits, ids[:, 1:])
            loss, grads = _jax.value_and_grad(loss_fn)(p)
            return _jax.tree.map(lambda a, g: a - lr * g, p, grads), \
                loss

        steps = 200
        for i in range(steps):
            tparams, _ = sgd_step(
                tparams, cyc_batch(8, 48),
                jnp.float32(0.5 if i < steps // 2 else 0.2))
        trained = {"params": tparams}

        budget = 20
        prompts = [np.asarray(
            cyc[(ph + np.arange(period + period // 2)) % period],
            np.int32) for ph in range(6)]
        engine = PagedEngine(model, trained, max_slots=3, block_size=8,
                             prefill_chunk=8, kv_dtype="int8")
        sched = Scheduler(engine)
        reqs = [sched.submit(Request(prompt=p, max_new_tokens=budget))
                for p in prompts]
        sched.drain()
        agree = total = 0
        for p, r in zip(prompts, reqs):
            ref = np.asarray(generate(
                model, trained, jnp.asarray(p[None]),
                max_new_tokens=budget))[0, len(p):]
            got = np.asarray(r.tokens)
            agree += int((got == ref).sum())
            total += budget
        assert engine.blocks_in_use == 0
        assert agree / total >= 0.95, (
            f"int8 KV greedy agreement {agree}/{total} "
            f"= {agree / total:.3f} < 0.95")


class TestSlidingWindow:
    """A windowed model through the engine (ISSUE 37): the paged reads
    take the model's window, and greedy chains are ``generate()``'s —
    whose dense path keeps a ring buffer of the window, another
    implementation of the same mask."""

    @staticmethod
    def _model(window):
        cfg = GPTConfig.tiny(position_embedding="learned",
                             scan_layers=True, sliding_window=window)
        model = GPTModel(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
        return model, {"params": params["params"]}

    @staticmethod
    def _check(model, params, prompts, reqs, n):
        for p, r in zip(prompts, reqs):
            ref = np.asarray(generate(
                model, params, jnp.asarray(p[None]),
                max_new_tokens=n))[0, len(p):]
            np.testing.assert_array_equal(
                np.asarray(r.tokens), ref, err_msg=f"prompt_len={len(p)}")

    @pytest.mark.parametrize("window", [6, 12])   # under a page, over one
    def test_engine_matches_generate_past_the_window(self, window):
        model, params = self._model(window)
        rng = np.random.default_rng(61)
        prompts = [rng.integers(0, model.cfg.vocab_size, size=(L,))
                   .astype(np.int32) for L in (3, 8, 13, 27, 40)]
        engine = PagedEngine(model, params, max_slots=2, block_size=8,
                             prefill_chunk=4)
        assert engine.window == window
        sched = Scheduler(engine)
        reqs = [sched.submit(Request(prompt=p, max_new_tokens=9))
                for p in prompts]
        sched.drain()
        self._check(model, params, prompts, reqs, 9)
        assert engine.blocks_in_use == 0
        # what a window layer swept is less than what a full one would
        assert 0 < engine.kv_window_pages < engine.kv_pages_live

    def test_a_shared_prefix_under_a_window(self):
        """A page's K/V are a function of the prefix whatever reads
        them: tenants that map a shared prompt prefix still reproduce
        generate()."""
        model, params = self._model(6)
        rng = np.random.default_rng(62)
        system = rng.integers(0, model.cfg.vocab_size, size=(24,)) \
            .astype(np.int32)
        prompts = [np.concatenate([system, rng.integers(
            0, model.cfg.vocab_size, size=(L,)).astype(np.int32)])
            for L in (3, 9, 1)]
        engine = PagedEngine(model, params, max_slots=3, block_size=8,
                             prefill_chunk=4, share_prefixes=True)
        sched = Scheduler(engine)
        reqs = [sched.submit(Request(prompt=prompts[0], max_new_tokens=7))]
        for _ in range(8):               # the first past its prefill
            sched.run_step()
        assert engine.trie_blocks == 3
        reqs += [sched.submit(Request(prompt=p, max_new_tokens=7))
                 for p in prompts[1:]]
        sched.run_step()
        assert engine.shared_blocks == 3     # mapped, not recomputed
        sched.drain()
        self._check(model, params, prompts, reqs, 7)
        assert engine.blocks_in_use == 0

    def test_a_draft_under_a_window(self):
        """A verify chunk is a chunk: each draft position masks the
        keys before its own window."""
        model, params = self._model(6)
        rng = np.random.default_rng(63)
        prompts = [np.tile(rng.integers(
            0, model.cfg.vocab_size, size=(4,)).astype(np.int32), 5),
            rng.integers(0, model.cfg.vocab_size, size=(17,))
            .astype(np.int32)]
        engine = PagedEngine(model, params, max_slots=2, block_size=8,
                             prefill_chunk=4, spec_tokens=3)
        sched = Scheduler(engine)
        reqs = [sched.submit(Request(prompt=p, max_new_tokens=8))
                for p in prompts]
        sched.drain()
        self._check(model, params, prompts, reqs, 8)
        assert engine.spec_proposed > 0
