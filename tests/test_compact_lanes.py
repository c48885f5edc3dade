"""A mixed serving step computes its real lanes only (ISSUE 42).

Contracts under test:
- **the list**: :class:`~apex_tpu.models.transformer.Lanes` lays a
  step's real lanes out as one compact list and back, pads zero, and
  names each row's last real lane;
- **the same tokens**: greedy chains through ``PagedEngine`` equal
  ``generate()``'s (or, for a model the dense cache refuses, the full
  forward's), and sampled chains equal the engine's width-1 path, for a
  GQA transformer, a model with recurrent state and a model with an
  expert share — prompts on page and chunk boundaries, rows decoding
  beside a prefill;
- **the budget**: a mixed step feeds at most ``lane_budget`` real
  lanes; in a burst the youngest prefilling rows wait, and a waiting
  row's cursor, pages and recurrent state stay as they were; every row
  finishes with the same tokens; the counters say what the plan did;
- no new executable: the retrace budgets stay exactly as pinned.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import (AfmoeConfig, AfmoeModel, FalconH1Config,
                             FalconH1Model, LlamaConfig, LlamaModel,
                             generate)
from apex_tpu.models.transformer import Lanes
from apex_tpu.serving import PagedEngine

f32 = jnp.float32
BUDGETS = {"decode_step": 1, "prefill_step": 1, "admit": 1, "release": 1}


def _init(model):
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))
    return {"params": params["params"]}


@functools.lru_cache(maxsize=None)
def _model(name):
    if name == "llama":
        model = LlamaModel(LlamaConfig.tiny(
            vocab_size=256, hidden_size=64, ffn_hidden_size=128,
            max_seq_len=128, dtype=f32, param_dtype=f32))
    elif name == "falcon_h1":
        model = FalconH1Model(FalconH1Config.tiny(dtype=f32,
                                                  param_dtype=f32))
    else:
        model = AfmoeModel(AfmoeConfig.tiny(dtype=f32, param_dtype=f32))
    return model, _init(model)


FAMILIES = ("afmoe", "falcon_h1", "llama")


@functools.lru_cache(maxsize=None)
def _forward(model):
    return jax.jit(model.apply)


def greedy_reference(model, params, prompt, n):
    """``generate()``'s chain; for a model the dense cache refuses the
    full forward's, one position at a time."""
    if isinstance(model, LlamaModel):
        return [int(t) for t in np.asarray(generate(
            model, params, jnp.asarray(prompt[None]),
            max_new_tokens=n))[0, -n:]]
    seq = [int(t) for t in prompt]
    for _ in range(n):
        ids = np.zeros((1, 64), np.int32)
        ids[0, :len(seq)] = seq
        logits = _forward(model)(params, jnp.asarray(ids))
        seq.append(int(jnp.argmax(logits[0, len(seq) - 1])))
    return seq[-n:]


def serve(engine, requests):
    """FIFO admission into free slots, one ``step()`` at a time; every
    mixed step's real lanes checked against the budget."""
    waiting = list(enumerate(requests))
    slots = [None] * engine.max_slots
    tokens = [[] for _ in requests]
    while waiting or any(i is not None for i in slots):
        for slot, held in enumerate(slots):
            if held is None and waiting:
                i, kw = waiting.pop(0)
                engine.admit(slot, **kw)
                slots[slot] = i
        real = engine.mixed_lanes_real
        out = engine.step()
        assert engine.mixed_lanes_real - real <= engine.lane_budget
        for slot, i in enumerate(slots):
            if i is None:
                continue
            tokens[i].extend(
                int(t) for t in out.tokens[slot, :out.counts[slot]])
            if out.finished[slot]:
                engine.release(slot)
                slots[slot] = None
    assert engine.blocks_in_use == 0
    return tokens


# ------------------------------------------------------------ the list
@pytest.mark.parametrize("lens", [(3, 1, 0, 4), (4, 4, 4, 4), (0, 0, 1, 0),
                                  (1, 1, 1, 1)])
def test_the_list_holds_the_real_lanes_and_lays_them_back(lens):
    rows, width, size = 4, 4, 16
    real = np.arange(width)[None] < np.asarray(lens)[:, None]
    lanes = Lanes.of(jnp.asarray(real), size)
    x = jnp.arange(rows * width * 2, dtype=f32).reshape(rows, width, 2) + 1
    flat = np.asarray(x).reshape(-1, 2)[real.reshape(-1)]
    listed = np.asarray(lanes.take(x))
    n = int(real.sum())
    assert listed.shape == (1, size, 2)
    np.testing.assert_array_equal(listed[0, :n], flat)
    np.testing.assert_array_equal(listed[0, n:], 0)
    np.testing.assert_array_equal(np.asarray(lanes.valid)[0],
                                  np.arange(size) < n)
    back = np.asarray(lanes.to_rows(lanes.take(x)))
    np.testing.assert_array_equal(back, np.where(real[..., None], x, 0))
    last = np.asarray(lanes.last_lanes(lanes.take(x)))[:, 0]
    for row, k in enumerate(lens):
        if k:
            np.testing.assert_array_equal(last[row], x[row, k - 1])


@pytest.mark.parametrize("slots,chunk,budget", [
    (16, 32, 256),        # Mistral's cells
    (64, 32, 1024),       # Falcon-H1's
    (48, 32, 768),        # Trinity's
    (3, 8, 24),           # the row layout holds fewer: all of it
    (8, 32, 128),         # half, a multiple of 128
])
def test_the_lane_budget_is_half_the_rows_rounded_up(slots, chunk,
                                                      budget):
    model, params = _model("llama")
    engine = PagedEngine(model, params, max_slots=slots, block_size=8,
                         prefill_chunk=chunk, pool_tokens=512)
    assert engine.lane_budget == budget
    # the oldest prompt's chunk beside every other row decoding fits
    assert engine.lane_budget >= slots - 1 + chunk


# ------------------------------------------------------ the same tokens
def _requests(model, greedy, seed=5):
    """Prompts on page (4) and chunk (8) boundaries and between, more
    requests than slots: rows decode beside every prefill."""
    rng = np.random.default_rng(seed)
    sizes = (1, 4, 8, 9, 16, 17, 3, 12, 25)
    budgets = (5, 3, 6, 4, 7, 3, 8, 5, 4)
    return [dict(prompt=rng.integers(0, model.cfg.vocab_size,
                                     size=(n,)).astype(np.int32),
                 max_new_tokens=b, seed=i,
                 temperature=0.0 if greedy else 0.9,
                 top_k=None if greedy else 12)
            for i, (n, b) in enumerate(zip(sizes, budgets))]


@pytest.mark.parametrize("mode", ["greedy", "sampled"])
@pytest.mark.parametrize("name", FAMILIES)
def test_the_compact_step_serves_the_same_tokens(name, mode):
    model, params = _model(name)
    engine = PagedEngine(model, params, max_slots=3, block_size=4,
                         prefill_chunk=8, pool_tokens=256)
    requests = _requests(model, mode == "greedy")
    got = serve(engine, requests)
    assert engine.trace_counts == BUDGETS
    # the mixed steps ran compact: some pads were never computed
    assert 0 < engine.mixed_lanes_real < engine.mixed_lanes_computed
    assert engine.prefill_rows_deferred == 0
    if mode == "greedy":
        for kw, tokens in zip(requests, got):
            assert tokens == greedy_reference(
                model, params, kw["prompt"], kw["max_new_tokens"])
        return
    # sampled: the engine's width-1 path (every step one lane a row,
    # no list) draws the same chains from the same seeds
    width_one = PagedEngine(model, params, max_slots=3, block_size=4,
                            prefill_chunk=1, pool_tokens=256)
    assert serve(width_one, requests) == got
    assert width_one.mixed_lanes_computed == 0


# ------------------------------------------------------------ the budget
def _state_rows(engine, slots):
    """The recurrent state and conv history of ``slots``, every layer."""
    leaves = jax.tree_util.tree_flatten_with_path(engine.cache)[0]
    return [np.asarray(a)[list(slots)] for path, a in leaves
            if path[-1].key in ("ssm_state", "conv_state")]


@pytest.mark.parametrize("name", FAMILIES)
def test_a_burst_waits_for_the_lane_budget(name):
    """Eight prompts of 44 tokens admitted at once: 8 x 32 lanes
    against a budget of 128, so the four youngest wait while the four
    oldest feed their first chunk — then everyone finishes with the
    reference's tokens."""
    model, params = _model(name)
    engine = PagedEngine(model, params, max_slots=8, block_size=8,
                         prefill_chunk=32, pool_tokens=512)
    assert engine.lane_budget == 128
    rng = np.random.default_rng(11)
    requests = [dict(prompt=rng.integers(0, model.cfg.vocab_size,
                                         size=(44,)).astype(np.int32),
                     max_new_tokens=3, seed=i) for i in range(8)]
    for slot, kw in enumerate(requests):
        engine.admit(slot, **kw)
    # what a slot's state held before (another tenant's): a waiting row
    # must leave it as it is
    engine.cache = jax.tree_util.tree_map_with_path(
        lambda p, a: (jax.random.normal(jax.random.PRNGKey(len(p)),
                                        a.shape, a.dtype)
                      if p[-1].key in ("ssm_state", "conv_state") else a),
        engine.cache)
    young = range(4, 8)
    before = _state_rows(engine, young)
    engine.dispatch()
    flight = engine._flights[-1]
    assert engine.prefill_rows_deferred == 4
    assert engine.mixed_lanes_real == 128
    assert list(np.flatnonzero(flight.n_tokens == 0)) == list(young)
    for slot in young:
        rec = engine._tenants[slot]
        assert rec.cursor == rec.fed == 0 and rec.blocks == []
    for slot in range(4):
        assert engine._tenants[slot].fed == 32
    out = engine.collect()
    assert not out.counts.any()
    for a, b in zip(before, _state_rows(engine, young)):
        np.testing.assert_array_equal(a, b)

    # the rest of the run: every row finishes, the same tokens, and the
    # deferred count is what the plans withheld, a row a step
    tokens = [[] for _ in requests]
    withheld = 4
    live = set(range(8))
    while live:
        deferred = engine.prefill_rows_deferred
        engine.dispatch()
        n_tokens = engine._flights[-1].n_tokens
        withheld += sum(1 for slot in live if n_tokens[slot] == 0)
        assert engine.prefill_rows_deferred - deferred == sum(
            1 for slot in live if n_tokens[slot] == 0)
        out = engine.collect()
        for slot in list(live):
            tokens[slot].extend(
                int(t) for t in out.tokens[slot, :out.counts[slot]])
            if out.finished[slot]:
                engine.release(slot)
                live.discard(slot)
    assert engine.prefill_rows_deferred == withheld
    assert engine.blocks_in_use == 0
    assert engine.trace_counts == BUDGETS
    for kw, got in zip(requests, tokens):
        assert got == greedy_reference(model, params, kw["prompt"],
                                       kw["max_new_tokens"])
