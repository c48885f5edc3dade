"""Falcon-H1 (a Mamba-2 mixer beside GQA attention in every block)
against the benchmark's plain float32 reference, and the life of its
recurrent state in the paged serving engine.

Everything runs in float32 at a tiny size that keeps the 34B model's
ratios (two B/C groups, five query heads a KV head, a head width that
is not hidden / heads, d_conv 4), with the published multipliers and
the benchmark's seeded weights, so model and reference agree to float32
round-off: the tolerances below are a few 1e-5 of logits of size 1.4,
what sums over 64-128 float32 terms in another order give."""

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import (FalconH1Config, FalconH1Model, LlamaConfig,
                             LlamaModel, TransformerConfig, generate)
from apex_tpu.models.generate import apply_decode
from apex_tpu.serving import (InferenceServer, PagedEngine, Request,
                              Scheduler)
from apex_tpu.serving import cache as slot_cache

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

HF = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=10, num_key_value_heads=2, head_dim=16,
    intermediate_size=128, max_position_embeddings=128, rms_norm_eps=1e-5,
    rope_theta=1e11, mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
    mamba_d_state=128, mamba_n_groups=2, mamba_d_conv=4,
    mamba_chunk_size=8,
    # the published multipliers of Falcon-H1-34B-Instruct
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
    attention_in_multiplier=1, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284])
TOL = dict(rtol=1e-4, atol=5e-5)
BUDGET = {"decode_step": 1, "prefill_step": 1, "admit": 1, "release": 1}


@pytest.fixture(scope="module")
def falcon():
    from lib import weights_falcon_h1 as weights
    from lib.reference import falcon_h1 as ref

    cfg = FalconH1Config.from_hf(HF)
    model = FalconH1Model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    shapes = {"params": shapes["params"]}
    params = jax.jit(lambda s: weights.make_weights(shapes, s, HF))(7)
    wref = weights.reference_weights(params)
    kw = dict(layers=HF["num_hidden_layers"], dims=ref.dims_of(HF),
              mult=ref.mult_of(HF))
    ref_logits = lambda ids, **more: ref.logits(
        wref, jnp.asarray(ids), **kw, **more)
    return model, params, ref_logits


def prompts(n, lo=3, hi=30, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, HF["vocab_size"], size=int(k)).astype(np.int32)
            for k in rng.integers(lo, hi, size=n)]


@pytest.fixture(scope="module")
def greedy(falcon):
    """``greedy(prompt, n)``: the chain the model's full forward (no
    cache, no state carried) gives, one position at a time."""
    model, params, _ = falcon
    pad = 64
    fwd = jax.jit(lambda ids: model.apply(params, ids))

    def chain(prompt, n):
        seq = list(int(t) for t in prompt)
        for _ in range(n):
            ids = np.zeros((1, pad), np.int32)
            ids[0, :len(seq)] = seq
            seq.append(int(jnp.argmax(fwd(jnp.asarray(ids))[0,
                                                            len(seq) - 1])))
        return np.asarray(seq[len(prompt):], np.int32)

    return chain


def serve(engine, requests):
    """Requests through a scheduler to the end; their token lists."""
    sched = Scheduler(engine)
    handles = [sched.submit(Request(prompt=p, max_new_tokens=n))
               for p, n in requests]
    sched.drain()
    return sched, [np.asarray(h.tokens, np.int32) for h in handles]


# ------------------------------------------------------------- the model
def test_model_full_forward_is_the_reference(falcon):
    model, params, ref_logits = falcon
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 37), 0, 512)
    out = model.apply(params, ids)            # 37 = 4 chunks of 8 + 5
    for i in range(2):
        np.testing.assert_allclose(np.asarray(out[i]),
                                   np.asarray(ref_logits(ids[i])), **TOL)


def test_every_branch_speaks_under_the_published_multipliers(falcon):
    """The seeded scales' purpose: mixer, attention and MLP each add to
    the residual at a comparable size, and the attention is not
    uniform."""
    from lib import weights_falcon_h1 as weights
    from lib.reference import falcon_h1 as ref

    model, params, _ = falcon
    ids = jax.random.randint(jax.random.PRNGKey(2), (48,), 0, 512)
    _, _, sizes = ref.hidden(weights.reference_weights(params), ids,
                             layers=2, dims=ref.dims_of(HF),
                             mult=ref.mult_of(HF))
    adds = np.asarray(sizes)[:, 1:]           # mixer, attention, MLP
    assert adds.min() > 0.1 and adds.max() / adds.min() < 4.0


def test_reference_controls_move_the_logits(falcon):
    """What the benchmark's wrong references change, the comparison
    must be able to see: logits from a recurrence restarted every 8
    positions, and from one started on another state, differ from the
    right ones after the first chunk / everywhere."""
    from lib import weights_falcon_h1 as weights
    from lib.reference import falcon_h1 as ref

    model, params, ref_logits = falcon
    ids = jax.random.randint(jax.random.PRNGKey(3), (40,), 0, 512)
    right = ref_logits(ids)
    dropped = ref_logits(ids, restart_every=8)
    np.testing.assert_allclose(np.asarray(dropped[:8]),
                               np.asarray(right[:8]), **TOL)
    assert float(jnp.max(jnp.abs(dropped[8:] - right[8:]))) > 0.1
    _, finals, _ = ref.hidden(
        weights.reference_weights(params), ids[::-1], layers=2,
        dims=ref.dims_of(HF), mult=ref.mult_of(HF))
    inherited = ref_logits(ids, init=finals)
    assert float(jnp.max(jnp.abs(inherited[:4] - right[:4]))) > 0.1


def test_the_tiny_preset_keeps_the_34b_models_ratios():
    """What these tests run at: ``FalconH1Config.tiny`` is the HF
    dictionary above with every multiplier at 1."""
    tiny = FalconH1Config.tiny()
    assert tiny.num_heads // tiny.kv_heads == 5 == 20 // 4
    assert tiny.head_dim * tiny.num_heads != tiny.hidden_size
    assert tiny.mamba_n_groups == 2 and tiny.mamba_d_conv == 4
    assert tiny.conv_channels == 64 + 2 * 2 * 128
    named = FalconH1Config.from_hf(HF)
    ones = {f.name: getattr(tiny, f.name) for f in dataclasses.fields(tiny)
            if "multiplier" in f.name}
    assert dataclasses.replace(named, rope_base=tiny.rope_base, **ones) == tiny
    with pytest.raises(ValueError, match="mamba_n_heads"):
        FalconH1Config.tiny(mamba_d_ssm=48)


# ------------------------------------- pages and state, logits compared
def test_chunked_prefill_then_decode_gives_the_references_logits(falcon):
    """Three ragged rows through the engine's paged model exactly as
    ``PagedEngine.step`` feeds it — tables, cursors and real-lane counts
    overwritten before every application, prompts in chunks of 8 that
    cross chunk and page boundaries, then width-1 decode steps, a
    decoding row riding mixed steps with 1 real lane of 8 — teacher
    forced; every real lane's logits against the reference's."""
    model, params, ref_logits = falcon
    engine = PagedEngine(model, params, max_slots=3, block_size=8,
                         prefill_chunk=8, pool_tokens=512)
    paged, cache = engine._paged_model, engine.cache
    rng = np.random.default_rng(5)
    lens, plens = [41, 17, 30], [19, 5, 24]
    seqs = [rng.integers(0, 512, size=n).astype(np.int32) for n in lens]
    want = [np.asarray(ref_logits(s)) for s in seqs]
    mb = engine._tables.shape[1]
    tables = 1 + np.arange(3 * mb, dtype=np.int32).reshape(3, mb)
    cursors = np.zeros(3, np.int32)
    step = jax.jit(lambda c, t, cur, n, ids: apply_decode(
        paged, params, slot_cache.set_paged_leaves(c, t, cur, n), ids))
    compared = 0
    while any(cursors[r] < lens[r] for r in range(3)):
        w = 8 if any(cursors[r] < plens[r] for r in range(3)) else 1
        feed, n = np.zeros((3, w), np.int32), np.zeros(3, np.int32)
        for r in range(3):
            left = (plens[r] if cursors[r] < plens[r] else lens[r]) \
                - cursors[r]
            n[r] = min(w if cursors[r] < plens[r] else 1, left)
            feed[r, :n[r]] = seqs[r][cursors[r]:cursors[r] + n[r]]
        logits, cache = step(cache, tables, cursors, n, feed)
        for r in range(3):
            got = np.asarray(logits[r, :n[r]])
            np.testing.assert_allclose(
                got, want[r][cursors[r]:cursors[r] + n[r]], **TOL)
            compared += int(n[r])
        cursors += n
    assert compared == sum(lens)


# ------------------------------------------------ the state's life cycle
@pytest.fixture(scope="module")
def engine(falcon):
    model, params, _ = falcon
    eng = PagedEngine(model, params, max_slots=3, block_size=8,
                      prefill_chunk=8, pool_tokens=512)
    eng.warmup()
    return eng


def test_engine_chains_match_the_full_forward(engine, greedy):
    """Prompts shorter than, equal to and longer than a chunk, more
    requests than slots: slots are reused with the last tenant's state
    still in them."""
    reqs = [(p, 9) for p in prompts(7)] \
        + [(prompts(1, 8, 9)[0], 6), (prompts(1, 16, 17)[0], 6)]
    _, got = serve(engine, reqs)
    for (p, n), tokens in zip(reqs, got):
        np.testing.assert_array_equal(tokens, greedy(p, n))
    assert engine.trace_counts == BUDGET and engine.blocks_in_use == 0


def test_a_reused_slot_gives_the_chain_it_gives_alone(falcon, greedy):
    model, params, _ = falcon
    first, second = prompts(2, 10, 20, seed=2)
    eng = PagedEngine(model, params, max_slots=1, block_size=8,
                      prefill_chunk=8, pool_tokens=256)
    _, (a, b) = serve(eng, [(first, 12), (second, 12)])
    fresh = PagedEngine(model, params, max_slots=1, block_size=8,
                        prefill_chunk=8, pool_tokens=256)
    _, (alone,) = serve(fresh, [(second, 12)])
    np.testing.assert_array_equal(b, alone)
    np.testing.assert_array_equal(b, greedy(second, 12))
    # the slot was not cleared in between: its buffers held the first
    # tenant's state when the second arrived
    assert eng.ssm_state_resets == 2


def test_cotenants_in_a_mixed_step_do_not_touch_each_other(engine, greedy):
    """A decodes alone, then B's prompt arrives: A's next tokens come
    out of mixed steps (1 real lane of 8) beside B's chunks."""
    a, b = prompts(2, 12, 28, seed=3)
    sched = Scheduler(engine)
    ha = sched.submit(Request(prompt=a, max_new_tokens=20))
    for _ in range(6):
        sched.run_step()
    assert 0 < len(ha.tokens) < 20
    hb = sched.submit(Request(prompt=b, max_new_tokens=10))
    sched.drain()
    np.testing.assert_array_equal(np.asarray(ha.tokens), greedy(a, 20))
    np.testing.assert_array_equal(np.asarray(hb.tokens), greedy(b, 10))


def test_a_preempted_request_resumes_token_identically(falcon, greedy):
    """The pool cannot hold both sequences: the youngest is preempted
    and requeued with ``prompt ++ streamed``; its state is rebuilt by
    the re-prefill from cursor 0."""
    model, params, _ = falcon
    eng = PagedEngine(model, params, max_slots=2, block_size=8,
                      pool_tokens=64, prefill_chunk=8, admit_headroom=0)
    eng.warmup()
    resets = eng.ssm_state_resets
    p1, p2 = prompts(2, 20, 23, seed=4)
    sched, (t1, t2) = serve(eng, [(p1, 30), (p2, 28)])
    assert sched.preempts >= 1
    np.testing.assert_array_equal(t1, greedy(p1, 30))
    np.testing.assert_array_equal(t2, greedy(p2, 28))
    assert eng.blocks_in_use == 0 and eng.trace_counts == BUDGET
    # two admissions and one restart a preemption
    assert eng.ssm_state_resets - resets == 2 + sched.preempts


def test_health_counts_state_bytes_resets_and_positions(falcon):
    model, params, _ = falcon
    server = InferenceServer(model, params, max_slots=2,
                             block_size=8, prefill_chunk=8, pool_tokens=256)
    server.start()
    try:
        h0 = server.health()
        # a layer and a slot: (4, 16, 128) float32 + (3, 64 + 4 x 128) float32
        assert h0["ssm_state_bytes"] == 2 * 2 * (4 * 16 * 128 + 3 * 576) * 4
        p = prompts(1, 11, 12, seed=5)[0]
        server.submit(p, max_new_tokens=5).result(timeout=120)
        h1 = server.health()
    finally:
        server.shutdown()
    assert h1["ssm_state_resets"] - h0["ssm_state_resets"] == 1
    # 11 prompt positions, then 4 decode steps (the fifth token needs
    # no further step)
    assert h1["ssm_positions"] - h0["ssm_positions"] == 11 + 4
    assert "ssm_state_bytes" not in _llama_health()


def _llama_health():
    cfg = LlamaConfig.tiny(scan_layers=True)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    server = InferenceServer(model, {"params": params["params"]},
                             max_slots=2, block_size=8,
                             prefill_chunk=8, pool_tokens=128)
    return server.health()


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("kw,names", [
    (dict(share_prefixes=True), "snapshot"),
    (dict(spec_tokens=2), "snapshot"),
    (dict(mesh=2), "sharding"),
])
def test_what_needs_a_state_snapshot_or_sharding_is_refused(falcon, kw,
                                                            names):
    model, params, _ = falcon
    with pytest.raises(ValueError, match="recurrent state") as e:
        PagedEngine(model, params, max_slots=2, block_size=8,
                    prefill_chunk=8, pool_tokens=128, **kw)
    assert names in str(e.value).lower() and next(iter(kw)) in str(e.value)


def test_the_dense_cache_refuses_the_model(falcon):
    model, params, _ = falcon
    with pytest.raises(ValueError, match="paged serving engine"):
        generate(model, params, jnp.zeros((1, 4), jnp.int32),
                 max_new_tokens=2)


def test_the_engine_states_what_a_model_must_provide():
    class NoCfg:
        cfg = None

    class Thin:
        cfg = dataclasses.make_dataclass("C", [("max_seq_len", int, 8)])()

    for model in (NoCfg(), Thin()):
        with pytest.raises(ValueError, match="contract") as e:
            PagedEngine(model, {})
        assert "vocab_size" in str(e.value) and "decode=True" in str(e.value)
    assert "missing on .cfg" in str(e.value) and "head_dim" in str(e.value)


# ----------------------------------------------- the shared core's knobs
@pytest.mark.parametrize("kw,want", [
    (dict(hidden_size=64, num_heads=4), 16),
    (dict(hidden_size=64, num_heads=4, kv_channels=32), 32),
    (dict(hidden_size=60, num_heads=8, kv_channels=16), 16),
])
def test_head_dim_is_the_stated_width_or_the_quotient(kw, want):
    assert TransformerConfig(**kw).head_dim == want


def test_heads_must_divide_hidden_only_without_a_stated_width():
    with pytest.raises(ValueError, match="kv_channels"):
        TransformerConfig(hidden_size=60, num_heads=8)
    with pytest.raises(ValueError, match="kv_channels"):
        TransformerConfig(hidden_size=64, num_heads=4, kv_channels=0)


@pytest.mark.parametrize("field", ["key_multiplier", "mlp_gate_multiplier"])
def test_a_multiplier_equals_the_same_factor_on_the_weight(field):
    """``key_multiplier`` on the keys = the keys' columns of the qkv
    kernel scaled; ``mlp_gate_multiplier`` = the gate's kernel scaled;
    1.0 is the unchanged Llama."""
    base = LlamaConfig.tiny(num_layers=1, scan_layers=False)
    cfg = dataclasses.replace(base, **{field: 0.25})
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 12), 0, 1024)
    params = LlamaModel(base).init(jax.random.PRNGKey(1), ids)
    layer = jax.tree.map(lambda x: x, params)["params"]["transformer"][
        "layer_0"]
    if field == "key_multiplier":
        h, hk, d = base.num_heads, base.kv_heads, base.head_dim
        kern = layer["attention"]["qkv_proj"]["kernel"]
        val = getattr(kern, "value", kern)
        grouped = val.reshape(val.shape[0], hk, h // hk + 2, d)
        grouped = grouped.at[:, :, h // hk].multiply(0.25)
        scaled = grouped.reshape(val.shape)
        layer["attention"]["qkv_proj"]["kernel"] = kern.replace_boxed(
            scaled) if hasattr(kern, "replace_boxed") else scaled
    else:
        kern = layer["mlp"]["dense_h_to_4h_gate"]["kernel"]
        val = getattr(kern, "value", kern)
        layer["mlp"]["dense_h_to_4h_gate"]["kernel"] = kern.replace_boxed(
            val * 0.25) if hasattr(kern, "replace_boxed") else val * 0.25
    moved = {"params": dict(params["params"], transformer={
        "layer_0": layer})}
    np.testing.assert_allclose(
        np.asarray(LlamaModel(cfg).apply(params, ids)),
        np.asarray(LlamaModel(base).apply(moved, ids)),
        rtol=2e-5, atol=2e-5)
