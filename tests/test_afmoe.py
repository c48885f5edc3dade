"""models/afmoe.py — the AFMoE decoder (Arcee's Trinity family) against
the benchmark's plain float32 reference, at tiny sizes on seeded
weights.

- the full forward against ``benchmarks/lib/reference/afmoe.py``:
  logits to float32 rounding (both compute in float32 at ``highest``
  matmul precision; what differs is the order of the sums: 1e-4);
- prefill in chunks, then decoding through ``PagedEngine``, against the
  reference's full forward over a request that crosses the window by
  several pages and chunk boundaries, through window AND full layers,
  dense AND expert layers: bf16-free float32 pools reproduce the
  reference's own choice at every step; int8 pools stay inside the
  coded pool's band;
- the shares add up: with 32 experts of which 4 are held a share, the
  eight shares' routed parts plus the shared expert counted once are
  the uncut layer, and the uncut layer is the reference's;
- the two parameter stacks, the experts' bank, the per-layer window and
  positional scheme, the configuration's validation;
- ``health()``'s expert and window counters against hand-counted
  values on a scripted run.
"""

import pathlib
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.models import AfmoeConfig, AfmoeModel
from apex_tpu.models.afmoe import FULL, WINDOW
from apex_tpu.serving import InferenceServer, PagedEngine

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from lib import weights_afmoe as weights            # noqa: E402
from lib.reference import afmoe as ref              # noqa: E402

f32 = jnp.float32


def hf_config(cfg):
    """The keys the reference and the weights read, from a model
    config."""
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.layernorm_eps, rope_theta=cfg.rope_base,
        sliding_window=cfg.sliding_window,
        num_experts_per_tok=cfg.num_experts_per_tok,
        route_scale=cfg.route_scale, route_norm=True,
        num_dense_layers=cfg.num_dense_layers,
        num_hidden_layers=cfg.num_layers,
        layer_types=list(cfg.layer_types), expert_offset=cfg.expert_offset,
        num_experts=cfg.expert_share.held)


def build(seed=3, **kw):
    cfg = AfmoeConfig.tiny(dtype=f32, param_dtype=f32, **kw)
    model = AfmoeModel(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    shapes = {"params": shapes["params"]}
    hf = hf_config(cfg)
    params = jax.jit(lambda s: weights.make_weights(shapes, s, hf))(seed)
    return cfg, model, params, hf


def reference_logits(params, hf, ids, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.logits(weights.reference_weights(params, hf),
                          jnp.asarray(ids), kinds=ref.layer_kinds(hf),
                          dims=ref.dims_of(hf), **kw)


@pytest.fixture(scope="module")
def tiny():
    return build()


# ------------------------------------------------------- full forward
@pytest.mark.parametrize("seed,length", [(3, 48), (11, 23), (12, 64)])
def test_full_forward_is_the_references(seed, length):
    cfg, model, params, hf = build(seed)
    ids = jax.random.randint(jax.random.PRNGKey(seed), (2, length), 0,
                             cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        out = model.apply(params, ids)
    for row in range(2):
        want = reference_logits(params, hf, ids[row])
        np.testing.assert_allclose(np.asarray(out[row]), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_wrong_reference_is_another_function(tiny, wrong):
    """Each control of the comparison moves the logits of a sequence
    that crosses the window: they are not the model."""
    cfg, _, params, hf = tiny
    ids = jax.random.randint(jax.random.PRNGKey(5), (48,), 0,
                             cfg.vocab_size)
    right = reference_logits(params, hf, ids)
    other = reference_logits(params, hf, ids, wrong=wrong)
    assert float(jnp.max(jnp.abs(right - other)[20:])) > 0.05


# -------------------------------------------- through the paged engine
def serve_one(model, params, prompt, new_tokens, **kw):
    engine = PagedEngine(model, params, max_slots=2, block_size=8,
                         prefill_chunk=8, pool_tokens=256, **kw)
    engine.admit(0, prompt, max_new_tokens=new_tokens)
    out = []
    while True:
        step = engine.step()
        if step.counts[0]:
            out.append(int(step.tokens[0, 0]))
        if step.finished[0]:
            return engine, out


def served_gap(params, hf, prompt, tokens):
    """How far the reference's logit of each served token lies below
    the reference's best."""
    seq = np.concatenate([prompt, tokens]).astype(np.int32)
    logits = reference_logits(params, hf, seq)
    rows = slice(len(prompt) - 1, len(seq) - 1)
    served = jnp.take_along_axis(
        logits, jnp.asarray(np.roll(seq, -1))[:, None], -1)[:, 0]
    return np.asarray(jnp.max(logits, -1) - served)[rows]


@pytest.mark.parametrize("prompt_len,new", [(37, 20), (8, 30), (61, 9)])
def test_chunked_prefill_then_decode_is_the_references_forward(
        tiny, prompt_len, new):
    """Window 16, pages and chunks of 8: the request runs 2-4 windows
    deep, so window layers stop reading pages that the full layer still
    reads, across chunk and page boundaries."""
    cfg, model, params, hf = tiny
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(prompt_len), (prompt_len,), 0, cfg.vocab_size))
    engine, out = serve_one(model, params, prompt, new)
    assert len(out) == new
    # float32 everywhere: every served token is the reference's first
    # choice, up to a tie within rounding
    assert served_gap(params, hf, prompt, out).max() <= 1e-4
    assert engine.trace_counts == {"decode_step": 1, "prefill_step": 1,
                                   "admit": 1, "release": 0}


def test_a_drafted_step_serves_the_same_tokens_and_counts_its_experts(tiny):
    """A verify step (``spec_tokens``) through window, full and expert
    layers: drafts that are right but for every second one are kept as
    far as they are right, the served tokens are the undrafted run's,
    and the experts' counts come back in the drafted step's one fetch
    like any other step's."""
    cfg, model, params, hf = tiny
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(21), (21,), 0, cfg.vocab_size))
    _, plain = serve_one(model, params, prompt, 18)
    full = np.concatenate([prompt, plain]).astype(np.int32)

    def drafter(context, cap, ngram):
        guess = full[len(context):len(context) + cap].copy()
        guess[1::2] = (guess[1::2] + 1) % cfg.vocab_size
        return guess

    engine = PagedEngine(model, params, max_slots=2, block_size=8,
                         prefill_chunk=8, pool_tokens=256, spec_tokens=3)
    engine._drafter = drafter
    engine.admit(0, prompt, max_new_tokens=18)
    out, steps = [], 0
    while True:
        step = engine.step()
        steps += 1
        out.extend(int(t) for t in step.tokens[0, :step.counts[0]])
        if step.finished[0]:
            break
    assert out == plain
    assert engine.spec_proposed > engine.spec_accepted > 0
    assert steps < 3 + 18                  # three chunks, then drafts
    assert engine.expert_layer_steps == 4 * steps
    assert engine.expert_assignments > 0
    assert engine.spans.snapshot()["apex/engine/fetch"]["n"] == steps


def test_int8_pool_stays_inside_the_coded_pools_band(tiny):
    """An int8 pool rounds K and V to 1/127 of their page's amax: the
    served tokens stay within 0.3 of the reference's best logit (the
    float32 pool: 1e-4; logits have a standard deviation of 1.4)."""
    cfg, model, params, hf = tiny
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(7), (37,), 0, cfg.vocab_size))
    _, out = serve_one(model, params, prompt, 20, kv_dtype="int8")
    assert served_gap(params, hf, prompt, out).max() <= 0.3


def test_a_window_wider_than_the_context_is_full_attention():
    """The same weights served under window 16 and under a window no
    request reaches differ; the latter equals all-full layers' window
    masks (nothing masked) but keeps the window layers' rotation."""
    cfg, model, params, hf = build()
    wide = AfmoeModel(AfmoeConfig.tiny(dtype=f32, param_dtype=f32,
                                       sliding_window=128))
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(9), (40,), 0, cfg.vocab_size))
    _, narrow_out = serve_one(model, params, prompt, 12)
    _, wide_out = serve_one(wide, params, prompt, 12)
    hf_wide = dict(hf, sliding_window=128)
    assert served_gap(params, hf_wide, prompt, wide_out).max() <= 1e-4
    assert served_gap(params, hf, prompt, narrow_out).max() <= 1e-4
    assert narrow_out != wide_out


# ------------------------------------------------------ the shares add up
def test_the_shares_add_up_to_the_uncut_layer():
    """32 experts, 4 held a share: the eight shares' routed parts plus
    the shared expert counted once are the uncut layer's output, which
    is the reference's."""
    from apex_tpu.models.transformer import ParallelMLP, TransformerConfig
    from apex_tpu.transformer.moe import ExpertShareConfig, ExpertShareMLP

    h, f, n, k = 64, 128, 32, 4
    base = dict(num_experts=n, top_k=k, route_scale=2.448,
                hidden_size=h, ffn_hidden_size=f)
    layer = lambda **kw: ExpertShareMLP(
        ExpertShareConfig(**base, **kw), ParallelMLP(TransformerConfig(
            hidden_size=h, num_heads=1, ffn_hidden_size=f,
            activation="silu", gated_mlp=True, add_bias_linear=False)))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, h), f32)
    key = jax.random.PRNGKey(2)
    w_in = jax.random.normal(key, (n, h, 2 * f)) / 8.0
    w_down = jax.random.normal(key, (n, f, h)) / 11.0
    params = layer().init(jax.random.PRNGKey(1), x, (w_in, w_down))["params"]
    params = dict(
        params, router=jax.random.normal(key, (h, n)) * 0.2,
        expert_bias=jax.random.normal(key, (n,)) * 0.1)
    with jax.default_matmul_precision("highest"):
        uncut, counts = layer().apply({"params": params}, x, (w_in, w_down))
        only_shared = layer(experts_held=4).apply(
            {"params": params}, x, (w_in[:4] * 0, w_down[:4]))[0]
        routed, held_counts = 0.0, []
        for j in range(8):
            # the bank whole, a share's groups from 4 j on
            part, c = layer(experts_held=4, expert_offset=4 * j).apply(
                {"params": params}, x, (w_in, w_down), jnp.int32(4 * j))
            routed = routed + (part - only_shared)
            held_counts.append(np.asarray(c))
    np.testing.assert_allclose(np.asarray(only_shared + routed),
                               np.asarray(uncut), atol=2e-5, rtol=2e-5)
    # every assignment lands on exactly one share
    np.testing.assert_array_equal(np.concatenate(held_counts),
                                  np.asarray(counts))
    assert int(counts.sum()) == 2 * 24 * k

    # ... and the uncut layer is the reference's expert MLP
    mlp = params["shared_expert"]
    val = lambda v: getattr(v, "value", v)
    w = dict(router=params["router"], bias=params["expert_bias"],
             w_in=w_in, w_down=w_down, first=jnp.int32(0),
             gate=val(mlp["dense_h_to_4h_gate"]["kernel"]),
             up=val(mlp["dense_h_to_4h"]["kernel"]),
             down=val(mlp["dense_4h_to_h"]["kernel"]))
    with jax.default_matmul_precision("highest"):
        want = ref.experts(x[0], w, lower=None, wrong=None, top_k=k,
                           route_scale=2.448, route_norm=True, offset=0,
                           held=n)[0]
    np.testing.assert_allclose(np.asarray(uncut[0]), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# --------------------------------------------- structure and validation
def test_parameters_stack_by_kind_and_the_experts_in_a_bank(tiny):
    cfg, _, params, _ = tiny
    p = params["params"]
    assert set(p) == {"embedding", "dense_layers", "expert_layers",
                      "expert_w_in", "expert_w_down", "final_norm",
                      "lm_head"}
    lead = lambda tree: {leaf.shape[0] for leaf in jax.tree.leaves(tree)}
    assert lead(p["dense_layers"]) == {1}
    assert lead(p["expert_layers"]) == {4}
    assert "mlp" in p["dense_layers"]["layer"]
    assert set(p["expert_layers"]["layer"]["moe"]) == {
        "router", "expert_bias", "shared_expert"}
    # 4 expert layers x 4 held experts, [gate | up] in one matrix
    assert p["expert_w_in"].shape == (16, 64, 256)
    assert p["expert_w_down"].shape == (16, 128, 64)
    att = p["expert_layers"]["layer"]["attention"]
    assert att["q_norm"].shape == att["k_norm"].shape == (4, 16)
    assert jax.tree.leaves(att["gate_proj"])[0].shape == (4, 64, 12 * 16)


def test_each_layer_has_its_own_window_and_positional_scheme(tiny):
    cfg = tiny[0]
    assert cfg.layer_types == (WINDOW,) * 4 + (FULL,)
    kinds = [(cfg.layer_config(i).sliding_window,
              cfg.layer_config(i).position_embedding) for i in range(5)]
    assert kinds == [(16, "rope")] * 4 + [(None, "none")]


def test_the_cache_is_a_subtree_a_layer_in_published_order(tiny):
    from apex_tpu.models.generate import cache_shapes
    import dataclasses

    cfg = tiny[0]
    paged = AfmoeModel(dataclasses.replace(
        cfg, kv_cache="paged", kv_block_size=8, kv_pool_blocks=9))
    shapes = cache_shapes(paged, 2)
    assert set(shapes) == {f"layer_{i}" for i in range(5)}
    assert "expert_counts" not in shapes["layer_0"]      # the dense layer
    for i in range(1, 5):
        assert shapes[f"layer_{i}"]["expert_counts"].shape == (4,)
        assert shapes[f"layer_{i}"]["chunk_lens"].shape == (2,)
    pools = {shapes[f"layer_{i}"]["attention"]["paged_key"].shape
             for i in range(5)}
    assert pools == {(2, 9, 8, 16)}                      # one geometry


@pytest.mark.parametrize("kw,match", [
    (dict(layer_types=(WINDOW,) * 4), "layer_types"),
    (dict(layer_types=(WINDOW,) * 4 + ("global",)), "layer_types"),
    (dict(num_dense_layers=6), "num_dense_layers"),
    (dict(experts_held=5, expert_offset=30), "experts"),
    (dict(num_experts_per_tok=33), "top_k"),
    (dict(num_moe_experts=8), "num_moe_experts"),
])
def test_config_validation(kw, match):
    with pytest.raises(ValueError, match=match):
        AfmoeConfig.tiny(**kw)


def test_a_window_layer_without_a_width_raises():
    cfg = AfmoeConfig.tiny(sliding_window=None)
    with pytest.raises(ValueError, match="sliding_window"):
        cfg.layer_config(0)
    assert cfg.layer_config(4).sliding_window is None


def test_from_hf_reads_the_cells_configuration():
    import json

    c = json.loads((BENCH / "configs" / "trinity_large_l5_e32.json")
                   .read_text())
    cfg = AfmoeConfig.from_hf(c)
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.ffn_size, cfg.moe_ffn_hidden_size) == (
        3072, 48, 8, 128, 12288, 3072)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_offset,
            cfg.num_experts_per_tok, cfg.num_shared_experts) == (
        256, 32, 0, 4, 1)
    # published layers 0 and 8-11: dense window, then one whole period
    assert cfg.layer_types == (WINDOW,) * 4 + (FULL,)
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.sliding_window,
            cfg.vocab_size, cfg.max_seq_len) == (5, 1, 4096, 25024, 8192)
    assert (cfg.route_scale, cfg.mup_enabled, cfg.layernorm_eps,
            cfg.rope_base, cfg.kv_window) == (2.448, True, 1e-5, 10000.0,
                                              4096)
    # the layer scores by a sigmoid and normalises; anything else is
    # refused by name
    for key, other in (("score_func", "softmax"), ("route_norm", False)):
        with pytest.raises(ValueError, match=key):
            AfmoeConfig.from_hf(dict(c, **{key: other}))
    full = dict(c, layer_types=["full_attention"] * len(c["layer_types"]))
    assert AfmoeConfig.from_hf(full).kv_window is None


# ------------------------------------------------------------- counters
def test_health_counts_experts_and_window_pages_by_hand():
    """Every expert held (32 of 32), so each real lane gives exactly
    top_k assignments an expert layer, and pad lanes none.  One request
    of 35 prompt tokens and 4 new ones, chunks of 8, pages of 8, window
    16: steps feed 8, 8, 8, 8, 3 prompt tokens, then 1 token three
    times."""
    cfg, model, params, _ = build(experts_held=None)
    server = InferenceServer(model, params, max_slots=2, block_size=8,
                             prefill_chunk=8, pool_tokens=256)
    before = server.health()         # not started: the test steps
    engine = server.engine
    prompt = np.arange(35, dtype=np.int32) + 5
    engine.admit(0, prompt, max_new_tokens=4)
    steps = 0
    while True:
        steps += 1
        if engine.step().finished[0]:
            break
    engine.release(0)
    after = server.health()
    moved = lambda k: after[k] - before[k]
    assert steps == 8
    real_lanes = 35 + 3                  # the 4th token is never fed
    # slot 1 is empty: a width-1 step gives an empty row one lane, the
    # five mixed steps' lane lists hold none of it
    lanes = real_lanes + steps - 5
    assert moved("expert_layer_steps") == 4 * steps
    assert moved("expert_assignments") == 4 * 4 * lanes
    assert 4 * steps <= moved("experts_active") <= 4 * 32 * steps
    assert moved("expert_load_max") >= moved("expert_assignments") / 32
    # cursors at dispatch (slot 1: 0 throughout)
    cursors = [0, 8, 16, 24, 32, 35, 36, 37]
    live = sum(c // 8 + 1 for c in cursors) + steps
    windowed = sum(c // 8 - max(c - 15, 0) // 8 + 1 for c in cursors) + steps
    assert moved("kv_pages_live") == live
    assert moved("kv_window_pages") == windowed
    assert windowed < live
    server.shutdown()


def test_a_model_without_experts_or_window_has_no_such_counters():
    from apex_tpu.models import LlamaConfig, LlamaModel

    model = LlamaModel(LlamaConfig.tiny())
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    engine = PagedEngine(model, {"params": params["params"]}, max_slots=2,
                         block_size=8)
    assert engine.window is None and engine.expert_layers == 0
    server = InferenceServer(model, {"params": params["params"]},
                             max_slots=2, block_size=8)
    health = server.health()
    assert not {"kv_window_pages", "expert_assignments", "expert_load_max",
                "experts_active", "expert_layer_steps"} & set(health)
    server.shutdown()


def test_mesh_is_refused_for_an_expert_share(tiny):
    _, model, params, _ = tiny
    with pytest.raises(ValueError, match="expert share"):
        PagedEngine(model, params, max_slots=2, block_size=8, mesh=2)
