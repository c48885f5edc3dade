"""MoE gating/dispatch golden tests + expert-parallel sharding
(beyond-reference extension; EP absent in apex — SURVEY.md §2.6)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.core import mesh as mesh_lib
from apex_tpu.transformer.moe import MoEConfig, MoEMLP, top_k_gating


class TestGating:
    def test_top1_routes_to_argmax(self, rng):
        logits = jnp.asarray(rng.normal(size=(12, 4)), jnp.float32)
        dispatch, combine, aux = top_k_gating(logits, k=1, capacity=12)
        choice = np.argmax(np.asarray(logits), axis=-1)
        d = np.asarray(dispatch)
        for t in range(12):
            assert d[t].sum() == 1.0
            assert d[t, choice[t]].sum() == 1.0
        # k=1 keeps the raw gate probability (Switch semantics — the
        # router's task-loss gradient flows through this scale)
        probs = np.asarray(jax.nn.softmax(logits, axis=-1))
        np.testing.assert_allclose(
            np.asarray(combine).sum(axis=(1, 2)),
            probs[np.arange(12), choice], rtol=1e-6)
        assert np.isfinite(float(aux))

    def test_capacity_drops_overflow(self):
        # all tokens prefer expert 0; capacity 2 keeps first 2 only
        logits = jnp.tile(jnp.asarray([[5.0, 0.0]]), (6, 1))
        dispatch, combine, _ = top_k_gating(logits, k=1, capacity=2)
        d = np.asarray(dispatch)
        assert d[:, 0].sum() == 2.0          # two tokens kept
        np.testing.assert_array_equal(d[2:].sum(axis=(1, 2)), 0.0)

    def test_top2_distinct_experts(self, rng):
        logits = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
        dispatch, _, _ = top_k_gating(logits, k=2, capacity=16)
        d = np.asarray(dispatch).sum(axis=2)  # (T, E)
        assert (d.sum(axis=1) == 2.0).all()
        assert (d <= 1.0).all()               # two different experts


class TestMoEMLP:
    @pytest.mark.l0
    def test_matches_manual_expert_computation(self, rng):
        cfg = MoEConfig(num_experts=4, top_k=1, hidden_size=8,
                        ffn_hidden_size=16, capacity_factor=4.0,
                        expert_axis=None)
        m = MoEMLP(cfg)
        x = jnp.asarray(rng.normal(size=(2, 3, 8)), jnp.float32)
        v = m.init(jax.random.PRNGKey(0), x)
        (y, aux) = m.apply(v, x)
        p = v["params"]
        xt = np.asarray(x).reshape(6, 8)
        logits = xt @ np.asarray(p["gate"])
        probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
        choice = logits.argmax(-1)
        want = np.zeros((6, 8), np.float32)
        for t in range(6):
            e = choice[t]
            h = xt[t] @ np.asarray(p["w1"])[e] + np.asarray(p["b1"])[e]
            h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
            out = h @ np.asarray(p["w2"])[e] + np.asarray(p["b2"])[e]
            # Switch semantics: top-1 output scaled by the gate prob
            want[t] = probs[t, e] * out
        np.testing.assert_allclose(np.asarray(y).reshape(6, 8), want,
                                   rtol=2e-3, atol=2e-4)
        assert np.isfinite(float(aux))

    def test_expert_parallel_matches_single_device(self, rng):
        cfg = MoEConfig(num_experts=4, top_k=2, hidden_size=8,
                        ffn_hidden_size=16, capacity_factor=2.0,
                        expert_axis="tensor")
        m = MoEMLP(cfg)
        x = jnp.asarray(rng.normal(size=(2, 4, 8)), jnp.float32)
        mesh = mesh_lib.initialize_mesh(tensor_model_parallel_size=4,
                                        data_parallel_size=2)
        try:
            with jax.set_mesh(mesh):
                v = jax.jit(m.init)(jax.random.PRNGKey(0), x)
                y_sh, aux_sh = jax.jit(m.apply)(v, x)
            # unsharded replay of the same params
            v_local = jax.tree.map(
                lambda a: np.asarray(a),
                jax.device_get(jax.tree.map(
                    lambda a: a.value if hasattr(a, "value") else a, v)))
            m_local = MoEMLP(
                MoEConfig(**{**cfg.__dict__, "expert_axis": None}))
            y_loc, aux_loc = m_local.apply(
                jax.tree.map(jnp.asarray, v_local), x)
            np.testing.assert_allclose(np.asarray(y_sh),
                                       np.asarray(y_loc),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(float(aux_sh), float(aux_loc),
                                       rtol=1e-5)
        finally:
            mesh_lib.destroy_mesh()

    def test_grads_flow(self, rng):
        cfg = MoEConfig(num_experts=2, top_k=1, hidden_size=4,
                        ffn_hidden_size=8, capacity_factor=4.0,
                        expert_axis=None)
        m = MoEMLP(cfg)
        x = jnp.asarray(rng.normal(size=(1, 4, 4)), jnp.float32)
        v = m.init(jax.random.PRNGKey(0), x)

        def loss(p):
            y, aux = m.apply({"params": p}, x)
            return jnp.mean(y ** 2) + aux

        g = jax.grad(loss)(v["params"])
        for leaf in jax.tree.leaves(g):
            assert np.all(np.isfinite(np.asarray(leaf)))
        # gate must receive gradient (through combine weights + aux)
        assert float(jnp.sum(jnp.abs(g["gate"]))) > 0.0


class TestMoEInModelZoo:
    """num_moe_experts wires MoEMLP into every transformer layer
    (Mixtral-style) — model-level contract: routing works under the
    scanned/unrolled stacks, the aux loss reaches the caller through
    the sown "losses" collection, and the router is trained by it."""

    def _tiny_moe(self, scan, **kw):
        from apex_tpu.models import LlamaConfig, LlamaModel

        cfg = LlamaConfig.tiny(num_moe_experts=4, moe_top_k=2,
                               scan_layers=scan, **kw)
        return cfg, LlamaModel(cfg)

    @pytest.mark.parametrize("scan", [False, True])
    def test_forward_and_aux_loss(self, rng, scan):
        import jax.numpy as jnp

        from apex_tpu.models import moe_aux_loss

        cfg, model = self._tiny_moe(scan)
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)),
                          jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids)
        logits, mut = model.apply(
            {"params": params["params"]}, ids, mutable=["losses"])
        assert logits.shape == (2, 16, cfg.vocab_size)
        aux = moe_aux_loss(mut)
        # Switch load-balance loss is >= 1 at weight 1 for an
        # imperfectly balanced router; weighted by 1e-2 x num_layers
        assert float(aux) > 0.0
        # without mutable=["losses"] the sow is dropped, not an error
        logits2 = model.apply({"params": params["params"]}, ids)
        np.testing.assert_allclose(np.asarray(logits2),
                                   np.asarray(logits), rtol=1e-6,
                                   atol=1e-6)

    def test_router_gets_gradient_from_aux(self, rng):
        import jax.numpy as jnp

        from apex_tpu.models import gpt_loss_fn, moe_aux_loss

        cfg, model = self._tiny_moe(False)
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 17)),
                          jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids[:, :-1])

        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p}, ids[:, :-1], mutable=["losses"])
            return (gpt_loss_fn(logits.astype(jnp.float32), ids[:, 1:])
                    + moe_aux_loss(mut))

        grads = jax.grad(loss_fn)(params["params"])
        gate = grads["transformer"]["layer_0"]["moe_mlp"]["gate"]
        assert float(jnp.max(jnp.abs(gate))) > 0.0
        assert all(bool(jnp.isfinite(g).all())
                   for g in jax.tree.leaves(grads))

    def test_decode_matches_full_forward(self, rng):
        """Greedy decode through the cache must match the full forward
        (per-token routing is independent; ample capacity -> no
        drops on either path)."""
        import jax.numpy as jnp

        cfg, model = self._tiny_moe(False, moe_capacity_factor=4.0)
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 10)),
                          jnp.int32)
        params = model.init(jax.random.PRNGKey(0), ids)
        params = {"params": params["params"]}
        full = model.apply(params, ids, deterministic=True)
        from apex_tpu.models import init_cache

        cache = init_cache(model, 2)
        logits, vars_ = model.apply(
            {**params, "cache": cache}, ids[:, :4],
            deterministic=True, decode=True, mutable=["cache"])
        outs = [logits]
        for t in range(4, 10):
            step, vars_ = model.apply(
                {**params, "cache": vars_["cache"]}, ids[:, t:t + 1],
                deterministic=True, decode=True, mutable=["cache"])
            outs.append(step)
        inc = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                                   atol=2e-5, rtol=2e-5)

    def test_mixtral_preset_geometry(self):
        from apex_tpu.models import LlamaConfig

        cfg = LlamaConfig.mixtral_8x7b()
        assert cfg.num_moe_experts == 8 and cfg.moe_top_k == 2
        assert cfg.sliding_window == 4096 and cfg.gated_mlp
        assert cfg.num_kv_heads == 8 and cfg.norm == "rmsnorm"

    def test_moe_config_validation(self):
        from apex_tpu.models import LlamaConfig

        with pytest.raises(ValueError, match="num_moe_experts"):
            LlamaConfig.tiny(num_moe_experts=1)
        with pytest.raises(ValueError, match="moe_top_k"):
            LlamaConfig.tiny(num_moe_experts=2, moe_top_k=3)

    def test_init_is_pure_params_and_biasfree_experts(self, rng):
        """Round-5 review regressions: (a) init must NOT leak a sown
        'losses' collection (it would ride into optimizer state and
        double-count on the first apply); (b) bias-free recipes
        (add_bias_linear=False, the Llama/Mixtral family) must get
        bias-free experts."""
        import jax.numpy as jnp

        cfg, model = self._tiny_moe(False)
        ids = jnp.zeros((1, 8), jnp.int32)
        variables = model.init(jax.random.PRNGKey(0), ids)
        assert set(variables) == {"params"}, set(variables)
        moe = variables["params"]["transformer"]["layer_0"]["moe_mlp"]
        assert cfg.add_bias_linear is False
        assert "b1" not in moe and "b2" not in moe, sorted(moe)
        assert "wg" in moe                      # gated (SwiGLU) experts


# --------------------------------------------------------------------- #
# the serving layer (ISSUE 37): drop-free, one chip's share
# --------------------------------------------------------------------- #
from apex_tpu.ops.expert_gmm import (ROW_TILE, expert_gmm,     # noqa: E402
                                     expert_gmm_reference)
from apex_tpu.transformer.moe import (ExpertShareConfig,       # noqa: E402
                                      ExpertShareMLP, route_top_k)


def _share(**kw):
    base = dict(num_experts=16, top_k=4, route_scale=2.448,
                hidden_size=32, ffn_hidden_size=128)
    base.update(kw)
    return ExpertShareConfig(**base)


def _layer(cfg):
    """The layer with the zoo's SwiGLU as its shared expert."""
    from apex_tpu.models.transformer import ParallelMLP, TransformerConfig

    return ExpertShareMLP(cfg, ParallelMLP(TransformerConfig(
        hidden_size=cfg.hidden_size, num_heads=1,
        ffn_hidden_size=cfg.ffn_hidden_size, activation="silu",
        gated_mlp=True, add_bias_linear=False)))


def _apply(cfg, params, x, **kw):
    """The layer on ``x``, its own matrices (``w_in``, ``w_down`` of
    ``params``) as the bank."""
    params = dict(params)
    bank = params.pop("w_in"), params.pop("w_down")
    return _layer(cfg).apply({"params": params}, x, bank, **kw)


def _layer_params(cfg, key, **over):
    x = jnp.zeros((1, 4, cfg.hidden_size), jnp.float32)
    h, f = cfg.hidden_size, cfg.ffn_hidden_size
    params = _layer(cfg).init(key, x, (
        jnp.zeros((cfg.held, h, 2 * f)), jnp.zeros((cfg.held, f, h))))[
            "params"]
    ks = jax.random.split(key, 4)
    params = dict(
        params,
        router=jax.random.normal(ks[0], (h, cfg.num_experts)) * 0.3,
        expert_bias=jax.random.normal(ks[1], (cfg.num_experts,)) * 0.1,
        w_in=jax.random.normal(ks[2], (cfg.held, h, 2 * f)) / h ** 0.5,
        w_down=jax.random.normal(ks[3], (cfg.held, f, h)) / f ** 0.5)
    params.update(over)
    return params


def _by_hand(cfg, params, x):
    """The layer in numpy, token by token, expert by expert."""
    val = lambda v: np.asarray(getattr(v, "value", v), np.float64)
    x = np.asarray(x, np.float64).reshape(-1, cfg.hidden_size)
    silu = lambda a: a / (1.0 + np.exp(-a))
    scores = 1.0 / (1.0 + np.exp(-(x @ val(params["router"]))))
    picked = np.argsort(-(scores + val(params["expert_bias"])),
                        axis=-1, kind="stable")[:, : cfg.top_k]
    mlp = params["shared_expert"]
    out = (silu(x @ val(mlp["dense_h_to_4h_gate"]["kernel"]))
           * (x @ val(mlp["dense_h_to_4h"]["kernel"]))) \
        @ val(mlp["dense_4h_to_h"]["kernel"])
    f = cfg.ffn_hidden_size
    counts = np.zeros(cfg.held, np.int64)
    for t in range(x.shape[0]):
        denom = scores[t, picked[t]].sum() + 1e-20
        for e in picked[t]:
            local = e - cfg.expert_offset
            if not 0 <= local < cfg.held:
                continue
            counts[local] += 1
            y = x[t] @ val(params["w_in"])[local]
            y = (silu(y[:f]) * y[f:]) @ val(params["w_down"])[local]
            out[t] += cfg.route_scale * scores[t, e] / denom * y
    return out, counts


class TestRouter:
    def test_selects_by_score_plus_bias_and_weights_by_score(self, rng):
        cfg = _share()
        x = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(32, 16)) * 0.3, jnp.float32)
        b = jnp.asarray(rng.normal(size=(16,)) * 0.2, jnp.float32)
        ids, weights = route_top_k(cfg, x, w, b)
        scores = 1 / (1 + np.exp(-np.asarray(x, np.float64)
                                 @ np.asarray(w, np.float64)))
        want = np.argsort(-(scores + np.asarray(b)), axis=-1)[:, :4]
        np.testing.assert_array_equal(np.sort(np.asarray(ids), -1),
                                      np.sort(want, -1))
        s = np.take_along_axis(scores, np.asarray(ids), -1)
        np.testing.assert_allclose(
            np.asarray(weights),
            2.448 * s / (s.sum(-1, keepdims=True) + 1e-20), rtol=1e-5)
        # normalised over all four selected, then scaled
        np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.448,
                                   rtol=1e-5)

    def test_a_large_bias_changes_the_selection_and_not_the_weights(
            self, rng):
        cfg = _share()
        x = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(32, 16)) * 0.3, jnp.float32)
        bias = jnp.zeros((16,)).at[5].set(10.0)
        ids, weights = route_top_k(cfg, x, w, bias)
        assert bool(jnp.all(jnp.any(ids == 5, axis=-1)))   # always chosen
        free, _ = route_top_k(cfg, x, w, jnp.zeros((16,)))
        assert not bool(jnp.all(jnp.any(free == 5, axis=-1)))
        # expert 5's weight is its SCORE's share, never its bias's
        scores = np.asarray(jax.nn.sigmoid(x @ w))
        s = np.take_along_axis(scores, np.asarray(ids), -1)
        np.testing.assert_allclose(
            np.asarray(weights), 2.448 * s / s.sum(-1, keepdims=True),
            rtol=1e-5)
        assert float(jnp.max(weights)) < 2.448

    @pytest.mark.parametrize("scale,top_k", [(1.0, 4), (0.5, 4),
                                             (2.448, 1)])
    def test_weights_of_the_selected_add_up_to_the_scale(self, rng, scale,
                                                         top_k):
        cfg = _share(route_scale=scale, top_k=top_k)
        x = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(32, 16)) * 0.3, jnp.float32)
        ids, weights = route_top_k(cfg, x, w, jnp.zeros((16,)))
        assert ids.shape == weights.shape == (8, top_k)
        np.testing.assert_allclose(np.asarray(weights).sum(-1), scale,
                                   rtol=1e-5)


class TestExpertShare:
    @pytest.mark.parametrize("held,offset", [(None, 0), (4, 0), (4, 8),
                                             (6, 10)])
    def test_layer_is_the_hand_computation(self, rng, held, offset):
        cfg = _share(experts_held=held, expert_offset=offset)
        params = _layer_params(cfg, jax.random.PRNGKey(held or 0))
        x = jnp.asarray(rng.normal(size=(2, 9, 32)), jnp.float32)
        with jax.default_matmul_precision("highest"):
            out, counts = _apply(cfg, params, x)
        want, want_counts = _by_hand(cfg, params, x)
        np.testing.assert_allclose(np.asarray(out).reshape(-1, 32), want,
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_array_equal(np.asarray(counts), want_counts)

    def test_no_token_is_dropped_when_every_token_picks_one_expert(
            self, rng):
        """A GShard layer at capacity 1.25 would drop most of these;
        here expert 2 multiplies every token, and says so."""
        cfg = _share(experts_held=4)
        params = _layer_params(
            cfg, jax.random.PRNGKey(1),
            expert_bias=jnp.zeros((16,)).at[2].set(10.0))
        x = jnp.asarray(rng.normal(size=(3, 40, 32)), jnp.float32)
        with jax.default_matmul_precision("highest"):
            out, counts = _apply(cfg, params, x)
        want, want_counts = _by_hand(cfg, params, x)
        assert int(counts[2]) == 3 * 40 == int(want_counts[2])
        assert int(jnp.max(counts)) == 120      # what expert_load_max sums
        np.testing.assert_allclose(np.asarray(out).reshape(-1, 32), want,
                                   atol=2e-5, rtol=2e-5)

    def test_pad_lanes_are_routed_nowhere(self, rng):
        cfg = _share(experts_held=None)
        params = _layer_params(cfg, jax.random.PRNGKey(2))
        x = jnp.asarray(rng.normal(size=(2, 8, 32)), jnp.float32)
        valid = jnp.arange(8)[None] < jnp.asarray([3, 8])[:, None]
        with jax.default_matmul_precision("highest"):
            out, counts = _apply(cfg, params, x, valid=valid)
            every, _ = _apply(cfg, params, x)
            shared = _apply(cfg, dict(
                params, w_down=params["w_down"] * 0), x)[0]
        assert int(counts.sum()) == (3 + 8) * cfg.top_k
        np.testing.assert_allclose(np.asarray(out[0, :3]),
                                   np.asarray(every[0, :3]), atol=1e-6)
        np.testing.assert_allclose(np.asarray(out[1]), np.asarray(every[1]),
                                   atol=1e-6)
        # a pad lane keeps the shared expert's part alone
        np.testing.assert_allclose(np.asarray(out[0, 3:]),
                                   np.asarray(shared[0, 3:]), atol=1e-6)

    def test_a_bank_of_several_layers_experts(self, rng):
        """Handed a bank and its first group, the layer multiplies its
        own experts' rows only: the result is the layer's that owns
        the same matrices."""
        cfg = _share(experts_held=4, expert_offset=4)
        params = _layer_params(cfg, jax.random.PRNGKey(3))
        x = jnp.asarray(rng.normal(size=(2, 9, 32)), jnp.float32)
        own = _apply(cfg, params, x)
        # other layers' matrices: loud, so that a wrong group shows
        junk = lambda like: jnp.asarray(
            100.0 * rng.normal(size=like.shape), jnp.float32)
        bank = tuple(
            jnp.concatenate([junk(params[k]), params[k], junk(params[k])])
            for k in ("w_in", "w_down"))
        rest = {k: v for k, v in params.items()
                if k not in ("w_in", "w_down")}
        got = _layer(cfg).apply({"params": rest}, x, bank, jnp.int32(4))
        for a, b in zip(got, own):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)

    def test_share_validation(self):
        for kw, match in ((dict(top_k=0), "top_k"),
                          (dict(experts_held=0), "experts"),
                          (dict(experts_held=8, expert_offset=12),
                           "experts")):
            with pytest.raises(ValueError, match=match):
                _share(**kw)


class TestExpertGmm:
    @pytest.mark.parametrize("sizes", [
        [16, 0, 40, 7, 0, 1],           # empty groups, a tail behind
        [0, 0, 0, 0, 0, 128],           # everything on the last expert
        [128, 0, 0, 0, 0, 0],           # ... on the first
        [0, 0, 0, 0, 0, 0],             # nothing at all
        [30, 30, 30, 30, 30, 106]])     # groups that straddle row tiles
    def test_kernel_is_the_ragged_dot(self, rng, sizes):
        m = 2 * ROW_TILE
        lhs = jnp.asarray(rng.normal(size=(m, 128)), jnp.float32)
        rhs = jnp.asarray(rng.normal(size=(6, 128, 256)), jnp.float32)
        sizes = jnp.asarray(sizes, jnp.int32)
        want = expert_gmm_reference(lhs, rhs, sizes)
        got = expert_gmm(lhs, rhs, sizes,
                         implementation="pallas_interpret")
        live = int(sizes.sum())
        # rows behind the last group are undefined in the kernel
        np.testing.assert_allclose(np.asarray(got)[:live],
                                   np.asarray(want)[:live],
                                   atol=2e-4, rtol=2e-4)

    def test_off_a_tpu_auto_is_the_reference(self, rng):
        lhs = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)
        rhs = jnp.asarray(rng.normal(size=(3, 16, 8)), jnp.float32)
        sizes = jnp.asarray([5, 0, 11], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(expert_gmm(lhs, rhs, sizes)),
            np.asarray(expert_gmm_reference(lhs, rhs, sizes)))
        # outside the kernel's envelope an explicit kernel raises
        with pytest.raises(ValueError, match="envelope"):
            expert_gmm(lhs, rhs, sizes, implementation="pallas")
        with pytest.raises(ValueError, match="do not fit"):
            expert_gmm(lhs, rhs[:, :8], sizes)
