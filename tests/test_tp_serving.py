"""Tensor-parallel paged serving (ISSUE 13): one replica spans the mesh.

Correctness contracts under test:

- the GQA group→shard mapping (``ops.paged_attention.tp_head_shards``)
  and its loud config-time divisibility gate — ``kv_heads % tp != 0``
  raises a ``ValueError`` at construction, never a shape error deep
  inside shard_map (TransformerConfig, PagedEngine and InferenceServer
  all reject it);
- the sharded ``paged_attention`` op is BITWISE identical to the
  unsharded reference — MHA and GQA, decode and multi-token chunks,
  unquantized and int8 pages (per-(kv_head, page) scales shard on the
  same leading axis);
- the TP engine's pool and weights are ACTUALLY placed across the mesh
  (and stay so after steps — the sharding fixed point behind the
  retrace budgets);
- greedy decode through a TP engine with prefix sharing + speculative
  decoding on is token-identical to ``generate()``, and with int8
  pages additionally token-identical to the single-chip quantized
  engine (quantized chains are deterministic per (tokens, knobs), not
  generate-bitwise — the PR-8 band contract);
- a mixed-traffic soak on the sharded engine stays at the EXACT 5×1
  executable budget with zero retraces — TP changes where tensors
  live, not how many programs exist;
- ``InferenceServer(tp=)`` plumbing: health()/metrics gain
  ``chips_per_replica`` / ``mesh_shape`` / per-chip throughput;
- autotune winners are keyed on the PER-SHARD kv_heads count: a TP
  engine adopts the winner swept at ``kv_heads / tp`` and never the
  full-head-count one (and vice versa).

The fleet-level merged chips view lives in ``test_fleet.py``; the
sharded-replica kill soak in ``test_chaos.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.core.mesh import TENSOR_AXIS
from apex_tpu.models import (
    GPTConfig,
    GPTModel,
    LlamaConfig,
    LlamaModel,
    generate,
)
from apex_tpu.ops.paged_attention import (
    paged_attention,
    quantize_kv_pages,
    tp_head_shards,
)
from apex_tpu.serving import (
    InferenceServer,
    PagedEngine,
    Request,
    Scheduler,
    tp_mesh,
)
from apex_tpu.utils import MetricsWriter


@pytest.fixture(scope="module")
def gpt():
    cfg = GPTConfig.tiny(position_embedding="learned",
                         scan_layers=True)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return model, {"params": params["params"]}


@pytest.fixture(scope="module")
def mesh2():
    return tp_mesh(2)


#: the full serving stack — sharing + drafting + quantized pages —
#: built ONCE per module at both layouts (every test that needs a
#: warmed engine reuses these; trace counts must end the module at
#: exactly 1 each)
FULL_KW = dict(max_slots=3, block_size=8, prefill_chunk=4,
               share_prefixes=True, spec_tokens=3, kv_dtype="int8")


@pytest.fixture(scope="module")
def full_engines(gpt, mesh2):
    model, params = gpt
    single = PagedEngine(model, params, **FULL_KW)
    tp = PagedEngine(model, params, mesh=mesh2, **FULL_KW)
    single.warmup()
    tp.warmup()
    return single, tp


def _drain(engine, cases, *, queue_capacity=32):
    """Run ``cases`` = [(prompt, n, kwargs)] through a scheduler to
    completion; returns uid-ordered token lists."""
    sched = Scheduler(engine, queue_capacity=queue_capacity)
    for prompt, n, kw in cases:
        sched.submit(Request(prompt=np.asarray(prompt, np.int32),
                             max_new_tokens=int(n), **kw))
    events = sched.drain()
    out = {}
    for ev in events:
        out.setdefault(ev.request.uid, []).append(ev.token)
    return [out[uid] for uid in sorted(out)]


# --------------------------------------------------------------------- #
# the GQA group→shard mapping
# --------------------------------------------------------------------- #
class TestHeadShardMapping:
    def test_mha_even_split(self):
        assert tp_head_shards(8, 8, 2) == [((0, 4), (0, 4)),
                                           ((4, 8), (4, 8))]

    def test_gqa_groups_stay_whole(self):
        # 8 q heads over 4 kv heads (rep=2), tp=2: each shard owns 2
        # whole GQA groups — 4 q heads aligned with its 2 kv heads
        assert tp_head_shards(8, 4, 2) == [((0, 4), (0, 2)),
                                           ((4, 8), (2, 4))]
        # tp == kv_heads: one group per shard (rep q heads each)
        assert tp_head_shards(8, 4, 4) == [
            ((0, 2), (0, 1)), ((2, 4), (1, 2)),
            ((4, 6), (2, 3)), ((6, 8), (3, 4))]

    def test_tp1_is_the_whole_model(self):
        assert tp_head_shards(16, 4, 1) == [((0, 16), (0, 4))]

    def test_indivisible_kv_heads_raise_loudly(self):
        with pytest.raises(ValueError, match="divisible by the "
                                             "tensor-parallel"):
            tp_head_shards(8, 4, 3)

    def test_bad_gqa_ratio_raises(self):
        with pytest.raises(ValueError, match="must divide num_heads"):
            tp_head_shards(6, 4, 2)


# --------------------------------------------------------------------- #
# op-level: sharded == unsharded, bitwise
# --------------------------------------------------------------------- #
class TestShardedPagedAttentionOp:
    def _pool(self, rng, *, h, hk, d=16, bs=8, mb=5, b=3, s=1,
              kv_dtype=None):
        nb = b * mb + 1
        q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(hk, nb, bs, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(hk, nb, bs, d)), jnp.float32)
        tables = jnp.asarray(
            rng.permutation(np.arange(1, nb))[:b * mb].reshape(b, mb),
            jnp.int32)
        lengths = jnp.asarray(
            rng.integers(0, mb * bs - s, size=(b,)), jnp.int32)
        scales = {}
        if kv_dtype is not None:
            kp, vp, ks, vs = quantize_kv_pages(kp, vp, kv_dtype)
            scales = dict(k_scales=ks, v_scales=vs)
        return q, kp, vp, tables, lengths, scales

    @pytest.mark.parametrize("h,hk", [(4, 4), (8, 4)],
                             ids=["mha", "gqa"])
    @pytest.mark.parametrize("s", [1, 4], ids=["decode", "chunk"])
    def test_sharded_matches_unsharded(self, mesh2, h, hk, s):
        rng = np.random.default_rng(7)
        q, kp, vp, tables, lengths, _ = self._pool(
            rng, h=h, hk=hk, s=s)
        ref = paged_attention(q, kp, vp, tables, lengths)
        tp = paged_attention(q, kp, vp, tables, lengths,
                             mesh=mesh2, shard_axis=TENSOR_AXIS)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(tp))

    def test_sharded_matches_unsharded_int8(self, mesh2):
        # quant scales carry the same leading kv_heads axis and shard
        # with their pages — the in-shard dequant is bitwise the
        # single-chip one
        rng = np.random.default_rng(8)
        q, kp, vp, tables, lengths, scales = self._pool(
            rng, h=8, hk=4, s=2, kv_dtype="int8")
        ref = paged_attention(q, kp, vp, tables, lengths, **scales)
        tp = paged_attention(q, kp, vp, tables, lengths, **scales,
                             mesh=mesh2, shard_axis=TENSOR_AXIS)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(tp))

    def test_sharded_under_jit(self, mesh2):
        rng = np.random.default_rng(9)
        q, kp, vp, tables, lengths, _ = self._pool(rng, h=4, hk=4)
        ref = paged_attention(q, kp, vp, tables, lengths)
        fn = jax.jit(lambda q: paged_attention(
            q, kp, vp, tables, lengths, mesh=mesh2,
            shard_axis=TENSOR_AXIS))
        np.testing.assert_array_equal(np.asarray(ref),
                                      np.asarray(fn(q)))

    def test_indivisible_heads_raise_inside_op(self, mesh2):
        rng = np.random.default_rng(10)
        q, kp, vp, tables, lengths, _ = self._pool(rng, h=3, hk=3)
        with pytest.raises(ValueError, match="divisible"):
            paged_attention(q, kp, vp, tables, lengths,
                            mesh=mesh2, shard_axis=TENSOR_AXIS)


# --------------------------------------------------------------------- #
# config-time validation — the loud gates
# --------------------------------------------------------------------- #
class TestConfigTimeValidation:
    def test_transformer_config_requires_paged(self, mesh2):
        with pytest.raises(ValueError, match="kv_cache='paged'"):
            GPTConfig.tiny(kv_shard_axis=TENSOR_AXIS, kv_mesh=mesh2)

    def test_axis_and_mesh_come_together(self):
        with pytest.raises(ValueError, match="come together"):
            GPTConfig.tiny(kv_cache="paged", kv_pool_blocks=4,
                           kv_shard_axis=TENSOR_AXIS)

    def test_axis_must_exist_in_mesh(self, mesh2):
        with pytest.raises(ValueError, match="not an[\\s]+axis"):
            GPTConfig.tiny(kv_cache="paged", kv_pool_blocks=4,
                           kv_shard_axis="nonesuch", kv_mesh=mesh2)

    def test_kv_heads_divisibility_at_config_time(self):
        # tiny GPT has 2 kv heads; a 3-wide tensor axis cannot split
        # them — the error fires in the frozen config's __post_init__
        mesh3 = tp_mesh(3)
        with pytest.raises(ValueError, match="divisible by the "
                                             "tensor-parallel"):
            GPTConfig.tiny(kv_cache="paged", kv_pool_blocks=4,
                           kv_shard_axis=TENSOR_AXIS, kv_mesh=mesh3)

    def test_engine_rejects_indivisible_tp(self, gpt):
        model, params = gpt
        with pytest.raises(ValueError, match="divisible by the "
                                             "tensor-parallel"):
            PagedEngine(model, params, mesh=3)

    def test_server_rejects_tp_mesh_mismatch(self, gpt, mesh2):
        model, params = gpt
        with pytest.raises(ValueError, match="disagrees with mesh"):
            InferenceServer(model, params, tp=4, mesh=mesh2)
        # mesh may be the engine's int spelling: still the loud
        # mismatch error, never an AttributeError on .shape
        with pytest.raises(ValueError, match="disagrees with mesh"):
            InferenceServer(model, params, tp=4, mesh=2)

    def test_tp_mesh_needs_enough_devices(self):
        with pytest.raises(ValueError, match="devices"):
            tp_mesh(2, jax.devices()[:1])

    def test_engine_rejects_mesh_without_tensor_axis(self, gpt):
        # loud, not a silent single-chip fallback: a foreign-axis mesh
        # means the caller BELIEVES they are tensor-parallel
        model, params = gpt
        foreign = jax.sharding.Mesh(
            np.asarray(jax.devices()[:2]), ("model",))
        with pytest.raises(ValueError, match="no 'tensor' axis"):
            PagedEngine(model, params, mesh=foreign)


class TestTrafficModelICI:
    def test_ici_column_and_per_chip_reads(self):
        import bench_configs as bc

        tm1 = bc._serving_traffic_model(
            num_layers=2, kv_heads=2, head_dim=16, max_seq_len=64,
            live_tokens=24, slots=2, block_size=8, dtype_bytes=4)
        assert tm1["tp"] == 1 and tm1["ici_bytes_per_step"] == 0
        tm2 = bc._serving_traffic_model(
            num_layers=2, kv_heads=2, head_dim=16, max_seq_len=64,
            live_tokens=24, slots=2, block_size=8, dtype_bytes=4,
            tp=2, hidden_size=32)
        # ring all-reduce: 2 reduces/layer × (slots·hidden·bytes) ×
        # 2(tp-1)/tp per chip
        assert tm2["ici_bytes_per_step_per_chip"] == int(
            2 * 2 * 2 * 32 * 4 * 2 * (2 - 1) / 2)
        assert tm2["ici_bytes_per_step"] == \
            2 * tm2["ici_bytes_per_step_per_chip"]
        assert tm2["paged_kv_read_bytes_per_step_per_chip"] * 2 == \
            tm2["paged_kv_read_bytes_per_step"]
        # the kv-head-sharded read column is live-dependent, like its
        # single-chip parent
        with pytest.raises(ValueError, match="hidden_size"):
            bc._serving_traffic_model(
                num_layers=2, kv_heads=2, head_dim=16, max_seq_len=64,
                live_tokens=24, slots=2, block_size=8, tp=2)

    def test_quantized_per_chip_read_uses_quantized_bytes(self):
        import bench_configs as bc

        tm = bc._serving_traffic_model(
            num_layers=2, kv_heads=2, head_dim=16, max_seq_len=64,
            live_tokens=24, slots=2, block_size=8, dtype_bytes=4,
            kv_dtype="int8", tp=2, hidden_size=32)
        assert tm["paged_kv_read_bytes_per_step_per_chip_quantized"] \
            * 2 == tm["paged_kv_read_bytes_per_step_quantized"]
        # the quantized per-chip read must sit well under the
        # unquantized one (1-byte codes vs 4-byte floats)
        assert tm["paged_kv_read_bytes_per_step_per_chip_quantized"] \
            < tm["paged_kv_read_bytes_per_step_per_chip"]


# --------------------------------------------------------------------- #
# engine-level: placement, parity, budgets
# --------------------------------------------------------------------- #
def _find_leaf(tree, name):
    hits = [leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]
            if str(getattr(path[-1], "key", path[-1])) == name]
    assert hits, f"no {name} leaf"
    return hits[0]


class TestTPPlacement:
    def test_pool_and_weights_span_the_mesh(self, full_engines):
        """The memory story is real only if the arrays are really
        split: the pool leaves shard their kv_heads dim over both
        chips, at least one weight is sharded per its GSPMD
        annotation, and the block tables stay replicated."""
        _single, tp = full_engines
        pk = _find_leaf(tp.cache, "paged_key")
        spec = pk.sharding.spec
        assert TENSOR_AXIS in spec, spec
        assert spec.index(TENSOR_AXIS) == pk.ndim - 4
        ks = _find_leaf(tp.cache, "key_scales")
        assert ks.sharding.spec.index(TENSOR_AXIS) == ks.ndim - 2
        bt = _find_leaf(tp.cache, "block_tables")
        assert TENSOR_AXIS not in tuple(bt.sharding.spec)
        sharded_params = [
            leaf for leaf in jax.tree.leaves(tp._variables)
            if TENSOR_AXIS in tuple(getattr(
                getattr(leaf, "sharding", None), "spec", ()) or ())]
        assert sharded_params, "no weight actually sharded"

    def test_placement_is_a_fixed_point_across_steps(self,
                                                     full_engines):
        # after real traffic the donated cache must land exactly where
        # it started (the retrace budgets depend on it)
        _single, tp = full_engines
        tp.admit(0, np.arange(5, dtype=np.int32) + 1,
                 max_new_tokens=2)
        while tp._tenants[0] is not None:
            out = tp.step()
            if int(out.counts[0]) and bool(out.finished[0]):
                break
        tp.release(0)
        pk = _find_leaf(tp.cache, "paged_key")
        assert pk.sharding.spec.index(TENSOR_AXIS) == pk.ndim - 4

    def test_gauges(self, full_engines):
        single, tp = full_engines
        assert single.chips_per_replica == 1
        assert single.mesh_shape is None
        assert tp.chips_per_replica == 2
        assert tp.mesh_shape == {"tensor": 2}


class TestTPTokenIdentity:
    #: prompt lengths straddling every boundary that matters at
    #: block_size=8 / prefill_chunk=4: page-1, page, page+1, chunk
    #: multiples, and a shared-prefix continuation
    LENGTHS = (7, 8, 9, 12, 16)

    def test_full_stack_tp_vs_single_chip(self, full_engines):
        """Sharing + drafting + int8 pages: the sharded engine's
        greedy chains equal the single-chip quantized engine's, page
        pools drain to 0 on both, and sharing actually engaged (the
        first 8-token block is common to every prompt)."""
        single, tp = full_engines
        rng = np.random.default_rng(3)
        base = rng.integers(0, 1024, size=(8,)).astype(np.int32)
        cases = []
        for i, L in enumerate(self.LENGTHS):
            tail = rng.integers(0, 1024, size=(max(L - 8, 0),))
            prompt = np.concatenate([base, tail])[:L].astype(np.int32)
            cases.append((prompt, 9, dict(seed=i)))
        # one sampled tenant rides along (sampled chains are a
        # function of the request's own seed — layout-independent)
        cases.append((base, 6, dict(temperature=0.9, top_p=0.9,
                                    seed=42)))
        got_single = _drain(single, cases)
        got_tp = _drain(tp, cases)
        assert got_single == got_tp
        assert single.blocks_in_use == 0 and tp.blocks_in_use == 0
        assert tp.trie_blocks == 0        # trie forgot freed pages

    def test_tp_greedy_token_identical_to_generate(self, gpt, mesh2):
        """Unquantized TP engine with sharing + drafting on: greedy
        output token-identical to ``generate()`` (the acceptance
        anchor — int8 runs compare engine-to-engine above because
        quantization is a band vs generate, by design)."""
        model, params = gpt
        eng = PagedEngine(model, params, max_slots=3, block_size=8,
                          prefill_chunk=4, share_prefixes=True,
                          spec_tokens=3, mesh=mesh2)
        eng.warmup()
        rng = np.random.default_rng(5)
        cases = [(rng.integers(0, 1024, size=(L,)).astype(np.int32),
                  8, dict(seed=i))
                 for i, L in enumerate(self.LENGTHS)]
        got = _drain(eng, cases)
        for (prompt, n, _kw), toks in zip(cases, got):
            ref = np.asarray(generate(
                model, params, jnp.asarray(prompt[None]),
                max_new_tokens=n))[0, len(prompt):]
            np.testing.assert_array_equal(
                np.asarray(toks), ref,
                err_msg=f"TP engine diverged from generate() at "
                        f"L={len(prompt)}")
        assert eng.blocks_in_use == 0
        # the soak engine budget: 5 executables × 1 trace
        assert eng.trace_counts == {
            "decode_step": 1, "prefill_step": 1, "admit": 1,
            "release": 1, "spec_step": 1}


class TestTPZeroRetraceSoak:
    def test_mixed_traffic_at_exactly_5x1(self, full_engines):
        """Steady-state mixed traffic (greedy / temperature / top-p /
        eos budgets, shared and private prompts, drafted and
        draft-hostile) on the SHARDED engine: the documented budget is
        5 executables × 1 trace — any retrace raises RetraceError, and
        the counts must still read exactly 1 afterwards."""
        _single, tp = full_engines
        before = dict(tp.trace_counts)
        assert all(v == 1 for v in before.values()), before
        rng = np.random.default_rng(11)
        cases = []
        for i in range(8):
            L = int(rng.integers(2, 20))
            kw = {"seed": i}
            if i % 3 == 1:
                kw.update(temperature=1.1, top_k=7)
            if i % 3 == 2:
                kw.update(temperature=0.8, top_p=0.85)
            cases.append((rng.integers(0, 1024, size=(L,)), 6, kw))
        _drain(tp, cases)
        after = dict(tp.trace_counts)
        assert after == {"decode_step": 1, "prefill_step": 1,
                         "admit": 1, "release": 1, "spec_step": 1}
        assert tp.blocks_in_use == 0


# --------------------------------------------------------------------- #
# server plumbing
# --------------------------------------------------------------------- #
class TestTPServer:
    def test_tp_server_serves_and_reports_mesh(self, gpt):
        model, params = gpt
        rows = []
        writer = MetricsWriter(sink=lambda s, m: rows.append(m))
        server = InferenceServer(
            model, params, max_slots=2, block_size=8, prefill_chunk=4, tp=2,
            metrics=writer, metrics_interval=1)
        rng = np.random.default_rng(2)
        with server:
            prompts = [rng.integers(0, 1024, size=(L,)).astype(
                np.int32) for L in (5, 11)]
            handles = [server.submit(p, max_new_tokens=6, seed=i)
                       for i, p in enumerate(prompts)]
            results = [h.result(timeout=300) for h in handles]
            health = server.health()
        assert health["chips_per_replica"] == 2
        assert health["mesh_shape"] == {"tensor": 2}
        # greedy through the TP server == generate()
        for p, toks in zip(prompts, results):
            ref = np.asarray(generate(
                model, params, jnp.asarray(p[None]),
                max_new_tokens=6))[0, len(p):]
            np.testing.assert_array_equal(np.asarray(toks), ref)
        merged = {}
        for m in rows:
            merged.update(m)
        assert merged.get("chips_per_replica") == 2
        assert "tokens_per_sec_per_chip" in merged
        assert merged["tokens_per_sec_per_chip"] * 2 == pytest.approx(
            merged["tokens_per_sec"])

    def test_single_chip_server_reports_one_chip(self, gpt):
        model, params = gpt
        server = InferenceServer(model, params, max_slots=1,
                                 block_size=8,
                                 prefill_chunk=4)
        health = server.health()      # probe works unstarted
        assert health["chips_per_replica"] == 1
        assert "mesh_shape" not in health


# --------------------------------------------------------------------- #
# autotune: per-shard kv_heads keying
# --------------------------------------------------------------------- #
class TestAutotunePerShardKeys:
    def test_tp_engine_adopts_per_shard_winner_only(
            self, gpt, mesh2, tmp_path, monkeypatch):
        model, params = gpt
        monkeypatch.setenv("APEX_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at.json"))
        from apex_tpu.ops import autotune

        autotune.clear_cache()
        try:
            dt = str(jnp.dtype(model.cfg.dtype))
            hd = int(model.cfg.head_dim)
            # full-count winner (kv_heads=2) and per-shard winner
            # (kv_heads=1, what each of 2 chips actually serves)
            autotune._store(autotune._key("paged_attention", hd, dt,
                                          kv_heads=2), 32)
            autotune._store(autotune._key("paged_attention", hd, dt,
                                          kv_heads=1), 8)
            e1 = PagedEngine(model, params, max_slots=1, block_size=0)
            e2 = PagedEngine(model, params, max_slots=1, block_size=0,
                             mesh=mesh2)
            assert e1.block_size == 32
            assert e2.block_size == 8
        finally:
            autotune.clear_cache()

    def test_missing_per_shard_entry_never_falls_back(
            self, gpt, mesh2, tmp_path, monkeypatch):
        """Only a full-head-count winner cached: the TP engine must
        NOT adopt it — it takes the built-in default instead (the
        satellite's exact failure mode)."""
        model, params = gpt
        monkeypatch.setenv("APEX_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at.json"))
        from apex_tpu.ops import autotune

        autotune.clear_cache()
        try:
            dt = str(jnp.dtype(model.cfg.dtype))
            hd = int(model.cfg.head_dim)
            autotune._store(autotune._key("paged_attention", hd, dt,
                                          kv_heads=2), 32)
            tp_engine = PagedEngine(model, params, max_slots=1,
                                    block_size=0, mesh=mesh2)
            assert tp_engine.block_size == 16      # default, not 32
        finally:
            autotune.clear_cache()

    def test_auto_pair_keyed_per_shard(self, gpt, mesh2, tmp_path,
                                       monkeypatch):
        model, params = gpt
        monkeypatch.setenv("APEX_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "at.json"))
        from apex_tpu.ops import autotune

        autotune.clear_cache()
        try:
            dt = str(jnp.dtype(model.cfg.dtype))
            hd = int(model.cfg.head_dim)
            autotune._store(autotune._key("paged_attention_pair", hd,
                                          dt, kv_heads=1),
                            [8, "int8"])
            tp_engine = PagedEngine(model, params, max_slots=1,
                                    block_size=0, kv_dtype="auto",
                                    mesh=mesh2)
            assert tp_engine.kv_dtype == "int8"
            assert tp_engine.block_size == 8
            # the single-chip engine queries kv_heads=2: a miss
            single = PagedEngine(model, params, max_slots=1,
                                 block_size=0, kv_dtype="auto")
            assert single.kv_dtype is None
        finally:
            autotune.clear_cache()


# --------------------------------------------------------------------- #
# slow tier: the GQA model twin
# --------------------------------------------------------------------- #
@pytest.mark.slow
class TestLlamaGQATwinSlow:
    def test_gqa_tp_engine_matches_single_chip(self, mesh2):
        """Llama tiny (4 q heads over 2 kv heads): the engine-level
        GQA twin of the tier-1 GPT parity — each chip owns one whole
        GQA group.  [slow: two extra engine builds on a second model;
        the mapping itself is tier-1-covered op-level.]"""
        cfg = LlamaConfig.tiny(scan_layers=True)
        model = LlamaModel(cfg)
        params = {"params": model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 4), jnp.int32))["params"]}
        kw = dict(max_slots=2, block_size=8, prefill_chunk=4,
                  share_prefixes=True, spec_tokens=2)
        single = PagedEngine(model, params, **kw)
        tp = PagedEngine(model, params, mesh=mesh2, **kw)
        rng = np.random.default_rng(6)
        cases = [(rng.integers(0, cfg.vocab_size,
                               size=(L,)).astype(np.int32),
                  7, dict(seed=i))
                 for i, L in enumerate((7, 8, 13))]
        assert _drain(single, cases) == _drain(tp, cases)
        assert tp.blocks_in_use == 0


# --------------------------------------------------------------------- #
# fused decode prologue under TP (ISSUE 14): shard-local write
# --------------------------------------------------------------------- #
class TestShardedFusedDecodePrologue:
    """``paged_decode_fused`` over the mesh: the new K/V rows shard on
    kv_heads beside the pool, the write stays shard-local, and the
    sharded step is BITWISE the single-chip one — output, written
    pages, codes and scales (the PR-12 layout is preserved through the
    fusion)."""

    def _setup(self, rng, *, h, hk, kv_dtype=None, d=16, bs=8, mb=5,
               b=3, S=None):
        from apex_tpu.ops.rope import rope_cos_sin

        S = S or mb * bs
        nb = b * mb + 1
        kp = jnp.asarray(rng.normal(size=(hk, nb, bs, d)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(hk, nb, bs, d)), jnp.float32)
        scales = {}
        if kv_dtype is not None:
            kp, vp, ks, vs = quantize_kv_pages(kp, vp, kv_dtype)
            scales = dict(k_scales=ks, v_scales=vs,
                          chunk_lens=jnp.ones((b,), jnp.int32))
        tables = jnp.asarray(
            rng.permutation(np.arange(1, nb))[:b * mb].reshape(b, mb),
            jnp.int32)
        lengths = jnp.asarray(
            rng.integers(0, mb * bs - 1, size=(b,)), jnp.int32)
        q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
        nk = jnp.asarray(rng.normal(size=(b, 1, hk, d)), jnp.float32)
        nv = jnp.asarray(rng.normal(size=(b, 1, hk, d)), jnp.float32)
        cos, sin = rope_cos_sin(S, d)
        pc = np.minimum(np.asarray(lengths)[:, None], S - 1)
        rope = dict(cos_b=jnp.asarray(cos[pc][:, :, None, :]),
                    sin_b=jnp.asarray(sin[pc][:, :, None, :]))
        return q, nk, nv, kp, vp, tables, lengths, rope, scales, S

    @pytest.mark.parametrize("h,hk", [(4, 4), (8, 4)],
                             ids=["mha", "gqa"])
    def test_sharded_matches_unsharded(self, mesh2, h, hk):
        from apex_tpu.ops.paged_attention import paged_decode_fused

        rng = np.random.default_rng(21)
        (q, nk, nv, kp, vp, tables, lengths, rope, sc,
         S) = self._setup(rng, h=h, hk=hk)
        ref = jax.jit(lambda *a: paged_decode_fused(
            *a, max_seq_len=S, **rope))(q, nk, nv, kp, vp, tables,
                                        lengths)
        tp = jax.jit(lambda *a: paged_decode_fused(
            *a, max_seq_len=S, **rope, mesh=mesh2,
            shard_axis=TENSOR_AXIS))(q, nk, nv, kp, vp, tables,
                                     lengths)
        for a, b_ in zip(ref, tp):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b_))

    def test_sharded_matches_unsharded_int8(self, mesh2):
        from apex_tpu.ops.paged_attention import paged_decode_fused

        rng = np.random.default_rng(22)
        (q, nk, nv, kp, vp, tables, lengths, rope, sc,
         S) = self._setup(rng, h=8, hk=4, kv_dtype="int8")
        ref = jax.jit(lambda *a: paged_decode_fused(
            *a, max_seq_len=S, **rope, **sc))(q, nk, nv, kp, vp,
                                              tables, lengths)
        tp = jax.jit(lambda *a: paged_decode_fused(
            *a, max_seq_len=S, **rope, **sc, mesh=mesh2,
            shard_axis=TENSOR_AXIS))(q, nk, nv, kp, vp, tables,
                                     lengths)
        for a, b_ in zip(ref, tp):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b_))
