"""apex_tpu.plan — the AMP-style auto-parallelism planner (ISSUE 15).

The claims under test, on the 8-virtual-device CPU mesh:

- **dedup**: ``bench_configs`` imports the lifted cost formulas back
  from ``apex_tpu.plan.costs`` (same function objects — zero drift
  possible), the model-block key sets are frozen at their recorded
  r01–r05 spellings, and the blocks recorded in ``BENCH_CONFIGS.json``
  recompute byte-identically.
- **enumeration**: tensor degrees pass the GQA ``tp_head_shards``
  gate, ring/ulysses appear only where the model supports them, ZeRO
  stages only where there is a data axis to shard over.
- **feasibility**: per-chip HBM pruning orders DP vs ZeRO-2 the way
  the measured ``bert_o1_zero`` rows did, and an
  infeasible-everywhere config raises the loud per-layout diagnostic.
- **prediction fidelity**: the planner's score ordering reproduces
  the measured relative ordering of the recorded bench rows —
  dense-vs-paged decode, dp-vs-zero2 hbm_peak, 1×M-vs-M×1 per-chip
  tokens/s, and the occupancy-sweep curve shape.
- **the CI smoke**: planning a tiny GPT for 8 CPU devices returns a
  feasible mesh + specs, and the emitted ZeRO placement equals the
  library's own ``zero_shardings``.
- **autotune seam**: kernel winners are adopted under the PER-SHARD
  kv-head key; a miss falls back to the analytic estimate with a
  counted ``plan.autotune_miss`` — never a full-head-count alias,
  never a zero score (the PR-12 rule, negative-tested).
"""

import json
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import apex_tpu
from apex_tpu import amp
from apex_tpu.models import BertConfig, GPTConfig, LlamaConfig
from apex_tpu.models.resnet import ResNetConfig
from apex_tpu.optim import fused_adam
from apex_tpu.parallel import zero_shardings, zero_state_specs
from apex_tpu.plan import (
    HardwareSpec,
    InfeasibleError,
    Layout,
    costs,
    emit_plan,
    enumerate_layouts,
    generic_profile,
    memory_model,
    profile_of,
    score_layout,
    xla_cost_seed,
)
from apex_tpu.plan.score import autotuned_paged_layout
from apex_tpu.utils.metrics import counters

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 8


# --------------------------------------------------------------- dedup

class TestCostModelDedup:
    """Satellite 1: ONE implementation, imported back by the bench."""

    def test_bench_imports_back_same_objects(self):
        import bench_configs

        assert bench_configs._resnet_traffic_model \
            is costs.resnet_traffic_model
        assert bench_configs._ddp_bytes_on_wire \
            is costs.ddp_bytes_on_wire
        assert bench_configs._zero_bytes_on_wire \
            is costs.zero_bytes_on_wire
        assert bench_configs._serving_traffic_model \
            is costs.serving_traffic_model

    def test_model_block_keys_frozen(self):
        # the r01–r05 emission key sets, pinned: a renamed column
        # would silently orphan every recorded row
        assert tuple(costs.resnet_traffic_model(128, 224)) \
            == ("floor", "bn_real")
        assert tuple(costs.resnet_traffic_model(
            128, 224, fused_bn=True)) \
            == ("floor", "bn_real", "bn_fused_kernel")
        assert tuple(costs.ddp_bytes_on_wire(1000, 8)) == (
            "replicas", "grad_elements", "wire_bytes_per_step_fp32",
            "wire_bytes_per_step_bf16", "wire_bytes_per_step_int8",
            "int8_wire_reduction_vs_fp32")
        assert tuple(costs.zero_bytes_on_wire(1000, 8)) == (
            "shards", "stage", "reduce_dtype", "grad_elements",
            "wire_bytes_reduce_scatter", "wire_bytes_param_all_gather",
            "wire_bytes_per_step_zero",
            "wire_bytes_per_step_dp_fp32_allreduce",
            "wire_reduction_vs_dp", "model_state_bytes_per_chip_dp",
            "model_state_bytes_per_chip_zero",
            "state_bytes_saved_per_chip", "state_savings_frac")
        tm = costs.serving_traffic_model(
            num_layers=2, kv_heads=2, head_dim=64, max_seq_len=256,
            live_tokens=40, slots=4, block_size=8)
        assert tuple(tm) == (
            "tp", "ici_bytes_per_step_per_chip", "ici_bytes_per_step",
            "paged_kv_read_bytes_per_step_per_chip",
            "dense_kv_read_bytes_per_step",
            "paged_kv_read_bytes_per_step", "dense_pool_bytes",
            "paged_pool_tokens", "live_tokens", "block_size",
            "shared_prefix_tokens", "paged_live_pool_tokens_unshared",
            "paged_live_pool_tokens_shared",
            "paged_live_pool_bytes_unshared",
            "paged_live_pool_bytes_shared",
            "shared_capacity_multiplier")

    def test_recorded_bench_blocks_recompute_byte_identical(self):
        # every model block the recorded rows carry recomputes
        # byte-for-byte from the lifted implementation
        path = os.path.join(_REPO, "BENCH_CONFIGS.json")
        recorded = json.load(open(path))
        checked = 0
        for leg in ("resnet50_o1", "resnet50_syncbn"):
            row = recorded[leg]
            block = row.get("analytic_traffic_bytes")
            if not block:
                continue
            got = costs.resnet_traffic_model(
                int(row["batch"]), 224,
                fused_bn="bn_fused_kernel" in block)
            assert json.dumps(got, sort_keys=True) \
                == json.dumps(block, sort_keys=True), leg
            checked += 1
        assert checked >= 2      # the rows exist — not vacuous

    def test_bench_configs_no_longer_defines_the_bodies(self):
        src = open(os.path.join(_REPO, "bench_configs.py")).read()
        for name in ("_resnet_traffic_model", "_ddp_bytes_on_wire",
                     "_zero_bytes_on_wire", "_serving_traffic_model"):
            assert f"def {name}(" not in src, name


# --------------------------------------------------------- enumeration

class TestEnumeration:
    def test_serve_tp_through_gqa_gate(self):
        # llama_1b: 16 q heads over 4 kv heads — tp ∈ divisors of 4
        prof = profile_of(LlamaConfig.llama_1b())
        layouts = enumerate_layouts(prof, N, "serve")
        tps = sorted(l.tp for l in layouts)
        assert tps == [1, 2, 4]
        assert all(l.dp * l.tp == N for l in layouts)

    def test_train_zero_needs_a_data_axis(self):
        prof = profile_of(GPTConfig.tiny())
        layouts = enumerate_layouts(prof, 4, "train")
        assert any(l.zero_stage == 2 and l.reduce_dtype == "int8"
                   for l in layouts)
        assert all(l.zero_stage == 0
                   for l in layouts if l.dp == 1)

    def test_context_axis_only_where_supported(self):
        # BERT is bidirectional: no ring/ulysses, no serving
        bert = profile_of(BertConfig.bert_large())
        assert all(l.cp == 1
                   for l in enumerate_layouts(bert, N, "train"))
        with pytest.raises(ValueError, match="causal"):
            enumerate_layouts(bert, N, "serve")
        # llama supports both at cp=2 (2048 % 2 == 0, 16 heads)
        llama = profile_of(LlamaConfig.llama_1b())
        attns = {(l.cp, l.attn)
                 for l in enumerate_layouts(llama, N, "train")}
        assert (2, "ring") in attns and (2, "ulysses") in attns
        # review regression: the ring gate divides the seq the caller
        # actually trains at, not the config's max_seq_len — at an
        # odd seq no ring layout may be emitted (ulysses, gated on
        # heads, survives)
        odd = {(l.cp, l.attn)
               for l in enumerate_layouts(llama, N, "train", seq=49)}
        assert not any(a == "ring" for _cp, a in odd)
        assert (2, "ulysses") in odd

    def test_resnet_and_generic_are_dp_only(self):
        for prof in (profile_of(ResNetConfig()),
                     generic_profile(10_000)):
            layouts = enumerate_layouts(prof, N, "train")
            assert layouts
            assert all(l.tp == 1 and l.cp == 1 for l in layouts)

    def test_profiles_count_params_sanely(self):
        # analytic counts within 2% of the measured bench rows
        assert abs(profile_of(LlamaConfig.llama_1b()).n_params
                   - 1_032_931_328) / 1_032_931_328 < 0.02
        assert abs(profile_of(GPTConfig.gpt2_1p3b()).n_params
                   - 1.316e9) / 1.316e9 < 0.02
        assert abs(profile_of(ResNetConfig()).n_params
                   - 25.6e6) / 25.6e6 < 0.02

    def test_moe_experts_counted_not_dense(self):
        # review regression: profiling 8 experts as one dense MLP
        # would pass the feasibility gate for layouts that OOM on
        # chip — mixtral_8x7b must land near its real 46.7B, and the
        # MoE profile must dominate its dense twin by ~the expert
        # multiplier on the MLP term
        moe = profile_of(LlamaConfig.mixtral_8x7b())
        dense = profile_of(LlamaConfig.mistral_7b())
        assert abs(moe.n_params - 46.7e9) / 46.7e9 < 0.02
        assert moe.n_params > 6 * dense.n_params


# --------------------------------------------------------- feasibility

class TestFeasibility:
    def test_context_axis_shards_the_residency(self):
        # review regression: the logits CE residual (like the
        # activations) shards its sequence axis on context — a cp
        # layout must not be charged the full-sequence residual
        prof = profile_of(LlamaConfig.llama_1b())
        solo = memory_model(prof, Layout(dp=1), batch_per_chip=1)
        cp2 = memory_model(prof, Layout(dp=1, cp=2, attn="ring"),
                           batch_per_chip=1)
        assert cp2["logits"] == solo["logits"] // 2
        assert cp2["activations"] == solo["activations"] // 2

    def test_zero2_frees_per_chip_hbm(self):
        # the measured bert_o1_zero ordering: ZeRO-2 residency <
        # replicated DP at equal batch, by ~the optimizer state
        prof = profile_of(BertConfig.bert_large())
        dp = memory_model(prof, Layout(dp=N), batch_per_chip=2)
        z2 = memory_model(prof, Layout(dp=N, zero_stage=2,
                                       reduce_dtype="int8"),
                          batch_per_chip=2)
        assert z2["total"] < dp["total"]
        saved = dp["optimizer_state"] - z2["optimizer_state"]
        # ~ (12 - 12/n) B/param of the fp32 master+moments move off
        assert saved > 0.8 * 12 * prof.n_params * (1 - 1 / N)

    def test_zero2_reclaimed_hbm_buys_batch(self):
        # the zero2_grown row's mechanism: at the DP layout's HBM
        # budget, the ZeRO-2 layout fits a strictly larger per-chip
        # batch
        prof = profile_of(BertConfig.bert_large())

        def max_batch(layout, budget):
            b = 0
            while memory_model(prof, layout,
                               batch_per_chip=b + 1)["total"] <= budget:
                b += 1
                if b > 512:
                    break
            return b

        budget = memory_model(prof, Layout(dp=N),
                              batch_per_chip=8)["total"]
        assert max_batch(Layout(dp=N, zero_stage=2), budget) \
            > max_batch(Layout(dp=N), budget)

    def test_infeasible_everywhere_is_loud(self):
        with pytest.raises(InfeasibleError) as ei:
            apex_tpu.plan(LlamaConfig.llama2_7b(), devices=1,
                          hw=HardwareSpec(hbm_bytes=8e9))
        msg = str(ei.value)
        assert "binding" in msg
        assert "optimizer_state" in msg or "activations" in msg
        assert "8.0 GB/chip" in msg
        assert ei.value.pruned     # the per-layout breakdown rides it

    def test_serve_infeasible_names_the_kv_pool(self):
        prof = profile_of(LlamaConfig.llama_1b())
        with pytest.raises(InfeasibleError) as ei:
            apex_tpu.plan(prof, devices=1, objective="serve",
                          slots=64, hw=HardwareSpec(hbm_bytes=3e9))
        assert "kv_pool" in str(ei.value) \
            or "params" in str(ei.value)


# ------------------------------------------------- prediction fidelity

class TestPredictionFidelity:
    """Satellite 3: the planner's score ordering reproduces the
    measured relative ordering of the recorded bench rows."""

    @pytest.fixture(scope="class")
    def recorded(self):
        return json.load(open(os.path.join(_REPO,
                                           "BENCH_CONFIGS.json")))

    def test_dense_vs_paged_read_ordering(self, recorded):
        # the recorded decode A/B: the live-read (blocked) step beats
        # the full-slab (einsum) read at S=2048, and the gap GROWS at
        # S=8192 — the live-independence the dense model encodes
        rows = recorded["decode"]["rows"]
        meas = {}
        for s in (2048, 8192):
            meas[s] = (rows[f"b8_S{s}"]["decode_tokens_per_sec"]
                       / rows[f"b8_S{s}_einsum"]
                       ["decode_tokens_per_sec"])
        assert meas[8192] > meas[2048] > 1.0     # the recorded facts

        cfg = LlamaConfig.llama_1b()
        prof = profile_of(cfg)
        pred = {}
        for s in (2048, 8192):
            tm = costs.serving_traffic_model(
                num_layers=cfg.num_layers, kv_heads=cfg.kv_heads,
                head_dim=cfg.head_dim, max_seq_len=s,
                live_tokens=1024 + 32, slots=8, block_size=16,
                dtype_bytes=2)
            params = 2 * prof.n_params
            pred[s] = ((params + tm["dense_kv_read_bytes_per_step"])
                       / (params
                          + tm["paged_kv_read_bytes_per_step"]))
        assert pred[8192] > pred[2048] > 1.0
        # dense reads are live-independent: the dense column does not
        # move when live tokens do, the paged one scales ~linearly
        tm_lo = costs.serving_traffic_model(
            num_layers=2, kv_heads=4, head_dim=64, max_seq_len=2048,
            live_tokens=64, slots=8, block_size=16)
        tm_hi = costs.serving_traffic_model(
            num_layers=2, kv_heads=4, head_dim=64, max_seq_len=2048,
            live_tokens=256, slots=8, block_size=16)
        assert tm_lo["dense_kv_read_bytes_per_step"] \
            == tm_hi["dense_kv_read_bytes_per_step"]
        assert tm_hi["paged_kv_read_bytes_per_step"] \
            == 4 * tm_lo["paged_kv_read_bytes_per_step"]

    def test_dp_vs_zero2_hbm_peak_ordering(self):
        # the recorded bert_o1_zero rows: hbm_peak dropped 56% at
        # equal batch (1.566 GB → 688 MB at the tiny preset) — the
        # planner's residency must order the same way, stage by stage
        prof = profile_of(BertConfig.bert_large())
        totals = [memory_model(prof, lay, batch_per_chip=2)["total"]
                  for lay in (Layout(dp=N),
                              Layout(dp=N, zero_stage=1),
                              Layout(dp=N, zero_stage=2))]
        assert totals[0] > totals[1] >= totals[2]
        zm = costs.zero_bytes_on_wire(prof.n_params, N)
        assert zm["state_savings_frac"] > 0.5    # the 56%-class drop

    def test_1xM_vs_Mx1_per_chip_ordering(self):
        # the tp_serving protocol: at equal chip count the M×1 fleet
        # is the per-chip throughput ceiling (zero ICI); the 1×M TP
        # row pays the ICI column for capacity
        prof = profile_of(LlamaConfig.llama_1b())
        fleet = score_layout(prof, Layout(objective="serve", dp=2),
                             slots=4)
        tp = score_layout(prof, Layout(objective="serve", dp=1, tp=2),
                          slots=4)
        assert fleet["value"] >= tp["value"]
        assert fleet["t_ici_s"] == 0.0 and tp["t_ici_s"] > 0.0
        assert tp["traffic_model"]["ici_bytes_per_step_per_chip"] > 0
        # ...and the TP row is the only one that shrinks per-chip
        # residency — the capacity it buys
        assert tp["hbm_residency"]["params"] \
            < fleet["hbm_residency"]["params"]

    def test_occupancy_sweep_curve_shape(self, recorded=None):
        # a CPU-dev-box occupancy sweep (the serving_decode leg that
        # went with the dense engine in PR 34): 1×/2×/4× the slots in
        # one budget measured 1 / 2.25 / 3.96× tokens/s — increasing,
        # sublinear at the top, ×4 under 2× the ×2 gain
        prof = profile_of(LlamaConfig.llama_1b())
        tps = {m: score_layout(
            prof, Layout(objective="serve", dp=1),
            slots=2 * m, live_tokens=144)["value"]
            for m in (1, 2, 4)}
        assert tps[4] > tps[2] > tps[1]
        sp2, sp4 = tps[2] / tps[1], tps[4] / tps[1]
        assert 1.0 < sp2 < 2.0 and sp2 < sp4 < 4.0
        assert sp4 < 2 * sp2        # measured: 3.96 < 2 × 2.25
        # per-slot efficiency decays with occupancy (the amortized
        # param stream saturates) — the measured curve's concavity
        assert tps[4] / 8 < tps[2] / 4


# ------------------------------------------------------- the CI smoke

class TestPlanSmoke:
    """Satellite 5: the tier-1 gate — plan a tiny GPT for the 8-device
    CPU mesh, feasible + emitted specs place like ``zero_shardings``."""

    def test_tiny_gpt_plans_feasibly(self):
        p = apex_tpu.plan(GPTConfig.tiny(), devices=N)
        assert p.objective == "train"
        assert p.layout.chips == N
        assert p.mesh is not None and p.mesh.devices.size == N
        assert p.score["value"] > 0
        assert p.alternatives    # the A/B is inspectable
        assert "samples/sec/chip" in p.describe()

    @pytest.mark.parametrize("ndev", [1, N])
    @pytest.mark.parametrize("cfg_fn", [
        GPTConfig.tiny, GPTConfig.gpt2_1p3b, BertConfig.bert_large,
        LlamaConfig.llama_1b, ResNetConfig],
        ids=["gpt_tiny", "gpt2_1p3b", "bert_large", "llama_1b",
             "resnet50"])
    def test_model_zoo_plans_on_cpu_meshes(self, cfg_fn, ndev):
        # the acceptance bar: a feasible Mesh + specs for the zoo on
        # 1- and 8-device CPU meshes at the default HBM budget
        p = apex_tpu.plan(cfg_fn(), devices=ndev)
        assert p.mesh.devices.size == ndev
        assert p.score["value"] > 0
        assert p.score["hbm_residency"]["total"] \
            <= apex_tpu.plan.DEFAULT_HW.hbm_bytes

    def test_emitted_zero_specs_place_like_zero_shardings(self):
        p = emit_plan(
            GPTConfig.tiny(), Layout(dp=N, zero_stage=2),
            jax.devices()[:N],
            score_layout(GPTConfig.tiny(), Layout(dp=N, zero_stage=2)),
            [])
        assert p.zero is not None and p.zero.axis_size == N
        params = {"w": jnp.ones((16, 33)), "b": jnp.zeros((33,))}
        state = amp.initialize(lambda pr, x: x @ pr["w"] + pr["b"],
                               params, fused_adam(1e-3),
                               opt_level="O2",
                               half_dtype=jnp.bfloat16, zero=p.zero)
        got = p.state_shardings(state)
        want = zero_shardings(state, mesh=p.mesh)
        assert jax.tree.all(jax.tree.map(lambda a, b: a == b, got,
                                         want))
        specs = p.state_specs(state)
        assert jax.tree.all(jax.tree.map(lambda a, b: a == b, specs,
                                         zero_state_specs(state)))
        # the master shards really land on the data axis
        flat = jax.tree.leaves(
            specs.opt_state,
            is_leaf=lambda x: isinstance(x, P))
        assert any(s and s[0] == "data" for s in flat)

    def test_tp_plan_emits_gspmd_layer_annotations(self):
        p = emit_plan(GPTConfig.tiny(), Layout(dp=4, tp=2),
                      jax.devices()[:N],
                      score_layout(GPTConfig.tiny(),
                                   Layout(dp=4, tp=2)), [])
        assert p.param_specs is not None
        flat = jax.tree.leaves(
            p.param_specs, is_leaf=lambda x: isinstance(x, P))
        assert any("tensor" in tuple(s) for s in flat
                   if isinstance(s, P))
        assert dict(p.mesh.shape)["tensor"] == 2
        assert p.data_spec == P("data")

    def test_serve_plan_splits_the_chips(self):
        p = apex_tpu.plan(GPTConfig.tiny(), devices=N,
                          objective="serve")
        assert p.replicas * p.tp == N
        assert "kv_cache" not in p.engine_kwargs
        flat = [d for devs in p.replica_devices for d in devs]
        assert sorted(flat, key=str) \
            == sorted(jax.devices()[:N], key=str)
        if p.tp > 1:
            assert len(p.replica_meshes()) == p.replicas

    def test_impossible_slo_is_loud(self):
        with pytest.raises(ValueError, match="ttft_ms"):
            apex_tpu.plan(LlamaConfig.llama_1b(), devices=N,
                          objective="serve", slo={"ttft_ms": 1e-9})

    def test_entry_point_validation(self):
        with pytest.raises(ValueError, match="objective"):
            apex_tpu.plan(GPTConfig.tiny(), devices=2,
                          objective="infer")
        with pytest.raises(ValueError, match="device"):
            apex_tpu.plan(GPTConfig.tiny(), devices=10**6)
        with pytest.raises(TypeError, match="profile"):
            apex_tpu.plan(object(), devices=2)
        # objective-mismatched knobs are loud, not silently ignored
        with pytest.raises(ValueError, match="cost_seed"):
            apex_tpu.plan(GPTConfig.tiny(), devices=2,
                          objective="serve",
                          cost_seed={"flops": 1.0,
                                     "bytes_accessed": 1.0})
        with pytest.raises(ValueError, match="slo"):
            apex_tpu.plan(GPTConfig.tiny(), devices=2,
                          objective="train", slo={"ttft_ms": 100})
        # ...and so is a typoed SLO key (it must not yield a plan
        # that merely LOOKS SLO-checked)
        with pytest.raises(ValueError, match="ttft_p50_ms"):
            apex_tpu.plan(GPTConfig.tiny(), devices=2,
                          objective="serve",
                          slo={"ttft_p50_ms": 200})

    def test_bare_profile_plans_for_train(self):
        # review regression: a ModelProfile is a documented plan()
        # input — emit must not try to trace a flax module out of it
        prof = profile_of(GPTConfig.tiny())
        p = apex_tpu.plan(prof, devices=N)
        assert p.param_specs is None     # geometry only, no module
        assert p.mesh.devices.size == N and p.score["value"] > 0

    def test_module_is_callable_and_a_package(self):
        # the ROADMAP-4 spelling apex_tpu.plan(...) AND the package
        # surface apex_tpu.plan.costs both work
        assert callable(apex_tpu.plan)
        assert apex_tpu.plan.costs.ddp_bytes_on_wire is \
            costs.ddp_bytes_on_wire


# ------------------------------------------------------- autotune seam

class TestAutotuneSeam:
    """Satellite 6: per-shard-keyed winners adopted; misses fall back
    analytic with a counted ``plan.autotune_miss`` — never 0."""

    @pytest.fixture
    def fresh_cache(self, tmp_path, monkeypatch):
        from apex_tpu.ops import autotune

        monkeypatch.setenv("APEX_TPU_AUTOTUNE_CACHE",
                           str(tmp_path / "autotune.json"))
        autotune.clear_cache()
        yield autotune
        autotune.clear_cache()

    def test_miss_counts_and_falls_back_analytic(self, fresh_cache):
        prof = profile_of(LlamaConfig.llama_1b())
        before = counters.get("plan.autotune_miss")
        tuned = autotuned_paged_layout(prof, tp=2)
        assert counters.get("plan.autotune_miss") == before + 1
        assert tuned == {"block_size": 16, "kv_dtype": None,
                         "autotuned": False}
        # ...and the score built on the fallback is a real number,
        # not the silent 0 the satellite forbids
        s = score_layout(prof, Layout(objective="serve", dp=1, tp=2),
                         slots=4)
        assert s["value"] > 0 and s["autotune"]["autotuned"] is False

    def test_per_shard_winner_adopted(self, fresh_cache):
        # bf16 inference config — the dtype the engine (and thus the
        # planner) keys the lookup on
        prof = profile_of(LlamaConfig.llama_1b(dtype=jnp.bfloat16))
        fresh_cache._store(
            fresh_cache._key("paged_attention_pair", prof.head_dim,
                             "bfloat16", kv_heads=2),
            [32, "int8"])
        before = counters.get("plan.autotune_miss")
        tuned = autotuned_paged_layout(prof, tp=2)   # shard width 2
        assert counters.get("plan.autotune_miss") == before
        assert tuned == {"block_size": 32, "kv_dtype": "int8",
                         "autotuned": True}
        s = score_layout(prof, Layout(objective="serve", dp=1, tp=2),
                         slots=4)
        assert s["value"] > 0
        assert s["autotune"]["autotuned"] is True
        assert s["traffic_model"]["block_size"] == 32
        assert s["traffic_model"]["kv_dtype"] == "int8"

    def test_fp16_config_keys_fp16_not_bf16(self, fresh_cache):
        # review regression: the cache dtype key is the EXACT config
        # dtype name (as PagedEngine keys it) — float16 shares bf16's
        # width but not its cache entry
        prof = profile_of(LlamaConfig.llama_1b(dtype=jnp.float16))
        assert prof.dtype_name == "float16"
        fresh_cache._store(
            fresh_cache._key("paged_attention_pair", prof.head_dim,
                             "float16", kv_heads=prof.kv_heads),
            [32, "int8"])
        tuned = autotuned_paged_layout(prof, tp=1)
        assert tuned == {"block_size": 32, "kv_dtype": "int8",
                         "autotuned": True}

    def test_full_head_count_winner_never_aliases(self, fresh_cache):
        # the PR-12 rule: an entry swept at FULL head count must not
        # be adopted by a tp plan querying its per-shard width — the
        # profile dtype matches the stored key exactly, so kv_heads is
        # the ONLY mismatched component (the aliasing under test)
        prof = profile_of(LlamaConfig.llama_1b(dtype=jnp.bfloat16))
        fresh_cache._store(
            fresh_cache._key("paged_attention_pair", prof.head_dim,
                             "bfloat16", kv_heads=prof.kv_heads),
            [64, "int8"])
        before = counters.get("plan.autotune_miss")
        tuned = autotuned_paged_layout(prof, tp=2)
        assert counters.get("plan.autotune_miss") == before + 1
        assert tuned["autotuned"] is False
        assert tuned["block_size"] == 16     # analytic default, not 64

    def test_xla_cost_seed_anchors_the_roofline(self):
        @jax.jit
        def f(x):
            return (x @ x).sum()

        compiled = f.lower(jnp.ones((64, 64))).compile()
        seed = xla_cost_seed(compiled)
        if seed is None:
            pytest.skip("backend offers no cost analysis")
        assert seed["flops"] > 0
        s = score_layout(profile_of(GPTConfig.tiny()), Layout(dp=1),
                         cost_seed=seed)
        assert s["cost_seed"] is seed and s["value"] > 0
        # review regression: the seed describes the single-chip step,
        # so a model-sharded layout's per-chip roofline must shrink by
        # its cp×tp degree — an un-rescaled seed would make every
        # layout's roofline identical and degenerate the ranking
        tp2 = score_layout(profile_of(GPTConfig.tiny()),
                           Layout(dp=1, tp=2), cost_seed=seed)
        assert tp2["t_mxu_s"] == pytest.approx(s["t_mxu_s"] / 2)
        assert tp2["t_hbm_s"] == pytest.approx(s["t_hbm_s"] / 2)

    def test_serve_feasibility_judged_on_tuned_pool(self, fresh_cache):
        # review regression: feasibility must adopt the SAME autotuned
        # (block_size, kv_dtype) the score and engine kwargs do — a
        # model whose bf16 pool busts the budget but whose tuned int8
        # pool fits must plan, not raise InfeasibleError
        cfg = LlamaConfig.llama_1b(dtype=jnp.bfloat16)
        prof = profile_of(cfg)
        fresh_cache._store(
            fresh_cache._key("paged_attention_pair", prof.head_dim,
                             "bfloat16", kv_heads=prof.kv_heads),
            [16, "int8"])
        bf16 = memory_model(prof, Layout(objective="serve", dp=1),
                            slots=8)["total"]
        int8 = memory_model(prof, Layout(objective="serve", dp=1),
                            slots=8, kv_dtype="int8")["total"]
        budget = (bf16 + int8) / 2          # between the two pools
        p = apex_tpu.plan(cfg, devices=1, objective="serve",
                          slots=8, hw=HardwareSpec(hbm_bytes=budget))
        assert p.engine_kwargs["kv_dtype"] == "int8"
        assert p.score["hbm_residency"]["total"] <= budget


# ----------------------------------------------------------- generics

class TestGenericProfile:
    def test_generic_plan_matches_example_usage(self):
        # the --plan auto path of examples/simple/distributed.py
        p = apex_tpu.plan(generic_profile(2305), devices=N)
        assert p.layout.dp == N and p.layout.tp == 1
        assert p.zero is None or p.zero.axis_size == N

    def test_resnet_zoo_plans(self):
        p = apex_tpu.plan(ResNetConfig(), devices=N,
                          batch_per_chip=32)
        assert p.layout.dp == N
        assert p.score["hbm_residency"]["activations"] > 0


class TestPipelinePlanning:
    """ISSUE-20: the ``pipe`` axis end-to-end through the planner —
    enumeration gates, per-stage residency, the bubble + boundary-wire
    score terms, and the emitted Plan driving an actual 1F1B run."""

    def _prof(self, layers=4):
        # the tiny residual-MLP stack the pipeline unit tests train:
        # 4 × (16·16 + 16 + 16·16) = 2112 fp32 params
        return generic_profile(2112, dtype_bytes=4, num_layers=layers)

    def test_pipe_degrees_enumerate_behind_the_gates(self):
        pipes = {l.pipe for l in
                 enumerate_layouts(self._prof(8), 8, "train")}
        assert pipes == {1, 2, 4, 8}
        # layer-divisibility gate: 6 layers admit only pipe ∈ {1, 2}
        assert {l.pipe for l in
                enumerate_layouts(self._prof(6), 8, "train")} \
            == {1, 2}
        # microbatch gate: pipe <= m
        assert {l.pipe for l in
                enumerate_layouts(self._prof(8), 8, "train",
                                  microbatches=2)} == {1, 2}
        # a profile with no layer count cannot pipeline
        flat = generic_profile(2112, dtype_bytes=4)
        assert {l.pipe for l in
                enumerate_layouts(flat, 8, "train")} == {1}

    def test_per_stage_residency_divides_state(self):
        prof = self._prof()
        dp = memory_model(prof, Layout(dp=8), batch_per_chip=4)
        p4 = memory_model(prof, Layout(dp=2, pipe=4),
                          batch_per_chip=4, microbatches=4)
        # each stage holds 1/pipe of params / optimizer / grads
        assert p4["params"] == dp["params"] / 4
        assert p4["optimizer_state"] == dp["optimizer_state"] / 4
        assert p4["gradients"] == dp["gradients"] / 4

    def test_pipeline_costs_match_the_schedule_quantities(self):
        from apex_tpu.parallel import pipeline as pl

        pc = costs.pipeline_costs(4, 8, microbatch_tokens=128,
                                  hidden_size=64, dtype_bytes=2)
        assert pc["bubble_fraction"] == \
            pytest.approx(pl.bubble_fraction(4, 8))
        assert pc["schedule_ticks"] == pl.schedule_ticks(4, 8)
        assert pc["live_microbatches"] == pl.live_microbatches(4)
        # boundary traffic: 2(p-1) activation hops per microbatch,
        # none at all without a pipe split
        payload = 128 * 64 * 2
        assert pc["boundary_bytes_per_step"] == 2 * 3 * 8 * payload
        assert costs.pipeline_costs(
            1, 8, microbatch_tokens=128, hidden_size=64,
            dtype_bytes=2)["boundary_bytes_per_step"] == 0

    def test_bubble_term_monotone_in_microbatches(self):
        # more microbatches amortize the (p-1)/m bubble: the score
        # must strictly improve, and the scorecard carries the
        # pipeline cost block for inspection
        prof = self._prof(8)
        lay = Layout(dp=2, pipe=4)
        s8 = score_layout(prof, lay, batch_per_chip=4, microbatches=8)
        s16 = score_layout(prof, lay, batch_per_chip=4,
                           microbatches=16)
        assert s8["bubble_fraction"] == pytest.approx(3 / 8)
        assert s16["bubble_fraction"] == pytest.approx(3 / 16)
        assert s16["value"] > s8["value"]
        assert s8["pipeline"]["stages"] == 4
        assert s8["microbatches"] == 8

    def test_tight_hbm_keeps_only_pipe_layouts_and_plan_trains(self):
        """The acceptance scenario: at a budget every dp/ZeRO layout
        busts (the best pipe-free residency is 12672 B here), the
        planner returns a pipelined layout — and adopting the emitted
        Plan (mesh, ZeroConfig, stage assignment, placement) actually
        trains."""
        import numpy as np

        from apex_tpu.parallel import pipeline as pl

        prof = self._prof()
        p = apex_tpu.plan(prof, devices=8,
                          hw=HardwareSpec(hbm_bytes=9000),
                          batch_per_chip=4, microbatches=4)
        assert p.layout.pipe > 1
        assert all(s["layout"].pipe > 1 for s in p.alternatives)
        assert p.microbatches == 4
        per = 4 // p.layout.pipe
        assert p.stage_assignment == [
            (s * per, (s + 1) * per) for s in range(p.layout.pipe)]
        assert p.mesh.shape["pipe"] == p.layout.pipe
        assert p.zero is not None and p.zero.axis_size == p.layout.dp

        # ---- adopt the plan: stage_split by its assignment, its
        # ZeroConfig, its mesh, its placement — and train
        hid, layers, mb = 16, 4, 2
        dp, pp, m = p.layout.dp, p.layout.pipe, p.microbatches
        r = np.random.default_rng(0)
        params = {"stages": (
            jnp.asarray(r.normal(size=(layers, hid, hid)) * 0.3,
                        jnp.float32),
            jnp.asarray(r.normal(size=(layers, hid)) * 0.1,
                        jnp.float32),
            jnp.asarray(r.normal(size=(layers, hid, hid)) * 0.3,
                        jnp.float32),
        )}
        x = jnp.asarray(r.normal(size=(dp * m, mb, hid)), jnp.float32)
        y = jnp.asarray(r.normal(size=(dp * m, mb, hid)), jnp.float32)

        staged = {"stages": pl.stage_split(params["stages"], pp)}
        state = amp.initialize(None, staged, fused_adam(1e-2),
                               opt_level="O0", zero=p.zero)
        state = pl.stage_local_zero(state, num_stages=pp)
        state = jax.device_put(state, p.state_shardings(state))

        def layer_apply(xx, args):
            w1, b1, w2 = args
            return xx + jnp.tanh(xx @ w1 + b1) @ w2, None

        def stage_fn(sp, xx):
            xx, _ = jax.lax.scan(layer_apply, xx, sp)
            return xx

        def body(state, mbs, labels):
            def loss_fn(out, i):
                yl = jax.lax.dynamic_index_in_dim(labels, i, 0,
                                                  keepdims=False)
                return jnp.mean((out - yl) ** 2)

            loss, grads = pl.run_1f1b(stage_fn, loss_fn,
                                      state.params["stages"], mbs)
            grads = pl.sync_grad_overflow({"stages": grads})
            new_state, _ = state.apply_gradients(grads=grads)
            return new_state, jax.lax.pmean(loss, "data")

        # the emitted mesh carries every library axis (degenerate
        # ones at size 1) — wrap_pipeline_step folds those into the
        # manual set, so this exercises the planner-mesh path
        step = pl.wrap_pipeline_step(
            body, state=state, mesh=p.mesh,
            batch_specs=(p.data_spec, p.data_spec))
        losses = []
        for _ in range(5):
            state, loss = step(state, x, y)
            losses.append(float(loss))
        assert np.all(np.isfinite(losses))
        assert losses[-1] < losses[0]


class TestCalibrate:
    """plan.calibrate — the measured HardwareSpec (ISSUE-20
    satellite): off-accelerator identity, forced sweeps, the
    ``hardware=`` alias."""

    def test_cpu_host_returns_defaults_untouched(self):
        from apex_tpu.plan import DEFAULT_HW, calibrate

        # a host-emulated "peak" would poison the feasibility gate:
        # off-accelerator the bench-constant defaults come back AS-IS
        assert calibrate() is DEFAULT_HW

    def test_forced_sweeps_measure_this_host(self):
        from apex_tpu.plan import DEFAULT_HW, calibrate

        hw = calibrate(force=True, matmul_n=64, copy_mbytes=1,
                       psum_mbytes=1, iters=1)
        assert hw is not DEFAULT_HW
        assert hw.peak_tflops > 0
        assert hw.peak_hbm_gbs > 0
        assert hw.peak_ici_gbs > 0      # 8 virtual devices: a wire
        # ... and they are measurements, not the bench constants
        assert hw.peak_tflops != DEFAULT_HW.peak_tflops

    def test_single_device_keeps_the_ici_default(self):
        from apex_tpu.plan import DEFAULT_HW, calibrate

        hw = calibrate(jax.devices()[:1], force=True, matmul_n=32,
                       copy_mbytes=1, iters=1)
        assert hw.peak_ici_gbs == DEFAULT_HW.peak_ici_gbs

    def test_hardware_alias_plans_and_double_spec_errors(self):
        from apex_tpu.plan import DEFAULT_HW, calibrate

        prof = generic_profile(2112, dtype_bytes=4, num_layers=4)
        p = apex_tpu.plan(prof, devices=8, hardware=calibrate())
        assert p.score["value"] > 0
        with pytest.raises(ValueError, match="not both"):
            apex_tpu.plan(prof, devices=8, hw=DEFAULT_HW,
                          hardware=DEFAULT_HW)
