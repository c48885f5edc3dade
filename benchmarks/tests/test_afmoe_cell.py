"""The Trinity cell: through ``run.py`` at its tiny ``rehearse`` sizes
on the CPU (window 16 against long prompts of 40-56 tokens, 4 of 32
experts held), every control of its comparison coming out not correct,
its traffic a pure function of its seeds, its counts against
hand-worked sums and its roofline reader on a made-up trace.

The rehearsal's limit was set as the chip's is, between the program's
readings on 8 seeds at these sizes and the nearest control's (the
cell's ``limits_from`` has the numbers)."""

import json
import pathlib

import numpy as np
import pytest

import run
from lib import counts_afmoe as counts

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "trinity_large_l5_e32.short_long_mixed_closed_loop"
CONFIG = "trinity_large_l5_e32"
NEW_METRICS = {
    "expert_matmul_share.serve", "expert_matmul_roofline.serve",
    "expert_assignments_per_step.serve", "expert_load_max_over_mean.serve",
    "kv_window_pages_per_step.serve"}


def rehearse(seed, seconds=3, trace=0, **kw):
    return run.run_cell(
        ["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--rehearse"], need_chip=False, **kw)


def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_the_cell_rehearses_correct_and_reads_its_counters():
    line = rehearse(3000000017, trace=1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["rehearse"] is True
    names = {m["name"] for m in bench()["per_layer"]
             if CELL in m["workloads"]}
    assert set(line["metrics"]) <= names
    # off a chip no share of a roofline or of a peak is written
    assert not any("mfu" in k or "roofline" in k for k in line["metrics"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # the sample crossed the window: the longest request is a long one
    assert line["notes"]["check_longest"] > 40
    # pad lanes are routed nowhere: at most 4 slots x 8 lanes x top-4
    assert 0 < m["expert_assignments_per_step.serve"] <= 128
    assert 0 < m["kv_window_pages_per_step.serve"] \
        < m["kv_live_pages_per_step.serve"]
    assert m["recompiles.serve"] == 0 and m["preempts.serve"] == 0
    sizes = line["notes"]["branch_rms"]
    # every branch speaks: attention, MLP, and in the expert layers the
    # routed part beside the shared one
    assert len(sizes) == 5
    assert all(row[1] > 0.1 and row[2] > 0.1 and row[4] > 0.1
               for row in sizes)
    assert all(row[3] > 0.05 for row in sizes[1:])


@pytest.mark.parametrize("kw", [
    dict(control=True), dict(fault="window_ignored"),
    dict(fault="rope_everywhere"), dict(fault="experts_dropped"),
    dict(fault="token_altered")])
def test_a_control_or_a_wrong_reference_is_not_correct(kw):
    line = rehearse(22, **kw)
    assert line["correct"] is False
    if "control" in kw or kw["fault"] != "token_altered":
        # the program itself was sound in that run
        assert line["notes"]["served_logit_gap_max"] \
            <= line["checks"]["served_logit_gap_max"]["limit"]


def test_the_cell_is_the_issues_letter_for_letter():
    cell = run.load_json("workloads", CELL)
    assert cell["traffic_params"] == {
        "clients": 48, "long_clients": 12, "per_client": 12,
        "prompt": {"min": 64, "max": 256},
        "long_prompt": {"min": 4608, "max": 6144},
        "output": {"min": 256, "max": 512}, "greedy_share": 1.0,
        "sampling": {"temperature": 0.8, "top_k": 40},
        "shape_seed": 20261004}
    assert cell["check"] == {"tokens": 1500, "requests": 4}
    assert cell["generator"] == "closed_loop_two_lengths"
    assert cell["driver"] == "serve_afmoe"
    config = run.load_json("configs", CONFIG)
    # the published widths (ISSUE 37), uncut
    assert {k: config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "moe_intermediate_size",
        "num_experts_per_tok", "num_shared_experts", "sliding_window",
        "rope_theta", "rms_norm_eps", "route_scale", "router_experts")} == {
        "hidden_size": 3072, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128,
        "intermediate_size": 12288, "moe_intermediate_size": 3072,
        "num_experts_per_tok": 4, "num_shared_experts": 1,
        "sliding_window": 4096, "rope_theta": 10000, "rms_norm_eps": 1e-05,
        "route_scale": 2.448, "router_experts": 256}
    assert config["published"] == {
        "num_hidden_layers": 60, "num_dense_layers": 6, "num_experts": 256,
        "vocab_size": 200192, "max_position_embeddings": 262144}
    assert {k: config[k] for k in config["published"]} == {
        "num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 32,
        "vocab_size": 25024, "max_position_embeddings": 8192}
    assert set(config["reduced"]) == set(config["published"])
    entry = next(c for c in bench()["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(config["published"])
    # the published list of 60 kinds, and which of them are run
    assert len(config["layer_types"]) == 60
    assert [config["layer_types"][i] for i in config["layers_run"]] == \
        ["sliding_attention"] * 4 + ["full_attention"]
    assert config["serve"]["server"] == {
        "kv_cache": "paged", "max_slots": 48, "pool_tokens": 122880}
    assert "8 chips share each layer" in config["deployment"]


def test_every_published_number_is_the_catalogs():
    """Every number of the catalog row's ``config`` stands in the file
    under the same key, but the five that ``reduced`` lists."""
    rows = pathlib.Path("/opt/skills/guides/model-configs/"
                        "architectures.jsonl")
    if not rows.exists():
        pytest.skip("no catalog on this machine")
    row = next(json.loads(line) for line in rows.read_text().splitlines()
               if '"Trinity-Large-Preview"' in line)
    config = run.load_json("configs", CONFIG)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key


def test_the_cell_is_on_the_metrics_the_issue_names():
    b = bench()
    for m in b["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"):
            assert m["workloads"][-1] == CELL, m["name"]
    on = {m["name"] for m in b["per_layer"] if CELL in m.get("workloads", [])}
    chat = {m["name"] for m in b["per_layer"]
            if "mistral_7b_l8.chat_open_loop" in m.get("workloads", [])}
    # one step in five is a width-1 decode step (PERF.md section 4), and
    # its span's mean moves itl_p95_ms here too.  The decode PROGRAM's
    # median is read from the 3 s trace, which in this cell may hold no
    # width-1 step at all (PERF.md section 6: one traced run of three
    # held none), and a metric a traced run cannot report refuses the
    # PR; the paged-decode roofline's count knows no window
    assert on == chat | NEW_METRICS | {"decode_step_ms_mean.serve"}
    assert not on & {"decode_program_ms_p50.serve",
                     "paged_decode_roofline.serve"}
    for m in b["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert (ROOT / "benchmarks" / "metrics"
                    / f"{m['name']}.json").exists()


# --------------------------------------------------------------- traffic
def test_traffic_is_a_pure_function_of_its_seeds():
    gen = run.load_module("traffic", "closed_loop_two_lengths")
    params = run.load_json("workloads", CELL)["traffic_params"]
    a = gen.generate(params, 7, 40, 25024)
    b = gen.generate(params, 7, 40, 25024)
    c = gen.generate(params, 8, 40, 25024)
    d = gen.generate(dict(params, shape_seed=1), 7, 40, 25024)
    shape = lambda t: [[(len(r["prompt"]), r["max_new_tokens"],
                         r["greedy"]) for r in rows]
                       for rows in t["clients"]]
    assert shape(a) == shape(b) == shape(c) != shape(d)
    ids = lambda t: [r["prompt"].tolist() for rows in t["clients"]
                     for r in rows]
    assert ids(a) == ids(b) != ids(c)
    assert a["mode"] == "closed" and a["max_tokens"] == 6144 + 512
    lengths = np.array([[len(r["prompt"]) for r in rows]
                        for rows in a["clients"]])
    long_rows = (lengths >= 4608).all(axis=1)
    assert long_rows.sum() == 12 and len(a["clients"]) == 48
    assert ((lengths[long_rows] >= 4608) & (lengths[long_rows] <= 6144)).all()
    assert ((lengths[~long_rows] >= 64) & (lengths[~long_rows] <= 256)).all()
    outs = np.array([[r["max_new_tokens"] for r in rows]
                     for rows in a["clients"]])
    assert outs.min() >= 256 and outs.max() <= 512
    assert all(r["greedy"] for rows in a["clients"] for r in rows)
    assert max(r["prompt"].max() for rows in a["clients"]
               for r in rows) < 25024
    # the pool holds every slot's longest request
    assert 12 * (6144 + 512) + 36 * (256 + 512) <= 122880


# ---------------------------------------------------------------- counts
def test_counts_against_hand_worked_sums():
    c = run.load_json("configs", CONFIG)
    assert counts.layer_kinds(c) == (4, 1, 1, 4)
    # q, k, v 3072 x (48 + 16) x 128; the gate and the output 3072 x 6144
    assert counts.attention_params(c) == 25_165_824 + 2 * 18_874_368
    assert counts.attention_params(c) == 62_914_560
    assert counts.expert_params(c) == 3 * 3072 * 3072 == 28_311_552
    # five attentions, the dense MLP, four routers of 256 and shared experts
    assert counts.token_params(c) == 5 * 62_914_560 + 3 * 3072 * 12288 \
        + 4 * (3072 * 256 + 28_311_552)
    one = counts.decoder_forward_flops(c, 1, 0, 0, 0, 0)
    assert one == 2 * counts.token_params(c)
    # one (query, key) pair: 4 x 48 x 128 a layer, by the layer's kind
    assert counts.decoder_forward_flops(c, 0, 10, 0, 0, 0) \
        == 1 * 4 * 48 * 128 * 10
    assert counts.decoder_forward_flops(c, 0, 0, 10, 0, 0) \
        == 4 * 4 * 48 * 128 * 10
    assert counts.decoder_forward_flops(c, 0, 0, 0, 1, 0) \
        == 2 * 3072 * 25024
    # an assignment: one expert's three matrices
    assert counts.decoder_forward_flops(c, 0, 0, 0, 0, 7) \
        == 7 * 2 * 28_311_552
    assert counts.prompt_pairs(5) == 15
    assert counts.prompt_pairs(5, 4096) == 15
    assert counts.prompt_pairs(6000, 4096) == 4096 * 4097 // 2 \
        + (6000 - 4096) * 4096
    ops, nbytes = counts.expert_products(c, 512, 32)
    assert ops == 512 * 2 * 28_311_552
    assert nbytes == 2 * (32 * 28_311_552 + 512 * (2 * 3072 + 3 * 3072))
    # 16 tokens an expert: the weights' stream binds, 2.2 ms a layer
    from lib import counts as base, peaks
    least, side = base.least_seconds(ops, nbytes,
                                     peaks.CHIP_PEAKS["TPU v5 lite"])
    assert side == "memory" and 2.2e-3 < least < 2.3e-3


def test_the_roofline_reader_on_a_made_up_trace():
    reader = run.load_module("readers", "expert_roofline")
    from lib import peaks

    c = run.load_json("configs", CONFIG)
    args = run.load_json("metrics", "expert_matmul_roofline.serve")["args"]
    # a program's first product is named %gmm, the others %gmm.N
    label = "%gmm{} = bf16[4096,6144] custom-call(...) tpu_custom_call"
    health = lambda a, e, s: {"expert_assignments": a, "experts_active": e,
                              "expert_layer_steps": s}
    run_ = {"peaks": peaks.CHIP_PEAKS["TPU v5 lite"], "config": c,
            "trace": {"ops": {
                "gmm": {"label": label.format(""), "count": 10,
                        "total_s": 0.02},
                "gmm.3": {"label": label.format(".3"), "count": 70,
                          "total_s": 0.08}}},
            "facts": {"health_before": health(100, 10, 4),
                      "health_after": health(100 + 512 * 400,
                                             10 + 32 * 400, 404)}}
    # 400 layer-steps in the window, 40 of them traced (80 events of 2)
    ops, nbytes = counts.expert_products(c, 512 * 400, 32 * 400)
    least = nbytes / 819e9
    assert reader.read(args, run_) == pytest.approx(
        100 * least * (40 / 400) / 0.1)
    assert reader.read(args, dict(run_, peaks=None)) is None
    # the parent's health() has no such counter: nothing, and no error
    assert reader.read(args, dict(run_, facts={
        "health_before": {}, "health_after": {"steps": 3}})) is None
    assert reader.read(args, dict(run_, trace={"ops": {}})) is None
