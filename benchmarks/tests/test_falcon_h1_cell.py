"""The Falcon-H1 cell: through ``run.py`` at its tiny ``rehearse`` sizes
on the CPU, the controls of its comparison, its counts against
hand-worked sums, its roofline reader on a made-up trace, and its
driver's keys against ``serve_server``'s.

The rehearsal's limit (0.12) was set as the chip's is: the program read
0.006-0.048 on 9 seeds at these sizes, the fp8 control 0.26-0.49, the
reference that drops its state every chunk 2.2-4.5, the one that
inherits another request's 1.1-2.3."""

import inspect
import json
import pathlib

import pytest

import run
from lib import counts_falcon_h1 as counts

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "falcon_h1_34b_l4.long_answers_closed_loop"


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
    # 4 slots: a step advances between 1 and 4 x 8 real positions
    assert 1.0 <= line["metrics"]["ssm_positions_per_step.serve"][
        "value"] <= 32.0
    assert line["metrics"]["recompiles.serve"]["value"] == 0
    sizes = line["notes"]["branch_rms"]
    assert len(sizes) == 2 and all(v > 0.1 for row in sizes for v in row)


@pytest.mark.parametrize("kw", [
    dict(control=True), dict(fault="state_dropped"),
    dict(fault="state_inherited"), dict(fault="token_altered")])
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
        "clients": 64, "per_client": 8, "prompt": {"min": 16, "max": 64},
        "output": {"min": 512, "max": 1024}, "greedy_share": 1.0,
        "sampling": {"temperature": 0.8, "top_k": 40},
        "shape_seed": 20261002}
    assert cell["check"] == {"tokens": 2400, "requests": 4}
    assert cell["generator"] == "closed_loop_uniform"
    config = run.load_json("configs", "falcon_h1_34b_l4")
    # the published widths (ISSUE 32's table), uncut
    assert {k: config[k] for k in (
        "hidden_size", "intermediate_size", "vocab_size", "mamba_d_ssm",
        "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
        "mamba_d_conv", "num_attention_heads", "num_key_value_heads",
        "head_dim", "rope_theta")} == {
        "hidden_size": 5120, "intermediate_size": 21504,
        "vocab_size": 261120, "mamba_d_ssm": 4096, "mamba_n_heads": 32,
        "mamba_d_head": 128, "mamba_d_state": 256, "mamba_n_groups": 2,
        "mamba_d_conv": 4, "num_attention_heads": 20,
        "num_key_value_heads": 4, "head_dim": 128,
        "rope_theta": 100000000000}
    assert config["published"] == {"num_hidden_layers": 72,
                                   "max_position_embeddings": 262144}
    assert (config["num_hidden_layers"],
            config["max_position_embeddings"]) == (4, 2048)
    assert set(config["reduced"]) == {"num_hidden_layers",
                                      "max_position_embeddings"}
    assert config["serve"]["server"] == {
        "kv_cache": "paged", "max_slots": 64, "pool_tokens": 81920}


def test_the_cell_is_on_every_serve_metric():
    """ISSUE 32: the three serve end-to-end metrics and every ``.serve``
    per-layer metric list the cell; the check judges it on all."""
    b = bench()
    for m in b["end_to_end"]:
        if m["name"] in ("serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"):
            assert m["workloads"][-1] == CELL, m["name"]
    serve = [m for m in b["per_layer"] if m["name"].endswith(".serve")]
    assert len(serve) == 23
    assert all(CELL in m["workloads"] for m in serve)


# ---------------------------------------------------------------- counts
def test_counts_against_hand_worked_sums():
    c = run.load_json("configs", "falcon_h1_34b_l4")
    # mixer 5120 x 9248 + 4096 x 5120; attention 5120 x (20 + 8) x 128
    # + 2560 x 5120; MLP 3 x 5120 x 21504
    assert counts.layer_matmul_params(c) == (
        47_349_760 + 20_971_520 + 18_350_080 + 13_107_200 + 330_301_440)
    assert counts.layer_matmul_params(c) == 430_080_000
    assert counts.ssd_flops_per_position(c) == 6 * 32 * 128 * 256 \
        + 2 * 4 * 5120
    one = counts.decoder_forward_flops(c, 1, 0, 0)
    assert one == 4 * (2 * 430_080_000 + 6_291_456 + 40_960)
    assert counts.decoder_forward_flops(c, 0, 10, 0) == 4 * 4 * 20 * 128 * 10
    assert counts.decoder_forward_flops(c, 0, 0, 1) == 2 * 5120 * 261120
    # one decode call at 64 slots: 64 x 32 x 128 x 256 state elements,
    # float32, in and out; x, B, C in bf16, dt and y in float32
    ops, nbytes = counts.ssm_decode(64, 32, 128, 256, 2)
    assert ops == 6 * 67_108_864
    assert nbytes == 2 * 268_435_456 + 64 * (4096 + 1024) * 2 \
        + 64 * 32 * 4 + 64 * 4096 * 4
    ops, nbytes = counts.ssd_chunk(64, 32, 32, 128, 256, 2)
    assert ops == 32 * 6 * 67_108_864
    assert nbytes == 2 * 268_435_456 + 64 * 32 * (
        (4096 + 1024) * 2 + 32 * 4 + 4096 * 4)
    # what the engine holds: 64 slots x 4 layers x (4 MiB + 3 x 5120 bf16)
    assert counts.recurrent_state_bytes(c, 64) == 64 * 4 * (
        4_194_304 + 30_720)


def test_the_engine_reports_the_bytes_the_counts_give():
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import FalconH1Config, FalconH1Model
    from apex_tpu.models.generate import cache_shapes
    from apex_tpu.serving import cache as slot_cache

    c = run.load_json("configs", "falcon_h1_34b_l4")
    cfg = FalconH1Config.from_hf(c, dtype=jnp.bfloat16,
                                 param_dtype=jnp.bfloat16, kv_cache="paged",
                                 kv_block_size=16, kv_pool_blocks=3)
    shapes = cache_shapes(FalconH1Model(cfg), 2)
    assert slot_cache.recurrent_state_bytes(shapes) \
        == counts.recurrent_state_bytes(c, 2)
    del jax


# ---------------------------------------------------------------- reader
def run_with(ops, shape_key="ssm_decode_shape"):
    shape = dict(slots=64, heads=32, d_head=128, d_state=256, groups=2)
    if shape_key == "ssd_chunk_shape":
        shape["width"] = 32
    return {"peaks": {"tflops_bf16": 197.0, "hbm_gbs": 819.0},
            "facts": {shape_key: shape},
            "trace": {"busy_s": 1.0, "ops": ops}}


def test_the_roofline_reader_on_a_made_up_trace():
    reader = run.load_module("readers", "ssm_roofline")
    spec = run.load_json("metrics", "ssm_decode_roofline.serve")
    label = ('%ssm_decode_update.8 = (f32[64,4,128,8]) custom-call(), '
             'custom_call_target="tpu_custom_call"')
    _, nbytes = counts.ssm_decode(64, 32, 128, 256, 2)
    least = nbytes / 819e9                    # memory binds
    ops = {"a": {"label": label, "count": 40, "total_s": 40 * 2 * least},
           "b": {"label": "%fusion.1 = bf16[64,5120] fusion()",
                 "count": 9, "total_s": 1.0}}
    got = reader.read(spec["args"], run_with(ops))
    assert got == pytest.approx(50.0)
    # no such kernel in the trace (the parent's program): no number
    assert reader.read(spec["args"], run_with({"b": ops["b"]})) is None
    off_chip = dict(run_with(ops), peaks=None)
    assert reader.read(spec["args"], off_chip) is None
    chunk = run.load_json("metrics", "ssm_chunk_scan_roofline.serve")
    ops = {"c": {"label": label.replace("ssm_decode_update",
                                        "ssm_chunk_scan"),
                 "count": 3, "total_s": 3 * 4 * counts.ssd_chunk(
                     64, 32, 32, 128, 256, 2)[1] / 819e9}}
    assert reader.read(chunk["args"], run_with(
        ops, "ssd_chunk_shape")) == pytest.approx(25.0)
    share = run.load_module("readers", "op_time_share").read(
        run.load_json("metrics", "ssm_kernels_share.serve")["args"],
        run_with(ops, "ssd_chunk_shape"))
    assert share == pytest.approx(100.0 * ops["c"]["total_s"])


# ------------------------------------------------- the two serve drivers
def driver_output(monkeypatch, cell, seed):
    """What the cell's driver hands ``run.py``: ``facts``, ``notes``,
    ``end_to_end`` and the rest, caught on its way."""
    seen = {}
    real = run.load_module

    def spy(kind, name):
        mod = real(kind, name)
        if kind == "drivers":
            inner = mod.run

            def caught(ctx):
                out = inner(ctx)
                seen.update(out)
                return out

            mod.run = caught
        return mod

    with monkeypatch.context() as patch:
        patch.setattr(run, "load_module", spy)
        run.run_cell(["--workload", cell, "--seed", str(seed), "--seconds",
                      "3", "--trace", "0", "--rehearse"], need_chip=False)
    return seen


def test_both_serve_drivers_report_the_same_keys(monkeypatch):
    """``serve_falcon_h1.run`` is ``serve_server.run`` around another
    model: every key a reader or the ledger's notes may ask for is
    there under the same name, and what is new is named here."""
    old = driver_output(monkeypatch, "mistral_7b_l8.decode_heavy", 31)
    new = driver_output(monkeypatch, CELL, 31)
    assert set(new) == set(old)
    assert set(new["end_to_end"]) == set(old["end_to_end"]) == {
        "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"}
    assert set(new["checks"]) == set(old["checks"])
    extra = {"other_gap_max", "branch_rms"}
    assert set(new["notes"]) - set(old["notes"]) == extra
    assert set(new["facts"]) - set(old["facts"]) == extra | {
        "ssm_decode_shape", "ssd_chunk_shape"}
    assert set(old["notes"]) <= set(new["notes"])
    assert set(old["facts"]) <= set(new["facts"])
    # the definitions themselves, line for line
    sources = [inspect.getsource(run.load_module("drivers", name).run)
               for name in ("serve_server", "serve_falcon_h1")]
    for line in ('"serve_tokens_per_s": in_window / ctx.seconds',
                 '"ttft_p95_ms": percentile(ttft, 95)',
                 '"itl_p95_ms": percentile(itl, 95)',
                 "ttft = [(s.times[0] - s.due) * 1e3 for s in sents "
                 "if s.times]",
                 "in_window = sum(1 for s in sents for t in s.times "
                 "if t <= t_close)"):
        assert all(line in src for src in sources), line
