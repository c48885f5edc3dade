"""The readers of the program's own measurement (PR 29): their
arithmetic on made-up runs, and that every path a metric names is one a
rehearsed serve cell's ``health()`` really holds."""

import json
import pathlib

import pytest

import run

HERE = pathlib.Path(__file__).resolve().parents[1]
SERVE = ["mistral_7b_l8.chat_open_loop", "mistral_7b_l8.decode_heavy"]
NEW = ["decode_program_ms_p50.serve", "prefill_program_ms_p50.serve",
       "decode_step_ms_mean.serve", "prefill_step_ms_mean.serve",
       "engine_host_ms_per_step.serve", "scheduler_host_ms_per_step.serve",
       "server_host_ms_per_step.serve", "queue_wait_ms_mean.serve",
       "prefill_ms_mean.serve", "recompiles.serve",
       "layer_norm_share.train", "flash_fwd_roofline.train",
       "flash_bwd_roofline.train"]

health_ratio = run.load_module("readers", "health_ratio")
op_time_share = run.load_module("readers", "op_time_share")


def health(steps, step_s, plan_s, admit_s, wait_s, admitted):
    return {"steps": steps, "queue_wait_s": wait_s, "admitted": admitted,
            "spans": {"apex/serve/step": {"n": steps, "s": step_s},
                      "apex/engine/plan": {"n": steps, "s": plan_s},
                      "apex/sched/admit": {"n": admitted, "s": admit_s}}}


def facts(before, after):
    return {"facts": {"health_before": before, "health_after": after}}


RUN = facts(health(10, 1.0, 0.010, 0.002, 0.5, 4),
            health(30, 3.5, 0.050, 0.012, 2.5, 8))


def test_health_ratio_scales_the_moved_numerator_over_the_moved_den():
    got = health_ratio.read(
        {"num": ["spans.apex/engine/plan.s", "spans.apex/sched/admit.s"],
         "den": "steps", "scale": 1e3}, RUN)
    assert got == pytest.approx(1e3 * (0.040 + 0.010) / 20)
    assert health_ratio.read(
        {"num": ["queue_wait_s"], "den": "admitted", "scale": 1e3},
        RUN) == pytest.approx(500.0)


def test_health_ratio_takes_the_minus_terms_off():
    got = health_ratio.read(
        {"num": ["spans.apex/serve/step.s"],
         "minus": ["spans.apex/engine/plan.s", "spans.apex/sched/admit.s"],
         "den": "steps", "scale": 1e3}, RUN)
    assert got == pytest.approx(1e3 * (2.5 - 0.040 - 0.010) / 20)


def test_health_ratio_without_a_den_is_the_difference():
    assert health_ratio.read({"num": ["admitted"]}, RUN) == 4


def test_health_ratio_is_none_where_the_den_did_not_move():
    same = facts(RUN["facts"]["health_after"], RUN["facts"]["health_after"])
    assert health_ratio.read(
        {"num": ["queue_wait_s"], "den": "admitted"}, same) is None


def test_health_ratio_misspelt_path_is_an_error_not_zero():
    with pytest.raises(KeyError):
        health_ratio.read(
            {"num": ["spans.apex/engine/plam.s"], "den": "steps"}, RUN)
    with pytest.raises(KeyError):
        health_ratio.read(
            {"num": ["queue_wait_s"], "den": "spans.apex/serve/step.m"}, RUN)


def test_health_ratio_is_none_for_a_program_without_the_field():
    """The parent of the PR that brought a field has no such key in
    ``health()``: the metric is left out, the run goes on."""
    old = facts({"steps": 10}, {"steps": 30})
    assert health_ratio.read(
        {"num": ["spans.apex/engine/plan.s"], "den": "steps"}, old) is None
    assert health_ratio.read({"num": ["compiles"]}, old) is None


def trace_of(ops, busy_s):
    return {"trace": {"busy_s": busy_s, "ops": {
        name: {"count": n, "total_s": s, "self_s": s, "label": name}
        for name, (n, s) in ops.items()}}}


def test_op_time_share_is_the_matching_ops_share_of_busy_time():
    got = op_time_share.read(
        {"patterns": ["%layer_norm_", "tpu_custom_call"]},
        trace_of({"%layer_norm_fwd.3 = custom-call(tpu_custom_call)": (4, 0.2),
                  "%layer_norm_bwd.1 = custom-call(tpu_custom_call)": (2, 0.1),
                  "%layer_norm_fwd.9 = fusion(x)": (1, 0.4),
                  "%attention.fwd.1 = custom-call(tpu_custom_call)": (2, 0.3)},
                 2.0))
    assert got == pytest.approx(15.0)


def test_op_time_share_is_none_where_nothing_matches():
    run_ = trace_of({"%fusion.1 = fusion(x)": (3, 0.5)}, 1.0)
    assert op_time_share.read({"patterns": ["%layer_norm_"]}, run_) is None
    idle = trace_of({"%layer_norm_fwd.1 = x": (1, 0.5)}, 0.0)
    assert op_time_share.read({"patterns": ["%layer_norm_"]}, idle) is None


@pytest.mark.parametrize("name", NEW)
def test_new_metric_has_its_file_its_reader_and_its_entry(name):
    spec = json.loads((HERE / "metrics" / f"{name}.json").read_text())
    assert (HERE / "readers" / f"{spec['reader']}.py").exists()
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"]
    moved = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    for cell in entry[0]["workloads"]:
        assert cell in moved[entry[0]["moves"]]


@pytest.mark.parametrize("cell", SERVE)
def test_rehearsed_serve_cell_reports_every_program_counter_metric(cell):
    """Every path that a ``health_ratio`` metric names is in the
    ``health()`` of the server the cell runs; the ``device_trace`` ones
    may be absent off the chip (no ``XLA Modules`` line), and no reader
    raises."""
    line = run.run_cell(
        ["--workload", cell, "--seed", "2900000051", "--seconds", "3",
         "--trace", "1", "--rehearse"], need_chip=False)
    assert line["correct"] is True
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    wanted = {m["name"] for m in bench["per_layer"]
              if m["name"] in NEW and cell in m["workloads"]
              and m["source"] == "program_counter"}
    assert len(wanted) >= 7 and wanted <= set(line["metrics"])
    assert line["metrics"]["recompiles.serve"]["value"] == 0
    for name in wanted:
        assert line["metrics"][name]["value"] >= 0
    gaps = [name for name, _ in line["breakdown"]["idle_gaps"]]
    assert "PjitFunction(counted)" not in gaps
