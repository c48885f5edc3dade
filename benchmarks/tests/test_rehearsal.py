"""``run.py`` end to end at the cells' tiny ``rehearse`` sizes on the
CPU: the last line's keys, the platform it names, and that ``correct``
comes out false when the timed path is broken underneath or the
lower-precision reference stands in the program's place.

The limits at these sizes are the ``rehearse.limits`` of each cell's
file, set by the same rule as the chip's from readings at these sizes
(program: gradient <= 0.0097, update <= 0.0127, median update <=
0.00038, logit gap <= 0.005; fp8 control: update >= 0.0191, median
update >= 0.0023, logit gap >= 0.047).  The two loss limits are the
chip's: at these sizes an unchanged state reads 4e-5 to 1.1e-3 against
the sound runs' 1.1e-4, so here they catch nothing and fail nothing."""

import json
import pathlib
import subprocess
import sys

import pytest

import run

ROOT = pathlib.Path(__file__).resolve().parents[2]
TRAIN = "bert_large_o2.phase2_s512_b16"
SERVE = ["mistral_7b_l8.chat_open_loop", "mistral_7b_l8.decode_heavy"]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def rehearse(cell, seed, seconds, trace=0, **kw):
    return run.run_cell(
        ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--rehearse"], need_chip=False, **kw)


def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell,seconds", [(TRAIN, 1), (SERVE[0], 3),
                                          (SERVE[1], 3)])
def test_command_prints_the_contracts_last_line(cell, seconds):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "3000000017", "--seconds", str(seconds), "--trace", "0",
         "--rehearse"], cwd=ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(ROOT / ".bench_trace")})
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[: len(KEYS)] == KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"       # never a chip run
    assert line["device"]["rehearse"] is True
    wanted = {m["name"] for m in bench()["end_to_end"]
              if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == wanted and "setup_s" in wanted
    assert all(v["value"] > 0 for v in line["metrics"].values())
    # every number compared, beside its limit, ends standard error
    tail = p.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and " limit " in t for t in tail)


def test_without_a_chip_the_command_fails_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", TRAIN, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(ROOT / ".bench_trace")})
    assert p.returncode != 0 and "no TPU" in p.stderr
    assert not p.stdout.strip()


@pytest.mark.parametrize("cell", [TRAIN, SERVE[1]])
def test_traced_run_reports_per_layer_metrics_and_breakdown(cell):
    line = rehearse(cell, 5, 3, trace=1)
    assert line["correct"] is True
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(line["breakdown"]["device_ops"]) <= 10
    names = {m["name"] for m in bench()["per_layer"]
             if cell in m["workloads"]}
    # off a chip a share of a peak or of a roofline is left out, never 0
    assert set(line["metrics"]) <= names
    assert not any("mfu" in k or "roofline" in k for k in line["metrics"])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_training_fault_is_not_correct(fault):
    line = rehearse(TRAIN, 21, 1, fault=fault)
    assert line["correct"] is False
    if fault == "state_unchanged":
        assert line["checks"]["update_norm_worst_leaf"]["value"] == 1.0


def test_altered_token_is_not_correct():
    line = rehearse(SERVE[1], 22, 3, fault="token_altered")
    assert line["correct"] is False


@pytest.mark.parametrize("cell", [TRAIN, SERVE[1]])
def test_lower_precision_control_is_not_correct(cell):
    assert rehearse(cell, 23, 3, control=True)["correct"] is False
    assert rehearse(cell, 23, 3)["correct"] is True


def test_reference_with_the_state_unchanged_is_not_correct():
    """The fault planted in the reference put in the program's place,
    as the chip's loss readings were taken (PERF.md section 6)."""
    line = run.run_cell(
        ["--workload", TRAIN, "--seed", "24", "--seconds", "1", "--trace",
         "0", "--rehearse"], need_chip=False, control=True,
        fault="state_unchanged")
    assert line["correct"] is False
    assert line["checks"]["update_norm_median_leaf"]["value"] == 1.0
    assert line["notes"]["not_compared"]["loss_step1_rel"] == 0.0


@pytest.mark.parametrize("step", [2, 3])
def test_a_loss_off_the_references_is_not_correct(step):
    """The cell's file holds the second and third loss to a limit: a
    step that trains the wrong way passes the norms and fails here."""
    train_step = run.load_module("drivers", "train_step")
    limits = run.load_json("workloads", TRAIN)["limits"]
    norms = {"a.w": 1.0, "b.w": 2.0, "c.w": 3.0}
    ref = ([10.5, 10.46, 10.43], norms, norms)
    good, _ = train_step.compare(ref, ref, limits)
    assert all(c["value"] <= c["limit"] for c in good.values())
    losses = list(ref[0])
    losses[step - 1] += 0.04              # the loss did not fall
    bad, _ = train_step.compare((losses, norms, norms), ref, limits)
    name = f"loss_step{step}_rel"
    assert bad[name]["value"] > bad[name]["limit"]
    assert "loss_step1_rel" not in bad
