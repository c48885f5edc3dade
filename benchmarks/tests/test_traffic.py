"""The traffic generators are pure functions of their arguments, and
the schedule is the cell's: only what the requests say follows the seed."""

import json
import pathlib

import numpy as np
import pytest

import run

WORKLOADS = pathlib.Path(__file__).resolve().parents[1] / "workloads"
SERVE = ["mistral_7b_l8.chat_open_loop", "mistral_7b_l8.decode_heavy"]


def traffic(cell, seed, seconds=30.0):
    spec = json.loads((WORKLOADS / f"{cell}.json").read_text())
    gen = run.load_module("traffic", spec["generator"])
    return gen.generate(spec["traffic_params"], seed, seconds, 32000), spec


def rows(t):
    return t["requests"] if t["mode"] == "open" else \
        [r for c in t["clients"] for r in c]


@pytest.mark.parametrize("cell", SERVE)
def test_same_seed_same_traffic(cell):
    a, _ = traffic(cell, 7)
    b, _ = traffic(cell, 7)
    for x, y in zip(rows(a), rows(b)):
        assert np.array_equal(x["prompt"], y["prompt"])
        assert {k: v for k, v in x.items() if k != "prompt"} == \
            {k: v for k, v in y.items() if k != "prompt"}


@pytest.mark.parametrize("cell", SERVE)
def test_seed_changes_ids_not_the_schedule(cell):
    a, _ = traffic(cell, 7)
    b, _ = traffic(cell, 3_000_000_019)
    ra, rb = rows(a), rows(b)
    assert len(ra) == len(rb)
    assert [len(r["prompt"]) for r in ra] == [len(r["prompt"]) for r in rb]
    assert [r["max_new_tokens"] for r in ra] == \
        [r["max_new_tokens"] for r in rb]
    assert [r["greedy"] for r in ra] == [r["greedy"] for r in rb]
    assert [r.get("due_s") for r in ra] == [r.get("due_s") for r in rb]
    assert any(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(ra, rb))


def test_open_loop_fills_the_window_at_its_rate():
    t, spec = traffic("mistral_7b_l8.chat_open_loop", 1, seconds=40.0)
    p = spec["traffic_params"]
    due = [r["due_s"] for r in t["requests"]]
    assert len(due) == round(p["rate_per_s"] * 40.0)
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 40.0
    lens = [len(r["prompt"]) for r in t["requests"]]
    assert min(lens) >= p["prompt"]["min"] and max(lens) <= p["prompt"]["max"]
    greedy = sum(r["greedy"] for r in t["requests"])
    assert greedy == round(p["greedy_share"] * len(due))
    assert all(r["sampling"] == {} for r in t["requests"] if r["greedy"])


def test_mlm_batches_follow_the_seed_and_rows_differ():
    gen = run.load_module("traffic", "mlm_batches")
    p = {"batch": 4, "seq": 32, "mlm_positions": 8, "distinct": 3}
    a, b, c = gen.generate(p, 5, 512), gen.generate(p, 5, 512), \
        gen.generate(p, 6, 512)
    for (i1, p1, l1), (i2, p2, l2) in zip(a, b):
        assert np.array_equal(i1, i2) and np.array_equal(p1, p2) \
            and np.array_equal(l1, l2)
    assert not np.array_equal(a[0][0], c[0][0])
    ids = np.concatenate([np.asarray(x[0]) for x in a])
    assert len({r.tobytes() for r in ids}) == len(ids)
    i0, p0, l0 = (np.asarray(x) for x in a[0])
    assert np.array_equal(np.take_along_axis(i0, p0, 1), l0)
