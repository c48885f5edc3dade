"""The trace reduction: its arithmetic on made-up intervals, and the
whole of it on a small trace recorded on the chip and kept here."""

import pathlib

import pytest

from lib import trace

RECORDED = pathlib.Path(__file__).resolve().parent / "recorded.xplane.pb"


def test_union_merges_touching_and_nested():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4), (10, 12), (10.5, 11)]) \
        == [[0, 4], [5, 6], [10, 12]]


def test_self_time_takes_enclosed_events_out():
    evs = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"), (50, 60, "c")]
    got = dict(trace.self_times(evs))
    assert got == {"while": 30, "a": 20, "b": 40, "c": 10}
    assert sum(got.values()) == 100          # the union's length


def test_short_name_keeps_the_instruction():
    text = "%fusion.37 = (f32[30528,1024]{1,0:T(8,128)}, f32[3]) fusion(x)"
    assert trace.short_name(text).startswith("fusion (f32[30528,1024]")
    assert trace.short_name("ThunkExecutor::Execute") == \
        "ThunkExecutor::Execute"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_chip_trace():
    s = trace.reduce_file(str(RECORDED), chips=1)
    # a device trace: busy below the window, both positive
    assert 0 < s["busy_s"] < s["window_s"]
    total_self = sum(r["self_s"] for r in s["ops"].values())
    assert total_self == pytest.approx(s["busy_s"], rel=1e-6)
    # the recorded program's matmul is found by pattern (all must hold)
    n, names, seconds = trace.events_matching(
        s, ["%fusion", "bf16[1024,1024]"])
    assert n >= names >= 1 and 0 < seconds <= s["busy_s"]
    assert trace.events_matching(s, ["%fusion", "no such"]) == (0, 0, 0.0)
    # the program ran more than once
    runs = [v for k, v in s["modules"].items() if "recorded_step" in k]
    assert runs and len(runs[0]) >= 2
    # the gaps between the runs were spent in the benchmark's own span
    gaps = dict(s["idle_gaps"])
    assert "bench/host_pause" in gaps and gaps["bench/host_pause"] > 0
    assert s["device_ops"][0][1] >= s["device_ops"][-1][1]
