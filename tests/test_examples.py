"""Example-script smoke tests (subprocess, CPU mesh).

Round-2 verdict weak #7: the ``--data FILE.npz`` branch of the imagenet
example had never executed (no dataset in this environment) — here a
tiny synthetic npz exercises the real-data code path end to end.  The
``--pp`` pipelined mode of transformer_tp (build_model + spmd_pipeline)
gets the same treatment.
"""

import os
import re
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(script, args, timeout=900):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, script), *args],
        env=env, capture_output=True, text=True, timeout=timeout)


_SAMPLE_NPZ = os.path.join(_REPO, "examples", "data",
                           "sample_imagenet.npz")


@pytest.mark.slow
class TestImagenetExample:
    # [slow: 3 subprocess train runs ≈ 200s — the --data loader branch
    # integration; the dcgan test below keeps a conv-example subprocess
    # in tier-1]
    def test_checked_in_shard_trains(self):
        # the in-repo uint8 sample shard (examples/data, regenerable
        # via make_sample.py) through the real --data loader branch
        r = _run_example(
            "examples/imagenet/main_amp.py",
            ["--data", _SAMPLE_NPZ, "--arch", "resnet18",
             "--batch-size", "16", "--image-size", "32",
             "--steps", "3", "--opt-level", "O2"])
        assert r.returncode == 0, r.stderr[-2000:]
        # num_classes must have been derived from the npz labels, and
        # the printed losses must be finite
        losses = re.findall(r"loss (\d+\.\d+)", r.stdout)
        assert losses, r.stdout[-2000:]
        assert all(np.isfinite(float(l)) for l in losses)

    def test_npz_data_branch_trains(self, tmp_path, rng):
        # tiny class-separable float32 dataset through the same loader
        n, size, classes = 16, 32, 4
        labels = rng.integers(0, classes, size=(n,))
        protos = rng.normal(size=(classes, size, size, 3))
        images = (protos[labels]
                  + 0.3 * rng.normal(size=(n, size, size, 3)))
        path = tmp_path / "tiny.npz"
        np.savez(path, images=images.astype(np.float32),
                 labels=labels.astype(np.int64))

        r = _run_example(
            "examples/imagenet/main_amp.py",
            ["--data", str(path), "--arch", "resnet18",
             "--batch-size", "16", "--image-size", str(size),
             "--steps", "3", "--opt-level", "O2"])
        assert r.returncode == 0, r.stderr[-2000:]
        losses = re.findall(r"loss (\d+\.\d+)", r.stdout)
        assert losses, r.stdout[-2000:]
        assert all(np.isfinite(float(l)) for l in losses)

    def test_npz_num_classes_from_labels(self, tmp_path, rng):
        path = tmp_path / "two.npz"
        np.savez(path,
                 images=rng.normal(size=(8, 32, 32, 3)).astype(
                     np.float32),
                 labels=np.asarray([0, 1, 2, 0, 1, 2, 0, 6],
                                   np.int64))
        r = _run_example(
            "examples/imagenet/main_amp.py",
            ["--data", str(path), "--arch", "resnet18",
             "--batch-size", "8", "--image-size", "32",
             "--steps", "1"])
        assert r.returncode == 0, r.stderr[-2000:]


class TestDCGANExample:
    def test_checked_in_shard_real_branch(self):
        # the dcgan --data branch (real images as the D's positive
        # distribution) on the in-repo shard
        r = _run_example(
            "examples/dcgan/main_amp.py",
            ["--data", _SAMPLE_NPZ, "--batch-size", "16",
             "--steps", "2"])
        assert r.returncode == 0, r.stderr[-2000:]
        pairs = re.findall(r"G (\d+\.\d+)\s+D (\d+\.\d+)", r.stdout)
        assert len(pairs) == 2, r.stdout[-1000:]
        assert all(np.isfinite(float(g)) and np.isfinite(float(d))
                   for g, d in pairs)


class TestTransformerTPExample:
    def test_pp_mode(self):
        r = _run_example(
            "examples/transformer_tp.py",
            ["--tp", "2", "--pp", "2", "--dp", "2", "--steps", "2",
             "--batch-size", "4", "--seq-len", "32"])
        assert r.returncode == 0, r.stderr[-2000:]
        losses = re.findall(r"loss (\d+\.\d+)", r.stdout)
        assert len(losses) == 2, r.stdout[-1000:]
        assert all(np.isfinite(float(l)) for l in losses)

    def test_pp_rejects_bad_batch(self):
        r = _run_example(
            "examples/transformer_tp.py",
            ["--tp", "2", "--pp", "2", "--dp", "2",
             "--batch-size", "3", "--seq-len", "32"])
        assert r.returncode != 0
        assert "multiple of the microbatch" in (r.stderr + r.stdout)


class TestDistributedExample:
    def test_zero2_trains_sharded(self):
        # ISSUE-11 satellite: the --zero path stops hand-replicating
        # optimizer state — sharded masters/moments over the 8-device
        # 'data' axis, reduce-scatter grad sync, ResilientLoop intact
        r = _run_example("examples/simple/distributed.py",
                         ["--zero", "2", "--steps", "30"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert "zero: stage 2 over 8-way 'data' axis" in r.stdout, \
            r.stdout[-2000:]
        # the printed state shard is a genuine 1/n slice
        assert "B/device (~1/8 of replicated)" in r.stdout
        losses = re.findall(r"loss (\d+\.\d+)", r.stdout)
        assert losses, r.stdout[-2000:]
        assert all(np.isfinite(float(l)) for l in losses)
        assert float(losses[-1]) < float(losses[0])

    def test_plan_auto_routes_layout(self):
        # ISSUE-15 satellite: --plan auto stops hand-picking the
        # layout — the ZeRO stage/wire come from apex_tpu.plan() over
        # a parameter-count profile; training must still converge on
        # the planned layout
        r = _run_example("examples/simple/distributed.py",
                         ["--plan", "auto", "--steps", "20"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert "plan: auto -> dp=8" in r.stdout, r.stdout[-2000:]
        assert "alternatives scored" in r.stdout
        losses = re.findall(r"loss (\d+\.\d+)", r.stdout)
        assert losses, r.stdout[-2000:]
        assert float(losses[-1]) < float(losses[0])

    @pytest.mark.slow
    def test_plan_auto_yields_to_explicit_zero(self):
        # [slow: a second subprocess run of the example; the
        # explicit-flag precedence itself is argument plumbing — the
        # tier-1 smoke above keeps the planner path exercised]
        # explicit flags still win: --zero 1 pins the stage, the
        # planner is never consulted
        r = _run_example("examples/simple/distributed.py",
                         ["--plan", "auto", "--zero", "1",
                          "--steps", "12"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert "plan: auto" not in r.stdout
        assert "zero: stage 1" in r.stdout, r.stdout[-2000:]

    @pytest.mark.slow
    def test_zero1_int8_wire_trains(self):
        # [slow: a second subprocess run of the same example; the
        # stage-1 and int8-wire semantics are tier-1-covered by
        # test_zero.py]
        r = _run_example("examples/simple/distributed.py",
                         ["--zero", "1", "--zero-int8",
                          "--steps", "30"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert "zero: stage 1" in r.stdout and "int8" in r.stdout
        losses = re.findall(r"loss (\d+\.\d+)", r.stdout)
        assert losses and float(losses[-1]) < float(losses[0])

    @pytest.mark.slow
    def test_zero2_ckpt_resume(self, tmp_path):
        # [slow: two subprocess runs — kill-free resume of the SHARDED
        # state through the zero_shardings restore target; the
        # placement semantics are tier-1-covered by test_zero.py]
        d = str(tmp_path / "ckpts")
        r1 = _run_example("examples/simple/distributed.py",
                          ["--zero", "2", "--steps", "25",
                           "--ckpt-dir", d])
        assert r1.returncode == 0, r1.stderr[-2000:]
        r2 = _run_example("examples/simple/distributed.py",
                          ["--zero", "2", "--steps", "40",
                           "--ckpt-dir", d])
        assert r2.returncode == 0, r2.stderr[-2000:]
        m = re.search(r"resumed_from (\d+)", r2.stdout)
        assert m and int(m.group(1)) >= 20, r2.stdout[-2000:]


class TestServingDemoExample:
    def test_mixed_traffic_serves(self):
        r = _run_example("examples/serving_demo.py",
                         ["--requests", "5", "--max-slots", "2"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert r.stdout.count("req ") == 5, r.stdout[-2000:]
        assert "done: 5 requests" in r.stdout, r.stdout[-2000:]
        # the metrics sink must have streamed at least one ordered row
        assert "metrics step=" in r.stdout, r.stdout[-2000:]

    @pytest.mark.slow
    def test_kv_dtype_serves_quantized_paged(self):
        # [slow: a second serving subprocess warming the paged server
        # ≈ 25s; the quantized datapath itself is tier-1-covered by
        # test_paged_serving.py::TestQuantizedKV]
        r = _run_example("examples/serving_demo.py",
                         ["--requests", "5", "--max-slots", "2",
                          "--kv-dtype", "int8"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert r.stdout.count("req ") == 5, r.stdout[-2000:]
        assert "kv: dtype=int8 bits=8" in r.stdout, r.stdout[-2000:]
        assert "done: 5 requests" in r.stdout, r.stdout[-2000:]

    @pytest.mark.slow
    def test_tp_path_serves_sharded_replica(self):
        # [slow: a serving subprocess warming the tensor-parallel
        # paged server ≈ 30s; the sharded datapath itself is
        # tier-1-covered by test_tp_serving.py]
        r = _run_example("examples/serving_demo.py",
                         ["--requests", "4", "--max-slots", "2",
                          "--tp", "2"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert r.stdout.count("req ") == 4, r.stdout[-2000:]
        assert "tp: chips_per_replica=2" in r.stdout, r.stdout[-2000:]
        assert "done: 4 requests" in r.stdout, r.stdout[-2000:]
        assert "chips_per_replica=2" in r.stdout, r.stdout[-2000:]

    @pytest.mark.slow
    def test_tp_composes_with_replicas_fleet(self):
        # [slow: a serving subprocess warming a 2×2 fleet (2 replicas
        # × 2 chips, each on its own device slice) ≈ 60s; the merged
        # chips gauges are tier-1-covered by test_tp_serving.py]
        r = _run_example("examples/serving_demo.py",
                         ["--requests", "4", "--max-slots", "2",
                          "--tp", "2", "--replicas", "2"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert r.stdout.count("req ") == 4, r.stdout[-2000:]
        assert "fleet: replicas=2 ready=2 chips_per_replica=2 " \
               "chips_total=4" in r.stdout, r.stdout[-2000:]
        assert "done: 4 requests" in r.stdout, r.stdout[-2000:]

    @pytest.mark.slow
    def test_plan_auto_respects_pinned_axis(self):
        # [slow: a serving subprocess warming a 2-chip TP replica ≈
        # 30s like the --tp smoke]  review regression: an explicit
        # flag PINS its axis — with replicas pinned at 1 on a 2-chip
        # budget the planner must pick the scored 1x2 TP split (never
        # graft an unscored combination or override the pin)
        r = _run_example("examples/serving_demo.py",
                         ["--plan", "auto", "--chips", "2",
                          "--replicas", "1", "--requests", "4",
                          "--max-slots", "2"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert "plan: auto -> 1x2 (replicas x tp)" in r.stdout, \
            r.stdout[-2000:]
        assert "tp: chips_per_replica=2" in r.stdout, r.stdout[-2000:]
        assert "done: 4 requests" in r.stdout, r.stdout[-2000:]

    @pytest.mark.slow
    def test_plan_auto_serves_planned_split(self):
        # [slow: a serving subprocess warming a 2-replica fleet ≈ 25s
        # like the --replicas smoke; the planner itself is
        # tier-1-covered by test_plan.py]  ISSUE-15 satellite: the
        # replicas×tp split comes from apex_tpu.plan(objective=
        # "serve") — on a 2-chip budget the per-chip score picks the
        # 2×1 fleet (the tp_serving protocol's throughput ceiling)
        r = _run_example("examples/serving_demo.py",
                         ["--plan", "auto", "--chips", "2",
                          "--requests", "4", "--max-slots", "2"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert "plan: auto -> 2x1 (replicas x tp)" in r.stdout, \
            r.stdout[-2000:]
        assert r.stdout.count("req ") == 4, r.stdout[-2000:]
        assert "fleet: replicas=2 ready=2" in r.stdout, \
            r.stdout[-2000:]
        assert "done: 4 requests" in r.stdout, r.stdout[-2000:]

    @pytest.mark.slow
    def test_replicas_path_routes_through_fleet(self):
        # [slow: a second serving subprocess warming 2 paged replicas
        # ≈ 25s; the fleet router itself is tier-1-covered by
        # test_fleet.py and the single-server demo test above stays]
        r = _run_example("examples/serving_demo.py",
                         ["--requests", "5", "--max-slots", "2",
                          "--replicas", "2"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert r.stdout.count("req ") == 5, r.stdout[-2000:]
        assert "fleet: replicas=2 ready=2" in r.stdout, \
            r.stdout[-2000:]
        assert "done: 5 requests" in r.stdout, r.stdout[-2000:]
        # per-replica emissions aggregate into the one fleet writer,
        # namespaced — the printed rows carry replica<N>/ keys
        assert "metrics step=" in r.stdout, r.stdout[-2000:]
        assert "replica0/" in r.stdout or "replica1/" in r.stdout, \
            r.stdout[-2000:]


@pytest.mark.slow
class TestLlamaGenerateExample:
    # [slow: two subprocess generate runs incl. a torch cross-check
    # ≈ 85s; greedy parity stays tier-1-covered by test_generate and
    # test_serving]
    def test_greedy_matches_torch(self):
        r = _run_example("examples/llama_generate.py", [])
        assert r.returncode == 0, r.stderr[-2000:]
        assert "token-identical to torch" in r.stdout

    def test_windowed_sampling(self):
        r = _run_example(
            "examples/llama_generate.py",
            ["--window", "8", "--temperature", "0.8",
             "--max-new-tokens", "6"])
        assert r.returncode == 0, r.stderr[-2000:]
        assert r.stdout.count("cont:") == 2
