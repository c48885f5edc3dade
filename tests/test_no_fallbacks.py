"""No hidden fallbacks (ISSUE 24): a path that cannot do what was
asked says so or raises — it never carries on on a CPU, a reference or
a default under the name of what was asked for."""

import json
import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.ops import _dispatch
from apex_tpu.ops.fused_sampling import fused_sample
from apex_tpu.ops.paged_attention import (paged_attention,
                                          paged_decode_fused)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------ strict dispatch
def _paged_args(bs):
    b, h, hk, d, nb, mb = 2, 4, 2, 16, 9, 4
    q = jnp.zeros((b, 1, h, d), jnp.float32)
    pool = jnp.zeros((hk, nb, bs, d), jnp.float32)
    tables = jnp.zeros((b, mb), jnp.int32)
    lengths = jnp.zeros((b,), jnp.int32)
    return q, pool, pool, tables, lengths


def _call_paged_attention(impl):
    return paged_attention(*_paged_args(bs=4), implementation=impl)


def _call_paged_decode_fused(impl):
    q, kp, vp, tables, lengths = _paged_args(bs=4)
    new = jnp.zeros((2, 1, 2, 16), jnp.float32)
    return paged_decode_fused(q, new, new, kp, vp, tables, lengths,
                              max_seq_len=16, implementation=impl)


def _call_fused_sample(impl):
    rows, vocab = 2, 100                    # vocab not 128-aligned
    return fused_sample(
        jnp.zeros((rows, vocab)), jnp.zeros((rows, 2), jnp.uint32),
        jnp.ones((rows,)), jnp.zeros((rows,), jnp.int32),
        jnp.zeros((rows,)), implementation=impl)


_OUTSIDE_ENVELOPE = {"paged_attention": _call_paged_attention,
                     "paged_decode_fused": _call_paged_decode_fused,
                     "fused_sample": _call_fused_sample}


@pytest.mark.parametrize("op", sorted(_OUTSIDE_ENVELOPE))
@pytest.mark.parametrize("impl", ["pallas", "pallas_interpret"])
def test_explicit_kernel_outside_envelope_raises(op, impl):
    with pytest.raises(ValueError, match="envelope"):
        _OUTSIDE_ENVELOPE[op](impl)


@pytest.mark.parametrize("op", sorted(_OUTSIDE_ENVELOPE))
def test_auto_outside_envelope_is_the_reference(op):
    auto = _OUTSIDE_ENVELOPE[op]("auto")
    xla = _OUTSIDE_ENVELOPE[op]("xla")
    for a, x in zip(jax.tree.leaves(auto), jax.tree.leaves(xla)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(x))


def test_auto_says_once_when_it_leaves_the_kernel_on_a_tpu(
        monkeypatch, caplog):
    monkeypatch.setattr(_dispatch.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(_dispatch, "_said_xla", set())
    with caplog.at_level(logging.WARNING, logger=_dispatch.__name__):
        for _ in range(3):
            assert _dispatch.resolve_impl(
                "auto", pallas_ok=False, op="some_op") == "xla"
        assert _dispatch.resolve_impl(
            "auto", pallas_ok=True, op="some_op") == "pallas"
    said = [r for r in caplog.records if "some_op" in r.getMessage()]
    assert len(said) == 1


# ------------------------------------------- zoo model, manual shard_map
def test_zoo_model_runs_inside_a_fully_manual_shard_map():
    """The data-parallel / ZeRO step shape: flax's own unboxing of the
    weights' partitioning metadata is refused there by the installed
    jax; ``sharded_param`` drops the axes the step holds Manual."""
    from apex_tpu.transformer.testing.commons import standalone_bert

    model, params = standalone_bert(seed=0)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
    ids = jnp.zeros((8, 16), jnp.int32)

    def local(p, x):
        logits, _ = model.apply({"params": p}, x)
        return jax.lax.pmean(jnp.mean(logits.astype(jnp.float32)),
                             "data")

    out = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P("data")), out_specs=P(),
        check_vma=False))(params, ids)
    assert np.isfinite(float(out))


# ------------------------------------------------------------ the bench
def test_unknown_device_kind_has_no_peaks():
    import bench

    assert bench.device_fields()["platform"] == "cpu"
    with pytest.raises(ValueError, match="no published peaks"):
        bench.chip_peaks()
    assert bench.frac_of_hbm_peak(100.0) is None


def test_aot_compile_lets_the_compile_error_out():
    import bench

    def bad(x):
        raise RuntimeError("refused")

    with pytest.raises(RuntimeError, match="refused"):
        bench._aot_compile(jax.jit(bad), jnp.zeros(()))


def test_bench_refuses_to_time_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_TINY", None)
    r = subprocess.run([sys.executable, os.path.join(_REPO, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=300, cwd=_REPO)
    assert r.returncode != 0
    assert "per-chip" in r.stderr and not r.stdout.strip()


def test_bench_configs_rows_name_devices_and_errors_surface(capsys):
    import bench_configs as bc

    rows = {"a": {"value": 1, "device": {"platform": "tpu"}},
            "b": {"rows": {"x": {"error": "boom",
                                 "device": {"platform": "tpu"}}}}}
    assert bc._errors_in(rows) == [("b/rows/x", "boom")]
    assert bc._child_devices(rows) == [{"platform": "tpu"}]
    # this process has a backend: its own device is the row's
    bc._emit({"metric": "m"})
    row = json.loads(capsys.readouterr().out)
    assert row["device"]["platform"] == "cpu"


def test_run_child_refuses_a_chip_leg_from_a_parent_on_jax():
    import bench_configs as bc

    jax.devices()                           # this parent holds a backend
    assert bc._backend_initialised()
    with pytest.raises(RuntimeError, match="one process|holds it"):
        bc._run_child("group_norm", {"JAX_PLATFORMS": "tpu"})


def test_dryrun_raises_with_fewer_devices_than_asked(monkeypatch):
    import __graft_entry__ as entry

    # the dry run edits XLA_FLAGS for its (virtual) devices: restore it
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    with pytest.raises(RuntimeError, match="does not swap"):
        entry.dryrun_multichip(len(jax.devices()) + 8)


def test_calibrate_lets_a_failed_sweep_raise(monkeypatch):
    import importlib

    cal = importlib.import_module("apex_tpu.plan.calibrate")

    def broken(*a, **k):
        raise RuntimeError("sweep failed")

    monkeypatch.setattr(cal, "_measure_hbm_gbs", broken)
    with pytest.raises(RuntimeError, match="sweep failed"):
        cal.calibrate(jax.devices()[:1], force=True, matmul_n=32,
                      copy_mbytes=1, iters=1)
    assert cal._ACCELERATOR_BACKENDS == ("tpu",)


# ------------------------------------------------------- compile cache
@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_compile_cache_is_one_guarded_call(monkeypatch, env_dir):
    from apex_tpu.utils import compile_cache as cc

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(_REPO, ".jax_cache")
        assert cc.enable_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want)]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert cc.enable_compile_cache() == env_dir
        assert calls == []                  # jax reads the variable


# ---------------------------------------------------------- chip_smoke
def test_chip_smoke_fails_off_the_chip_before_any_work():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=300, cwd=_REPO)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not r.stdout.strip()             # no phase ran, no result
