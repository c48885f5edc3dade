"""Test harness: hermetic multi-device testing on CPU.

The reference's distributed tests require >=2 real GPUs (SURVEY.md §4).
We do strictly better: every DP/TP/PP/SP test runs on CPU with 8 virtual
XLA devices, so the whole suite is hermetic.  Pallas kernels run in
interpret mode on CPU; the same code paths compile natively on TPU.

This file must set env vars BEFORE jax is imported anywhere.
"""

import os

# Force CPU whatever platform the ambient environment selects: the unit
# suite must be hermetic and fast.  Set APEX_TPU_TEST_PLATFORM=tpu to
# run kernel tests on real hardware (tools/onchip_tests.py does).
_platform = os.environ.get("APEX_TPU_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def _ensure_native_extension():
    """Build the optional _apex_C extension if it is missing (a fresh
    checkout has no in-place .so): the native tests are skip-guarded
    on it, and a silently-skipped native suite defeats the point of
    having one.  Failure is non-fatal — setup.py already treats the
    extension as optional — but is reported once and remembered via a
    sentinel so a toolchain-less machine doesn't re-pay the build
    attempt (and re-hide its error) on every pytest run.

    Every xdist worker imports this file at the same moment, so the
    build runs under an exclusive file lock: one worker builds, the
    others wait and then find the extension (or the sentinel) there.
    """
    import fcntl
    import importlib
    import subprocess
    import sys

    from apex_tpu import native

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    build = os.path.join(root, "build")
    sentinel = os.path.join(build, ".native_build_failed")

    def settled():
        importlib.invalidate_caches()
        importlib.reload(native)      # re-attempts the _apex_C import
        return native.HAVE_NATIVE or os.path.exists(sentinel)

    if settled():
        return
    os.makedirs(build, exist_ok=True)
    with open(os.path.join(build, ".native_build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if settled():                 # another worker got there first
            return
        res = subprocess.run(
            [sys.executable, "setup.py", "build_ext", "--inplace"],
            cwd=root, capture_output=True, text=True, timeout=120,
            check=False)
        if not settled():
            with open(sentinel, "w") as f:
                f.write(res.stdout[-2000:] + "\n" + res.stderr[-2000:])
            print(f"warning: _apex_C build failed — native tests will "
                  f"skip; log: {sentinel}")


try:
    _ensure_native_extension()
except Exception as _exc:                           # noqa: BLE001
    print(f"warning: _apex_C auto-build errored: {_exc!r}")

# Something may have imported jax before this conftest ran, making the
# env var above a no-op.  Setting the config directly still works as
# long as no backend has been used.
jax.config.update("jax_platforms", _platform)
_want = {"cuda": "gpu", "rocm": "gpu"}.get(
    _platform.split(",")[0], _platform.split(",")[0])
assert jax.default_backend() == _want, (
    f"test suite must run on {_want}, got {jax.default_backend()}")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def mesh8():
    """An 8-device (2 data, 2 pipe, 2 tensor) mesh on virtual CPU devices."""
    from apex_tpu.core import mesh as mesh_lib

    m = mesh_lib.initialize_mesh(
        tensor_model_parallel_size=2,
        pipeline_model_parallel_size=2,
        data_parallel_size=2,
    )
    yield m
    mesh_lib.destroy_mesh()
