"""Measure a :class:`~apex_tpu.plan.score.HardwareSpec` on-device.

The planner's roofline defaults (:data:`~apex_tpu.plan.score.
DEFAULT_HW`) are the bench harness's *assumed* peaks — fine for
orderings, but a deployment planning against real silicon should score
against what this chip actually sustains (the recorded PR-14
follow-up).  :func:`calibrate` runs three short micro-sweeps and
returns the measured spec::

    import apex_tpu
    from apex_tpu import plan

    p = apex_tpu.plan(cfg, devices=8, hardware=plan.calibrate())

- **MXU**: a square bf16 matmul large enough to saturate the unit,
  timed best-of-k → ``2·N³ / t`` FLOP/s;
- **HBM**: a copy of a buffer far larger than any cache, timed the
  same way → ``2 × bytes / t`` (one read + one write stream);
- **ICI**: a ring ``psum`` over the attached devices — wire bytes per
  chip are ``2·(n−1)/n × payload`` (the same ring model
  :func:`~apex_tpu.plan.costs.ddp_bytes_on_wire` scores with); a
  single-device host keeps the default (there is no wire to time);
- **HBM capacity**: the device's own ``memory_stats()['bytes_limit']``
  where the backend reports one, the default budget otherwise.

Off-accelerator (the CPU test/CI environment) :func:`calibrate`
returns :data:`DEFAULT_HW` untouched — a host-emulated "peak" would
poison every feasibility decision with numbers three orders of
magnitude off.  ``force=True`` runs the sweeps anyway (how the CPU
unit tests exercise the measurement path itself).

Measurements are sustained-throughput, not datasheet peaks: scoring
against them tightens the roofline uniformly, and the planner's
*orderings* — the contract — are insensitive to uniform rescaling.
"""

from __future__ import annotations

import time
from typing import Any, Optional, Sequence

from apex_tpu.plan.score import DEFAULT_HW, HardwareSpec

__all__ = ["calibrate"]

#: backends worth measuring — a host CPU "calibration" would report
#: ~0.1 TFLOP/s and starve every layout at the feasibility gate
_ACCELERATOR_BACKENDS = ("tpu",)


def _time_best(fn, *, warmup: int = 2, iters: int = 5) -> float:
    """Best-of-``iters`` wall time of ``fn()`` (a thunk returning jax
    arrays), after ``warmup`` undcounted runs to absorb compilation
    and first-touch allocation.  Best-of (not mean) because every
    source of noise — preemption, clock ramp, other tenants — only
    ever makes a run SLOWER than the hardware's sustained rate."""
    import jax

    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _measure_tflops(device, *, n: int = 2048, iters: int = 5) -> float:
    """Sustained matmul rate on one device: 2·N³ flops / best time."""
    import jax
    import jax.numpy as jnp

    # placement by the input: the jitted computation runs wherever
    # its operand lives (jit's device= kwarg is deprecated)
    x = jax.device_put(jnp.ones((n, n), jnp.bfloat16), device)
    f = jax.jit(lambda a: a @ a)
    t = _time_best(lambda: f(x), iters=iters)
    return 2.0 * n ** 3 / t / 1e12


def _measure_hbm_gbs(device, *, mbytes: int = 256,
                     iters: int = 5) -> float:
    """Sustained memory bandwidth: one read + one write stream over a
    buffer far past any cache, so the copy is bandwidth-bound."""
    import jax
    import jax.numpy as jnp

    elems = mbytes * (1 << 20) // 4
    x = jax.device_put(jnp.ones((elems,), jnp.float32), device)
    # the +1.0 defeats a copy-elision: the output must be written
    f = jax.jit(lambda a: a + 1.0)
    t = _time_best(lambda: f(x), iters=iters)
    return 2.0 * elems * 4 / t / 1e9


def _measure_ici_gbs(devices, *, mbytes: int = 64,
                     iters: int = 5) -> Optional[float]:
    """Sustained per-chip collective wire rate: time a ``psum`` over
    all attached devices and divide the ring all-reduce's per-chip
    wire bytes (``2·(n−1)/n × payload``) by it.  None on a single
    device — nothing crosses a wire."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n = len(devices)
    if n < 2:
        return None
    elems = mbytes * (1 << 20) // 4
    mesh = Mesh(np.asarray(devices), ("i",))
    xs = jax.device_put(jnp.ones((n, elems), jnp.float32),
                        NamedSharding(mesh, P("i")))
    f = jax.jit(jax.shard_map(lambda a: lax.psum(a, "i"), mesh=mesh,
                              in_specs=P("i"), out_specs=P("i")))
    t = _time_best(lambda: f(xs), iters=iters)
    wire = 2.0 * (n - 1) / n * elems * 4
    return wire / t / 1e9


def _device_hbm_bytes(device) -> Optional[float]:
    stats = device.memory_stats()      # None where the backend has none
    if stats and stats.get("bytes_limit"):
        return float(stats["bytes_limit"])
    return None


def calibrate(devices: Optional[Sequence[Any]] = None, *,
              force: bool = False,
              matmul_n: int = 2048,
              copy_mbytes: int = 256,
              psum_mbytes: int = 64,
              iters: int = 5) -> HardwareSpec:
    """Measure this machine's :class:`HardwareSpec` from micro-sweeps.

    ``devices`` — the device set to calibrate on (all attached by
    default; the ICI sweep spans them, the MXU/HBM sweeps run on the
    first).  ``force`` — measure even off-accelerator (CPU hosts
    normally get :data:`DEFAULT_HW` back unchanged, because a
    host-emulated peak would poison the feasibility gate).  The sweep
    sizes (``matmul_n``, ``copy_mbytes``, ``psum_mbytes``) default
    large enough to saturate a TPU core; shrink them only to make a
    forced CPU measurement cheap.

    A sweep that cannot run (one device has no wire to time) keeps
    that field's default; a sweep that fails raises — a default peak
    must never pass for a measured one.  Total cost is a few hundred
    milliseconds on a TPU host — cheap enough to run once per process
    at plan time:
    ``apex_tpu.plan(cfg, hardware=plan.calibrate())``.
    """
    import jax

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if not devices:
        raise ValueError("calibrate() needs at least one device")
    if devices[0].platform not in _ACCELERATOR_BACKENDS and not force:
        return DEFAULT_HW
    kw = {
        "peak_tflops": _measure_tflops(
            devices[0], n=matmul_n, iters=iters),
        "peak_hbm_gbs": _measure_hbm_gbs(
            devices[0], mbytes=copy_mbytes, iters=iters),
    }
    ici = _measure_ici_gbs(devices, mbytes=psum_mbytes, iters=iters)
    if ici is not None:
        kw["peak_ici_gbs"] = ici
    hbm = _device_hbm_bytes(devices[0])
    if hbm:
        kw["hbm_bytes"] = hbm
    return HardwareSpec(**kw)
