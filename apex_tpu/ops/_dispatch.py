"""Implementation dispatch for fused ops.

Each op in :mod:`apex_tpu.ops` ships (a) a Pallas TPU kernel and (b) an
XLA (plain jnp) composition with identical semantics — the golden
reference the kernel is tested against, and the fallback on CPU/GPU.
This mirrors the reference's import-try pattern (every
``apex/contrib/*`` python half falls back or skips when its CUDA ext
isn't built) but resolution here is per-call and explicit.

``implementation=`` accepted values:

- ``"auto"``   — Pallas on TPU backends, XLA elsewhere (default);
- ``"pallas"`` — force the Pallas kernel (compiled);
- ``"pallas_interpret"`` — Pallas kernel in interpreter mode (runs on
  CPU; used by the hermetic kernel tests);
- ``"xla"``    — force the jnp composition.

Env override ``APEX_TPU_OPS_IMPL`` sets the default for "auto".
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax

__all__ = ["resolve_impl", "pick_block_rows"]

_VALID = ("auto", "pallas", "pallas_interpret", "xla")

_logger = logging.getLogger(__name__)
#: ops whose "auto" already said it left the kernel for XLA on a TPU
_said_xla = set()


def resolve_impl(implementation: Optional[str], *,
                 pallas_ok: bool = True,
                 auto_default: str = "pallas",
                 op: Optional[str] = None) -> str:
    """Resolve an ``implementation`` argument to a concrete choice.

    ``pallas_ok=False`` signals the caller's shapes are outside the
    kernel's support envelope (e.g. unaligned hidden size) — "auto"
    then resolves to "xla".  ``auto_default`` is the op's own
    TPU preference for "auto" — ops whose XLA composition measured
    FASTER than their kernel (group_norm, BASELINE.md round 4) pass
    ``"xla"`` so the measured winner is the default while explicit
    ``implementation=``/env overrides still reach the kernel.

    Ops that pass their name as ``op`` get the strict contract: the
    result is exactly what runs.  Asking for the kernel outside its
    envelope raises (the reference never answers under the kernel's
    name), and on a TPU "auto" says once per op, through this module's
    logger, that it chose XLA.
    """
    impl = implementation or os.environ.get("APEX_TPU_OPS_IMPL", "auto")
    if impl not in _VALID:
        raise ValueError(
            f"implementation={impl!r} not in {_VALID}")
    if impl == "auto":
        on_tpu = jax.default_backend() == "tpu"
        if auto_default == "pallas" and pallas_ok and on_tpu:
            return "pallas"
        if op is not None and on_tpu and op not in _said_xla:
            _said_xla.add(op)
            _logger.warning(
                "%s: implementation='auto' runs the XLA reference on "
                "this TPU (the call is outside the Pallas kernel's "
                "envelope)", op)
        return "xla"
    if op is not None and impl != "xla" and not pallas_ok:
        raise ValueError(
            f"{op}: implementation={impl!r} asks for the Pallas kernel "
            f"but the call is outside its envelope (shapes, dtypes or "
            f"fast memory) — pass 'auto' or 'xla' for the reference")
    return impl


def pick_block_rows(n_rows: int, width: int, *,
                    op: Optional[str] = None, dtype=None) -> int:
    """Rows per grid step for row-wise kernels (LN/softmax): keep the
    fp32 x-block ≲ 2 MB of VMEM, ≥ 8 rows, multiple of 8 (fp32 sublane).

    When ``op`` is given and :mod:`apex_tpu.ops.autotune` has a measured
    entry for (device, op, width, dtype), the measured block size takes
    precedence over the heuristic.
    """
    if op is not None:
        from apex_tpu.ops import autotune
        hit = autotune.cached_block_rows(op, width, str(dtype))
        if hit:
            br = max(8, min(hit, max(8, n_rows)))
            return max(8, (br // 8) * 8)   # fp32 sublane alignment
    budget = (2 * 1024 * 1024) // max(1, width * 4)
    br = max(8, min(256, budget))
    br = (br // 8) * 8
    return max(8, min(br, max(8, n_rows)))
