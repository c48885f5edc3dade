"""Grouped matrix products over ragged groups — the expert GEMMs of a
drop-free mixture-of-experts layer.

``lhs`` ``(m, k)`` holds the rows of every group one after the other
(rows sorted by expert), ``rhs`` ``(groups, k, n)`` one matrix a group
and ``group_sizes`` ``(groups,)`` how many rows each group has::

    out[start_g : start_g + size_g] = lhs[start_g : start_g + size_g] @ rhs[g]

The sizes may add up to less than ``m``: the rows behind the last
group belong to no group, cost nothing and come back UNDEFINED (the
caller selects them away; it must not multiply them by zero).  A group
of no rows costs nothing either: its matrix is never read.  That is
what makes the layer above drop-free at static shapes — the buffer is
as long as the worst case and the work is what the routing really
sent.

Two implementations under :mod:`apex_tpu.ops._dispatch`:

- **Pallas TPU kernel** (``implementation="pallas"``): the grouped
  matmul of ``jax.experimental.pallas.ops.tpu.megablox`` at this
  module's tiling, under ``jax.named_scope("expert_gmm")``.  Its grid
  visits the (row tile, group) pairs that hold rows, so a step's time
  follows the assignments and the experts that got one;
- **XLA** (``implementation="xla"``; CPU/GPU fallback and the golden
  semantics): :func:`jax.lax.ragged_dot`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.ops._dispatch import resolve_impl

__all__ = ["expert_gmm", "expert_gmm_reference", "ROW_TILE"]

#: rows a tile of the kernel holds: callers pad ``m`` to a multiple.
#: The tiling is the fastest of nine measured on the chip at Trinity's
#: shapes (32 experts of 3072 x 6144 and 3072 x 3072, 512 of 4096 rows
#: in groups; PERF.md section 6, PR 37): the whole contraction in one tile,
#: 512 columns — 2.71 ms for both products of a layer against 6.03 for
#: ``jax.lax.ragged_dot`` (whose own kernel takes 512-row tiles)
ROW_TILE = 128
_K_TILE = 3072
_N_TILE = 512


def expert_gmm_reference(lhs, rhs, group_sizes):
    """:func:`jax.lax.ragged_dot`: rows behind the last group are 0."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))


def _tile(size, want):
    """The widest tile of at most ``want`` that divides ``size`` into
    128-multiples, else the whole extent."""
    for t in range(min(want, size), 127, -128):
        if size % t == 0 and t % 128 == 0:
            return t
    return size


def expert_gmm(lhs, rhs, group_sizes, *,
               implementation: Optional[str] = None):
    """Grouped matrix product (module docstring); output in
    ``lhs.dtype``, float32 accumulation."""
    m, k = lhs.shape
    groups, k2, n = rhs.shape
    if k2 != k or group_sizes.shape != (groups,):
        raise ValueError(
            f"expert_gmm: lhs {lhs.shape}, rhs {rhs.shape}, group_sizes "
            f"{group_sizes.shape} do not fit (m, k) x (groups, k, n)")
    pallas_ok = (m % ROW_TILE == 0 and k % 128 == 0 and n % 128 == 0
                 and lhs.dtype == rhs.dtype)
    impl = resolve_impl(implementation, pallas_ok=pallas_ok,
                        op="expert_gmm")
    if impl == "xla":
        return expert_gmm_reference(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    with jax.named_scope("expert_gmm"):
        return gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype,
                   (ROW_TILE, _tile(k, _K_TILE), _tile(n, _N_TILE)),
                   interpret=impl == "pallas_interpret")
