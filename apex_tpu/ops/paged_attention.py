"""Paged-attention decode — block-table-gathered KV attention.

The serving engine's paged KV-cache (``apex_tpu.serving``) stores K/V
in fixed-size **pages** of a shared pool instead of a dense
``max_slots × max_seq_len`` slab: page ``p`` of sequence ``b`` lives at
physical pool block ``block_tables[b, p]``, and the pool is sized in
*tokens* (``num_blocks × block_size``), shared by every co-resident
tenant.  This op computes one decode/chunk attention step over that
layout: each query row attends over exactly its own pages, gathered
through its block table.

Why it matters: a dense slab's steady decode reads (or at best
cond-skips over) a ``max_seq_len`` cache row per slot per step, and its
HBM *footprint* reserves ``max_slots × max_seq_len`` tokens no matter
how short the live sequences are.  Here the footprint, the per-step
bytes and the kernel's time all scale with **live tokens**: a slot at
position ``L`` owns ``ceil((L+1)/block_size)`` pages and the kernel
fetches and visits only those (the TPU-serving recipe of "Fine-Tuning
and Serving Gemma on Cloud TPU", PAPERS.md).

Layouts::

    q             (batch, s, num_heads, head_dim)   s = chunk (1 = decode)
    k_pages       (kv_heads, num_blocks, block_size, head_dim)
    v_pages       (kv_heads, num_blocks, block_size, head_dim)
    block_tables  (batch, pages_per_seq)  int32 physical block ids
    lengths       (batch,)  int32 — tokens already cached *before* this
                  chunk; query i of row b sits at position lengths[b]+i

The chunk's own K/V must already be written into the pool (the model's
write-then-attend convention, ``models/transformer.py``: a step wider
than one token writes through :func:`paged_write`, the width-1 step
inside :func:`paged_decode_fused`); visibility is
by absolute position — key position ``p`` is visible to query ``i``
iff ``p <= lengths[b] + i`` — so garbage beyond the cursor (freed
pages, pad-token writes) is never read.  Physical block 0 is the
engine's **null page** (pad writes land there); the mask makes its
contents unreachable, so the op needs no special case for it.

**Sliding window (``window=``)**: a windowed layer's query at position
``p`` sees keys ``p - window < j <= p`` only.  Every read of the pool
takes ``window=None | int``: the references mask the positions before
a query's window, and the kernels' sweep STARTS at the chunk that holds
position ``max(0, lengths[b] - window + 1)`` — the window's start of the
row's first query lane — so a windowed row costs what its window holds,
not what its context holds; later lanes mask the positions before
their own start.  The pages behind the window stay allocated (the
engine's to release, ROADMAP M1); they are no longer read.
``window=None`` is the program it always was.

**Multi-query verify (speculative decoding)**: the same ``s > 1``
chunk path scores a draft run ``[current, d_1..d_k]`` in one
application — query ``i`` sits at ``lengths[b] + i`` and sees exactly
the pool prefix plus the drafts written before it, i.e. the context a
sequential decode would have given it, so per-position logits equal
``k+1`` one-token steps bit-for-bit up to blocked-accumulation order.
Rejection needs no cleanup here: the engine rolls its cursor back over
the rejected tail, the stale draft K/V sits at positions past the new
``lengths`` where this mask cannot reach it, and the next step's
write-then-attend overwrites it.  A verify chunk is just a decode
chunk whose ``s = 1 + spec_tokens`` — no dedicated kernel variant, no
extra executable.

**Quantized KV pages (``k_scales``/``v_scales``)**: the pool may store
int8 or fp8 (``float8_e4m3fn``) codes instead of bf16/fp32 K/V — the
ISSUE-8 capacity lever: at 1 byte/element the same HBM holds ~2× (bf16)
to ~4× (fp32) the tokens, which the serving engine converts into
admitted occupancy.  Quantization is symmetric per **(kv_head, page)**:
``code = round(x · qmax / scale)`` (int8, ``qmax = 127``) or a
saturating fp8 cast (``qmax = 448``), with ``scale`` the page region's
running amax, stored in fp32 ``(kv_heads, num_blocks)`` arrays that
live beside the block table and travel with the page through sharing /
CoW / preemption.  Dequant happens **in-register inside the kernel**:
the per-page scale is constant over its page's ``block_size`` rows of
the chunk tile, so it factors out of the score and value contractions
— the kernel DMAs 1-byte pages, carries the row's page scales as one
small block, and multiplies by a per-row column after the dot, before
the log2-domain online softmax.  The
XLA reference dequantizes the gathered pages explicitly (the parity
anchor); both paths are exercised by
``tests/test_paged_attention.py::TestQuantizedKernel``.  Without
scales (``kv_dtype=None`` upstream) every code path below is
byte-identical to the unquantized module.

Two implementations under the :mod:`apex_tpu.ops._dispatch`
conventions:

- **Pallas TPU kernel** (``implementation="pallas"``): grid
  ``(batch,)`` — one grid step a row, all kv heads in it — and the
  sweep over the row's pages is a LOOP INSIDE the kernel.  The pool
  stays in HBM, unblocked (``memory_space=pl.ANY``); the block table
  and lengths ride **scalar prefetch**
  (``pltpu.PrefetchScalarGridSpec``), and the kernel fetches the
  row's live pages itself, in chunks of ``C = max(1, 128 //
  block_size)`` pages (about 128 key positions, the MXU's width):
  one strided ``make_async_copy`` brings a page for all kv heads.
  The trip count is the row's live chunk count, ``ceil(((length + s
  - 1) // block_size + 1) / C)``, so a call costs what its live
  tokens cost.  The sweep used to be a third grid axis over
  ``pages_per_seq``, dead pages clamped and ``pl.when``-skipped; that
  saved their bytes and not their time — a grid step on this chip
  costs 0.2–0.35 µs whether or not it does anything, and 16 × 8 × 256
  of them made the decode kernel 10.8 ms against 15 µs of live bytes
  (PERF.md §6).  The chunks of all rows are ONE stream of
  fetches, run in the grid's order: while a chunk is attended the two
  after it are in flight in VMEM's other two slots, whether they are
  the row's own or the next rows' first (the scalar-prefetched
  lengths and block tables say which pages those are), and the fused
  kernel's page write-back is waited one row later — so neither a row
  nor a chunk boundary drains the DMA queue (PERF.md §6).
  Online softmax runs in the log2 domain, one score tile per chunk
  and kv head: a wide step's (a prefill chunk's) is the transposed
  ``(C·block_size, rep·s)`` tile of ``ops/attention.py``, keys on
  sublanes; a narrow step's (decode, a speculative verify) is ``(rep·s,
  C·block_size)``, keys on lanes, a register or two, and every head's
  tile is issued before any head's statistics so that the heads'
  matrix-unit round trips overlap.
- **XLA gather reference** (``implementation="xla"``; golden semantics,
  CPU/GPU fallback): ``k_pages[:, block_tables]`` then a masked fp32
  einsum — bit-comparable to the dense cache's attention in
  ``generate()``.

**The chunk write** (:func:`paged_write`, PERF.md §6, PR 36): a step
wider than one token — a mixed step's prefill chunks, a speculative
verify's draft runs — puts its ``(b, s, kv_heads, d)`` rows into the
pool before the attend.  As an XLA scatter that write made the
compiler transpose each pool into the scatter's layout and back, four
pool-sized copies a layer whatever the rows touched; the Pallas kernel
fetches the at most ``(s - 1) // block_size + 2`` pages a row touches,
places the new lanes by a sublane rotate and a masked select and sends
the pages home through outputs aliased to the pools — its cost is its
rows', and the pool never moves.  Off a TPU the op IS the scatter
(:func:`paged_write_reference`).

The *block size itself* is the tunable (the analogue of the row-wise
kernels' block-rows): sweep it offline with
``apex_tpu.ops.autotune.tune_paged_attention`` and the serving engine
picks the measured winner up by default — the cache entry is keyed on
the PER-SHARD ``kv_heads`` count, so a tensor-parallel engine never
adopts a block size swept at full head count.

**Tensor-parallel pool (``mesh=``/``shard_axis=``)**: one serving
replica can span M chips (the ISSUE-13 tentpole) by sharding the pool
on the ``kv_heads`` axis — each chip owns ``kv_heads / M`` heads'
pages (and their per-(kv_head, page) quant scales, which carry the
same leading axis and shard with them) while the block table and
lengths stay **replicated**, so the host-side allocator / refcount /
trie logic never learns about the mesh.  With both arguments set, the
op runs through ``jax.shard_map`` over ``shard_axis``: every chip
executes the ordinary kernel (Pallas on TPU, gather reference
elsewhere) on its local head slice — attention is embarrassingly
parallel over kv heads, so the sharded step needs NO collective here
(the per-layer all-reduces live in the surrounding RowParallel
projections).  Queries shard by the matching GQA grouping: q head
``i`` belongs to kv group ``i // (num_heads/kv_heads)``, so a
contiguous shard of ``num_heads/M`` q heads sees exactly its shard's
kv heads (:func:`tp_head_shards` is the one mapping, validated loudly
at config time when ``kv_heads % M != 0``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import resolve_impl

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_decode_fused", "paged_decode_fused_reference",
           "paged_write", "paged_write_reference", "rope_rows",
           "kv_quant_spec", "kv_store_bytes_per_token",
           "quantize_kv", "quantize_kv_pages", "tp_head_shards"]

_NEG_INF = -1e30
_LOG2E = 1.4426950408889634

#: fp8 storage dtype when this jax build ships one (ml_dtypes-backed)
_FP8_DTYPE = getattr(jnp, "float8_e4m3fn", None)

#: storage dtype → qmax, the one table behind kv_quant_spec (name →
#: spec) and the pool-dtype lookups below — a storage dtype absent
#: here cannot silently dequantize with a wrong divisor
_QMAX_BY_DTYPE = {jnp.dtype(jnp.int8): 127.0}
if _FP8_DTYPE is not None:
    _QMAX_BY_DTYPE[jnp.dtype(_FP8_DTYPE)] = 448.0

# scales below qmax/float32_max would overflow the quantization
# multiplier to +inf (0 * inf = NaN poisons zero K/V) — same guard as
# the int8 AllReduce in parallel/ddp.py
_TINY_SCALE = 448.0 / float(jnp.finfo(jnp.float32).max)


def kv_quant_spec(kv_dtype):
    """Resolve a KV-pool quantization name to ``(storage_dtype, qmax)``.

    ``None`` → ``(None, None)`` (unquantized pool, the default);
    ``"int8"`` → ``(int8, 127.0)``; ``"fp8"`` → ``(float8_e4m3fn,
    448.0)`` where the jax build supports it (a loud ``ValueError``
    otherwise — silently falling back to int8 would change numerics
    behind the caller's back).  The single source of truth for every
    ``kv_dtype=`` knob (``TransformerConfig`` / ``PagedEngine`` /
    ``InferenceServer`` / autotune / bench traffic model).
    """
    if kv_dtype is None:
        return None, None
    if kv_dtype == "int8":
        return jnp.int8, _QMAX_BY_DTYPE[jnp.dtype(jnp.int8)]
    if kv_dtype == "fp8":
        if _FP8_DTYPE is None:
            raise ValueError(
                "kv_dtype='fp8' needs a jax build with "
                "jnp.float8_e4m3fn (this one has none) — use "
                "kv_dtype='int8', which every build supports")
        return _FP8_DTYPE, _QMAX_BY_DTYPE[jnp.dtype(_FP8_DTYPE)]
    raise ValueError(
        f"kv_dtype={kv_dtype!r} not in (None, 'int8', 'fp8')")


def kv_store_bytes_per_token(head_dim, block_size, kv_dtype=None, *,
                             dtype=None):
    """Pool bytes per cached token per (kv_head, layer).

    K+V codes at the storage width plus, under quantization, the two
    fp32 page scales amortized over ``block_size`` tokens.  THE single
    formula behind ``PagedEngine``'s equal-HBM ``pool_tokens`` default,
    the bench ``_serving_traffic_model`` capacity rows, and the
    ``quantized_kv_serving`` leg's byte budget — one site to change if
    the scale granularity ever does, so engine-admitted capacity and
    the analytic model can't silently disagree.  ``dtype`` (the compute
    dtype) is only consulted for an unquantized pool
    (``kv_dtype=None``); multiply by ``kv_heads × num_layers`` for a
    whole model's per-token footprint.
    """
    store_dt, _ = kv_quant_spec(kv_dtype)
    if store_dt is None:
        if dtype is None:
            raise ValueError(
                "dtype is required for an unquantized pool "
                "(kv_dtype=None)")
        return 2 * int(head_dim) * jnp.dtype(dtype).itemsize
    return (2 * int(head_dim) * jnp.dtype(store_dt).itemsize
            + 2 * 4.0 / int(block_size))


def quantize_kv(x, scales, qmax, dtype):
    """Symmetric quantization of ``x`` against per-row amax ``scales``.

    ``x`` ``(..., d)`` float; ``scales`` ``(...)`` fp32 amax — each
    row's last axis is scaled by ``qmax/scale`` and cast to ``dtype``
    (rounded first for integer codes; the fp8 cast rounds itself).
    ``scale == 0`` marks an all-zero row and quantizes to exact 0; the
    near-zero guard keeps ``qmax/scale`` finite.  Clipping only ever
    engages when ``scale`` is *stale-smaller* than the row's amax —
    with the write path's monotone running amax that cannot happen, so
    the codes are exact round-to-nearest at all times.
    """
    scales = scales.astype(jnp.float32)
    ok = scales > _TINY_SCALE
    inv = jnp.where(ok, qmax / jnp.maximum(scales, _TINY_SCALE), 0.0)
    y = jnp.clip(x.astype(jnp.float32) * inv[..., None], -qmax, qmax)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        y = jnp.round(y)
    return y.astype(dtype)


def quantize_kv_pages(k_pages, v_pages, kv_dtype):
    """Quantize a full float K/V pool to ``kv_dtype`` pages + scales.

    Per-(kv_head, page) amax over the ``(block_size, head_dim)`` tile —
    the same granularity the serving write path maintains
    incrementally.  Returns ``(kq, vq, k_scales, v_scales)`` with
    scales of shape ``(kv_heads, num_blocks)`` fp32.  Test/offline
    helper (autotune sweeps, golden fixtures): the engine never
    quantizes a whole pool at once, it quantizes each write.
    """
    store_dt, qmax = kv_quant_spec(kv_dtype)
    if store_dt is None:
        raise ValueError("quantize_kv_pages needs kv_dtype in "
                         "('int8', 'fp8'), got None")
    ks = jnp.max(jnp.abs(k_pages.astype(jnp.float32)), axis=(2, 3))
    vs = jnp.max(jnp.abs(v_pages.astype(jnp.float32)), axis=(2, 3))
    kq = quantize_kv(k_pages, ks[:, :, None], qmax, store_dt)
    vq = quantize_kv(v_pages, vs[:, :, None], qmax, store_dt)
    return kq, vq, ks, vs


def tp_head_shards(num_heads: int, kv_heads: int, tp: int):
    """The GQA group→shard mapping of the tensor-parallel paged pool.

    Shard ``j`` of ``tp`` owns q heads ``[j·h/tp, (j+1)·h/tp)`` and kv
    heads ``[j·hk/tp, (j+1)·hk/tp)`` — contiguous ranges, because q
    heads are stored g-major (head ``i`` attends kv group
    ``i // (h/hk)``, both qkv layouts — see
    ``models/transformer.py::ParallelAttention``), so an even split of
    the kv heads splits the q heads at exactly the matching group
    boundaries and every shard's attention is self-contained.  Returns
    ``[((q_lo, q_hi), (kv_lo, kv_hi)), ...]`` per shard; raises the
    loud config-time ``ValueError`` when ``kv_heads % tp != 0`` (the
    alternative is a shape error deep inside shard_map).
    """
    num_heads, kv_heads, tp = int(num_heads), int(kv_heads), int(tp)
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if num_heads % kv_heads:
        raise ValueError(
            f"kv_heads ({kv_heads}) must divide num_heads "
            f"({num_heads})")
    if kv_heads % tp:
        raise ValueError(
            f"kv_heads ({kv_heads}) must be divisible by the "
            f"tensor-parallel degree ({tp}) — the paged KV pool "
            f"shards on the kv_heads axis, one equal slice per chip "
            f"(GQA groups cannot straddle shards); choose tp from "
            f"the divisors of kv_heads")
    rep = num_heads // kv_heads
    hkl = kv_heads // tp
    return [((j * hkl * rep, (j + 1) * hkl * rep),
             (j * hkl, (j + 1) * hkl)) for j in range(tp)]


def _run_sharded(q, k_pages, v_pages, tables, lengths, scale,
                 implementation, k_scales, v_scales, mesh, axis,
                 window=None):
    """shard_map wrapper: each chip runs the unsharded op on its
    kv-head slice (pool + scales sharded on axis 0, q on its head
    axis, tables/lengths replicated — no collective in here)."""
    _b, _s, h, _d = q.shape
    hk = k_pages.shape[0]
    tp_head_shards(h, hk, mesh.shape[axis])   # loud divisibility check
    P = jax.sharding.PartitionSpec
    q_spec = P(None, None, axis, None)
    pool_spec = P(axis, None, None, None)
    rep_spec = P()
    in_specs = [q_spec, pool_spec, pool_spec, rep_spec, rep_spec]
    args = [q, k_pages, v_pages, tables, lengths]
    if k_scales is not None:
        in_specs += [P(axis, None), P(axis, None)]
        args += [k_scales, v_scales]

    def local(q, kp, vp, bt, ln, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_attention(q, kp, vp, bt, ln, scale=scale,
                               implementation=implementation,
                               k_scales=ks, v_scales=vs, window=window)

    return jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=q_spec, check_vma=False)(*args)


def _check_window(window) -> None:
    if window is not None and int(window) < 1:
        raise ValueError(f"window must be >= 1 or None, got {window}")


def _is_quantized_pool(dtype) -> bool:
    return jnp.dtype(dtype) in _QMAX_BY_DTYPE


def _qmax_for_pool(dtype) -> float:
    try:
        return _QMAX_BY_DTYPE[jnp.dtype(dtype)]
    except KeyError:
        raise ValueError(
            f"no KV quantization spec for pool dtype {jnp.dtype(dtype)}"
        ) from None


# --------------------------------------------------------------------- #
# XLA reference (golden semantics; CPU/GPU fallback)
# --------------------------------------------------------------------- #
def paged_attention_reference(q, k_pages, v_pages, block_tables,
                              lengths, *, scale: Optional[float] = None,
                              k_scales=None, v_scales=None,
                              window: Optional[int] = None):
    """Gather-then-attend reference: softmax(q·K_gatheredᵀ·scale)·V.

    Shapes as in the module docstring.  The gather materializes each
    row's ``pages_per_seq × block_size`` keys (reference semantics —
    the Pallas kernel never does); masking is by absolute position, so
    pool garbage beyond ``lengths[b] + i`` is unreachable.  fp32
    softmax, output in ``q.dtype`` — the same numerics contract as the
    dense cache's attention in ``generate()``.  With ``window`` a
    query at position ``p`` sees keys ``p - window < j <= p`` only.

    With quantized pages (``k_scales``/``v_scales`` given, one fp32
    amax per (kv_head, pool block)), the GATHERED pages are dequantized
    explicitly — ``code · scale / qmax`` in fp32, scales gathered
    through the same block table — so the cost stays O(live pages),
    never O(pool): the quantize-dequant parity anchor the Pallas
    kernel's in-register dequant is tested against.
    """
    b, s, h, d = q.shape
    hk, _nb, bs, _ = k_pages.shape
    rep = h // hk
    scale = (d ** -0.5) if scale is None else scale
    mb = block_tables.shape[1]
    # (hk, b, mb, bs, d) -> (b, mb, bs, hk, d): logical order restored,
    # so key position == gathered index
    keys = jnp.moveaxis(k_pages[:, block_tables], 0, 3)
    vals = jnp.moveaxis(v_pages[:, block_tables], 0, 3)
    if k_scales is not None:
        qmax = _qmax_for_pool(k_pages.dtype)
        ks = jnp.moveaxis(k_scales[:, block_tables], 0, 2)  # (b, mb, hk)
        vs = jnp.moveaxis(v_scales[:, block_tables], 0, 2)
        keys = (keys.astype(jnp.float32)
                * (ks.astype(jnp.float32) / qmax)[:, :, None, :, None])
        vals = (vals.astype(jnp.float32)
                * (vs.astype(jnp.float32) / qmax)[:, :, None, :, None])
    keys = keys.reshape(b, mb * bs, hk, d)
    vals = vals.reshape(b, mb * bs, hk, d)
    qg = q.reshape(b, s, hk, rep, d).astype(jnp.float32)
    scores = jnp.einsum("bsgrd,bkgd->bsgrk", qg,
                        keys.astype(jnp.float32)) * scale
    pos_q = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)  # (b, s)
    k_pos = jnp.arange(mb * bs, dtype=jnp.int32)
    visible = k_pos[None, None, :] <= pos_q[:, :, None]        # (b, s, K)
    if window is not None:
        visible &= k_pos[None, None, :] > pos_q[:, :, None] - window
    scores = jnp.where(visible[:, :, None, None, :], scores, _NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bsgrk,bkgd->bsgrd", p, vals.astype(jnp.float32))
    return o.reshape(b, s, h, d).astype(q.dtype)


# --------------------------------------------------------------------- #
# Pallas TPU kernel
# --------------------------------------------------------------------- #
def _row_page_scales(scales, tables):
    """``(kv_heads, num_blocks)`` page scales -> ``(b, kv_heads, 1,
    pages_per_seq)`` fp32 in each row's LOGICAL page order, gathered
    through the block table in XLA (``b·kv_heads·pages_per_seq``
    floats) and carried whole per row grid step.  A ``(1, 1)`` block
    of the pool-wide array is below the TPU's (8, 128) tile — the
    chip's compiler refuses it — and a scalar per page is too small to
    be worth a DMA of its own anyway."""
    g = scales.astype(jnp.float32)[:, tables]            # (hk, b, mb)
    return g.transpose(1, 0, 2)[:, :, None, :]


def _row_spec(*block):
    """BlockSpec of one row's ``(1, *block)`` slab of a ``(b, ...)``
    operand — every blocked operand of both kernels is indexed by the
    grid's one axis, the row."""
    zeros = (0,) * len(block)
    return pl.BlockSpec((1, *block), lambda row, *_: (row, *zeros))


def _page_scale(row, j):
    """Logical page ``j``'s scale out of one (row, head)'s ``(1,
    pages_per_seq)`` lane row of :func:`_row_page_scales`, as a (1, 1)
    tile: a one-hot lane select (the sum adds exact zeros, so the
    value is bitwise the stored scale; a ``j`` past the table reads
    0)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == j, row, 0.0), axis=1,
                   keepdims=True)


def _chunk_pages(bs):
    """Pages a sweep chunk fetches: about 128 key positions, the MXU's
    width on this chip — derived from the block size, never set."""
    return max(1, 128 // bs)


def _live_pages(length, s, bs, mb):
    """Pages holding a key some query of the row can see: the prefix
    up to the newest query's position, clamped to the table (a cursor
    at or past the table's end sweeps all of it)."""
    return jnp.minimum(
        jax.lax.div(jnp.maximum(length + s - 1, 0), bs) + 1, mb)


def _pick(pred, a, b):
    """``a`` where ``pred``, else ``b``: the sweep's scalar index
    arithmetic is plain ``lax`` (a select, a division or remainder of
    non-negative values), because ``jnp.where``, ``//`` and ``%`` each
    lower a nested function, and every kernel body is lowered again at
    each start of the program."""
    return jax.lax.select(pred, jnp.asarray(a, jnp.int32),
                          jnp.asarray(b, jnp.int32))


#: chunk buffers of the sweep: the chunk attended and the two behind it
#: in flight (PERF.md §6)
_SLOTS = 3

#: a sweep's pallas_call runs its rows in order: each hands the stream
#: of chunk fetches on to the rows after it
_ROWS_IN_ORDER = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _key_axis(lanes):
    """The axis of a sweep's score tile that holds the chunk's keys.

    A wide step (a prefill chunk: ``rep·s`` of 128 query lanes or
    more) puts the keys on sublanes — ``(C·bs, rep·s)``, the
    ops/attention.py layout, measured there — so the query lanes fill
    the vector registers.  A narrow one (decode: ``rep`` of 4–6 query
    rows; a speculative verify) puts them on lanes — ``(rep·s, C·bs)``
    — where the transposed tile would be 16 registers of which a few
    lanes are real, and every statistic, exponential and the value
    contraction's transpose would pay for all of them (PERF.md
    §6)."""
    return 0 if lanes >= 128 else 1


def _sweep_scratch(hk, bs, d, lanes, pool_dtype):
    """Scratch of :func:`_sweep_row`: the K and V chunk tiles for all
    kv heads in :data:`_SLOTS` slots, their DMA semaphores (side ×
    slot), the slot of the row's first chunk (carried from row to
    row), and the per-head online-softmax state, laid out along
    :func:`_key_axis`'s other axis."""
    t = _chunk_pages(bs) * bs
    keys_on_sublanes = _key_axis(lanes) == 0
    stat = (1, lanes) if keys_on_sublanes else (lanes, 1)
    return [
        pltpu.VMEM((_SLOTS, hk, t, d), pool_dtype),      # K chunks
        pltpu.VMEM((_SLOTS, hk, t, d), pool_dtype),      # V chunks
        pltpu.SemaphoreType.DMA((2, _SLOTS)),
        pltpu.SMEM((1,), jnp.int32),                     # first slot
        pltpu.VMEM((hk, *stat), jnp.float32),            # m
        pltpu.VMEM((hk, *stat), jnp.float32),            # l
        pltpu.VMEM((hk, *((d, lanes) if keys_on_sublanes
                          else (lanes, d))), jnp.float32),   # acc
    ]


def _row_span(length, *, s, bs, mb, window):
    """A row's sweep: its live pages, the first chunk a query of the
    row can see into (a static 0 without a window: the window's start
    of the row's first query lane otherwise) and its chunk count —
    never 0, so every row fetches and waits at least one chunk."""
    c_pages = _chunk_pages(bs)
    live = _live_pages(length, s, bs, mb)
    n_chunks = jax.lax.div(live + c_pages - 1, c_pages)
    first = 0 if window is None else jnp.minimum(
        jax.lax.div(jnp.maximum(length - window + 1, 0), c_pages * bs),
        n_chunks - 1)
    return live, first, n_chunks


def _stream_ahead(k, c, n_chunks, first1, n1, first2):
    """Where the grid's stream of chunks is ``k`` (at most 2) chunks
    after chunk ``c`` of a row of ``n_chunks``, as ``(rows on,
    chunk)``: in this row, in the next (its sweep runs from ``first1``
    to ``n1``) or in the one after (its ``first2``; every row sweeps
    at least one chunk, so two chunks on is never past its first)."""
    past = k - (n_chunks - c)          # chunks into the rows after this
    on = _pick(past < 0, 0, _pick(past < n1 - first1, 1, 2))
    chunk = _pick(on == 0, c + k, _pick(on == 1, first1 + past, first2))
    return on, chunk


def _sweep_row(tables_ref, lens_ref, k_hbm, v_hbm, scratch, o_ref, *,
               s, bs, rep, mb, q_tile, qmax=None, page_scales=None,
               on_last_chunk=None, window=None):
    """One row's online-softmax sweep over its LIVE pages — the one
    body behind both kernels (the masking/softmax algebra must never
    fork).  The row is the grid's (``pl.program_id(0)``), its length
    ``lens_ref[row]``.

    The pool stays in HBM (``k_hbm``/``v_hbm``, unblocked); the row's
    live pages arrive in CHUNKS of :func:`_chunk_pages` pages by the
    kernel's own DMA — one strided copy brings a page for ALL kv heads
    — into one of :data:`_SLOTS` VMEM tiles.  The trip count is the
    row's live chunk count, so a call costs what its live tokens cost,
    whatever the table's width.  Page slots of the last chunk past the
    row's last live page re-fetch that page (every buffer row holds
    real pool data); the position mask makes them unreachable.

    The fetches are ONE STREAM over the grid: the chunks of row 0,
    then row 1's, and so on, each in slot ``ordinal mod 3``.  While a
    chunk is attended the two after it in the stream are in flight —
    the row's next two, or the next rows' first ones, read from the
    scalar-prefetched lengths and block tables (:func:`_stream_ahead`)
    — so a row starts with its data on the way and a short row costs
    no round trip of its own.  The grid's first step starts the
    stream's first two chunks; nothing is started past the last row;
    each chunk is waited by its own row, so the semaphores balance.
    The rows must run in order (:data:`_ROWS_IN_ORDER`).  No row reads
    a live page another row writes in the call: a row's write page is
    its own last page (the engine forks a shared page before a write),
    and a row that may not write starts no write.

    A wide step's score tiles are TRANSPOSED — ``(C·bs, rep·s)``: key
    slots on sublanes, (q-head, chunk-offset) lanes — so the softmax
    statistics are native lane rows and the value accumulation
    contracts over the chunk at full MXU shape (the ops/attention.py
    layout, measured there).  Lane ``l`` holds q head ``l // s`` at
    chunk offset ``l % s``.  A narrow step's are ``(rep·s, C·bs)``,
    the same queries on sublanes and the keys on lanes
    (:func:`_key_axis`); the algebra is the same along the other axis.
    ``q_tile(head)`` returns the head's scaled ``(rep·s, d)`` queries
    (log2 domain).

    With ``qmax`` set the buffers hold int8/fp8 codes and
    ``page_scales(head, page)`` returns the page's fp32 K and V amax
    scales as (1, 1) tiles.  The per-page dequant multiplier
    ``scale/qmax`` is constant over its page's ``bs`` rows of the
    chunk tile, so it factors out of both contractions as a
    ``(C·bs, 1)`` column (a ``(1, C·bs)`` row of a narrow step's
    scores): codes are cast up (exact — |int8| ≤ 127 and
    e4m3 fit any float) for the MXU dot, and the product is rescaled
    in-register before the log2-domain softmax statistics (scores) /
    the output accumulation (values).

    ``on_last_chunk(slot)`` runs once, after the last chunk has landed
    in ``slot`` and before it is attended — the fused kernel's
    prologue hook.

    With ``window`` the sweep starts at the chunk that holds position
    ``max(0, length - window + 1)``, the window's start of the row's
    FIRST query lane (no lane's window starts earlier), and every lane
    masks the positions before its own start.  A later lane may find
    no live key in the first chunk or two: its statistics stay at the
    floor there and what it summed meanwhile is zeroed — explicitly,
    the floor minus the floor being 0 and not minus infinity.
    ``window=None`` is the sweep it always was, instruction for
    instruction.
    """
    kbuf, vbuf, sems, slot_ref, m_ref, l_ref, acc_ref = scratch
    _slots, hk, t, _d = kbuf.shape
    c_pages = t // bs
    lanes = rep * s
    row, rows = pl.program_id(0), pl.num_programs(0)
    length = lens_ref[row]
    # this row's sweep and the next two rows' (clamped to the grid: a
    # row past it is never started)
    (live, first, n_chunks), (live1, first1, n1), (live2, first2, _) = [
        _row_span(lens_ref[jnp.minimum(row + on, rows - 1)], s=s, bs=bs,
                  mb=mb, window=window) for on in range(3)]
    # the slot of the row's first chunk: the stream's ordinal mod 3
    base = _pick(row == 0, 0, slot_ref[0])

    def copies(r, r_live, c, slot):
        out = []
        for i in range(c_pages):
            phys = tables_ref[r, jnp.minimum(c * c_pages + i, r_live - 1)]
            span = pl.ds(i * bs, bs)
            out.append(pltpu.make_async_copy(
                k_hbm.at[:, phys], kbuf.at[slot, :, span],
                sems.at[0, slot]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[:, phys], vbuf.at[slot, :, span],
                sems.at[1, slot]))
        return out

    def fetch(k, c, slot):
        # start the stream's chunk k places after chunk c of this row
        on, chunk_no = _stream_ahead(k, c, n_chunks, first1, n1, first2)
        r_live = _pick(on == 0, live, _pick(on == 1, live1, live2))

        @pl.when(row + on < rows)
        def _start():
            for cp in copies(row + on, r_live, chunk_no, slot):
                cp.start()

    @pl.when(row == 0)
    def _open_stream():
        def one(k, carry):
            fetch(k, first, k)
            return carry
        jax.lax.fori_loop(0, 2, one, None)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    ax = _key_axis(lanes)
    tile = (t, lanes) if ax == 0 else (lanes, t)

    def chunk(c, carry):
        slot = jax.lax.rem(base + c - first, _SLOTS)
        fetch(2, c, jax.lax.rem(slot + 2, _SLOTS))
        for cp in copies(row, live, c, slot):
            cp.wait()
        if on_last_chunk is not None:
            pl.when(c == n_chunks - 1)(lambda: on_last_chunk(slot))

        k_pos = c * t + jax.lax.broadcasted_iota(jnp.int32, tile, ax)
        q_off = jax.lax.broadcasted_iota(jnp.int32, tile, 1 - ax) % s
        # visible up to the query's own position, and never past the
        # table (the last chunk's spare slots re-fetch the last live
        # page: a cursor at or past the table's end must not see them)
        dead = k_pos > jnp.minimum(length + q_off, mb * bs - 1)
        if window is not None:
            dead |= k_pos <= length + q_off - window

        def scores(head):
            qs = q_tiles[head] if q_tiles else q_tile(head)
            kt = kbuf[slot, head]
            kq = kt if qmax is None else kt.astype(qs.dtype)  # exact
            sc = jax.lax.dot_general(
                *((kq, qs) if ax == 0 else (qs, kq)),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # tile
            vcol = None
            if qmax is not None:
                per_page = [page_scales(head, c * c_pages + i)
                            for i in range(c_pages)]
                unit = jnp.float32(1.0 / qmax)
                # a page's scale over its bs keys: along the score
                # tile's key axis, down the value tile's rows
                kscale = jnp.concatenate(
                    [jnp.broadcast_to(ks, (bs, 1) if ax == 0 else (1, bs))
                     for ks, _ in per_page], axis=ax) * unit
                vcol = jnp.concatenate(
                    [jnp.broadcast_to(vs, (bs, 1)) for _, vs in per_page],
                    axis=0) * unit
                # in-register dequant: one f32 multiply per score tile
                sc = sc * kscale
            return jnp.where(dead, _NEG_INF, sc), vcol

        def stats(head, sc):
            m_prev = m_ref[head]                         # per query
            m_new = jnp.maximum(m_prev,
                                jnp.max(sc, axis=ax, keepdims=True))
            # every query sees >= 1 live key in chunk 0 (position 0 is
            # always visible), so m is finite from the first chunk on
            # and exp2(-1e30 - m) underflows to exactly 0 at dead
            # positions — no explicit dead-row zeroing needed (see
            # ops/attention.py)
            p = jnp.exp2(sc - m_new)
            if window is not None:
                # a query whose window starts in a later chunk has seen
                # no live key yet: m is still the floor and exp2(0) = 1
                p = jnp.where(dead, 0.0, p)
            alpha = jnp.exp2(m_prev - m_new)
            l_ref[head] = l_ref[head] * alpha + jnp.sum(
                p, axis=ax, keepdims=True)
            m_ref[head] = m_new
            return p, alpha

        def accumulate(head, p, alpha, vcol):
            vt = vbuf[slot, head]
            if qmax is None:
                vq, pv = vt, p.astype(vt.dtype)
            else:
                vq, pv = vt.astype(jnp.float32) * vcol, p
            acc_ref[head] = acc_ref[head] * alpha + (
                jax.lax.dot_general(vq, pv, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
                if ax == 0 else          # (d, lanes), else (lanes, d)
                jax.lax.dot_general(pv, vq, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32))

        # narrow tiles are a register each: every head's scores are
        # issued, then every head's statistics, then every head's value
        # products, so the heads' matrix-unit round trips overlap; a
        # wide tile is attended head by head
        wave = hk if ax == 1 else 1
        for h0 in range(0, hk, wave):
            heads = range(h0, h0 + wave)
            tiles = [scores(head) for head in heads]
            probs = [stats(head, sc) for head, (sc, _) in zip(heads, tiles)]
            for head, (_, vcol), (p, alpha) in zip(heads, tiles, probs):
                accumulate(head, p, alpha, vcol)
        return carry

    # a narrow step's queries are rotated and scaled once a row, not
    # once a chunk (the same values: bitwise the same sweep)
    q_tiles = [q_tile(head) for head in range(hk)] if ax == 1 else None
    jax.lax.fori_loop(first, n_chunks, chunk, None)
    slot_ref[0] = jax.lax.rem(base + n_chunks - first, _SLOTS)

    for head in range(hk):
        l = l_ref[head]
        out = acc_ref[head] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, head] = (out.T if ax == 0 else out).astype(o_ref.dtype)


def _paged_kernel(tables_ref, lens_ref, q_ref, k_hbm, v_hbm, *refs,
                  bs, s, rep, scale, mb, qmax=None, window=None):
    """One row of the chunk / verify / plain-decode attend: the shared
    sweep (:func:`_sweep_row`) over the row's live pages, all kv heads
    in one grid step.  With ``qmax`` set two extra refs —
    ``ks_ref``/``vs_ref``, the row's pages' fp32 amax scales in
    LOGICAL page order (:func:`_row_page_scales`; lane ``j`` is page
    ``j``'s) — precede the output."""
    if qmax is None:
        ks_ref = vs_ref = None
        o_ref, *scratch = refs
    else:
        ks_ref, vs_ref, o_ref, *scratch = refs

    def q_tile(head):
        return q_ref[0, head] * jnp.asarray(scale * _LOG2E, q_ref.dtype)

    def page_scales(head, j):
        return (_page_scale(ks_ref[0, head], j),
                _page_scale(vs_ref[0, head], j))

    _sweep_row(tables_ref, lens_ref, k_hbm, v_hbm, scratch, o_ref, s=s,
               bs=bs, rep=rep, mb=mb, q_tile=q_tile, qmax=qmax,
               page_scales=page_scales, window=window)


def _run_paged(q4, k_pages, v_pages, tables, lengths, scale, interpret,
               k_scales=None, v_scales=None, window=None):
    b, s, h, d = q4.shape
    hk, _nb_pool, bs, _ = k_pages.shape
    rep = h // hk
    mb = tables.shape[1]
    # (b, s, h, d) -> (b, hk, rep*s, d): lane l = (head r)*s + offset i
    q3 = (q4.reshape(b, s, hk, rep, d)
          .transpose(0, 2, 3, 1, 4).reshape(b, hk, rep * s, d))
    quantized = k_scales is not None
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [_row_spec(hk, rep * s, d), pool_spec, pool_spec]
    args = [tables, lengths, q3, k_pages, v_pages]
    if quantized:
        in_specs += [_row_spec(hk, 1, mb), _row_spec(hk, 1, mb)]
        args += [_row_page_scales(k_scales, tables),
                 _row_page_scales(v_scales, tables)]
    kernel = functools.partial(
        _paged_kernel, bs=bs, s=s, rep=rep, scale=scale, mb=mb,
        qmax=_qmax_for_pool(k_pages.dtype) if quantized else None,
        window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs,
        out_specs=_row_spec(hk, rep * s, d),
        scratch_shapes=_sweep_scratch(hk, bs, d, rep * s,
                                      k_pages.dtype),
    )
    # the scope names the kernel in HLO metadata and profiler traces
    with jax.named_scope("paged_attention"):
        o3 = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, hk, rep * s, d),
                                           q4.dtype),
            compiler_params=_ROWS_IN_ORDER,
            interpret=interpret,
        )(*args)
    return (o3.reshape(b, hk, rep, s, d)
            .transpose(0, 3, 1, 2, 4).reshape(b, s, h, d))


def rope_rows(x, cos_b, sin_b):
    """Half-rotation RoPE with PER-ROW position tables.

    ``x`` (b, s, heads, d); ``cos_b``/``sin_b`` (b, s, 1, rot/2) —
    gathered at each row's absolute positions.  The shared-table
    :func:`~apex_tpu.ops.rope.fused_rope` broadcasts one (s, rot/2)
    table over the batch, which cannot express a ragged batch of
    tenants each at its own decode position (the paged serving path;
    ``models/transformer.py`` routes both its chunk path and — through
    :func:`paged_decode_fused` — its decode prologue here).
    """
    half = cos_b.shape[-1]
    rot = 2 * half
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rot].astype(jnp.float32)
    o1 = (x1 * cos_b - x2 * sin_b).astype(x.dtype)
    o2 = (x2 * cos_b + x1 * sin_b).astype(x.dtype)
    return jnp.concatenate([o1, o2, x[..., rot:]], axis=-1)


# --------------------------------------------------------------------- #
# fused decode prologue — RoPE + (quantize +) page write + attend
# --------------------------------------------------------------------- #
def paged_decode_fused_reference(q, k_new, v_new, k_pages, v_pages,
                                 block_tables, lengths, *,
                                 max_seq_len: int,
                                 cos_b=None, sin_b=None,
                                 scale: Optional[float] = None,
                                 k_scales=None, v_scales=None,
                                 chunk_lens=None,
                                 window: Optional[int] = None):
    """The unfused decode-step prologue + attend, verbatim — golden
    semantics of :func:`paged_decode_fused` and its CPU/GPU dispatch
    target.

    This is exactly the XLA op sequence ``models/transformer.py``'s
    ``_paged_decode`` historically ran per step at chunk width 1:
    per-row RoPE of ``q``/``k_new`` at each row's absolute position
    (``cos_b``/``sin_b`` are the gathered per-row tables; ``None``
    for non-rotary models), the new row's pool scatter at
    ``lengths[b]`` (positions past ``max_seq_len`` route to the null
    page), and the block-table-gathered attend.  With
    ``k_scales``/``v_scales`` the write quantizes under the PR-8
    monotone per-page running-amax discipline — reset at offset 0,
    each row's amax chained through its previous page's scale, pad
    lanes (``chunk_lens <= 0``) routed to the null page — specialized
    to width 1 (the chunk ``cummax`` degenerates to the row amax).
    Returns ``(o, k_pages, v_pages)`` plus ``(k_scales, v_scales)``
    when quantized.
    """
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(
            f"paged_decode_fused is the WIDTH-1 decode fusion (chunk "
            f"and verify steps write through paged_write), got "
            f"s={s}")
    hk, NB, BS, _ = k_pages.shape
    MB = block_tables.shape[1]
    S = int(max_seq_len)
    scale = (d ** -0.5) if scale is None else scale
    if cos_b is not None:
        q = rope_rows(q, cos_b, sin_b)
        k_new = rope_rows(k_new, cos_b, sin_b)
    positions = lengths[:, None]                        # (b, 1)
    logical = jnp.minimum(positions // BS, MB - 1)
    phys = jnp.take_along_axis(block_tables, logical, axis=1)
    phys = jnp.where(positions < S, phys, 0)
    off = positions % BS
    kT = k_new.transpose(2, 0, 1, 3)                    # (hk, b, 1, d)
    vT = v_new.transpose(2, 0, 1, 3)
    if k_scales is None:
        kp = k_pages.at[:, phys, off].set(kT)
        vp = v_pages.at[:, phys, off].set(vT)
        o = paged_attention_reference(q, kp, vp, block_tables,
                                      lengths, scale=scale, window=window)
        return o, kp, vp
    qmax = _qmax_for_pool(k_pages.dtype)
    store_dt = k_pages.dtype
    cl = (jnp.full((b,), S, jnp.int32) if chunk_lens is None
          else chunk_lens)
    real = (jnp.zeros((b, 1), jnp.int32)
            < cl[:, None])                              # (b, 1)
    phys = jnp.where(real, phys, 0)
    ka = jnp.max(jnp.abs(kT.astype(jnp.float32)), axis=-1)
    va = jnp.max(jnp.abs(vT.astype(jnp.float32)), axis=-1)
    ka = jnp.where(real[None], ka, 0.0)                 # (hk, b, 1)
    va = jnp.where(real[None], va, 0.0)
    base_logical = jnp.clip((lengths - 1) // BS, 0, MB - 1)
    base_phys = jnp.take_along_axis(
        block_tables, base_logical[:, None], axis=1)[:, 0]
    has_prefix = lengths > 0
    k_base = jnp.where(has_prefix[None, :],
                       k_scales[:, base_phys], 0.0)     # (hk, b)
    v_base = jnp.where(has_prefix[None, :],
                       v_scales[:, base_phys], 0.0)
    k_run = jnp.maximum(jax.lax.cummax(ka, axis=2),
                        k_base[:, :, None])
    v_run = jnp.maximum(jax.lax.cummax(va, axis=2),
                        v_base[:, :, None])
    fresh = jnp.where(off == 0, phys, 0)
    ks_new = k_scales.at[:, fresh].set(0.0).at[:, phys].max(k_run)
    vs_new = v_scales.at[:, fresh].set(0.0).at[:, phys].max(v_run)
    kp = k_pages.at[:, phys, off].set(
        quantize_kv(kT, ks_new[:, phys], qmax, store_dt))
    vp = v_pages.at[:, phys, off].set(
        quantize_kv(vT, vs_new[:, phys], qmax, store_dt))
    o = paged_attention_reference(q, kp, vp, block_tables, lengths,
                                  scale=scale, k_scales=ks_new,
                                  v_scales=vs_new, window=window)
    return o, kp, vp, ks_new, vs_new


def _paged_fused_kernel(tables_ref, lens_ref, wphys_ref, woff_ref,
                        real_ref, q_ref, k_hbm, v_hbm, nk_ref, nv_ref,
                        *refs, bs, rep, scale, mb, S, half, qmax=None,
                        window=None):
    """The decode sweep of :func:`_paged_kernel` (s = 1) with the
    step's PROLOGUE folded in.  The row's write page IS the last live
    page of its sweep, so once the last chunk has landed each head
    rotates the row's new K (RoPE at the row's absolute position),
    quantizes it under the monotone running-amax discipline when the
    pool is coded, and patches it — with its V — into the chunk
    buffer at the write position.  The attend reads the patched
    buffer, so the new token is visible to its own query
    (write-then-attend) without a pool round-trip, and one DMA per
    side copies the patched page for all heads back into the pool
    through the aliased output instead of a separate XLA scatter pass;
    the next row waits for it as it begins (the copy's slot is not
    refilled before), the last row before it ends.

    Extra scalar prefetch vs the plain kernel: ``wphys``/``woff`` (the
    write page and offset, null-routed on the host side of the trace)
    and ``real`` (the pad-lane routing bit).  ``half`` is the RoPE
    half-rotation width (0 = non-rotary model).  Outputs gain the
    pool, aliased to its input and as unblocked as it, so untouched
    pages persist; a row that may not write (a pad lane, a cursor at
    or past ``S``) starts no copy at all.  A coded pool adds
    ``ks_ref``/``vs_ref`` (the row's page scales,
    :func:`_row_page_scales`), ``side_ref`` (the write page's current
    and the previous page's K/V scales — the running-amax chain's
    inputs, lanes ``wk, wv, bk, bv``) and ``ns_out`` (the write
    page's new K/V scales, lanes ``k, v``; the caller scatters them
    into the pool-wide arrays — ``kv_heads`` floats per row).
    """
    rest = list(refs)
    cos_ref = sin_ref = ks_ref = vs_ref = side_ref = ns_out = None
    if half:
        cos_ref, sin_ref = rest[:2]
        rest = rest[2:]
    if qmax is None:
        o_ref, kp_out, vp_out, wsem, *scratch = rest
    else:
        (ks_ref, vs_ref, side_ref, o_ref, kp_out, vp_out, ns_out,
         wsem, *scratch) = rest
    kbuf, vbuf = scratch[:2]
    _slots, hk, t, d = kbuf.shape
    row, rows = pl.program_id(0), pl.num_programs(0)

    def may_write(r):
        return (lens_ref[r] < S) & (real_ref[r] != 0)

    length = lens_ref[row]
    wphys = wphys_ref[row]
    woff = woff_ref[row]
    real = real_ref[row] != 0
    write_ok = may_write(row)
    wpage = _live_pages(length, 1, bs, mb) - 1   # the sweep's last page
    wslot = wpage % (t // bs)                    # its place in the chunk

    def _rot_row(x_row):
        # half-rotation RoPE of (rows, d) at this row's position —
        # bitwise rope_rows (f32 math in the reference's own
        # expression forms, cast back).  Lane-aligned: each lane's
        # rotation partner arrives by an XLU roll against full-width
        # tables ``[cos, cos]`` / ``[sin, sin]`` — a slice at lane
        # ``half`` (64 at d=128) is not a shape the chip's compiler
        # takes.
        if not half:
            return x_row
        xf = x_row.astype(jnp.float32)
        lo = pltpu.roll(xf, half, 1)                 # x[lane - half]
        hi = lo if 2 * half == d else pltpu.roll(xf, d - half, 1)
        c, sn = cos_ref[0], sin_ref[0]
        lane = jax.lax.broadcasted_iota(jnp.int32, xf.shape, 1)
        out = jnp.where(lane < half, xf * c - hi * sn,
                        xf * c + lo * sn).astype(x_row.dtype)
        return out if 2 * half == d else jnp.where(
            lane < 2 * half, out, x_row)

    def _code(x_row, sc):
        ok = sc > _TINY_SCALE
        inv = jnp.where(ok, qmax / jnp.maximum(sc, _TINY_SCALE), 0.0)
        y = jnp.clip(x_row.astype(jnp.float32) * inv, -qmax, qmax)
        if jnp.issubdtype(jnp.dtype(kbuf.dtype), jnp.integer):
            y = jnp.round(y)
        return y.astype(kbuf.dtype)

    def _writeback(slot, i):
        span = pl.ds(i * bs, bs)
        return (pltpu.make_async_copy(kbuf.at[slot, :, span],
                                      kp_out.at[:, wphys], wsem.at[0]),
                pltpu.make_async_copy(vbuf.at[slot, :, span],
                                      vp_out.at[:, wphys], wsem.at[1]))

    def _landed():
        for cp in _writeback(0, 0):      # any same-sized source waits
            cp.wait()

    def _prologue(slot):
        # the row lands at its sublane of the chunk tile by a masked
        # select (the compiler has no dynamic in-register slice)
        here = write_ok & (jax.lax.broadcasted_iota(
            jnp.int32, (t, d), 0) == wslot * bs + woff)
        for head in range(hk):
            k_row = _rot_row(nk_ref[0, head])            # (1, d)
            v_row = nv_ref[0, head]
            if qmax is not None:
                # monotone running-amax scale chain, width-1 form: the
                # write page's new scale = max(row amax, previous
                # scale) where "previous" is the prior page's scale at
                # a fresh page (offset 0) and the page's own at an
                # append — bitwise the reference's reset + scatter-max
                ka = jnp.max(jnp.abs(k_row.astype(jnp.float32)))
                va = jnp.max(jnp.abs(v_row.astype(jnp.float32)))
                ka = jnp.where(real, ka, 0.0)
                va = jnp.where(real, va, 0.0)
                side = side_ref[0, head]                 # (1, 4)
                wks, wvs = side[:, 0:1], side[:, 1:2]
                bk = jnp.where(length > 0, side[:, 2:3], 0.0)
                bv = jnp.where(length > 0, side[:, 3:4], 0.0)
                cur_k = jnp.where(woff == 0, 0.0, wks)
                cur_v = jnp.where(woff == 0, 0.0, wvs)
                nks = jnp.maximum(cur_k, jnp.maximum(ka, bk))
                nvs = jnp.maximum(cur_v, jnp.maximum(va, bv))
                k_row, v_row = _code(k_row, nks), _code(v_row, nvs)
                ns_out[0, head] = jnp.concatenate(
                    [jnp.where(write_ok, nks, wks),
                     jnp.where(write_ok, nvs, wvs)], axis=1)
            kbuf[slot, head] = jnp.where(here, k_row, kbuf[slot, head])
            vbuf[slot, head] = jnp.where(here, v_row, vbuf[slot, head])
        # the patched page goes home: one copy a side, all heads (the
        # source slice is static per branch — a page's place in the
        # chunk is not a tile boundary for every pool dtype)
        for i in range(t // bs):
            @pl.when(write_ok & (wslot == i))
            def _start():
                for cp in _writeback(slot, i):
                    cp.start()

    def q_tile(head):
        qt = _rot_row(q_ref[0, head])
        return qt * jnp.asarray(scale * _LOG2E, qt.dtype)

    def page_scales(head, j):
        # the write page's scale moved with the write (ns_out holds it
        # from the prologue on; no earlier chunk holds the write page)
        use_new = write_ok & (j == wpage)
        new = ns_out[0, head]
        return (jnp.where(use_new, new[:, 0:1],
                          _page_scale(ks_ref[0, head], j)),
                jnp.where(use_new, new[:, 1:2],
                          _page_scale(vs_ref[0, head], j)))

    # the previous row's page went home from the slot of its last chunk
    # while that chunk was attended; the stream refills that slot no
    # sooner than this row's first chunk's fetch two ahead, so its
    # wait comes here, before the sweep — and the last row drains its
    # own
    pl.when((row > 0) & may_write(jnp.maximum(row - 1, 0)))(_landed)
    _sweep_row(tables_ref, lens_ref, k_hbm, v_hbm, scratch, o_ref, s=1,
               bs=bs, rep=rep, mb=mb, q_tile=q_tile, qmax=qmax,
               page_scales=page_scales, on_last_chunk=_prologue,
               window=window)
    pl.when(write_ok & (row == rows - 1))(_landed)


def _run_decode_fused(q4, k_new, v_new, k_pages, v_pages, tables,
                      lengths, S, cos_b, sin_b, scale, interpret,
                      k_scales=None, v_scales=None, chunk_lens=None,
                      window=None):
    b, s, h, d = q4.shape
    hk, _nb_pool, bs, _ = k_pages.shape
    rep = h // hk
    mb = tables.shape[1]
    quantized = k_scales is not None
    half = 0 if cos_b is None else int(cos_b.shape[-1])
    q3 = (q4.reshape(b, 1, hk, rep, d)
          .transpose(0, 2, 3, 1, 4).reshape(b, hk, rep, d))
    # the new row rides (b, hk, 1, d) and the RoPE tables (b, 1, half):
    # a block's last two dims must equal the array's (or tile by
    # (8, 128)), so the per-head row keeps a unit axis before d
    nk = k_new.reshape(b, hk, 1, d)
    nv = v_new.reshape(b, hk, 1, d)
    # the write target, resolved once in-trace (the kernel's scalar
    # prefetch): position -> clamped logical page -> physical, with
    # past-the-cache and pad-lane writes routed to the null page
    # exactly as the reference
    positions = lengths
    logical = jnp.minimum(positions // bs, mb - 1)
    wphys = jnp.take_along_axis(tables, logical[:, None],
                                axis=1)[:, 0]
    wphys = jnp.where(positions < S, wphys, 0)
    woff = positions % bs
    real = (jnp.ones((b,), jnp.int32)
            if chunk_lens is None
            else (chunk_lens > 0).astype(jnp.int32))
    wphys = jnp.where(real != 0, wphys, 0)

    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [_row_spec(hk, rep, d), pool_spec, pool_spec,
                _row_spec(hk, 1, d), _row_spec(hk, 1, d)]
    args = [tables, lengths, wphys, woff, real,
            q3, k_pages, v_pages, nk, nv]
    if half:
        # full-width tables for the kernel's lane-aligned rotation:
        # [cos, cos, 0] and [sin, sin, 0] over head_dim
        def _full(t):
            t = t.reshape(b, 1, half).astype(jnp.float32)
            return jnp.concatenate(
                [t, t, jnp.zeros((b, 1, d - 2 * half))], axis=-1)

        in_specs += [_row_spec(1, d), _row_spec(1, d)]
        args += [_full(cos_b), _full(sin_b)]
    out_specs = [_row_spec(hk, rep, d), pool_spec, pool_spec]
    out_shapes = [
        jax.ShapeDtypeStruct((b, hk, rep, d), q4.dtype),
        jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
        jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
    ]
    if quantized:
        ksf = k_scales.astype(jnp.float32)
        vsf = v_scales.astype(jnp.float32)
        base_logical = jnp.clip((lengths - 1) // bs, 0, mb - 1)
        base_phys = jnp.take_along_axis(tables, base_logical[:, None],
                                        axis=1)[:, 0]
        # the scale chain's inputs per (row, head): the write page's
        # current and the previous page's scales, (b, hk, 1, 4)
        side = jnp.stack([ksf[:, wphys], vsf[:, wphys],
                          ksf[:, base_phys], vsf[:, base_phys]],
                         axis=-1).transpose(1, 0, 2)[:, :, None, :]
        in_specs += [_row_spec(hk, 1, mb), _row_spec(hk, 1, mb),
                     _row_spec(hk, 1, 4)]
        args += [_row_page_scales(ksf, tables),
                 _row_page_scales(vsf, tables), side]
        out_specs.append(_row_spec(hk, 1, 2))
        out_shapes.append(
            jax.ShapeDtypeStruct((b, hk, 1, 2), jnp.float32))
    kernel = functools.partial(
        _paged_fused_kernel, bs=bs, rep=rep, scale=scale, mb=mb,
        S=S, half=half,
        qmax=_qmax_for_pool(k_pages.dtype) if quantized else None,
        window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.SemaphoreType.DMA((2,)),   # write-back
                        *_sweep_scratch(hk, bs, d, rep, k_pages.dtype)],
    )
    with jax.named_scope("paged_decode_fused"):
        outs = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shapes,
            # inputs count scalar prefetch first: 5 scalars, then q3
            # (5), k_pages (6), v_pages (7) — aliased to the pool
            # outputs, so the one page a row writes moves in place
            input_output_aliases={6: 1, 7: 2},
            compiler_params=_ROWS_IN_ORDER,
            interpret=interpret,
        )(*args)
    o3 = outs[0].reshape(b, hk, rep, 1, d) \
        .transpose(0, 3, 1, 2, 4).reshape(b, 1, h, d)
    if not quantized:
        return o3, outs[1], outs[2]
    # the write pages' new scales land in the pool-wide arrays here
    # (kv_heads floats per row; rows that did not write carry their
    # page's old scale back, so the scatter is a no-op for them)
    ns = outs[3][:, :, 0, :].transpose(1, 0, 2)          # (hk, b, 2)
    return (o3, outs[1], outs[2],
            ksf.at[:, wphys].set(ns[..., 0]),
            vsf.at[:, wphys].set(ns[..., 1]))


def _run_decode_fused_sharded(q, k_new, v_new, k_pages, v_pages,
                              tables, lengths, S, cos_b, sin_b, scale,
                              implementation, k_scales, v_scales,
                              chunk_lens, mesh, axis, window=None):
    """shard_map wrapper for the fused decode step: pool, scales and
    the new K/V rows shard on their kv_heads axes, q on its head axis,
    everything host-authoritative replicated — the write is
    shard-local (every chip scatters its own heads' row), so the TP
    layout of PR 12 is preserved bitwise with no collective here."""
    _b, _s, h, _d = q.shape
    hk = k_pages.shape[0]
    tp_head_shards(h, hk, mesh.shape[axis])
    P = jax.sharding.PartitionSpec
    q_spec = P(None, None, axis, None)
    pool_spec = P(axis, None, None, None)
    rep_spec = P()
    # optional operands ride one dict pytree whose keys ARE the local
    # call's kwargs — shard_map specs mirror the structure, and the
    # body needs no per-case unpacking
    opt, opt_specs = {}, {}
    if cos_b is not None:
        opt.update(cos_b=cos_b, sin_b=sin_b)
        opt_specs.update(cos_b=rep_spec, sin_b=rep_spec)
    quantized = k_scales is not None
    if quantized:
        opt.update(k_scales=k_scales, v_scales=v_scales)
        opt_specs.update(k_scales=P(axis, None),
                         v_scales=P(axis, None))
    if chunk_lens is not None:
        opt["chunk_lens"] = chunk_lens
        opt_specs["chunk_lens"] = rep_spec
    in_specs = (q_spec, q_spec, q_spec, pool_spec, pool_spec,
                rep_spec, rep_spec, opt_specs)
    out_specs = (q_spec, pool_spec, pool_spec)
    if quantized:
        out_specs += (P(axis, None), P(axis, None))

    def local(q, nk, nv, kp, vp, bt, ln, opt):
        return paged_decode_fused(
            q, nk, nv, kp, vp, bt, ln, max_seq_len=S, scale=scale,
            implementation=implementation, window=window, **opt)

    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(
        q, k_new, v_new, k_pages, v_pages, tables, lengths, opt)


def paged_decode_fused(q, k_new, v_new, k_pages, v_pages, block_tables,
                       lengths, *, max_seq_len: int, cos_b=None,
                       sin_b=None, scale: Optional[float] = None,
                       implementation: Optional[str] = None,
                       k_scales=None, v_scales=None, chunk_lens=None,
                       mesh=None, shard_axis: Optional[str] = None,
                       window: Optional[int] = None):
    """One fused decode step over the paged pool: per-row RoPE of
    ``q``/``k_new``, (quantized) write of the new K/V row into its
    page, and the block-table-gathered attend — the attention
    PROLOGUE that used to run as detached XLA passes
    (``rope_rows → quantize_kv → pool scatter``) folded into the
    Pallas kernel, so the row is rotated, coded and written
    in-register on its way into the attend (ISSUE 14's second fusion
    front).  Strictly the WIDTH-1 step: chunked prefill and the
    speculative verify write through :func:`paged_write` before their
    attend (a chunk's rows straddle pages, and only the decode row's
    write page is always the sweep's last).

    ``q`` (b, 1, h, d) and ``k_new``/``v_new`` (b, 1, hk, d) arrive
    UNROTATED; ``cos_b``/``sin_b`` (b, 1, 1, rot/2) are the per-row
    RoPE tables gathered at ``lengths`` (``None`` for non-rotary
    models).  Pool/table/length shapes as in the module docstring;
    ``lengths[b]`` is both the mask horizon and the write position.
    Quantized pools add ``k_scales``/``v_scales`` (updated copies are
    returned) and ``chunk_lens`` (the engine's pad-lane routing leaf).
    ``window`` is a windowed layer's width (module docstring): the row
    is written as ever and the attend starts at the window's start.
    Returns ``(o, k_pages, v_pages[, k_scales, v_scales])`` — the
    pool leaves updated with the written row, everything else
    byte-preserved (the kernel aliases the pool, so only the write
    page moves; the null page's contents stay garbage-by-contract on
    every path).

    With ``mesh``/``shard_axis`` the whole fused step runs
    tensor-parallel exactly like :func:`paged_attention` — pool,
    scales and the new rows shard on kv_heads, the write staying
    shard-local, block tables replicated (bitwise the PR-12 layout).

    Dispatch per :mod:`apex_tpu.ops._dispatch`;
    :func:`paged_decode_fused_reference` is the golden anchor — the
    historical unfused sequence verbatim — and the kernel is
    bit-compatible with it up to the blocked-vs-einsum accumulation
    order of the attend (the ``paged_attention`` contract), with
    codes, scales and written pages bitwise identical on live pages.
    """
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(
            f"paged_decode_fused handles the width-1 decode step "
            f"only, got s={s}")
    if k_new.shape != v_new.shape:
        raise ValueError(
            f"k_new/v_new shapes differ: {k_new.shape} vs "
            f"{v_new.shape}")
    hk, nb, bs, dk = k_pages.shape
    if k_new.shape != (b, 1, hk, d):
        raise ValueError(
            f"k_new shape {k_new.shape} != (b, 1, kv_heads, d) = "
            f"{(b, 1, hk, d)}")
    if (cos_b is None) != (sin_b is None):
        raise ValueError("cos_b and sin_b come together")
    quantized = _is_quantized_pool(k_pages.dtype)
    if quantized and (k_scales is None or v_scales is None):
        raise ValueError(
            f"quantized pages ({k_pages.dtype}) need k_scales AND "
            "v_scales")
    if not quantized and (k_scales is not None or chunk_lens is not None):
        raise ValueError(
            "k_scales/v_scales/chunk_lens only apply to quantized "
            f"pools; pages are {k_pages.dtype}")
    scale = (d ** -0.5) if scale is None else float(scale)
    _check_window(window)
    if shard_axis is not None and mesh is not None \
            and mesh.shape.get(shard_axis, 1) > 1:
        return _run_decode_fused_sharded(
            q, k_new, v_new, k_pages, v_pages, block_tables, lengths,
            int(max_seq_len), cos_b, sin_b, scale, implementation,
            k_scales, v_scales, chunk_lens, mesh, shard_axis,
            window=window)
    half = 0 if cos_b is None else int(cos_b.shape[-1])
    pallas_ok = (bs % 8 == 0 and d % 8 == 0
                 and (half == 0 or half % 8 == 0)
                 and (quantized
                      or q.dtype == k_pages.dtype == v_pages.dtype))
    impl = resolve_impl(implementation, pallas_ok=pallas_ok,
                        op="paged_decode_fused")
    if impl == "xla":
        return paged_decode_fused_reference(
            q, k_new, v_new, k_pages, v_pages, block_tables, lengths,
            max_seq_len=int(max_seq_len), cos_b=cos_b, sin_b=sin_b,
            scale=scale, k_scales=k_scales, v_scales=v_scales,
            chunk_lens=chunk_lens, window=window)
    return _run_decode_fused(
        q, k_new, v_new, k_pages, v_pages,
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(lengths, jnp.int32), int(max_seq_len), cos_b,
        sin_b, scale, impl == "pallas_interpret",
        k_scales=k_scales, v_scales=v_scales, chunk_lens=chunk_lens,
        window=window)


# --------------------------------------------------------------------- #
# chunk write — a width > 1 step's K/V rows into their pages, in place
# --------------------------------------------------------------------- #
def paged_write_reference(k, v, k_pages, v_pages, phys, off):
    """The one-pass XLA scatter of a chunk's rows into the pool —
    golden semantics of :func:`paged_write` and its CPU/GPU dispatch
    target (``models/transformer.py``'s historical width > 1 write,
    verbatim).  On a TPU XLA transposes each pool into the scatter's
    layout and back around it, four pool-sized copies a layer
    whatever the rows touch (PERF.md §6, PR 36): the reason the kernel
    exists."""
    kT = k.transpose(2, 0, 1, 3)                         # (hk, b, s, d)
    vT = v.transpose(2, 0, 1, 3)
    return (k_pages.at[:, phys, off].set(kT),
            v_pages.at[:, phys, off].set(vT))


#: fast memory the write kernel may hold (page buffers, the rotated
#: rows, the double-buffered blocks of new rows); a wider chunk takes
#: the scatter
_WRITE_VMEM_BYTES = 8 * 1024 * 1024


def _write_pages(s, bs):
    """Pages a row's ``s`` consecutive positions can touch, the first
    one anywhere in its page."""
    return (s - 1) // bs + 2


def _write_vmem_bytes(s, hk, bs, d, dtype):
    rows = _write_pages(s, bs) * bs
    return 2 * hk * d * (rows * (jnp.dtype(dtype).itemsize + 4)
                         + 2 * s * jnp.dtype(dtype).itemsize)


def _paged_write_kernel(pid_ref, shift_ref, put_ref, nk_ref, nv_ref,
                        k_hbm, v_hbm, kp_out, vp_out, kbuf, vbuf, kmov,
                        vmov, sems, *, bs, s):
    """One row of the chunk write: each page its lanes touch — at most
    :func:`_write_pages`, ``pid_ref[row, p]``, 0 where slot ``p`` has
    nothing to write — comes in by one DMA a pool (all kv heads),
    takes the new lanes by a masked select and goes home by one DMA a
    pool through the aliased output.  Nothing else of the pool moves.

    The new rows arrive ``(hk, s, d)``; rotated down by the first
    lane's offset in its page (``shift_ref[row]``) inside a buffer of
    all the slots' rows, lane ``i`` sits at buffer row ``shift + i``,
    its place in the row's consecutive pages (a sublane rotate in 32
    bits — exact for every pool dtype; the compiler has no dynamic
    in-register slice).  ``put_ref`` marks the buffer rows that take a
    lane.

    The body is three short loops over the page slots (start the
    fetches; wait, patch and send home; wait) and one rotate a pool:
    what a server's start pays to lower it is a few tens of
    milliseconds (PERF.md §6, PR 36), whatever ``s`` and the head
    count."""
    pages = kbuf.shape[0]
    row = pl.program_id(0)

    def each_page(fn):
        def body(p, carry):
            pid = pid_ref[row, p]
            pl.when(pid != 0)(lambda: fn(p, pid))
            return carry

        jax.lax.fori_loop(0, pages, body, None)

    def fetches(p, pid):
        return (pltpu.make_async_copy(k_hbm.at[:, pid], kbuf.at[p],
                                      sems.at[0, p]),
                pltpu.make_async_copy(v_hbm.at[:, pid], vbuf.at[p],
                                      sems.at[1, p]))

    def homes(p, pid):
        return (pltpu.make_async_copy(kbuf.at[p], kp_out.at[:, pid],
                                      sems.at[0, p]),
                pltpu.make_async_copy(vbuf.at[p], vp_out.at[:, pid],
                                      sems.at[1, p]))

    def start_fetch(p, pid):
        for cp in fetches(p, pid):
            cp.start()

    def patch(p, pid):
        for cp in fetches(p, pid):
            cp.wait()
        rows = pl.ds(pl.multiple_of(p * bs, bs), bs)
        put = put_ref[0, rows] != 0                      # (bs, 1)
        for buf, mov in ((kbuf, kmov), (vbuf, vmov)):
            buf[p] = jnp.where(put, mov[:, rows],
                               buf[p].astype(jnp.float32)
                               ).astype(buf.dtype)
        for cp in homes(p, pid):
            cp.start()

    def landed(p, pid):
        for cp in homes(p, pid):
            cp.wait()

    each_page(start_fetch)
    shift = shift_ref[row]
    for new_ref, mov in ((nk_ref, kmov), (nv_ref, vmov)):
        # rows past the chunk's width hold whatever the buffer held:
        # put_ref never selects them
        mov[:, :s] = new_ref[0].astype(jnp.float32)
        mov[...] = pltpu.roll(mov[...], shift, 1)
    each_page(patch)
    each_page(landed)


def _run_paged_write(k, v, k_pages, v_pages, phys, off, interpret):
    b, s, hk, d = k.shape
    bs = k_pages.shape[2]
    pages = _write_pages(s, bs)
    rows = pages * bs
    # buffer row r of a row's consecutive pages takes lane r - shift;
    # the page id it carries there, 0 where no lane lands or the lane
    # is routed to the null page
    shift = off[:, 0]
    lane = jnp.arange(rows, dtype=jnp.int32)[None, :] - shift[:, None]
    at = jnp.where(
        (lane >= 0) & (lane < s),
        jnp.take_along_axis(phys, jnp.clip(lane, 0, s - 1), axis=1), 0)
    pid = at.reshape(b, pages, bs).max(axis=2)           # (b, pages)
    put = (at != 0).astype(jnp.int32)[:, :, None]        # (b, rows, 1)
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[_row_spec(rows, 1), _row_spec(hk, s, d),
                  _row_spec(hk, s, d), pool_spec, pool_spec],
        out_specs=[pool_spec, pool_spec],
        scratch_shapes=[
            pltpu.VMEM((pages, hk, bs, d), k_pages.dtype),
            pltpu.VMEM((pages, hk, bs, d), v_pages.dtype),
            pltpu.VMEM((hk, rows, d), jnp.float32),      # rotated K rows
            pltpu.VMEM((hk, rows, d), jnp.float32),      # rotated V rows
            pltpu.SemaphoreType.DMA((2, pages)),
        ],
    )
    with jax.named_scope("paged_write"):
        return pl.pallas_call(
            functools.partial(_paged_write_kernel, bs=bs, s=s),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
            # inputs count scalar prefetch first: 2 scalars, the mask
            # (2), the new rows (3, 4), then the pools (5, 6), aliased
            # to the outputs so that only the touched pages move
            input_output_aliases={5: 0, 6: 1},
            interpret=interpret,
        )(pid, shift, put, k.transpose(0, 2, 1, 3),
          v.transpose(0, 2, 1, 3), k_pages, v_pages)


def paged_write(k, v, k_pages, v_pages, phys, off, *,
                implementation: Optional[str] = None,
                mesh=None, shard_axis: Optional[str] = None):
    """Write a chunk's K/V rows into the paged pool, in place: lane
    ``i`` of row ``r`` goes to page ``phys[r, i]``, offset ``off[r,
    i]``.  The write of every step wider than one token — the mixed
    step's prefill chunks and the speculative verify's draft runs (the
    width-1 step writes inside :func:`paged_decode_fused`).

    ``k``/``v`` are ``(b, s, kv_heads, d)``, already rotated and in
    the pool's dtype (a coded pool's caller quantizes first: the op
    moves bits).  ``phys``/``off`` are ``(b, s)`` int32 as the model
    computes them: a row's lanes sit at CONSECUTIVE positions (``off[r,
    i] == (off[r, 0] + i) % block_size``, the lanes of one page of the
    row carrying one id), and a lane routed to the null page 0 — a
    pad lane, a position past the cache — is dropped, the null page's
    content being garbage by contract on every path.  Returns
    ``(k_pages, v_pages)``: every live page bit for bit the scatter's,
    every other page byte-preserved.

    The Pallas kernel moves only the pages the rows touch, through
    outputs aliased to the pools; the XLA composition is the one-pass
    scatter (:func:`paged_write_reference`), which on a TPU costs four
    pool-sized transposed copies a layer.  Dispatch per
    :mod:`apex_tpu.ops._dispatch`; outside the kernel's envelope are
    a block size or head width off the 8-row tile, rows not in the
    pool's dtype, a chunk too wide for fast memory, and a
    tensor-parallel pool (``mesh``/``shard_axis``, as
    :func:`paged_attention` takes them), whose scatter the partitioner
    keeps shard-local.
    """
    if k.shape != v.shape or k_pages.shape != v_pages.shape:
        raise ValueError(
            f"k/v shapes differ: new {k.shape} vs {v.shape}, pages "
            f"{k_pages.shape} vs {v_pages.shape}")
    b, s, hk, d = k.shape
    hkp, _nb, bs, dp = k_pages.shape
    if (hkp, dp) != (hk, d):
        raise ValueError(
            f"new rows {k.shape} do not match pages {k_pages.shape} "
            f"in (kv_heads, head_dim)")
    if phys.shape != (b, s) or off.shape != (b, s):
        raise ValueError(
            f"phys {phys.shape} / off {off.shape} are not (b, s) = "
            f"{(b, s)}")
    sharded = (shard_axis is not None and mesh is not None
               and mesh.shape.get(shard_axis, 1) > 1)
    pallas_ok = (not sharded and bs % 8 == 0 and d % 8 == 0
                 and k.dtype == v.dtype == k_pages.dtype == v_pages.dtype
                 and _write_vmem_bytes(s, hk, bs, d, k_pages.dtype)
                 <= _WRITE_VMEM_BYTES)
    impl = resolve_impl(implementation, pallas_ok=pallas_ok,
                        op="paged_write")
    if impl == "xla":
        return paged_write_reference(k, v, k_pages, v_pages, phys, off)
    return _run_paged_write(k, v, k_pages, v_pages,
                            jnp.asarray(phys, jnp.int32),
                            jnp.asarray(off, jnp.int32),
                            impl == "pallas_interpret")


# --------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------- #
def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    scale: Optional[float] = None,
                    implementation: Optional[str] = None,
                    k_scales=None, v_scales=None,
                    mesh=None, shard_axis: Optional[str] = None,
                    window: Optional[int] = None):
    """Attention of chunk queries over a paged KV pool (shapes in the
    module docstring).  ``window``: a windowed layer's width — a query
    at position ``p`` sees keys ``p - window < j <= p`` and the sweep
    starts where the first lane's window does.

    With ``mesh`` and ``shard_axis`` both set (and the axis larger
    than 1), the op runs tensor-parallel through ``jax.shard_map``:
    the pool (and quant scales) shard on their leading ``kv_heads``
    axis, queries on their head axis by the matching GQA grouping
    (:func:`tp_head_shards`), block tables and lengths replicated —
    each chip attends over exactly its own head slice's pages, no
    collective inside the op.  The GLOBAL shapes are unchanged;
    ``kv_heads`` must be divisible by the axis size (loud
    ``ValueError`` otherwise).

    Inference-only (the decode path has no backward); the chunk's own
    K/V must already be written into the pool.  ``s > 1`` serves both
    chunked prefill and the speculative-decoding verify (one
    application scores ``1 + spec_tokens`` draft positions — see the
    module docstring's multi-query verify section).  ``implementation``
    follows :mod:`apex_tpu.ops._dispatch`: ``"auto"`` picks the Pallas
    kernel on TPU when the geometry fits its envelope (``block_size``
    and ``head_dim`` multiples of 8, GQA head ratio integral) and the
    gather reference elsewhere.

    Quantized pools (int8 / fp8 pages) REQUIRE ``k_scales``/``v_scales``
    — ``(kv_heads, num_blocks)`` fp32 per-page amax arrays (see the
    module docstring); passing scales with a float pool (or omitting
    them with a quantized one) raises.  The verify chunk and every
    other ``s`` ride the identical quantized path — no extra variant.
    """
    b, s, h, d = q.shape
    if k_pages.shape != v_pages.shape:
        raise ValueError(
            f"k_pages/v_pages shapes differ: {k_pages.shape} vs "
            f"{v_pages.shape}")
    hk, nb, bs, dk = k_pages.shape
    if dk != d:
        raise ValueError(
            f"head_dim mismatch: q has {d}, pages have {dk}")
    if h % hk:
        raise ValueError(
            f"kv_heads ({hk}) must divide num_heads ({h})")
    if block_tables.shape[0] != b or lengths.shape != (b,):
        raise ValueError(
            f"block_tables {block_tables.shape} / lengths "
            f"{lengths.shape} do not match batch {b}")
    quantized = _is_quantized_pool(k_pages.dtype)
    if quantized:
        if k_pages.dtype != v_pages.dtype:
            raise ValueError(
                f"k_pages/v_pages dtypes differ: {k_pages.dtype} vs "
                f"{v_pages.dtype}")
        if k_scales is None or v_scales is None:
            raise ValueError(
                f"quantized pages ({k_pages.dtype}) need k_scales AND "
                "v_scales (per-page fp32 amax arrays)")
        for name, sc in (("k_scales", k_scales),
                         ("v_scales", v_scales)):
            if sc.shape != (hk, nb):
                raise ValueError(
                    f"{name} shape {sc.shape} != (kv_heads, "
                    f"num_blocks) = {(hk, nb)}")
    elif k_scales is not None or v_scales is not None:
        raise ValueError(
            f"k_scales/v_scales only apply to quantized pools; pages "
            f"are {k_pages.dtype}")
    scale = (d ** -0.5) if scale is None else float(scale)
    _check_window(window)
    if shard_axis is not None and mesh is not None \
            and mesh.shape.get(shard_axis, 1) > 1:
        return _run_sharded(q, k_pages, v_pages, block_tables,
                            lengths, scale, implementation,
                            k_scales, v_scales, mesh, shard_axis,
                            window=window)
    pallas_ok = (bs % 8 == 0 and d % 8 == 0
                 and (quantized
                      or q.dtype == k_pages.dtype == v_pages.dtype))
    impl = resolve_impl(implementation, pallas_ok=pallas_ok,
                        op="paged_attention")
    if impl == "xla":
        return paged_attention_reference(
            q, k_pages, v_pages, block_tables, lengths, scale=scale,
            k_scales=k_scales, v_scales=v_scales, window=window)
    return _run_paged(q, k_pages, v_pages,
                      jnp.asarray(block_tables, jnp.int32),
                      jnp.asarray(lengths, jnp.int32), scale,
                      impl == "pallas_interpret",
                      k_scales=k_scales, v_scales=v_scales, window=window)
