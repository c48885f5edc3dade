"""Fused multi-head attention — flash-attention Pallas kernels.

Reference: ``apex/contrib/multihead_attn`` (~10 fused CUDA kernels:
self/enc-dec attention, norm-add/bias/mask variants) and
``apex/contrib/fmha`` (fixed-seqlen fused MHA, seqlen ≤ 512) — both
pre-flash-era fused attention (SURVEY.md §2.7, "north-star op").

TPU design — a single flash-attention family subsumes the whole kernel
zoo, exactly as flash attention subsumed them upstream:

- **forward**: grid ``(batch*heads, q_blocks, kv_blocks)`` — or, on
  the causal-LM hot path (sq == sk, square blocks), the triangular
  ``(batch*heads, t)`` grid that enumerates ONLY the live tiles (see
  ``_tri_ij``; no dead-tile visits, no predicated body).  The TPU
  executes the trailing grid axis sequentially, so VMEM scratch
  carries the online-softmax state (running max ``m``, normalizer
  ``l``, fp32 accumulator) across kv steps.  O(S) memory — the
  fmha/multihead_attn kernels' O(S²) score tensor never materializes.
- score tiles are TRANSPOSED (kv on sublanes, q on lanes) and the
  softmax runs in the log2 domain — both measured wins on the v5e
  VPU/MXU (see ``_scores``); the saved per-query statistics residual
  is the LOG2-domain logsumexp ``lse2 = m2 + log2(l)`` and never
  leaves the fwd/bwd kernel pair.
- **backward**: ``delta = rowsum(dO·O)`` (XLA), then two Pallas kernels:
  ``dq`` accumulates over kv blocks; ``dk/dv`` accumulate over q blocks —
  probabilities recomputed from the saved lse2 (flash-2 style), with
  (d, ·)-shaped accumulators so every accumulation matmul contracts
  over the big dim at full MXU rate.
- causal masking is generated in-kernel from block indices; on the
  rectangular (non-tri) grids, fully-masked kv blocks are skipped via
  ``pl.when``.

Layout: ``(batch, seq, heads, head_dim)`` (BSHD).  MQA/GQA: pass k/v
with fewer heads and ``num_kv_heads`` dividing ``num_heads``.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import resolve_impl

__all__ = ["fused_attention", "attention_reference", "mask_to_bias"]

_NEG_INF = -1e30
_logger = logging.getLogger(__name__)


# --------------------------------------------------------------------- #
# attention-prob dropout — counter-based hash, identical in the Pallas
# kernels and the XLA composition
# --------------------------------------------------------------------- #
# The reference's fused MHA kernels take a dropout prob and drop
# attention probabilities in-kernel (apex/contrib/multihead_attn, the
# *_dropout_* kernel variants).  Here the mask is a pure function of
# (seed, batch*head lane, global q position, global k position) — a
# murmur3-fmix32 counter hash — so the forward kernel, both backward
# kernels and the jnp reference regenerate bit-identical masks with no
# mask tensor ever materialized in HBM, and the golden tests compare
# kernel vs composition exactly.  (pltpu.prng_random_bits would tie the
# mask to grid iteration order and has no CPU-interpret support.)

def _fmix32(x):
    """murmur3 finalizer — avalanche a uint32 counter."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _drop_threshold(rate: float) -> int:
    return min(int(rate * 4294967296.0), 4294967295)


def _keep_from_counters(seed_u32, lane_u32, q_pos, k_pos, rate):
    """Boolean keep-mask from integer position counters (any shape).

    ``seed_u32``/``lane_u32`` scalars (or broadcastable), ``q_pos`` /
    ``k_pos`` int32 arrays of the tile's global positions.  Two hash
    stages (row, then column) instead of a flat ``q*sk + k`` counter:
    the flat product wraps uint32 at ~64k×64k and would alias whole
    mask rows at long context; here ``q -> fmix32(q*C + h)`` is a
    bijection on uint32, so distinct (q, k) pairs never collide by
    construction at any sequence length."""
    h = seed_u32 ^ (lane_u32 * jnp.uint32(0x9E3779B9))
    row = _fmix32(q_pos.astype(jnp.uint32) * jnp.uint32(0x9E3779B9) + h)
    x = _fmix32(row ^ (k_pos.astype(jnp.uint32)
                       * jnp.uint32(0x85EBCA6B)))
    return x >= jnp.uint32(_drop_threshold(rate))


def _dropout_keep_tile(seed_ref, lane, i, j, bq, bk, rate):
    """(bk, bq) keep-mask for grid tile (lane, i, j) — the in-kernel
    (transposed-score-tile) form of the same counter hash; the mask
    value at (k row, q lane) is hash(q_pos, k_pos), bit-identical to
    :func:`dropout_keep_mask`'s (q, k) element."""
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    seed = seed_ref[0].astype(jnp.uint32)
    return _keep_from_counters(seed, jnp.uint32(lane), q_pos, k_pos,
                               rate)


def dropout_keep_mask(seed, b, h, sq, sk, rate):
    """(b, h, sq, sk) keep-mask — the plain-jnp form of the kernels'
    in-tile hash (bit-identical), used by the XLA composition and the
    golden tests."""
    lane = (jnp.arange(b, dtype=jnp.uint32)[:, None] * jnp.uint32(h)
            + jnp.arange(h, dtype=jnp.uint32)[None, :])   # (b, h)
    q_pos = jnp.arange(sq, dtype=jnp.int32)
    k_pos = jnp.arange(sk, dtype=jnp.int32)
    keep = _keep_from_counters(
        jnp.asarray(0 if seed is None else seed).astype(jnp.uint32),
        lane[:, :, None, None],
        q_pos[None, None, :, None], k_pos[None, None, None, :],
        rate)
    return keep


def mask_to_bias(masked):
    """Boolean mask (True = masked) → additive -inf bias, fp32.

    The single source of the masking sentinel: biases built with this
    helper hit the kernels' dead-position zeroing (positions below
    ``0.5 * _NEG_INF`` contribute exactly zero probability).
    """
    return jnp.where(masked, _NEG_INF, 0.0).astype(jnp.float32)


# --------------------------------------------------------------------- #
# XLA reference composition (golden semantics; CPU/GPU fallback)
# --------------------------------------------------------------------- #
def attention_reference(q, k, v, *, causal: bool = False,
                        scale: Optional[float] = None, bias=None,
                        window: Optional[int] = None,
                        dropout_rate: float = 0.0,
                        dropout_seed=None):
    """Eager attention: softmax(q·kᵀ·scale + bias [causal]) · v.

    Shapes: q (b, sq, h, d); k/v (b, sk, hk, d) with h % hk == 0.
    Query rows with no visible key (causal with sq > sk) output zeros —
    the flash-attention convention, matched by the Pallas kernel.
    ``window``: sliding-window (requires ``causal``) — each query sees
    only the last ``window`` key positions, self included.
    ``dropout_rate`` drops attention probabilities post-softmax using
    the counter-hash mask (:func:`dropout_keep_mask`) — bit-identical
    to the Pallas kernels' in-tile dropout.
    """
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    b, sq, h, d = q.shape
    hk = k.shape[2]
    scale = (d ** -0.5) if scale is None else scale
    if hk != h:                                    # GQA: repeat kv heads
        rep = h // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        sk = k.shape[1]
        q_idx = jnp.arange(sq)[:, None]
        k_idx = jnp.arange(sk)[None, :]
        s = jnp.where(k_idx > q_idx + (sk - sq), _NEG_INF, s)
        if window is not None:
            s = jnp.where(k_idx <= q_idx + (sk - sq) - window,
                          _NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    if causal or bias is not None:
        # dead positions (score pushed below the -inf sentinel) get
        # exactly zero probability; fully-dead rows output zeros — the
        # flash-attention convention, matched by the Pallas kernel
        p = jnp.where(s < 0.5 * _NEG_INF, 0.0, p)
    if dropout_rate > 0.0:
        keep = dropout_keep_mask(dropout_seed, b, h, sq, k.shape[1],
                                 dropout_rate)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


# --------------------------------------------------------------------- #
# forward kernel
# --------------------------------------------------------------------- #
# The softmax runs in the log2 domain: scores are computed as
# s2 = (q·scale·log2(e))@kᵀ (+ bias·log2(e)) and probabilities as
# exp2(s2 - m2) — ``exp2`` measured 2.2x cheaper than ``exp`` on the
# VPU (tools/mxu_probe.py) and the probabilities are bit-identical up
# to fp rounding.  The saved logsumexp residual is likewise log2-domain
# (lse2 = m2 + log2(l)); it never leaves the fwd/bwd kernel pair.
_LOG2E = 1.4426950408889634


def _scores(q_ref, k_ref, kvb_ref, i, j, *, scale, causal, per_q, bq,
            bk, sq, sk, window=None):
    """log2-domain scaled scores for one (q-block, kv-block) tile,
    TRANSPOSED — (bk, bq): kv positions on sublanes, q positions on
    lanes — computed as k(q·scale·log2e)ᵀ (+ biasᵀ·log2e) with causal
    positions at -inf.

    The transposed orientation is the load-bearing layout decision
    (measured, tools/mxu_probe.py): per-q softmax statistics become
    native (1, bq) lane rows — so the saved (bh, 1, s) lse/delta blocks
    broadcast into the tile with NO per-step sublane↔lane relayout —
    and every downstream accumulation (O, dQ, dK, dV) contracts over
    the tile's big dim with the head dim as M, the dot_general forms
    that run the MXU at ~190 TFLOP/s vs ~86 for the (·, d)-output
    forms whose N=64 pads half the array.  The score matmul itself
    contracts d (irreducibly half-padded at d=64, ~89 TFLOP/s) in both
    orientations.  The scale rides the small (bq, d) q tile (a ~0.06 µs
    VPU pass) instead of the score tile (a ~1 µs pass at 1024² tiles).
    ``per_q``: the bias block is (1, bk, bq) (per-query columns, from
    the wrapper's pre-transposed bias) instead of (1, bk, 1) per-key.
    """
    # operands stay in their input dtype (bf16 runs the MXU at full
    # rate; an fp32 upcast here would cost ~6-8x matmul throughput —
    # the reference's fused MHA likewise runs half-precision tensor-op
    # matmuls with fp32 softmax); accumulation is always fp32
    qs = q_ref[0] * jnp.asarray(scale * _LOG2E, q_ref.dtype)
    s = jax.lax.dot_general(
        k_ref[0], qs, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (bk, bq) f32
    if kvb_ref is not None:
        # the bias arrives pre-multiplied by log2e (folded into
        # _normalize_bias's one-time f32 copy, not a per-tile pass)
        if per_q:
            s = s + kvb_ref[0]                     # (bk, bq) tile
        else:
            s = s + kvb_ref[0, :, 0:1]             # (bk, 1) kv bias
    if causal:
        # unconditional iota+select on every tile: restricting the mask
        # to diagonal-straddling tiles via an in-kernel lax.cond was
        # measured 1.5x SLOWER overall (the branch defeats Mosaic's
        # tile-loop pipelining), so the cheap always-on form stays
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
        q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        s = jnp.where(k_pos > q_pos + (sk - sq), _NEG_INF, s)
        if window is not None:
            # sliding window: only the last `window` positions
            # (self included) are visible — k > q_abs - window
            s = jnp.where(k_pos <= q_pos + (sk - sq) - window,
                          _NEG_INF, s)
    return s


# --------------------------------------------------------------------- #
# triangular (causal) grid enumeration
# --------------------------------------------------------------------- #
# For causal self-attention (sq == sk, bq == bk) the live (i, j) tiles
# form the lower triangle j <= i.  Instead of a rectangular grid with a
# ``pl.when(block_live)`` skip — whose predicated body measured
# ~+0.5 µs per 1024² tile on top of visiting twice the tiles — the
# kernels enumerate ONLY the live tiles on one linear grid axis and
# recover (i, j) from the step index with closed-form integer math
# (f32 sqrt + one-step correction; exact for any practical block
# count).  The same formulas run in the BlockSpec index maps (scalar
# core) and the kernel body.

def _tri_ij(t):
    """Lower-triangle enumeration, j inner: t -> (i, j), j <= i."""
    tf = 8.0 * t.astype(jnp.float32) + 1.0
    i = ((jnp.sqrt(tf) - 1.0) * 0.5).astype(jnp.int32)
    i = jnp.where(i * (i + 1) // 2 > t, i - 1, i)
    i = jnp.where((i + 1) * (i + 2) // 2 <= t, i + 1, i)
    j = t - i * (i + 1) // 2
    return i, j


def _tri_ji(t, nb):
    """Upper-wedge enumeration, i inner: t -> (i, j), i >= j.

    Row j holds ``nb - j`` tiles (i = j..nb-1), offset
    ``off(j) = j·nb - j(j-1)/2``."""
    a = 2 * nb + 1
    tf = jnp.abs(a * a - 8 * t).astype(jnp.float32)
    j = ((a - jnp.sqrt(tf)) * 0.5).astype(jnp.int32)

    def off(x):
        return x * nb - x * (x - 1) // 2

    j = jnp.where(off(j) > t, j - 1, j)
    j = jnp.where(off(j + 1) <= t, j + 1, j)
    i = j + (t - off(j))
    return i, j


# --------------------------------------------------------------------- #
# banded (sliding-window causal) grid enumeration
# --------------------------------------------------------------------- #
# With a sliding window of W kv blocks behind the diagonal, the live
# tiles form the band max(0, i - W) <= j <= i: a triangular head
# (rows i <= W) followed by a uniform part (W + 1 tiles per row).
# W = nb - 1 covers the whole triangle, making these a strict
# generalization of the _tri_* enumerations (which they call for their
# triangular pieces) — the causal kernels always run the band grid.

def _band_tiles(nb: int, W: int) -> int:
    """Live-tile count of the band grid."""
    head = min(nb, W + 1)
    return head * (head + 1) // 2 + max(0, nb - W - 1) * (W + 1)


def _band_ij(t, W):
    """Banded lower-wedge enumeration, j inner: t -> (i, j) with
    max(0, i - W) <= j <= i.  ``W >= nb - 1`` degenerates to
    :func:`_tri_ij`."""
    i1, j1 = _tri_ij(t)                          # triangular head
    head = (W + 1) * (W + 2) // 2
    tq = t - head
    i2 = (W + 1) + tq // (W + 1)                 # uniform tail
    j2 = (i2 - W) + (tq % (W + 1))
    tail = t >= head
    return jnp.where(tail, i2, i1), jnp.where(tail, j2, j1)


def _band_ji(t, W, nb):
    """Banded upper-wedge enumeration, i inner: t -> (i, j) with
    j <= i <= min(j + W, nb - 1): a uniform head (full-length kv rows
    j <= nb-1-W, W + 1 tiles each) then a shrinking triangular tail."""
    J0 = nb - 1 - W                              # last full-length row
    headN = (J0 + 1) * (W + 1)
    j1 = t // (W + 1)
    i1 = j1 + (t % (W + 1))
    it, jt = _tri_ji(t - headN, W)               # tail rows, len W-j'
    tail = t >= headN
    return (jnp.where(tail, J0 + 1 + it, i1),
            jnp.where(tail, J0 + 1 + jt, j1))


def _dead_rows_possible(causal, has_bias, sq, sk) -> bool:
    """Can a query row be FULLY masked (every key dead)?  Only then is
    the explicit dead-position zeroing needed: a fully-dead row has
    running max / lse == -inf, making ``exp2(s - m) == 1`` where it
    must be 0.  When every row has at least one live key (plain causal
    self-attention with sq <= sk, or no masking at all), the running
    max is finite from each lane's first live tile on, so
    ``exp2(-1e30 - m)`` underflows to EXACTLY zero on dead positions
    and the zeroing is redundant — and it is the single most expensive
    VPU element of the tile loop (+1.15 µs of 4.7 on a 1024² tile,
    measured in the round-4 ablation), so skipping it statically is a
    ~20% forward-kernel win on the causal-LM hot path."""
    if has_bias:
        return True       # padding masks can kill whole rows
    return causal and sq > sk


def _zero_dead(s, p, causal, has_bias, sq, sk):
    """Zero probabilities at dead positions (score below the -inf
    sentinel) — only when a fully-dead row is statically possible
    (see :func:`_dead_rows_possible`)."""
    if _dead_rows_possible(causal, has_bias, sq, sk):
        return jnp.where(s < 0.5 * _NEG_INF, 0.0, p)
    return p


def _fa_fwd_kernel(*refs, scale, causal, has_bias, per_q, rate, bq, bk,
                   sk_blocks, sq, sk, tri, window=None, W=None):
    n = 3
    q_ref, k_ref, v_ref = refs[:3]
    kvb_ref = refs[n] if has_bias else None
    n += 1 if has_bias else 0
    seed_ref = refs[n] if rate > 0.0 else None
    n += 1 if rate > 0.0 else 0
    o_ref, lse_ref, acc_ref, m_ref, l_ref = refs[n:]
    lane = pl.program_id(0)
    if tri:
        # banded grid: only live tiles are visited, no predicated
        # body (the pl.when wrap alone measured ~+0.5 µs/tile);
        # W = nb-1 (no window) is the full causal triangle
        i, j = _band_ij(pl.program_id(1), W)
        init_pred = j == jnp.maximum(i - W, 0)
        final_pred = j == i
    else:
        j = pl.program_id(2)
        i = pl.program_id(1)
        init_pred = j == 0
        final_pred = j == sk_blocks - 1

    @pl.when(init_pred)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _step():
        s = _scores(q_ref, k_ref, kvb_ref, i, j, scale=scale,
                    causal=causal, per_q=per_q, bq=bq, bk=bk, sq=sq,
                    sk=sk, window=window)          # (bk, bq)
        m_prev = m_ref[:]                          # (1, bq) lane row
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = _zero_dead(s, jnp.exp2(s - m_new), causal, has_bias,
                       sq, sk)
        alpha = jnp.exp2(m_prev - m_new)           # (1, bq)
        # the normalizer accumulates the UNDROPPED probabilities (the
        # softmax denominator is dropout-independent, torch semantics);
        # only the value accumulation sees the dropped/rescaled probs
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=0, keepdims=True)
        if rate > 0.0:
            keep = _dropout_keep_tile(seed_ref, lane, i, j, bq, bk,
                                      rate)
            p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
        # probs ride the MXU in the value dtype (fp32 softmax, half pv
        # matmul — reference fused-MHA recipe), accumulate fp32; the
        # (d, bq) accumulator contracts over bk at full MXU rate and
        # the (1, bq) alpha broadcasts with no relayout (see _scores)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            v_ref[0], p.astype(v_ref.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    if tri:
        _step()
    else:
        # causal block skip: kv block j is live iff its first key
        # position <= last query position (+ rectangular offset)
        q_last = (i + 1) * bq - 1 + (sk - sq)
        block_live = jnp.logical_or(not causal, j * bk <= q_last)
        if window is not None:
            # window block skip: the block's newest key must reach the
            # oldest query's window start
            q_first = i * bq + (sk - sq)
            block_live = jnp.logical_and(
                block_live, (j + 1) * bk - 1 >= q_first - window + 1)
        pl.when(block_live)(_step)

    @pl.when(final_pred)
    def _final():
        l = l_ref[:]                               # (1, bq)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # one amortized (d, bq) -> (bq, d) transpose per q block
        o_ref[0] = jnp.transpose(acc_ref[:] / l_safe).astype(o_ref.dtype)
        # lse saved in the log2 domain (consumed only by the backward);
        # already a lane row — no relayout
        lse_ref[0] = m_ref[:] + jnp.log2(l_safe)


def _tri_maps(tri, swapped, nb, W=None):
    """(i_map, j_map): block-index extractors for the grid's trailing
    axes — rectangular (b, i, j) / (b, j, i), or banded/triangular
    (b, t) with (i, j) recovered from t (``W`` kv blocks behind the
    diagonal; ``None``/``nb - 1`` = full triangle)."""
    if W is None:
        W = nb - 1
    if tri and swapped:
        return ((lambda t: _band_ji(t, W, nb)[0]),
                (lambda t: _band_ji(t, W, nb)[1]))
    if tri:
        return ((lambda t: _band_ij(t, W)[0]),
                (lambda t: _band_ij(t, W)[1]))
    if swapped:
        return (lambda j, i: i), (lambda j, i: j)
    return (lambda i, j: i), (lambda i, j: j)


def _qkv_specs(d, bq, bk, rep, tri=False, swapped=False, nb=0, W=None):
    """BlockSpecs for q/k/v under grid (b*h, i, j) (or the banded
    (b*h, t)).  GQA: `rep` consecutive q heads share one kv head — the
    kv BlockSpecs index b // rep, so kv is never materialized
    per-q-head in HBM."""
    im, jm = _tri_maps(tri, swapped, nb, W)
    return [
        pl.BlockSpec((1, bq, d), lambda b, *g: (b, im(*g), 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), lambda b, *g: (b // rep, jm(*g), 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), lambda b, *g: (b // rep, jm(*g), 0),
                     memory_space=pltpu.VMEM),
    ]


def _bias_spec(mode, nh, bq, bk, *, swapped: bool = False, tri=False,
               nb=0, W=None):
    """BlockSpec for the normalized TRANSPOSED (B0*H0, sk, S0) bias
    (key dim on sublanes, matching the kernels' (bk, bq) score tiles).

    ``mode = (has_batch, has_head, per_q)`` statics; the leading array
    index is ``batch*H0 + head`` with H0 == nh when has_head.  The
    per-key form keeps a trailing singleton so the (bk, 1) block
    broadcasts over lanes natively.  ``swapped``: the dkv grid is
    (b, j, i)."""
    has_batch, has_head, per_q = mode
    h0 = nh if has_head else 1
    im, jm = _tri_maps(tri, swapped, nb, W)

    def lead(bb):
        batch = bb // nh if has_batch else 0
        head = (bb % nh) if has_head else 0
        return batch * h0 + head

    if per_q:
        return pl.BlockSpec((1, bk, bq),
                            lambda b, *g: (lead(b), jm(*g), im(*g)),
                            memory_space=pltpu.VMEM)
    return pl.BlockSpec((1, bk, 1), lambda b, *g: (lead(b), jm(*g), 0),
                        memory_space=pltpu.VMEM)


_SEED_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _use_tri(causal, sq, sk, bq, bk) -> bool:
    """Triangular-grid eligibility: causal self-attention with equal
    seq lengths and square blocks (the LM hot path)."""
    return bool(causal) and sq == sk and bq == bk


def _band_w(window, tri, nb, bk):
    """Window width in kv blocks behind the diagonal (band grid)."""
    if not tri or window is None:
        return nb - 1
    return min(nb - 1, (window + bk - 2) // bk)


def _run_fa_fwd(q3, k3, v3, kvb, seed, scale, causal, window, bias_mode,
                rate, rep, nh, bq, bk, interpret):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    tri = _use_tri(causal, sq, sk, bq, bk)
    nb = sq // bq
    W = _band_w(window, tri, nb, bk)
    grid = (bh, _band_tiles(nb, W)) if tri else (bh, nb, sk // bk)
    im, jm = _tri_maps(tri, False, nb, W)
    has_bias = kvb is not None
    kernel = functools.partial(
        _fa_fwd_kernel, scale=scale, causal=causal, has_bias=has_bias,
        per_q=bool(bias_mode and bias_mode[2]), rate=rate,
        bq=bq, bk=bk, sk_blocks=sk // bk, sq=sq, sk=sk, tri=tri,
        window=window, W=W)
    in_specs = _qkv_specs(d, bq, bk, rep, tri=tri, nb=nb, W=W)
    args = [q3, k3, v3]
    if has_bias:
        in_specs.append(_bias_spec(bias_mode, nh, bq, bk, tri=tri,
                                   nb=nb, W=W))
        args.append(kvb)
    if rate > 0.0:
        in_specs.append(_SEED_SPEC)
        args.append(seed)
    # the scope names the kernel in HLO metadata and profiler traces
    with jax.named_scope("attention.fwd"):
        o, lse = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, bq, d), lambda b, *g: (b, im(*g), 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, bq), lambda b, *g: (b, 0, im(*g)),
                             memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
                # (bh, 1, sq): middle singleton keeps blocks TPU-tileable
                jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((d, bq), jnp.float32),      # transposed acc
                pltpu.VMEM((1, bq), jnp.float32),      # m (lane row)
                pltpu.VMEM((1, bq), jnp.float32),      # l (lane row)
            ],
            interpret=interpret,
        )(*args)
    return o, lse


# --------------------------------------------------------------------- #
# backward kernels
# --------------------------------------------------------------------- #
def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref,
                      *refs, scale, causal, has_bias, per_q, rate, bq,
                      bk, sk_blocks, sq, sk, tri, window=None, W=None):
    n = 0
    kvb_ref = refs[n] if has_bias else None
    n += 1 if has_bias else 0
    seed_ref = refs[n] if rate > 0.0 else None
    n += 1 if rate > 0.0 else 0
    do_ref, lse_ref, delta_ref, dq_ref, acc_ref = refs[n:]
    lane = pl.program_id(0)
    if tri:
        i, j = _band_ij(pl.program_id(1), W)
        init_pred = j == jnp.maximum(i - W, 0)
        final_pred = j == i
    else:
        j = pl.program_id(2)
        i = pl.program_id(1)
        init_pred = j == 0
        final_pred = j == sk_blocks - 1

    @pl.when(init_pred)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _step():
        lse = lse_ref[0]                           # (1, bq), log2 dom
        delta = delta_ref[0]                       # (1, bq)
        s = _scores(q_ref, k_ref, kvb_ref, i, j, scale=scale,
                    causal=causal, per_q=per_q, bq=bq, bk=bk, sq=sq,
                    sk=sk, window=window)          # (bk, bq)
        # dead rows have lse == -inf making exp2(s - lse) == 1 there;
        # _zero_dead restores exact zeros
        p = _zero_dead(s, jnp.exp2(s - lse), causal, has_bias,
                       sq, sk)
        # dPᵀ = V dOᵀ — half-dtype operands, fp32 accumulation; the
        # d contraction is the irreducibly-padded one (see _scores)
        dp = jax.lax.dot_general(
            v_ref[0], do_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bk, bq)
        if rate > 0.0:
            # dS = P ∘ (D∘dP - delta): same mask as the forward tile;
            # delta = rowsum(dO·O) already contains the dropout factor
            keep = _dropout_keep_tile(seed_ref, lane, i, j, bq, bk,
                                      rate)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - rate)), 0.0)
        # the softmax scale is deferred to the final write (dq is
        # linear in it); dsᵀ here is pᵀ·(dpᵀ - delta)
        ds = p * (dp - delta)                      # (bk, bq)
        # (d, bq) accumulator: dqᵀ += kᵀ dS — contracts over bk at
        # full MXU rate (tools/mxu_probe.py)
        acc_ref[:] += jax.lax.dot_general(
            k_ref[0], ds.astype(k_ref.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if tri:
        _step()
    else:
        q_last = (i + 1) * bq - 1 + (sk - sq)
        block_live = jnp.logical_or(not causal, j * bk <= q_last)
        if window is not None:
            q_first = i * bq + (sk - sq)
            block_live = jnp.logical_and(
                block_live, (j + 1) * bk - 1 >= q_first - window + 1)
        pl.when(block_live)(_step)

    @pl.when(final_pred)
    def _final():
        # one amortized (d, bq) -> (bq, d) transpose per q block
        dq_ref[0] = jnp.transpose(
            acc_ref[:] * scale).astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref,
                       *refs, scale, causal, has_bias, per_q, rate, bq,
                       bk, sq_blocks, sq, sk, tri, window=None, W=None):
    n = 0
    kvb_ref = refs[n] if has_bias else None
    n += 1 if has_bias else 0
    seed_ref = refs[n] if rate > 0.0 else None
    n += 1 if rate > 0.0 else 0
    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs[n:]
    lane = pl.program_id(0)
    if tri:
        # banded upper-wedge enumeration: kv block j outer, q block i
        # inner from the diagonal down (i = j..min(j+W, nb-1))
        i, j = _band_ji(pl.program_id(1), W, sq_blocks)
        init_pred = i == j
        last_pred = i == jnp.minimum(j + W, sq_blocks - 1)
    else:
        i = pl.program_id(2)      # q block (sequential axis)
        j = pl.program_id(1)      # kv block
        init_pred = i == 0
        last_pred = i == sq_blocks - 1

    @pl.when(init_pred)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _step():
        lse = lse_ref[0]                           # (1, bq), log2 dom
        delta = delta_ref[0]                       # (1, bq)
        s = _scores(q_ref, k_ref, kvb_ref, i, j, scale=scale,
                    causal=causal, per_q=per_q, bq=bq, bk=bk, sq=sq,
                    sk=sk, window=window)          # (bk, bq)
        p = _zero_dead(s, jnp.exp2(s - lse), causal, has_bias,
                       sq, sk)
        if rate > 0.0:
            keep = _dropout_keep_tile(seed_ref, lane, i, j, bq, bk,
                                      rate)
            inv = 1.0 / (1.0 - rate)
            pd = jnp.where(keep, p * inv, 0.0)     # dropped probs
        else:
            keep, pd = None, p
        # TRANSPOSED accumulators (d, bk): contracting over bq with the
        # head dim as M runs the MXU at full rate (194 vs 86 TFLOP/s,
        # tools/mxu_probe.py); one (d, bk) -> (bk, d) transpose per kv
        # block at the end (amortized over the inner q sweep).
        # dvᵀ += dOᵀ (P∘D)ᵀ — half-dtype operands, fp32 accumulation
        dv_acc[:] += jax.lax.dot_general(
            do_ref[0], pd.astype(do_ref.dtype), (((0,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dPᵀ = V dOᵀ (d contraction, irreducibly padded)
        dp = jax.lax.dot_general(
            v_ref[0], do_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # (bk, bq)
        if rate > 0.0:
            dp = jnp.where(keep, dp * inv, 0.0)
        # dkᵀ += (q·scale·log2e)ᵀᵀ dSᵀᵀ with the log2e divided back out
        # at the final write — reuses the score recompute's scaled q
        # tile (CSE'd) and keeps the softmax scale off the score-sized
        # (bk, bq) pass entirely
        ds = p * (dp - delta)                      # (bk, bq) f32
        qs = q_ref[0] * jnp.asarray(scale * _LOG2E, q_ref.dtype)
        dk_acc[:] += jax.lax.dot_general(
            qs, ds.astype(q_ref.dtype), (((0,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    if tri:
        _step()
    else:
        q_last = (i + 1) * bq - 1 + (sk - sq)
        block_live = jnp.logical_or(not causal, j * bk <= q_last)
        if window is not None:
            q_first = i * bq + (sk - sq)
            block_live = jnp.logical_and(
                block_live, (j + 1) * bk - 1 >= q_first - window + 1)
        pl.when(block_live)(_step)

    @pl.when(last_pred)
    def _final():
        dk_ref[0] = jnp.transpose(
            dk_acc[:] * (1.0 / _LOG2E)).astype(dk_ref.dtype)
        dv_ref[0] = jnp.transpose(dv_acc[:]).astype(dv_ref.dtype)


def _run_fa_bwd(q3, k3, v3, kvb, seed, o3, lse, do3, scale, causal,
                window, bias_mode, rate, rep, nh, bq, bk, interpret):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    has_bias = kvb is not None
    per_q = bool(bias_mode and bias_mode[2])
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]           # (bh, 1, sq)

    tri = _use_tri(causal, sq, sk, bq, bk)
    nb = sq // bq
    W = _band_w(window, tri, nb, bk)
    n_tiles = _band_tiles(nb, W)
    im, jm = _tri_maps(tri, False, nb, W)
    dq_kernel = functools.partial(
        _fa_bwd_dq_kernel, scale=scale, causal=causal, has_bias=has_bias,
        per_q=per_q, rate=rate, bq=bq, bk=bk, sk_blocks=sk // bk, sq=sq,
        sk=sk, tri=tri, window=window, W=W)
    in_specs = _qkv_specs(d, bq, bk, rep, tri=tri, nb=nb, W=W)
    args = [q3, k3, v3]
    if has_bias:
        in_specs.append(_bias_spec(bias_mode, nh, bq, bk, tri=tri,
                                   nb=nb, W=W))
        args.append(kvb)
    if rate > 0.0:
        in_specs.append(_SEED_SPEC)
        args.append(seed)
    in_specs += [
        pl.BlockSpec((1, bq, d), lambda b, *g: (b, im(*g), 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, bq), lambda b, *g: (b, 0, im(*g)),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, bq), lambda b, *g: (b, 0, im(*g)),
                     memory_space=pltpu.VMEM),
    ]
    # the scope names the kernel in HLO metadata and profiler traces
    with jax.named_scope("attention.bwd_dq"):
        dq = pl.pallas_call(
            dq_kernel,
            grid=(bh, n_tiles) if tri else (bh, nb, sk // bk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, d), lambda b, *g: (b, im(*g), 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
            scratch_shapes=[pltpu.VMEM((d, bq), jnp.float32)],
            interpret=interpret,
        )(*args, do3, lse, delta)

    dkv_kernel = functools.partial(
        _fa_bwd_dkv_kernel, scale=scale, causal=causal,
        has_bias=has_bias, per_q=per_q, rate=rate, bq=bq, bk=bk,
        sq_blocks=sq // bq, sq=sq, sk=sk, tri=tri, window=window, W=W)
    # dk/dv are computed per *q* head (grid axis 0 = b*h) so each output
    # block is owned by one grid lane; for GQA the rep-sized head groups
    # are summed afterwards (cheap, fp32) instead of making the kernel
    # revisit shared kv output blocks.  NB grid order (b, j, i) — or
    # the triangular (b, t) upper-wedge enumeration: the index maps
    # permute accordingly.
    im2, jm2 = _tri_maps(tri, True, nb, W)
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda b, *g: (b, im2(*g), 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), lambda b, *g: (b // rep, jm2(*g), 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, bk, d), lambda b, *g: (b // rep, jm2(*g), 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [q3, k3, v3]
    if has_bias:
        in_specs.append(_bias_spec(bias_mode, nh, bq, bk, swapped=True,
                                   tri=tri, nb=nb, W=W))
        args.append(kvb)
    if rate > 0.0:
        in_specs.append(_SEED_SPEC)
        args.append(seed)
    in_specs += [
        pl.BlockSpec((1, bq, d), lambda b, *g: (b, im2(*g), 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, bq), lambda b, *g: (b, 0, im2(*g)),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, bq), lambda b, *g: (b, 0, im2(*g)),
                     memory_space=pltpu.VMEM),
    ]
    # the scope names the kernel in HLO metadata and profiler traces
    with jax.named_scope("attention.bwd_dkv"):
        dk, dv = pl.pallas_call(
            dkv_kernel,
            grid=(bh, n_tiles) if tri else (bh, sk // bk, nb),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, bk, d), lambda b, *g: (b, jm2(*g), 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bk, d), lambda b, *g: (b, jm2(*g), 0),
                             memory_space=pltpu.VMEM),
            ],
            out_shape=[
                # fp32 only when a cross-head group sum follows (rep > 1);
                # otherwise write the kv dtype directly (half the HBM bytes)
                jax.ShapeDtypeStruct(
                    (bh, sk, d), jnp.float32 if rep > 1 else k3.dtype),
                jax.ShapeDtypeStruct(
                    (bh, sk, d), jnp.float32 if rep > 1 else v3.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((d, bk), jnp.float32),      # transposed dk acc
                pltpu.VMEM((d, bk), jnp.float32),      # transposed dv acc
            ],
            interpret=interpret,
        )(*args, do3, lse, delta)
    if rep > 1:
        dk = dk.reshape(bh // rep, rep, sk, d).sum(axis=1)
        dv = dv.reshape(bh // rep, rep, sk, d).sum(axis=1)
    return dq, dk.astype(k3.dtype), dv.astype(v3.dtype)


# --------------------------------------------------------------------- #
# custom VJP over (b*h, s, d) arrays
# --------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12, 13, 14))
def _fa_pallas(q3, k3, v3, kvb, seed, scale, causal, window, bias_mode,
               rate, rep, nh, bq, bk, interpret):
    o, _ = _run_fa_fwd(q3, k3, v3, kvb, seed, scale, causal, window,
                       bias_mode, rate, rep, nh, bq, bk, interpret)
    return o


def _fa_pallas_fwd(q3, k3, v3, kvb, seed, scale, causal, window,
                   bias_mode, rate, rep, nh, bq, bk, interpret):
    o, lse = _run_fa_fwd(q3, k3, v3, kvb, seed, scale, causal, window,
                         bias_mode, rate, rep, nh, bq, bk, interpret)
    # named so a remat policy can save the kernel's residuals and skip
    # re-running the forward kernel in the backward pass entirely
    # (remat_policy="save_only:attn_out,attn_lse" — the o/lse pair is
    # all the bwd kernels need beyond q/k/v; storage is b·s·(hd+h)
    # vs recomputing O(S²) flash work)
    from jax.ad_checkpoint import checkpoint_name

    lse = checkpoint_name(lse, "attn_lse")
    o = checkpoint_name(o, "attn_out")
    return o, (q3, k3, v3, kvb, seed, o, lse)


def _fa_pallas_bwd(scale, causal, window, bias_mode, rate, rep, nh, bq,
                   bk, interpret, res, do):
    q3, k3, v3, kvb, seed, o, lse = res
    dq, dk, dv = _run_fa_bwd(q3, k3, v3, kvb, seed, o, lse, do, scale,
                             causal, window, bias_mode, rate, rep, nh,
                             bq, bk, interpret)
    # the bias is treated as a constant (padding masks / ALiBi slopes);
    # learned biases must pass bias_requires_grad=True at the API level,
    # which routes to the differentiable XLA composition
    return dq, dk, dv, None, None


_fa_pallas.defvjp(_fa_pallas_fwd, _fa_pallas_bwd)


# --------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------- #
def _pick_block(s: int, want: int) -> int:
    """Largest block ≤ ``want`` that divides ``s`` (multiple-of-128
    lane alignment preferred), so e.g. s=768 gets 384 blocks instead of
    falling off the Pallas path; short/odd sequences run as one block."""
    if s <= want:
        return s
    best = 0
    for cand in range(128, want + 1, 128):
        if s % cand == 0:
            best = cand
    if best:
        return best
    # s not a multiple of 128: single-block only if small enough for
    # VMEM; otherwise return `want` (won't divide s -> XLA fallback)
    return s if s <= 2 * want else want

def _normalize_bias(bias, b, h, sq, sk):
    """Normalize a broadcastable 4-d additive bias to the kernels'
    TRANSPOSED (B0*H0, sk, S0) layout (key dim on sublanes, matching
    the (bk, bq) score tiles) + static ``(has_batch, has_head, per_q)``
    mode.  The transpose is free for the common per-key masks (S0 == 1)
    and one XLA pass for full per-query score biases.  Returns
    (None, None) when the bias can't ride the kernel (wrong rank,
    unbroadcastable dims, or a sub-sk key dim)."""
    if bias is None or bias.ndim != 4:
        return None, None
    b0, h0, s0, k0 = bias.shape
    if (k0 != sk or b0 not in (1, b) or h0 not in (1, h)
            or s0 not in (1, sq)):
        return None, None
    mode = (b0 == b, h0 == h, s0 == sq)
    # fold the log2-domain conversion into this one-time copy so the
    # kernels never spend a per-tile pass on it; the -1e30 mask
    # sentinel stays below the dead-position threshold either way
    bias3 = (bias.reshape(b0 * h0, s0, sk).swapaxes(1, 2)
             .astype(jnp.float32) * _LOG2E)
    return bias3, mode


def _derive_seed(dropout_rng) -> jnp.ndarray:
    """(1,) int32 seed from a PRNG key or python/array integer."""
    if dropout_rng is None:
        return jnp.zeros((1,), jnp.int32)
    if isinstance(dropout_rng, (int, jnp.integer)):
        return jnp.asarray([dropout_rng], jnp.int32)
    arr = jnp.asarray(dropout_rng)
    if jnp.issubdtype(arr.dtype, jax.dtypes.prng_key) or (
            arr.dtype == jnp.uint32 and arr.shape == (2,)):
        key = arr if jnp.issubdtype(
            arr.dtype, jax.dtypes.prng_key) else \
            jax.random.wrap_key_data(arr)
        return jax.random.randint(
            key, (1,), jnp.iinfo(jnp.int32).min,
            jnp.iinfo(jnp.int32).max, dtype=jnp.int32)
    return arr.reshape(1).astype(jnp.int32)


def fused_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    bias=None,
                    bias_requires_grad: bool = False,
                    window: Optional[int] = None,
                    dropout_rate: float = 0.0,
                    dropout_rng=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    implementation: Optional[str] = None):
    """Flash multi-head attention (BSHD layout), O(S) memory.

    Drop-in for the reference's ``SelfMultiheadAttn`` core /
    ``fmha`` (SURVEY.md §2.7).  GQA/MQA supported via fewer kv heads.

    ``window``: sliding-window attention (Mistral/Gemma-style; requires
    ``causal``) — each query attends only to the last ``window``
    positions, self included.  On the causal self-attention hot path
    the kernels enumerate ONLY the tiles inside the band (the same
    linearized-live-tile trick as the causal triangle), so compute AND
    time drop to ~``window/seq`` of full attention rather than just
    masking — beyond-reference: the reference's fmha has no windowing.

    ``bias``: any additive bias broadcastable as ``(b|1, h|1, sq|1,
    sk)`` rides the Pallas kernel — key-padding rows from
    :func:`mask_to_bias`, per-head ALiBi ``(1, h, 1, sk)``,
    relative-position / full score biases ``(b|1, h, sq, sk)``.  The
    kernel treats the bias as a constant; set
    ``bias_requires_grad=True`` for a *learned* bias (T5-style) to get
    its gradient via the XLA composition instead (O(S²), logged).

    ``dropout_rate``: in-kernel attention-probability dropout — the
    reference's fused-MHA dropout semantics (softmax denominator
    undropped, probs dropped and rescaled before the value matmul).
    The mask is a counter hash of (seed, lane, positions), regenerated
    bit-identically in the backward kernels and in
    :func:`attention_reference` (pass the same seed to cross-check).
    ``dropout_rng`` accepts a JAX PRNG key or an integer seed.
    """
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if h % hk:
        raise ValueError(
            f"num_kv_heads ({hk}) must divide num_heads ({h})")
    if window is not None:
        window = int(window)
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if not causal:
            raise ValueError(
                "sliding-window attention requires causal=True")
        if window >= sk:
            window = None              # window covers everything
    scale = (d ** -0.5) if scale is None else float(scale)
    # seq-aware default tiles: 512 short (fastest end-to-end at s=512,
    # BASELINE.md round-2 sweep), 1024 from 16k (21% faster fwd+bwd
    # measured at 32k — the VMEM-budget ceiling; 2048 blocks OOM)
    if block_q is None:
        block_q = 1024 if sq >= 16384 else 512
    if block_k is None:
        block_k = 1024 if sk >= 16384 else 512
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    kvb, bias_mode = _normalize_bias(bias, b, h, sq, sk)
    rate = float(dropout_rate)
    if rate > 0.0 and dropout_rng is None:
        raise ValueError(
            "fused_attention: dropout_rate > 0 requires dropout_rng "
            "(a JAX PRNG key or integer seed) — a silent constant "
            "seed would drop the same positions every step")
    seed = _derive_seed(dropout_rng) if rate > 0.0 else None
    pallas_ok = (
        (bias is None or kvb is not None)
        and not (bias is not None and bias_requires_grad)
        # blocks span the whole head dim, so any multiple of the fp32
        # sublane works (d=64 covers BERT-Large; 128 fills MXU lanes)
        and d % 8 == 0
        and sq % bq == 0 and sk % bk == 0
        and q.dtype == k.dtype == v.dtype
    )
    impl = resolve_impl(implementation, pallas_ok=pallas_ok)
    if impl == "xla" or not pallas_ok:
        if implementation in (None, "auto") and not pallas_ok:
            reason = ("bias_requires_grad" if bias_requires_grad
                      else "bias shape" if bias is not None
                      and kvb is None else "shape/dtype constraints")
            _logger.info(
                "fused_attention: falling back to the O(S^2) XLA "
                "composition (%s); q=%s bias=%s", reason, q.shape,
                None if bias is None else bias.shape)
        seed_val = seed[0] if seed is not None else 0
        return attention_reference(
            q, k, v, causal=causal, scale=scale, bias=bias,
            window=window, dropout_rate=rate, dropout_seed=seed_val)
    interpret = impl == "pallas_interpret"
    # (b, s, h, d) -> (b*h, s, d); GQA kv stays at (b*hk, s, d) — the
    # kernels' kv BlockSpecs map rep consecutive q heads to one kv head
    q3 = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    k3 = k.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)
    v3 = v.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)
    o3 = _fa_pallas(q3, k3, v3, kvb, seed, scale, bool(causal), window,
                    bias_mode, rate, h // hk, h, bq, bk, interpret)
    return o3.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
