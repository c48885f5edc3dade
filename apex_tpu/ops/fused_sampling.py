"""Fused decode-step sampling — one-pass categorical draw over vocab.

The serving engines' decode tail (``apex_tpu.serving.engine.
sample_dynamic``) turns a ``(slots, vocab)`` logits tensor into one
token per row with DEVICE-ARRAY parameters (temperature / top_k /
top_p / key per slot), so one executable serves any traffic mix.  Its
XLA composition pays a *tail of separate full-vocab passes every decode
step*: an O(V·logV) sort (the top-k threshold), a softmax, a cumsum
(the nucleus mass), the masking passes, and the categorical draw's
Gumbel pass — each materializing ``(slots, vocab)`` intermediates in
HBM.  This is exactly the softmax+sampling normalization pattern of
"LLM Inference Acceleration via Efficient Operation Fusion"
(PAPERS.md, arxiv 2502.17728): none of those intermediates is ever
needed again, so the whole tail folds into one kernel that reads the
logits ONCE.

:func:`fused_sample` is that tail under the
:mod:`apex_tpu.ops._dispatch` conventions:

- **Pallas TPU kernel** (``implementation="pallas"``): grid over
  row blocks, each step holding its rows' full vocab in VMEM (ONE HBM
  read of the logits — everything after is on-chip).  Per row:
  temperature scale; the top-k threshold by **bit-sliced radix
  selection** over the order-preserving uint32 transform of the scaled
  logits (32 predicated count-reductions — *no full-vocab sort*, and
  the k-th largest VALUE is exact, it is selection not arithmetic);
  the nucleus cut by the same bit descent over the value axis of the
  unnormalized mass curve (``G(t) = Σ exp(x−m)·[x > t]`` against
  ``top_p·Z`` — the online-softmax statistics ``m``/``Z`` accumulate
  across vocab tiles exactly like the log2-domain machinery of
  :mod:`~apex_tpu.ops.paged_attention`); and a **Gumbel-max draw whose
  noise replays jax's threefry-2x32 bit-for-bit** (counter-mode over
  vocab positions, the same 20-round block cipher
  ``jax.random.categorical`` evaluates), so the winning index is the
  token ``sample_dynamic`` would have drawn with the same key.
- **XLA reference** (``implementation="xla"``; golden semantics,
  CPU/GPU fallback): the engines' historical sort-based composition,
  verbatim — plus a ``lax.cond`` short-circuit that skips the whole
  sort + softmax + cumsum tail at runtime when NO row enables top-k or
  top-p (all-greedy and plain-temperature steps previously paid the
  sort anyway; the skipped branch is bitwise equivalent on that
  predicate, see :func:`fused_sample_reference`).

Parity contract (the serving acceptance bar):

- greedy rows (``temperature <= 0``) are fp32 argmax — token-identical
  to ``generate()``'s static ``sample_logits`` path;
- sampled rows are **key-for-key identical to ``sample_dynamic``**:
  the top-k threshold is the exact k-th largest (selection), the
  Gumbel field is bit-identical (threefry replay), and argmax
  tie-breaking is first-index in both.  The one caveat: the nucleus
  *boundary* compares a sum of exponentials against ``top_p·Z``, and
  the kernel accumulates that sum in vocab-tile order while the
  reference cumsums in sorted order — a token flips only when the
  boundary lands within float-rounding of the mass target AND the
  straddling token is the one drawn (measure-zero on real logits; the
  same ULP class as cross-backend transcendentals).  On one backend,
  kernel-vs-reference tests assert exact token equality across the
  whole parameter grid.

**Width axis**: the speculative-decoding verify step samples ``1 + K``
positions per row in one executable — ``logits`` may be ``(rows,
width, vocab)`` with per-position ``keys`` ``(rows, width, 2)`` and
per-ROW sampling params; the op flattens width into the row grid (the
previous spec path looped ``width`` separate sorted passes).

The **vocab tile** (``block_v``) is the tunable: the kernel's
reduction passes sweep the VMEM-resident row in ``block_v``-wide
chunks (VPU granularity / temporary pressure).  Sweep it offline with
:func:`apex_tpu.ops.autotune.tune_fused_sampling` — the cache entry is
keyed on ``(vocab, width)`` and the serving engines pick the winner up
by default, the same adoption discipline as the paged-attention block
size.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import resolve_impl

__all__ = ["fused_sample", "fused_sample_reference",
           "pallas_envelope_ok", "sampling_cost_bytes"]

_NEG_INF = np.float32(-1e30)
#: smallest positive normal fp32 — jax.random.gumbel's uniform floor
_TINY = np.float32(np.finfo(np.float32).tiny)
#: threefry-2x32 round rotations (Salmon et al.; jax.random's cipher)
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
#: rows per kernel grid step (fp32 sublane height)
_BLOCK_ROWS = 8
#: the TPU compiler's scoped fast-memory limit for one kernel (the
#: v5e default; the kernel asks for no more)
_VMEM_LIMIT = 16 * 1024 * 1024


def pallas_envelope_ok(rows: int, vocab: int, dtype,
                       block_v: int) -> bool:
    """Whether the kernel's support envelope admits this geometry: a
    128-aligned vocab (lane alignment), a tile that divides it, and
    the kernel's fast memory inside the compiler's scoped limit.  Per
    row block that is the double-buffered logits block, the two fp32
    scratch rows, the two whole-row uint32 order images the compiler
    hoists out of the radix descents, and about three tile-wide fp32
    temporaries — a bound fitted to what the v5e compiler accepted and
    refused (bf16 fits to V=65536; V=131072 does not at any tile;
    ``tests/test_chip_compile.py`` pins the edge).  THE gate behind
    ``"auto"`` dispatch, and the check :func:`~apex_tpu.ops.autotune.
    tune_fused_sampling` applies per candidate so an out-of-envelope
    sweep errors out instead of silently timing the XLA reference."""
    br = min(_BLOCK_ROWS, int(rows))
    need = (br * vocab * (2 * jnp.dtype(dtype).itemsize + 16)
            + 3 * br * block_v * 4)
    return (vocab % 128 == 0 and block_v >= 128
            and vocab % block_v == 0 and need <= _VMEM_LIMIT)


def sampling_cost_bytes(rows: int, vocab: int, dtype) -> int:
    """True HBM traffic of the ONE-PASS fused sampler: the logits read
    once, plus the per-row parameter/key reads and the token write.
    This is the cost estimate the Pallas kernel declares to XLA (so
    TPU cost analysis of a decode executable rolls up the kernel's
    real traffic, not zero) and the analytic model the
    ``decode_epilogue`` bench leg reports beside the measured A/B —
    one formula, two consumers, like ``kv_store_bytes_per_token``."""
    return (int(rows) * int(vocab) * jnp.dtype(dtype).itemsize
            + int(rows) * (8 + 4 + 4 + 4)     # key pair + t/k/p params
            + int(rows) * 4)                  # sampled tokens out


# --------------------------------------------------------------------- #
# XLA reference (golden semantics; CPU/GPU fallback)
# --------------------------------------------------------------------- #
def fused_sample_reference(logits, keys, temperature, top_k, top_p,
                           vocab_size: int):
    """Branchless per-row sampling with device-array parameters — the
    engines' historical ``sample_dynamic`` composition, verbatim.

    ``logits`` (rows, vocab); ``keys`` (rows, 2) uint32;
    ``temperature`` / ``top_k`` / ``top_p`` (rows,).  Per row: fp32
    argmax when ``temperature <= 0`` else top-k- and/or
    nucleus-truncated categorical at ``logits/temperature``
    (``top_k == 0`` and ``top_p <= 0`` / ``>= 1`` disable their
    filters — a disabled filter is an exact no-op, not an epsilon
    approximation).  The math mirrors ``generate``'s static
    :func:`~apex_tpu.models.generate.sample_logits` — kth-largest /
    nucleus threshold on the scaled logits, ``-1e30`` mask, top-k
    before top-p (the HF warper order) — but every parameter is
    traced, so one executable serves any mix.  The nucleus pass reuses
    the top-k sort (the post-mask order is the pre-mask order with the
    masked tail replaced), so mixed top-p traffic costs no second
    O(V·logV) sort.

    The sort + softmax + cumsum tail rides a ``lax.cond`` on *any row
    enabling a filter*: an all-greedy / plain-temperature step skips
    it at runtime entirely.  The skip is EXACT, not approximate — with
    every filter disabled the old masking passes were provable
    no-ops: ``top_k == 0`` gives ``kth = min(scaled)`` so
    ``scaled < kth`` is everywhere false, and ``p_on == False``
    bypasses the nucleus mask — so both branches compute bitwise the
    same tokens on the predicate that selects them.
    """
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / safe_t
    p_on = (top_p > 0.0) & (top_p < 1.0)                 # (rows,)
    any_filter = jnp.any((top_k > 0) | p_on)

    def _filtered(scaled):
        k = jnp.where(top_k > 0, top_k, vocab_size)      # (rows,)
        ordered = jnp.sort(scaled, axis=-1)              # ascending
        kth = jnp.take_along_axis(
            ordered, (vocab_size - k)[:, None], axis=-1)  # k-th largest
        masked = jnp.where(scaled < kth, _NEG_INF, scaled)
        # nucleus filter over the top-k-masked distribution, sort
        # reused: descending masked order = reversed `ordered` with
        # the SAME `< kth` criterion applied that masked `scaled` —
        # value-based, not position-based, so k-th-boundary ties
        # survive in both or neither (keeps engine/generate parity in
        # tie cases)
        rev = ordered[:, ::-1]
        desc = jnp.where(rev < kth, _NEG_INF, rev)
        # fp32 by construction (scaled is the fp32 cast's quotient);
        # the astype is a bitwise no-op that re-anchors the dtype for
        # the nested-closure scope
        probs = jax.nn.softmax(desc.astype(jnp.float32), axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < jnp.where(p_on, top_p, 1.0)[:, None]
        thresh = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                         keepdims=True)
        return jnp.where(p_on[:, None] & (masked < thresh), _NEG_INF,
                         masked)

    masked = jax.lax.cond(any_filter, _filtered, lambda s: s, scaled)
    sampled = jax.vmap(jax.random.categorical)(keys, masked)
    sampled = sampled.astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


# --------------------------------------------------------------------- #
# Pallas TPU kernel
# --------------------------------------------------------------------- #
def _threefry2x32(k0, k1, c0, c1):
    """The threefry-2x32 block cipher (20 rounds), elementwise over
    uint32 counter arrays — the exact cipher behind jax's default PRNG,
    replayed in-kernel so the Gumbel field matches
    ``jax.random.categorical`` bit-for-bit."""
    ks2 = k0 ^ k1 ^ jnp.uint32(0x1BD11BDA)
    x0, x1 = c0 + k0, c1 + k1
    ks = (k0, k1, ks2)
    for i in range(5):
        for d in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = (x1 << jnp.uint32(d)) | (x1 >> jnp.uint32(32 - d))
            x1 = x0 ^ x1
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


def _gumbel(k0, k1, pos):
    """``jax.random.gumbel(key, (V,))[pos]`` for the raw key pair
    ``(k0, k1)``, bit for bit.  Counter layout: jax's partitionable
    threefry (``jax_threefry_partitionable``, the installed default) —
    position ``j`` is the 64-bit counter ``(hi=0, lo=j)`` and its 32
    random bits are the XOR of the cipher's two output lanes."""
    r0, r1 = _threefry2x32(k0, k1, jnp.zeros_like(pos), pos)
    fb = ((r0 ^ r1) >> jnp.uint32(9)) | jnp.uint32(0x3F800000)
    floats = jax.lax.bitcast_convert_type(fb, jnp.float32) - 1.0
    u = jnp.maximum(_TINY, floats * (jnp.float32(1.0) - _TINY) + _TINY)
    return -jnp.log(-jnp.log(u))


def _mono_u32(x):
    """Order-preserving uint32 image of fp32: flip the sign bit of
    non-negatives, invert negatives — ``a < b  ⇔  mono(a) < mono(b)``.
    Radix selection over this image finds exact order statistics with
    compare-and-count passes only (no sort, no arithmetic on values,
    hence no rounding)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where((u >> jnp.uint32(31)) == 0,
                     u | jnp.uint32(0x80000000), ~u)


def _unmono_f32(u):
    b = jnp.where((u >> jnp.uint32(31)) != 0,
                  u & jnp.uint32(0x7FFFFFFF), ~u)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _chunks(vocab: int, block_v: int):
    return [(c, block_v) for c in range(0, vocab, block_v)]


def _sampling_kernel(x_ref, keys_ref, temp_ref, topk_ref, topp_ref,
                     out_ref, scaled_ref, e_ref, *, vocab: int,
                     block_v: int):
    """One row-block of the fused sampler.  The row's vocab sits in
    VMEM (``x_ref`` — its one HBM read); every pass below sweeps it in
    ``block_v``-wide tiles.  Scratch: ``scaled_ref`` (the fp32
    temperature-scaled row, materialized once) and ``e_ref`` (the
    unnormalized softmax terms the nucleus bit-descent re-reads 32×).

    Pass structure per block of rows:

    1. scale + online max/first-argmax sweep (the greedy token and the
       softmax ``m`` statistic — max is selection, so ``m`` is bitwise
       the reference's);
    2. top-k: 32-bit radix descent, each step one predicated
       count-reduction over the tiles — yields the EXACT k-th largest;
    3. ``e = exp(masked − m)`` materialization + ``Z`` (the online-
       softmax denominator, accumulated across tiles);
    4. nucleus: radix descent over the value axis of
       ``G(t) = Σ e·[x > t]`` against ``top_p·Z`` — the value-space
       twin of the reference's sorted cumsum cut;
    5. Gumbel-max: threefry counter replay over vocab positions, add,
       online first-argmax — the categorical draw.
    """
    br = x_ref.shape[0]
    temp = temp_ref[:]                                   # (br, 1)
    safe_t = jnp.maximum(temp.astype(jnp.float32), 1e-6)
    k = jnp.where(topk_ref[:, 0] > 0, topk_ref[:, 0], vocab)
    topp = topp_ref[:, 0].astype(jnp.float32)
    p_on = (topp > 0.0) & (topp < 1.0)

    # ---- pass 1: scale into scratch; online max + first-argmax.
    # The greedy argmax runs on the RAW fp32 logits, like the
    # reference: IEEE division is monotone but NOT injective — a
    # greedy row's /1e-6 scaling can collide two adjacent logits into
    # one value and flip the winner to the earlier index.  The
    # softmax statistic m tracks the SCALED max (the value the masked
    # row actually attains).
    m_run = jnp.full((br, 1), -jnp.inf, jnp.float32)
    g_run = jnp.full((br, 1), -jnp.inf, jnp.float32)
    i_run = jnp.full((br, 1), vocab, jnp.int32)
    for off, width in _chunks(vocab, block_v):
        xr = x_ref[:, off:off + width].astype(jnp.float32)
        xs = xr / safe_t
        scaled_ref[:, off:off + width] = xs
        m_run = jnp.maximum(m_run,
                            jnp.max(xs, axis=-1, keepdims=True))
        cmax = jnp.max(xr, axis=-1, keepdims=True)
        idx = jax.lax.broadcasted_iota(jnp.int32, (br, width), 1) + off
        cidx = jnp.min(jnp.where(xr == cmax, idx, vocab), axis=-1,
                       keepdims=True)
        # strictly-greater update keeps the earlier tile on ties —
        # whole-row first-argmax semantics, tile by tile
        take = cmax > g_run
        i_run = jnp.where(take, cidx, i_run)
        g_run = jnp.maximum(g_run, cmax)
    greedy = i_run[:, 0]
    m = m_run                                            # (br, 1) fp32

    # ---- pass 2: exact k-th largest by bit-sliced radix descent over
    # the order-preserving uint32 image (selection, not arithmetic —
    # the threshold VALUE is bitwise the sorted reference's).
    def _count_ge(cand):
        cnt = jnp.zeros((br,), jnp.int32)
        for off, width in _chunks(vocab, block_v):
            mu = _mono_u32(scaled_ref[:, off:off + width])
            cnt = cnt + jnp.sum((mu >= cand[:, None]).astype(jnp.int32),
                                axis=-1)
        return cnt

    def _kth_body(i, acc):
        cand = acc | (jnp.uint32(1) << (jnp.uint32(31)
                                        - i.astype(jnp.uint32)))
        return jnp.where(_count_ge(cand) >= k, cand, acc)

    kth_bits = jax.lax.fori_loop(0, 32, _kth_body,
                                 jnp.zeros((br,), jnp.uint32))
    kth = _unmono_f32(kth_bits)[:, None]                 # (br, 1)

    # ---- pass 3: e = exp(masked - m) into scratch, Z accumulated
    # tile-by-tile (masked tail exp-underflows to exact 0, as in the
    # reference's softmax over the -1e30 tail)
    z = jnp.zeros((br, 1), jnp.float32)
    for off, width in _chunks(vocab, block_v):
        xs = scaled_ref[:, off:off + width]
        es = jnp.exp(jnp.where(xs < kth, _NEG_INF, xs) - m)
        e_ref[:, off:off + width] = es
        z = z + jnp.sum(es, axis=-1, keepdims=True)
    mass_cut = jnp.where(p_on, topp, 1.0) * z[:, 0]      # top_p · Z

    # ---- pass 4: nucleus boundary B = the largest value (uint32
    # image) whose STRICTLY-GREATER mass still reaches the target —
    # everything at or below B is outside the nucleus.  Value-space
    # bit descent again; the mass sums re-read e from scratch.
    def _mass_gt(cand):
        g = jnp.zeros((br,), jnp.float32)
        for off, width in _chunks(vocab, block_v):
            xs = scaled_ref[:, off:off + width]
            mu = _mono_u32(jnp.where(xs < kth, _NEG_INF, xs))
            g = g + jnp.sum(
                jnp.where(mu > cand[:, None],
                          e_ref[:, off:off + width], 0.0), axis=-1)
        return g

    def _p_body(i, acc):
        cand = acc | (jnp.uint32(1) << (jnp.uint32(31)
                                        - i.astype(jnp.uint32)))
        return jnp.where(_mass_gt(cand) >= mass_cut, cand, acc)

    p_bits = jax.lax.fori_loop(0, 32, _p_body,
                               jnp.zeros((br,), jnp.uint32))

    # ---- pass 5: Gumbel-max categorical over vocab positions
    k0, k1 = keys_ref[:, 0:1], keys_ref[:, 1:2]
    s_run = jnp.full((br, 1), -jnp.inf, jnp.float32)
    si_run = jnp.full((br, 1), vocab, jnp.int32)
    for off, width in _chunks(vocab, block_v):
        pos = jax.lax.broadcasted_iota(
            jnp.uint32, (br, width), 1) + jnp.uint32(off)
        gum = _gumbel(k0, k1, pos)
        xs = scaled_ref[:, off:off + width]
        masked = jnp.where(xs < kth, _NEG_INF, xs)
        mu = _mono_u32(masked)
        masked = jnp.where(p_on[:, None] & (mu <= p_bits[:, None]),
                           _NEG_INF, masked)
        tot = masked + gum
        cmax = jnp.max(tot, axis=-1, keepdims=True)
        idx = jax.lax.broadcasted_iota(jnp.int32, (br, width), 1) + off
        cidx = jnp.min(jnp.where(tot == cmax, idx, vocab), axis=-1,
                       keepdims=True)
        take = cmax > s_run
        si_run = jnp.where(take, cidx, si_run)
        s_run = jnp.maximum(s_run, cmax)

    out_ref[:] = jnp.where(temp[:, 0] > 0.0, si_run[:, 0],
                           greedy)[:, None].astype(jnp.int32)


def _run_fused(logits, keys, temperature, top_k, top_p, vocab: int,
               block_v: int, interpret: bool):
    rows = logits.shape[0]
    br = min(_BLOCK_ROWS, rows)
    nrb = -(-rows // br)
    pad = nrb * br - rows
    if pad:
        # pad rows compute garbage greedily (temp 0) and are sliced off
        logits = jnp.pad(logits, ((0, pad), (0, 0)))
        keys = jnp.pad(keys, ((0, pad), (0, 0)))
        temperature = jnp.pad(temperature, (0, pad))
        top_k = jnp.pad(top_k, (0, pad))
        top_p = jnp.pad(top_p, (0, pad))
    kernel = functools.partial(_sampling_kernel, vocab=vocab,
                               block_v=block_v)
    # declare the kernel's TRUE traffic: the one-shot logits read +
    # params + tokens (sampling_cost_bytes, the number the
    # decode_epilogue bench models) — without it XLA scores the custom
    # call as free and the executable's cost analysis undercounts
    cost = pl.CostEstimate(
        flops=98 * nrb * br * vocab,               # threefry dominates
        bytes_accessed=sampling_cost_bytes(nrb * br, vocab,
                                           logits.dtype),
        transcendentals=3 * nrb * br * vocab)      # exp + 2 logs
    with jax.named_scope("fused_sample"):
        out = pl.pallas_call(
            kernel,
            grid=(nrb,),
            in_specs=[
                pl.BlockSpec((br, vocab), lambda i: (i, 0)),
                pl.BlockSpec((br, 2), lambda i: (i, 0)),
                pl.BlockSpec((br, 1), lambda i: (i, 0)),
                pl.BlockSpec((br, 1), lambda i: (i, 0)),
                pl.BlockSpec((br, 1), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((br, 1), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((nrb * br, 1), jnp.int32),
            scratch_shapes=[
                # fp32 scaled row + softmax terms, re-swept by the radix
                # descents at VMEM speed (the HBM read happened once)
                pltpu.VMEM((br, vocab), jnp.float32),
                pltpu.VMEM((br, vocab), jnp.float32),
            ],
            interpret=interpret,
            cost_estimate=cost,
        )(logits, keys.astype(jnp.uint32), temperature[:, None],
          top_k[:, None], top_p[:, None])
    return out[:rows, 0]


# --------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------- #
def fused_sample(logits, keys, temperature, top_k, top_p, *,
                 vocab_size: Optional[int] = None,
                 implementation: Optional[str] = None,
                 block_v: int = 0):
    """Sample one token per row from ``logits`` in a single pass.

    ``logits``: ``(rows, vocab)`` — or ``(rows, width, vocab)`` for a
    multi-position step (the speculative verify's ``1 + K`` draws per
    row), in which case ``keys`` carries the matching leading dims and
    the per-ROW params broadcast over width.  ``keys`` ``(…, 2)``
    uint32 (the raw threefry key pair each row consumes —
    ``jax.random.split`` products, as the serving engines hand them);
    ``temperature`` / ``top_k`` / ``top_p``: ``(rows,)`` device
    arrays, per-row semantics as in :func:`fused_sample_reference`.

    ``implementation`` follows :mod:`apex_tpu.ops._dispatch`:
    ``"auto"`` takes the Pallas kernel on TPU when the geometry fits
    its envelope (:func:`pallas_envelope_ok`) and the XLA reference
    elsewhere; asking for the kernel outside the envelope raises.
    ``block_v`` is the vocab tile (0 = the autotuned winner for
    ``(vocab, width)`` when one is cached, else the whole row).
    Returns ``(rows,)`` — or ``(rows, width)`` — int32 tokens,
    token-identical to the reference per the module parity contract.
    """
    width = None
    if logits.ndim == 3:
        rows, width, vocab = logits.shape
        if keys.shape != (rows, width, 2):
            raise ValueError(
                f"keys shape {keys.shape} != (rows, width, 2) = "
                f"{(rows, width, 2)}")
        logits = logits.reshape(rows * width, vocab)
        keys = keys.reshape(rows * width, 2)
        temperature = jnp.repeat(temperature, width)
        top_k = jnp.repeat(top_k, width)
        top_p = jnp.repeat(top_p, width)
    elif logits.ndim == 2:
        rows, vocab = logits.shape
        if keys.shape != (rows, 2):
            raise ValueError(
                f"keys shape {keys.shape} != (rows, 2) = {(rows, 2)}")
    else:
        raise ValueError(
            f"logits must be (rows, vocab) or (rows, width, vocab), "
            f"got {logits.shape}")
    if vocab_size is not None and int(vocab_size) != vocab:
        raise ValueError(
            f"vocab_size ({vocab_size}) != logits vocab axis ({vocab})")
    for name, arr in (("temperature", temperature), ("top_k", top_k),
                      ("top_p", top_p)):
        if arr.shape != (logits.shape[0],):
            raise ValueError(
                f"{name} shape {arr.shape} != (rows,) = "
                f"{(logits.shape[0],)}")
    if block_v == 0:
        from apex_tpu.ops import autotune
        block_v = autotune.cached_sampling_tile(
            vocab, width or 1) or vocab
    # the kernel replays the partitionable threefry layout only
    pallas_ok = (pallas_envelope_ok(logits.shape[0], vocab,
                                    logits.dtype, block_v)
                 and jax.config.jax_threefry_partitionable)
    impl = resolve_impl(implementation, pallas_ok=pallas_ok,
                        op="fused_sample")
    if impl == "xla":
        out = fused_sample_reference(logits, keys, temperature, top_k,
                                     top_p, vocab)
    else:
        out = _run_fused(logits, keys, temperature, top_k, top_p,
                         vocab, int(block_v),
                         impl == "pallas_interpret")
    if width is not None:
        return out.reshape(rows, width)
    return out
