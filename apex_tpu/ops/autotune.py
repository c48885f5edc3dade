"""Sweep-and-cache block-size autotuner for the row-wise Pallas kernels.

The reference's FastLayerNorm ships hand-written template
specializations per hidden size (``csrc/layer_norm/`` instantiates a
kernel per {768, 1024, 2048, ...}).  The TPU analogue: the block-rows
parameter of the row-wise kernels (LN/RMSNorm/softmax) defaults to a
VMEM-budget heuristic (:func:`apex_tpu.ops._dispatch.pick_block_rows`),
and this module can *measure* the best value per (backend, width,
dtype) and cache it — the measured table then takes precedence over
the heuristic.

Usage (offline, on the target chip)::

    python -m apex_tpu.ops.autotune --widths 1024 4096 --rows 8192

or programmatically::

    from apex_tpu.ops import autotune
    autotune.tune_layer_norm(n_rows=8192, width=1024)

The cache persists to ``APEX_TPU_AUTOTUNE_CACHE`` (default
``<checkout>/.autotune.json``, beside the compile cache) keyed by
backend+device kind, so one sweep serves all subsequent processes on
the same hardware.

**Measure end-to-end before trusting a sweep.**  Isolated-kernel
winners can lose inside a full training step (measured on v5e:
micro-bench-optimal LN blocks of 32–64 rows cost ~1% of BERT-Large
step time vs the VMEM-budget heuristic, because XLA overlaps the
row-wise kernels differently in context) — the same lesson as
attention-tile sweeps (BASELINE.md round-1 notes).  Tune, run your
real step, and delete the cache entry if it regresses.
"""

from __future__ import annotations

import json
import logging
import os
import pathlib
import time
from typing import Dict, Iterable, Optional

_logger = logging.getLogger(__name__)

__all__ = ["cached_block_rows", "cached_paged_pair",
           "cached_sampling_tile", "tune_layer_norm",
           "tune_softmax", "tune_batch_norm", "tune_paged_attention",
           "tune_fused_sampling", "clear_cache"]

_CACHE: Optional[Dict[str, int]] = None


def _cache_path() -> pathlib.Path:
    from apex_tpu.utils.compile_cache import CHECKOUT

    return pathlib.Path(os.environ.get(
        "APEX_TPU_AUTOTUNE_CACHE", CHECKOUT / ".autotune.json"))


def _device_key() -> str:
    import jax

    dev = jax.devices()[0]
    return f"{jax.default_backend()}:{getattr(dev, 'device_kind', '?')}"


def _load() -> Dict[str, int]:
    global _CACHE
    if _CACHE is None:
        try:
            _CACHE = json.loads(_cache_path().read_text())
        except (OSError, ValueError):
            _CACHE = {}
    return _CACHE


def _store(key: str, value: int) -> None:
    cache = _load()
    cache[key] = value
    path = _cache_path()
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(cache, indent=2, sort_keys=True))
    except OSError:
        pass  # read-only FS: keep the in-memory entry


def _key(op: str, width: int, dtype, kv_heads=None,
         sample_w=None) -> str:
    """Cache key.  ``kv_heads`` (paged_attention only) qualifies the
    entry with the PER-SHARD kv-head count the sweep ran at: a
    tensor-parallel serving engine gathers ``kv_heads / tp`` heads'
    pages per chip, so its measured-best page size is a different
    quantity than the full-head-count winner — the two must never
    alias (ISSUE 13 satellite).  ``sample_w`` (fused_sampling only)
    qualifies the entry with the SAMPLE WIDTH the sweep ran at: the
    decode step samples one position per row, the speculative verify
    step ``1 + K`` — different row counts through the same vocab, so
    their measured-best vocab tiles must never alias either (the same
    per-key discipline, ISSUE 14 satellite)."""
    base = f"{_device_key()}/{op}/w{width}/{dtype}"
    if kv_heads is not None:
        base += f"/kvh{int(kv_heads)}"
    if sample_w is not None:
        base += f"/sw{int(sample_w)}"
    return base


def cached_block_rows(op: str, width: int, dtype,
                      kv_heads: Optional[int] = None) -> Optional[int]:
    """Measured best block-rows for ``op`` at ``width``, or None if
    this (device, op, width, dtype[, kv_heads]) was never tuned.
    ``kv_heads`` applies to the paged-attention entries only (the
    per-shard head count — see :func:`_key`); the row-wise ops ignore
    it."""
    return _load().get(_key(op, width, dtype, kv_heads=kv_heads))


def cached_paged_pair(width: int, dtype,
                      kv_heads: Optional[int] = None) -> Optional[tuple]:
    """Measured best ``(block_size, kv_dtype)`` pair for the paged
    decode step at head_dim ``width``, COMPUTE dtype ``dtype`` and
    (per-shard) ``kv_heads`` (``kv_dtype`` is ``None`` when the
    unquantized pool won), or None if :func:`tune_paged_attention`
    never ran its joint sweep here.
    ``PagedEngine(block_size=0, kv_dtype="auto")`` adopts this pair,
    querying with its own shard's head count."""
    val = _load().get(_key("paged_attention_pair", width, dtype,
                           kv_heads=kv_heads))
    if val is None:
        return None
    bs, kvd = val
    return int(bs), (None if kvd in (None, "none") else str(kvd))


def cached_sampling_tile(vocab: int, width: int) -> Optional[int]:
    """Measured best vocab tile for the fused sampling kernel at
    ``(vocab, width)``, or None if :func:`tune_fused_sampling` never
    ran here.  ``width`` is the SAMPLE width (1 for the decode step,
    ``1 + spec_tokens`` for the speculative verify step — separate
    entries, like the paged per-shard keys).  The key dtype is pinned
    ``float32``: the kernel's working set is its fp32 scratch
    regardless of the logits dtype (the ``tune_batch_norm``
    precedent)."""
    return _load().get(_key("fused_sampling", int(vocab), "float32",
                            sample_w=int(width)))


def clear_cache() -> None:
    """Drop the in-memory cache (tests; the file is left alone)."""
    global _CACHE
    _CACHE = None


def _time_call(fn, *args, iters: int = 10, warmup: int = 2) -> float:
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _best_candidate(build_fn, candidates: Iterable[int],
                    n_rows: Optional[int] = None) -> tuple:
    """Time ``build_fn(c)`` over the candidates (multiples of 8 only,
    ``c <= n_rows`` when given; a candidate the compiler refuses — a
    block too large for fast memory is the usual one — is skipped and
    logged) and return ``(winner, seconds)`` — ``(None, inf)`` when
    nothing measured."""
    best, best_dt = None, float("inf")
    for c in candidates:
        if c % 8 or (n_rows is not None and c > n_rows):
            continue
        try:
            fn, args = build_fn(c)
            dt = _time_call(fn, *args)
        except Exception as e:
            _logger.warning("autotune: candidate %d skipped: %s", c,
                            str(e).splitlines()[0] if str(e) else e)
            continue
        if dt < best_dt:
            best, best_dt = c, dt
    return best, best_dt


def _tune(op: str, build_fn, n_rows: int, width: int, dtype,
          candidates: Iterable[int]) -> int:
    """Time ``build_fn(block_rows)`` over the candidates, cache and
    return the winner."""
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    best, _ = _best_candidate(build_fn, candidates, n_rows=n_rows)
    if best is not None:
        _store(_key(op, width, str(dtype)), best)
    return best


_DEFAULT_CANDIDATES = (8, 16, 32, 64, 128, 256, 512, 1024)


def tune_layer_norm(n_rows: int = 8192, width: int = 1024,
                    dtype="bfloat16",
                    candidates: Iterable[int] = _DEFAULT_CANDIDATES) -> int:
    """Sweep block-rows for the fused LN forward at (n_rows, width)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import layer_norm as _ln

    x = jax.random.normal(jax.random.PRNGKey(0), (n_rows, width),
                          jnp.dtype(dtype))
    w2 = jnp.ones((1, width), jnp.float32)
    b2 = jnp.zeros((1, width), jnp.float32)

    def build(br):
        fn = jax.jit(lambda x: _ln._run_ln_fwd(
            x, w2, b2, 1e-5, False, False, block_rows=br)[0])
        return fn, (x,)

    return _tune("layer_norm", build, n_rows, width, str(jnp.dtype(dtype)),
                 candidates)


def tune_softmax(n_rows: int = 8192, width: int = 512,
                 dtype="bfloat16",
                 candidates: Iterable[int] = _DEFAULT_CANDIDATES) -> int:
    """Sweep block-rows for the fused scale-mask-softmax."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import softmax as _sm

    x = jax.random.normal(jax.random.PRNGKey(0), (n_rows, width),
                          jnp.dtype(dtype))

    def build(br):
        fn = jax.jit(lambda x: _sm._run_softmax_fwd(
            x, None, 1.0, False, n_rows, width, False, block_rows=br))
        return fn, (x,)

    return _tune("softmax", build, n_rows, width, str(jnp.dtype(dtype)),
                 candidates)


def tune_batch_norm(n_rows: int = 65536, width: int = 256,
                    dtype="bfloat16",
                    candidates: Iterable[int] = _DEFAULT_CANDIDATES) -> int:
    """Sweep block-rows for the fused BatchNorm forward (reduce +
    apply) at (n_rows, width).  The cache key is fp32 — the kernels'
    VMEM blocks are sized by the fp32 compute copy regardless of the
    activation dtype (see ``batch_norm._pick_rows``)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import batch_norm as _bn

    x = jax.random.normal(jax.random.PRNGKey(0), (n_rows, width),
                          jnp.dtype(dtype))
    w2 = jnp.ones((1, width), jnp.float32)
    b2 = jnp.zeros((1, width), jnp.float32)

    def build(br):
        if n_rows % br:
            raise ValueError("block must divide rows")
        spec = _bn._Spec(eps=1e-5, act="relu", axes=(),
                         impl="pallas", br=br, interpret=False,
                         has_res=False)
        fn = jax.jit(lambda x: _bn._fwd_compute(spec, x, w2, b2,
                                                None)[0])
        return fn, (x,)

    return _tune("batch_norm", build, n_rows, width, "float32",
                 candidates)


def tune_paged_attention(n_rows: int = 8, width: int = 128,
                         dtype="bfloat16", kv_heads: int = 8,
                         live_tokens: int = 1024,
                         candidates: Iterable[int] = (8, 16, 32, 64,
                                                      128),
                         kv_dtypes: Optional[Iterable] = None) -> tuple:
    """Jointly sweep the paged KV-cache **page size** (tokens per
    block) and **pool storage dtype** for the decode step at
    (batch=``n_rows``, head_dim=``width``).

    Unlike the row-wise sweeps the tunables here are cache *layout*
    parameters: small pages waste less pool on the last partial page
    per sequence but issue more (and smaller) gather DMAs per step;
    large pages amortize the DMA at the cost of internal
    fragmentation; and a quantized pool (``kv_dtype="int8"`` /
    ``"fp8"``, ISSUE 8) halves-to-quarters the bytes each gather moves
    at the cost of the in-kernel dequant multiply — on an HBM-bound
    decode step the 1-byte pages usually win outright, and the best
    page size can shift with the storage width (the DMA payload per
    page shrinks).  The pool is sized to the sweep (``n_rows`` rows at
    ``live_tokens`` live, shuffled physical placement), so any
    rows/width combination measures.

    ``kv_dtypes`` defaults to every storage the build supports:
    ``(None, "int8")`` plus ``"fp8"`` where ``jnp.float8_e4m3fn``
    exists.  Two kinds of cache entries are written:

    - per-STORAGE-dtype block-size winners under the engine's
      ``block_size=0`` lookup key (device, "paged_attention",
      head_dim, storage dtype, **kv_heads**) — ``kv_dtype=None`` keys
      the compute dtype, and the kv-head count qualifies every entry
      so a tensor-parallel engine (which sweeps and serves at its
      per-shard ``kv_heads / tp``) never adopts a winner measured at
      full head count;
    - the joint ``(block_size, kv_dtype)`` winner under
      "paged_attention_pair" keyed on the COMPUTE dtype (+ kv_heads),
      which ``PagedEngine(block_size=0, kv_dtype="auto")`` adopts via
      :func:`cached_paged_pair`.

    A TP deployment therefore sweeps with ``kv_heads`` set to the
    model's ``kv_heads // tp`` (what one chip actually serves).

    Returns the joint winner as ``(block_size, kv_dtype)``.  From the
    CLI pass the model's head_dim as ``--widths`` (NOT the hidden
    size), the serving batch as ``--rows``, and the PER-SHARD kv-head
    count as ``--kv-heads`` (``kv_heads // tp`` for a TP deployment —
    the engine looks the winner up under that count)::

        python -m apex_tpu.ops.autotune --ops paged_attention \\
            --widths 128 --rows 16 --kv-heads 4
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops.paged_attention import (
        kv_quant_spec,
        paged_attention as _paged,
        quantize_kv_pages,
    )

    # n_rows arrives from the shared --rows CLI flag whose row-wise
    # default (8192) means activation rows; a decode BATCH that size
    # is meaningless and would OOM the pool — clamp to serving scale
    n_rows = max(1, min(int(n_rows), 256))
    dt = jnp.dtype(dtype)
    if kv_dtypes is None:
        kv_dtypes = [None, "int8"]
        try:
            kv_quant_spec("fp8")
            kv_dtypes.append("fp8")
        except ValueError:
            pass           # no float8_e4m3fn in this jax build
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(n_rows, 1, kv_heads, width)), dt)

    def build(bs, kvd):
        mb = -(-live_tokens // bs)
        nb = n_rows * mb + 1           # pool sized to the sweep
        kp = jnp.asarray(
            rng.normal(size=(kv_heads, nb, bs, width)), dt)
        vp = jnp.asarray(
            rng.normal(size=(kv_heads, nb, bs, width)), dt)
        if kvd is not None:
            kp, vp, ks, vs = quantize_kv_pages(kp, vp, kvd)
        free = np.arange(1, nb, dtype=np.int32)
        rng.shuffle(free)
        tables = free[: n_rows * mb].reshape(n_rows, mb).copy()
        lengths = jnp.full((n_rows,), live_tokens - 1, jnp.int32)
        if kvd is None:
            fn = jax.jit(lambda q: _paged(
                q, kp, vp, jnp.asarray(tables), lengths))
        else:
            fn = jax.jit(lambda q: _paged(
                q, kp, vp, jnp.asarray(tables), lengths,
                k_scales=ks, v_scales=vs))
        return fn, (q,)

    best_pair, best_pair_dt = None, float("inf")
    for kvd in kv_dtypes:
        store_dt, _ = kv_quant_spec(kvd)
        key_dt = str(dt) if store_dt is None else str(jnp.dtype(store_dt))
        best_bs, best_dt_s = _best_candidate(
            lambda bs, kvd=kvd: build(bs, kvd), candidates)
        if best_bs is None:
            continue
        # keyed on the swept kv-head count: a TP engine queries with
        # its PER-SHARD count (kv_heads / tp) and must only find an
        # entry swept at that count — sweep once per shard width
        _store(_key("paged_attention", width, key_dt,
                    kv_heads=kv_heads), best_bs)
        if best_dt_s < best_pair_dt:
            best_pair, best_pair_dt = (best_bs, kvd), best_dt_s
    if best_pair is not None:
        _store(_key("paged_attention_pair", width, str(dt),
                    kv_heads=kv_heads),
               [best_pair[0], best_pair[1] or "none"])
    return best_pair


def tune_fused_sampling(n_rows: int = 16, width: int = 32768,
                        dtype="float32", sample_width: int = 1,
                        candidates: Optional[Iterable[int]] = None,
                        implementation: str = "pallas") -> Optional[int]:
    """Sweep the fused sampling kernel's **vocab tile** at
    ``(vocab=width, sample_width)``.

    The tile sets the chunk the kernel's reduction passes sweep the
    VMEM-resident row in (VPU granularity vs temporary pressure —
    the radix descents re-read the row 64×, so the tile is the hot
    loop's register-blocking knob).  ``width`` is the VOCAB here
    (the shared ``--widths`` CLI flag names the row width of every
    sweep); ``n_rows`` the decode batch (slots × sample width rows
    reach the kernel); ``sample_width`` the per-row positions (1 =
    decode step, ``1 + spec_tokens`` = the speculative verify step —
    a SEPARATE cache entry, the per-key discipline of the paged
    sweeps).  Candidates default to the 128-aligned divisors of the
    vocab up to 8192 plus the whole row; non-divisors are skipped.

    The winner lands under the key
    :func:`cached_sampling_tile` reads and the serving engines adopt
    via ``fused_sample(block_v=0)``.  ``implementation`` defaults to
    the compiled kernel (sweeping anything else measures the wrong
    artifact); tests exercise the cache mechanics with
    ``"pallas_interpret"``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops.fused_sampling import (
        fused_sample,
        pallas_envelope_ok,
    )

    n_rows = max(1, min(int(n_rows), 256))
    vocab = int(width)
    if candidates is None:
        candidates = [c for c in (128, 256, 512, 1024, 2048, 4096,
                                  8192) if vocab % c == 0] + [vocab]
    rng = np.random.default_rng(0)
    shape = ((n_rows, vocab) if sample_width <= 1
             else (n_rows, sample_width, vocab))
    logits = jnp.asarray(rng.normal(size=shape), jnp.dtype(dtype))
    keys = jnp.asarray(
        rng.integers(0, 2**32, size=shape[:-1] + (2,), dtype=np.uint32))
    temp = jnp.full((n_rows,), 0.8, jnp.float32)
    topk = jnp.full((n_rows,), 40, jnp.int32)
    topp = jnp.full((n_rows,), 0.9, jnp.float32)

    rows_flat = n_rows * max(1, int(sample_width))

    def build(bv):
        if not pallas_envelope_ok(rows_flat, vocab, jnp.dtype(dtype),
                                  bv):
            # outside the kernel envelope fused_sample would silently
            # dispatch to the XLA reference — timing THAT would cache
            # a meaningless "measured" tile (the wrong-artifact trap
            # the docstring warns about); skip the candidate instead
            raise ValueError(
                f"vocab tile {bv} outside the kernel envelope at "
                f"vocab={vocab}")
        fn = jax.jit(lambda l: fused_sample(
            l, keys, temp, topk, topp, implementation=implementation,
            block_v=bv))
        return fn, (logits,)

    best, _ = _best_candidate(build, candidates)
    if best is not None:
        _store(_key("fused_sampling", vocab, "float32",
                    sample_w=int(sample_width)), best)
    return best


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--widths", type=int, nargs="+", default=[1024])
    p.add_argument("--rows", type=int, default=8192)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--kv-heads", type=int, default=8,
                   help="paged_attention only: the kv-head count the "
                        "sweep (and its cache keys) run at — for a "
                        "tensor-parallel deployment pass the model's "
                        "kv_heads // tp, what ONE chip serves")
    p.add_argument("--sample-width", type=int, default=1,
                   help="fused_sampling only: positions sampled per "
                        "row (1 = decode step, 1 + spec_tokens = the "
                        "speculative verify step) — each width is its "
                        "own cache entry; --widths is the VOCAB for "
                        "this op")
    p.add_argument("--ops", nargs="+", default=["layer_norm", "softmax"],
                   choices=["layer_norm", "softmax", "batch_norm",
                            "paged_attention", "fused_sampling"])
    args = p.parse_args(argv)
    for width in args.widths:
        for op in args.ops:
            tune = {"layer_norm": tune_layer_norm,
                    "softmax": tune_softmax,
                    "batch_norm": tune_batch_norm,
                    "paged_attention": tune_paged_attention,
                    "fused_sampling": tune_fused_sampling}[op]
            kw = ({"kv_heads": args.kv_heads}
                  if op == "paged_attention" else {})
            if op == "fused_sampling":
                kw = {"sample_width": args.sample_width}
            best = tune(n_rows=args.rows, width=width,
                        dtype=args.dtype, **kw)
            if op == "paged_attention":
                bs, kvd = best if best else (None, None)
                print(f"{op} w={width}: best block_size={bs} "
                      f"kv_dtype={kvd or 'none'} "
                      f"(cache: {_cache_path()})")
            elif op == "fused_sampling":
                print(f"{op} vocab={width} sw={args.sample_width}: "
                      f"best vocab tile={best} "
                      f"(cache: {_cache_path()})")
            else:
                print(f"{op} w={width}: best block_rows={best} "
                      f"(cache: {_cache_path()})")


if __name__ == "__main__":
    main()
