"""The unified compute/HBM/ICI cost model — ONE home for every
analytic formula the benches and the planner score with.

These functions grew up as bench-local models inside ``bench_configs.py``
(each beside the leg that measured it); the ISSUE-15 planner needs the
same arithmetic as *library* code, so they were lifted here verbatim and
``bench_configs`` imports them back — one implementation, two consumers,
zero drift (``tests/test_plan.py::TestCostModelDedup`` byte-compares the
emitted model blocks against the recorded bench rows).  They join the
two formulas that were already shared library code:

- :func:`apex_tpu.ops.paged_attention.kv_store_bytes_per_token` — pool
  bytes per cached token (the equal-HBM capacity formula), re-exported
  here;
- :func:`apex_tpu.ops.fused_sampling.sampling_cost_bytes` — the decode
  epilogue's one-pass traffic, re-exported here.

Every function returns a plain ``dict`` of ints/floats (the benches
emit them as JSON rows; the planner reads named columns).  None of them
touch devices: they are host-side arithmetic over config numbers, safe
to call in a tight enumeration loop.

What models what:

- :func:`resnet_traffic_model` — architecture-mandated HBM traffic of a
  ResNet train step (activation passes + BN stat passes + param state).
- :func:`ddp_bytes_on_wire` — ring-all-reduce grad-sync wire bytes per
  replica per step, fp32/bf16/int8 (the EQuARX-style quantized wire).
- :func:`zero_bytes_on_wire` — ZeRO-1/2 wire (reduce-scatter +
  all-gather legs) AND resident optimizer-state bytes per chip — the
  planner's params+optimizer residency column.
- :func:`pipeline_costs` — the 1F1B schedule's first-class
  quantities: the (p−1)/m bubble fraction, tick counts, the ≤p
  live-microbatch bound, and the stage-boundary activation ICI
  column — the planner's pipe-degree bubble/wire terms and the
  bench leg's measured-vs-modeled pin.
- :func:`serving_traffic_model` — per-decode-step KV bytes (dense vs
  paged), pool capacity (shared-prefix and quantized variants), and the
  tensor-parallel ICI column — the planner's serving HBM/ICI columns.
"""

from __future__ import annotations

# the two formulas that were ALREADY shared library code — re-exported
# so `apex_tpu.plan.costs` is the one import a cost consumer needs
from apex_tpu.ops.fused_sampling import sampling_cost_bytes
from apex_tpu.ops.paged_attention import kv_store_bytes_per_token

__all__ = [
    "resnet_conv_shapes",
    "resnet_traffic_model",
    "ddp_bytes_on_wire",
    "zero_bytes_on_wire",
    "pipeline_costs",
    "serving_traffic_model",
    "kv_store_bytes_per_token",
    "sampling_cost_bytes",
]


def resnet_conv_shapes(size, stage_sizes=(3, 4, 6, 3), width=64):
    """The bottleneck stack's conv geometry, once: yields
    ``(in_elems, out_elems, bn?)`` per conv — stem, then the v1.5
    blocks (the 3×3 conv carries the stride, so conv1's output and
    conv2's input stay at FULL resolution in strided blocks), with
    the projection shortcut where stride/width change.  THE single
    walk behind :func:`resnet_traffic_model`'s pass counting and the
    planner's activation-residency column
    (``plan.enumerate.memory_model``) — one site to change if the
    block convention ever does."""
    convs = []                            # (in_elems, out_elems, bn?)
    hw = size // 2                        # stem s=2
    convs.append((size * size * 3, hw * hw * width, True))
    hw //= 2                              # maxpool
    cin = width
    for i, n_blocks in enumerate(stage_sizes):
        f = width * (2 ** i)
        for j in range(n_blocks):
            stride = 2 if (j == 0 and i > 0) else 1
            hw_out = hw // stride
            inp = hw * hw * cin
            convs.append((inp, hw * hw * f, True))               # 1x1
            convs.append((hw * hw * f,
                          hw_out * hw_out * f, True))            # 3x3
            convs.append((hw_out * hw_out * f,
                          hw_out * hw_out * 4 * f, True))        # 1x1
            if stride != 1 or cin != 4 * f:
                convs.append((inp, hw_out * hw_out * 4 * f, True))
            cin, hw = 4 * f, hw_out
    return convs


def resnet_traffic_model(b, size, stage_sizes=(3, 4, 6, 3), width=64,
                         act_bytes=2, fused_bn=False):
    """Analytic HBM-traffic model of a ResNet train step (round-4
    verdict weak #1: XLA's cost-model "bytes accessed" double-counts
    fusion-internal traffic by an uncalibrated amount, so the resnet
    legs scored roofline_frac 1.07 "of peak" — a certification no
    reader could trust).  Two bounds, both from the architecture:

    - ``floor``: every conv reads its input (fwd + wgrad = 2×), writes
      its output, and the grad chain mirrors it (read dOut, write dIn)
      — 3·in + 2·out activation passes per conv, perfect fusion of
      BN/ReLU/residual into conv epilogues, params+optimizer once.
      A true lower bound: no real schedule moves fewer bytes.
    - ``bn_real``: + 2 extra passes per BN'd activation (batch-stat
      reductions fwd and bwd cannot fuse into the producing conv's
      epilogue — the stats must see the whole activation before
      normalize) — the achievable bound for a batch-norm network.

    roofline_frac scored against ``bn_real`` is ≤ 1 by construction
    and *means something*: 1.0 = the step streams exactly its
    architecture-mandated bytes at peak bandwidth.

    ``fused_bn=True`` adds a third key, ``bn_fused_kernel``: the pass
    count the ISSUE-3 fused kernels (apex_tpu/ops/batch_norm.py)
    actually execute — per BN'd activation, fwd = stats read +
    normalize read/write (+3 beyond floor: the kernels materialize the
    normalized tensor instead of folding the per-channel affine into
    the consumer conv, which ``bn_real`` idealizes away), bwd = one
    (dy, x) reduction + one (dy, x) map writing dx (+5) — so +8 passes
    vs ``bn_real``'s idealized +2.  It is the *kernel program's own
    mandated traffic*: measured fused steps land between
    ``bn_real`` and ``bn_fused_kernel``, and the leg's score stays
    against ``bn_real`` so A/B rows share one bound.  Note the
    space-to-depth stem does not move any bound — (224·224·3) and
    (112·112·12) are the same element count; its win (no 3-channel
    patch materialization) lives in the overhead above the bound.
    """
    convs = resnet_conv_shapes(size, stage_sizes, width)
    floor = sum(3 * i + 2 * o for i, o, _ in convs) * b * act_bytes
    bn_extra = sum(2 * o for _, o, bn in convs if bn) * b * act_bytes
    # params + SGD-momentum state: fp32 master read+write, momentum
    # read+write, fp32 grad read (+ its bf16 write in bwd)
    n_params = 25.6e6
    param_traffic = n_params * (4 * 2 + 4 * 2 + 4 + 2)
    out = {"floor": int(floor + param_traffic),
           "bn_real": int(floor + bn_extra + param_traffic)}
    if fused_bn:
        fused_extra = sum(8 * o for _, o, bn in convs if bn) \
            * b * act_bytes
        out["bn_fused_kernel"] = int(floor + fused_extra
                                     + param_traffic)
    return out


def ddp_bytes_on_wire(n_params, replicas, *, scale_stages=2):
    """Analytic grad-sync wire traffic per replica per step (ISSUE-8
    satellite / ROADMAP 2b): a ring all-reduce moves
    ``2 (n-1)/n × n_params`` elements over the wire (reduce-scatter +
    all-gather legs), so the bytes are element-width-proportional:

    - fp32: × 4 bytes;
    - bf16/fp16 (``allreduce_dtype=jnp.bfloat16``): × 2;
    - int8 (``allreduce_dtype="int8"``, the EQuARX-style path in
      ``parallel/ddp.py``): × 1 — the int8 ``all_to_all``
      reduce-scatter and int8 ``all_gather`` keep every wire transfer
      at 1 byte/element — plus ``scale_stages`` scalar amax pmax
      collectives (4 bytes × n each, negligible).

    The measured companion row is the ``bert_o1`` DDP A/B child; the
    quantization-error side is pinned by ``test_loss_trajectory``'s
    exact-vs-int8 band test and ``test_parallel``'s amax/127 bound.
    """
    n = int(replicas)
    frac = 2 * (n - 1) / n
    scales = scale_stages * 4 * n
    fp32 = frac * n_params * 4
    int8 = frac * n_params * 1 + scales
    return {
        "replicas": n,
        "grad_elements": int(n_params),
        "wire_bytes_per_step_fp32": int(fp32),
        "wire_bytes_per_step_bf16": int(frac * n_params * 2),
        "wire_bytes_per_step_int8": int(int8),
        "int8_wire_reduction_vs_fp32": round(fp32 / int8, 2),
    }


def zero_bytes_on_wire(n_params, shards, *, stage=2,
                       reduce_dtype="fp32", param_bytes=2,
                       opt_bytes_per_param=12, scale_stages=1):
    """Analytic wire + resident-state model for the ZeRO step
    (ISSUE 11), extending :func:`ddp_bytes_on_wire`:

    **wire, per replica per step** — a reduce-scatter (or all-gather)
    moves ``(n-1)/n × n_params`` elements; the ZeRO-2 step is one
    reduce-scatter of grads (element width set by ``reduce_dtype``:
    fp32 4 B, bf16 2 B, int8 1 B + ``scale_stages`` scalar amax pmax
    collectives) plus one all-gather of params at ``param_bytes``
    (bf16 under O2).  ZeRO-1 runs the full :func:`ddp_bytes_on_wire`
    all-reduce instead of the reduce-scatter.  The DP baseline is the
    fp32 all-reduce: ``2 (n-1)/n × 4 × n_params``.

    **resident, per chip** — where the bytes *live* (the HBM lever):
    DP-O2 keeps fp32 masters + both Adam moments replicated
    (``opt_bytes_per_param`` = 12 B/param; the bf16 forward copy is a
    temp either way), ZeRO keeps a bf16 param replica
    (``param_bytes``) plus ``opt_bytes_per_param / n`` of shards.
    The measured companion is ``bench_bert_o1_zero`` (hbm_peak A/B +
    exact placed-array shard bytes); trajectory agreement is gated by
    ``test_loss_trajectory``'s DP-vs-ZeRO-2 band leg.
    """
    n = int(shards)
    frac = (n - 1) / n
    gbytes = {"fp32": 4, "bf16": 2, "fp16": 2, "int8": 1}[
        str(reduce_dtype)]
    scales = scale_stages * 4 * n if gbytes == 1 else 0
    rs = frac * n_params * gbytes + scales
    if stage == 1:
        # full all-reduce (both legs) instead of the single RS leg
        rs = 2 * frac * n_params * gbytes + scales
    ag = frac * n_params * param_bytes
    dp_wire = 2 * frac * n_params * 4
    state_dp = opt_bytes_per_param * n_params
    state_zero = param_bytes * n_params + opt_bytes_per_param * n_params / n
    return {
        "shards": n,
        "stage": int(stage),
        "reduce_dtype": str(reduce_dtype),
        "grad_elements": int(n_params),
        "wire_bytes_reduce_scatter": int(rs),
        "wire_bytes_param_all_gather": int(ag),
        "wire_bytes_per_step_zero": int(rs + ag),
        "wire_bytes_per_step_dp_fp32_allreduce": int(dp_wire),
        "wire_reduction_vs_dp": round(dp_wire / (rs + ag), 2),
        "model_state_bytes_per_chip_dp": int(state_dp),
        "model_state_bytes_per_chip_zero": int(state_zero),
        "state_bytes_saved_per_chip": int(state_dp - state_zero),
        "state_savings_frac": round(1 - state_zero / state_dp, 3),
    }


def pipeline_costs(num_stages, num_microbatches, *,
                   microbatch_tokens=0, hidden_size=0, dtype_bytes=2):
    """Analytic schedule + wire model of the 1F1B pipeline step
    (:mod:`apex_tpu.parallel.pipeline`) — the quantities the planner's
    pipe degree scores with and the bench leg pins measured numbers
    against:

    - **bubble_fraction** ``(p−1)/m``: the idle fraction of the ideal
      (work-only) step time — p−1 microbatch-slots of warmup fill and
      p−1 of drain, amortized over m microbatches of work per stage.
      The throughput multiplier the scorer applies is ``1 + bubble``.
    - **schedule_ticks** ``m + 2p − 1``: lockstep SPMD ticks per step
      (:func:`~apex_tpu.parallel.pipeline.schedule_ticks` — every
      stage executes every tick; a fully-busy 1F1B tick runs one
      forward and one backward, so m ticks of pure work stretch to
      ``m + 2p − 1``).  ``tick_bubble_fraction`` =
      ``(2p − 1)/(m + 2p − 1)`` — the dead-tick share of the tick
      count, the number a tick-resolved trace shows directly.
    - **live_microbatches** ``min(p, m)``: the 1F1B stash bound — at
      most p microbatch activation sets are held per stage
      (:func:`~apex_tpu.parallel.pipeline.live_microbatches`), the
      per-stage HBM residency term.
    - **boundary_bytes_per_step_per_chip**: the stage-boundary
      activation ICI column.  Each microbatch activation
      (``microbatch_tokens × hidden_size × dtype_bytes``) crosses
      p−1 stage boundaries forward and the cotangent mirrors it
      backward — ``2(p−1)·m`` ppermute sends per replica per step,
      averaged over the p stage chips: ``2(p−1)/p × m × payload``.

    ``num_stages == 1`` degenerates cleanly (zero bubble, zero wire).
    """
    p, m = int(num_stages), int(num_microbatches)
    if p < 1 or m < 1:
        raise ValueError(
            f"num_stages and num_microbatches must be >= 1, got "
            f"p={p}, m={m}")
    ticks = m + 2 * p - 1
    payload = int(microbatch_tokens) * int(hidden_size) * dtype_bytes
    return {
        "stages": p,
        "microbatches": m,
        "bubble_fraction": round((p - 1) / m, 6),
        "schedule_ticks": ticks,
        "tick_bubble_fraction": round((2 * p - 1) / ticks, 6),
        "live_microbatches": min(p, m),
        "microbatch_payload_bytes": payload,
        "boundary_bytes_per_step_per_chip": int(
            0 if p == 1 else 2 * (p - 1) / p * m * payload),
        "boundary_bytes_per_step": int(
            0 if p == 1 else 2 * (p - 1) * m * payload),
    }


def serving_traffic_model(*, num_layers, kv_heads, head_dim,
                          max_seq_len, live_tokens, slots,
                          block_size, dtype_bytes=2,
                          shared_prefix_tokens=0, kv_dtype=None,
                          tp=1, hidden_size=0):
    """Analytic per-step KV-cache traffic of the serving decode step —
    the measured defect behind the ISSUE-5 paged tentpole, in bytes:

    - **dense** (``generate()``'s cache, ``cfg.kv_cache="dense"``,
      one row a sequence): the slab reserves
      ``slots × max_seq_len`` tokens of K+V per layer
      (``dense_pool_bytes``), and the steady-decode attention reads a
      whole ``max_seq_len`` row per slot per step — the cursor only
      *masks*, it does not shrink the read
      (``models/transformer.py::_cache_attention``; the ``blocked``
      variant cond-skips dead pages at runtime but the reservation,
      and the einsum default's reads, are pinned at ``max_seq_len``).
      ``dense_kv_read_bytes_per_step`` is therefore LIVE-INDEPENDENT
      — asserted so by ``tests/test_paged_attention.py``'s
      cost-analysis check.
    - **paged** (``serving.PagedEngine``): the pool is sized in TOKENS
      (``paged_pool_tokens``; block 0 is the null page) and the decode
      kernel gathers exactly ``ceil(live/block_size)`` pages per slot
      per step — ``paged_kv_read_bytes_per_step`` scales with live
      tokens, which is what lets the same HBM budget hold 2–4× the
      dense slot count in the occupancy sweep.

    With ``shared_prefix_tokens`` (ISSUE 7), every slot's first that
    many live tokens are one copy-on-write shared prompt prefix: the
    prefix's pages are counted ONCE in the live pool footprint
    (``paged_live_pool_tokens_shared``) instead of per tenant
    (``..._unshared``) — capacity reclaimed that the shared-aware
    admission gate converts into occupancy.  Per-step READ bytes are
    deliberately NOT discounted: every row still gathers its whole
    prefix each step — sharing is an HBM-capacity lever, not a
    bandwidth one.

    With ``kv_dtype`` (``"int8"``/``"fp8"``, ISSUE 8) the paged pool
    stores 1-byte codes plus one fp32 amax scale per (kv_head, page)
    per side per layer.  The model then also reports the quantized
    bytes/token (scale overhead amortized over ``block_size``), the
    pool capacity in TOKENS the dense slab's byte budget buys at the
    quantized width (``paged_pool_tokens_at_equal_hbm`` — the
    admitted-occupancy lever; ≥1.9× at int8 from bf16, ~3.9× from
    fp32), and the per-step quantized read bytes INCLUDING the scale
    traffic (one 4-byte scalar per page per side — the kernel DMAs it
    through the same block-table prefetch).

    With ``tp`` > 1 (ISSUE 13, tensor-parallel paged serving) one
    replica spans ``tp`` chips: the pool shards on ``kv_heads``, so
    each chip reads only its slice
    (``paged_kv_read_bytes_per_step_per_chip`` = the paged count /
    tp), and every decode step pays **ICI collective traffic** — the
    two RowParallel all-reduces per layer (attention out-proj + MLP
    down-proj) over the ``(slots, hidden_size)`` step activations.
    The new ICI column counts them at the ring-all-reduce wire cost of
    ``2·(tp-1)/tp`` × payload per chip (``ici_bytes_per_step_per_chip``;
    ``ici_bytes_per_step`` sums the chips).  The vocab-parallel logits
    all-reduce and the shard_map-internal attention (which needs NO
    collective — kv heads are independent) are deliberately excluded:
    the column isolates the per-layer activation collectives that
    scale with depth, the term the 1×M vs M×1 A/B trades against
    per-chip HBM reads.  ``hidden_size`` is required when ``tp > 1``.

    Both counts are K+V (×2) across all layers; the param stream
    (identical for both engines) is excluded — this model isolates the
    cache term the paged tentpole changed.
    """
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if tp > 1 and not hidden_size:
        raise ValueError(
            "hidden_size is required for the ICI column (tp > 1) — "
            "the per-step collectives move (slots, hidden) "
            "activations")
    per_tok = 2 * kv_heads * head_dim * dtype_bytes * num_layers
    pages = lambda t: -(-int(t) // int(block_size))   # noqa: E731
    live_pages = pages(live_tokens)
    shared = min(int(shared_prefix_tokens), int(live_tokens))
    shared_pages = (int(shared) // int(block_size))   # full blocks only
    private_pages = pages(live_tokens - shared_pages * block_size)
    unshared_pool = slots * live_pages * block_size
    shared_pool = (shared_pages + slots * private_pages) * block_size
    quant = {}
    if kv_dtype is not None:
        import jax.numpy as jnp

        from apex_tpu.ops.paged_attention import kv_quant_spec

        store_dt, _ = kv_quant_spec(kv_dtype)   # validates the name
        store_bytes = jnp.dtype(store_dt).itemsize
        # per-token quantized storage, scale overhead amortized: the
        # shared per-(kv_head, layer) formula (2 sides × head_dim
        # codes + 2 fp32 scales per page) × kv_heads × layers — the
        # SAME arithmetic PagedEngine's equal-HBM default admits with
        scale_per_page = 2 * kv_heads * 4 * num_layers
        q_tok = (kv_heads * num_layers
                 * kv_store_bytes_per_token(head_dim, block_size,
                                            kv_dtype))
        dense_bytes = slots * max_seq_len * per_tok
        q_read = (slots * live_pages
                  * (block_size * 2 * kv_heads * head_dim
                     * store_bytes * num_layers + scale_per_page))
        quant = {
            "kv_dtype": str(kv_dtype),
            "kv_store_bytes_per_token_quantized": round(q_tok, 3),
            "kv_store_bytes_per_token_unquantized": int(per_tok),
            "paged_pool_tokens_at_equal_hbm": int(dense_bytes / q_tok),
            "quantized_capacity_multiplier": round(per_tok / q_tok, 3),
            "paged_kv_read_bytes_per_step_quantized": int(q_read),
            # per-chip quantized twin of the TP column below: the
            # sharded pool divides the (1-byte + scale) gather by tp —
            # the unquantized per-chip key would overstate a quantized
            # TP pool's HBM reads 2-4x, exactly the HBM-vs-ICI ratio
            # this model quantifies
            "paged_kv_read_bytes_per_step_per_chip_quantized": int(
                q_read / tp),
        }
    paged_read = slots * live_pages * block_size * per_tok
    # ring all-reduce: each chip sends+receives 2·(tp-1)/tp of the
    # payload; 2 RowParallel reduces per layer on the (slots, hidden)
    # decode-step activations
    ici_per_chip = (0 if tp == 1 else int(
        2 * num_layers * slots * hidden_size * dtype_bytes
        * 2 * (tp - 1) / tp))
    return {
        **quant,
        "tp": tp,
        "ici_bytes_per_step_per_chip": ici_per_chip,
        "ici_bytes_per_step": ici_per_chip * tp,
        "paged_kv_read_bytes_per_step_per_chip":
            int(paged_read / tp),
        "dense_kv_read_bytes_per_step":
            int(slots * max_seq_len * per_tok),
        "paged_kv_read_bytes_per_step": int(paged_read),
        "dense_pool_bytes": int(slots * max_seq_len * per_tok),
        "paged_pool_tokens": int(slots * max_seq_len),
        "live_tokens": int(live_tokens),
        "block_size": int(block_size),
        "shared_prefix_tokens": int(shared),
        "paged_live_pool_tokens_unshared": int(unshared_pool),
        "paged_live_pool_tokens_shared": int(shared_pool),
        "paged_live_pool_bytes_unshared": int(unshared_pool * per_tok),
        "paged_live_pool_bytes_shared": int(shared_pool * per_tok),
        "shared_capacity_multiplier": round(
            unshared_pool / max(shared_pool, 1), 3),
    }
