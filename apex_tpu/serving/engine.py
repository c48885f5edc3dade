"""Continuous-batching decode engine over a paged KV-cache pool.

One model, ``max_slots`` concurrent tenants, and at most five compiled
executables for the engine's whole lifetime (:class:`PagedEngine`):

- ``decode_step``  — the width-1 step: ONE application of the model's
  ``decode=True`` path over the whole ragged batch (per-row cursors
  and block tables live in the cache collection; attention goes through
  :func:`apex_tpu.ops.paged_attention`), followed by branchless
  per-slot sampling whose parameters (temperature / top_k / top_p /
  eos / budget) are device arrays in
  :class:`~apex_tpu.serving.cache.SlotState` — mixed sampling configs
  (nucleus sampling included) share the executable.  The sampling
  tail is the FUSED epilogue of :mod:`apex_tpu.ops.fused_sampling`:
  one Pallas pass over the ``(slots, vocab)`` logits on TPU, the
  sort-based reference elsewhere — token-identical either way, and
  the reference ``lax.cond``-skips its sort when no admitted row
  enables top-k/top-p.
- ``prefill_step`` — the same function at width ``prefill_chunk``:
  the mixed step in which prompts ride as chunks beside decoding
  tenants, so any prompt length replays one shape.  It computes its
  REAL lanes only: the model runs on a compact list of at most
  ``lane_budget`` of them (:class:`~apex_tpu.models.transformer.
  Lanes`), and only the sequence mixers see ``(slots, width)`` rows.
- ``spec_step``    — only with ``spec_tokens > 0``: the width
  ``1 + spec_tokens`` draft/verify step.
- ``admit``        — scatter every waiting tenant's token, budget,
  sampling parameters and key into the slot state in one call; no
  cache writes.
- ``release``      — clear a slot's active bit.

Every executable is wrapped in
:func:`apex_tpu.utils.tracecheck.retrace_guard` with a budget of
exactly 1, so a shape or signature leak raises ``RetraceError`` instead
of silently recompiling per request — the engine *enforces* its own
zero-retrace steady state rather than merely promising it.

Greedy decoding through the engine is token-identical to
``generate()``: the same model code computes every position (chunked
prefill and paged attention change the schedule and the cache layout,
not the function), followed by the same fp32 argmax; sampled chains
are a function of the request's seed alone.

The step boundary is the only device→host sync: ``collect()`` returns
a step's per-slot tokens, counts and finished flags as numpy
(:class:`StepOutput`) so the scheduler can evict and refill.  A step
has two halves: ``dispatch()`` plans it and enqueues its program,
``collect()`` fetches what it made — and the next step may be
dispatched before the last one is collected, so the chip runs step
n + 1 while the host fetches, routes and delivers step n (``step()``
is the two halves back to back).  Inactive slots still compute (static
shapes — no dynamic batch) into the null page; their outputs are
ignored on the host.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from apex_tpu.core.mesh import TENSOR_AXIS
from apex_tpu.models.generate import apply_decode, cache_shapes
from apex_tpu.models.transformer import Lanes
from apex_tpu.ops.fused_sampling import fused_sample, \
    fused_sample_reference
from apex_tpu.ops.paged_attention import tp_head_shards
from apex_tpu.serving import cache as slot_cache
from apex_tpu.utils import tracecheck
from apex_tpu.utils.metrics import counters
from apex_tpu.utils.profiler import SpanTotals, span

__all__ = ["PagedEngine", "StepOutput", "sample_dynamic",
           "prompt_lookup_draft", "tp_mesh"]

#: the engine's spans (``apex_tpu.utils.profiler.span``): one engine
#: step, named by the program it runs — one count a device step, its
#: seconds those of its two halves — and the halves' parts: ``plan``
#: (may the step run ahead, drafts, feed, page allocation) and
#: ``dispatch`` (the jitted call: enqueue only; then the mirrors the
#: next plan reads), ``fetch`` (the host's wait for the oldest step in
#: flight and the copy back) and ``commit`` (the host mirrors that
#: need the fetched values).
STEP_PREFILL = "apex/engine/step_prefill"
STEP_DECODE = "apex/engine/step_decode"
STEP_SPEC = "apex/engine/step_spec"
PLAN = "apex/engine/plan"
DISPATCH = "apex/engine/dispatch"
FETCH = "apex/engine/fetch"
COMMIT = "apex/engine/commit"


def tp_mesh(tp: int, devices=None):
    """A one-replica tensor-parallel serving mesh: ``tp`` chips on the
    ``tensor`` axis (every other axis 1).

    ``devices`` defaults to the first ``tp`` of ``jax.devices()``; a
    fleet packing N replicas × M chips onto one host passes each
    replica its own device slice (``jax.devices()[i*M:(i+1)*M]``).
    Never touches the library-global mesh (``set_current=False``) —
    replicas own disjoint meshes, and serving must not hijack the
    training topology."""
    from apex_tpu.core.mesh import initialize_mesh

    tp = int(tp)
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) < tp:
        raise ValueError(
            f"tp={tp} needs {tp} devices, only {len(devices)} "
            f"available")
    return initialize_mesh(tensor_model_parallel_size=tp,
                           devices=devices[:tp], set_current=False)


def _shard_params_for_tp(variables, mesh):
    """Place one replica's weights on its mesh: flax ``Partitioned``
    boxes shard per their annotations (the GSPMD tensor-parallel
    layers mark qkv/out/mlp kernels over the ``tensor`` axis — this is
    where a model too big for one chip actually fits), axes absent
    from the mesh are dropped, a dim the axis size doesn't divide
    falls back to replicated, and plain (unboxed) leaves replicate."""
    from flax.core import meta

    repl = jax.sharding.NamedSharding(mesh,
                                      jax.sharding.PartitionSpec())
    axes = set(mesh.axis_names)

    def place(x):
        if isinstance(x, meta.Partitioned):
            names = tuple(n if n in axes else None for n in x.names)
            sh = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(*names))
            try:
                return x.replace_boxed(jax.device_put(x.unbox(), sh))
            except ValueError:
                return x.replace_boxed(jax.device_put(x.unbox(),
                                                      repl))
        return jax.device_put(x, repl)

    return jax.tree.map(place, variables,
                        is_leaf=lambda x: isinstance(x,
                                                     meta.Partitioned))


def _pin_replicated(tree, mesh):
    """In-trace: constrain every leaf of ``tree`` replicated over
    ``mesh`` — the SlotState / sampling outputs' fixed point (see
    ``serving.cache.constrain_paged_cache`` for why out-shardings
    must be pinned under retrace budgets of 1)."""
    repl = jax.sharding.NamedSharding(mesh,
                                      jax.sharding.PartitionSpec())
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, repl), tree)


class StepOutput(NamedTuple):
    """One engine step's host-visible result.

    ``tokens`` is ``(max_slots, width)`` — a speculative verify step
    can emit several tokens per slot per step; ``counts[i]`` says how
    many of row i's tokens are REAL this step (``tokens[i, :counts[i]]``,
    in emission order; 0 for a mid-prefill tenant, which computes but
    emits nothing).  ``finished[i]`` latches on row i's LAST emitted
    token; ``emitted`` is the legacy ``counts > 0`` mask.
    ``preempted`` lists slots the engine evicted for block exhaustion
    before the step ran — their tenants' blocks and slot state are
    already released, and the scheduler requeues them to continue from
    their streamed prefix.
    """

    tokens: np.ndarray
    finished: np.ndarray
    emitted: np.ndarray
    preempted: Tuple[int, ...]
    counts: np.ndarray


def prompt_lookup_draft(context: np.ndarray, k: int,
                        max_ngram: int = 3) -> np.ndarray:
    """Propose up to ``k`` draft tokens by PROMPT LOOKUP (n-gram
    continuation) — the model-free drafter of the speculative-decoding
    tentpole.

    Finds the most recent earlier occurrence of the context's trailing
    n-gram (longest ``n <= max_ngram`` first) and proposes the tokens
    that followed it.  Pure host-side numpy over ``prompt ++ streamed
    tokens``; returns an empty array when nothing matches — the row
    then rides the step as a plain one-token decode.  Summarization /
    code-editing / few-shot traffic repeats long prompt spans, which
    is exactly when lookup drafts hit ("LLM Inference Acceleration via
    Efficient Operation Fusion", PAPERS.md reports the same
    no-second-model recipe).
    """
    context = np.asarray(context, np.int32).reshape(-1)
    n_ctx = int(context.size)
    if k < 1 or n_ctx < 2:
        return np.empty((0,), np.int32)
    for n in range(min(int(max_ngram), n_ctx - 1), 0, -1):
        pattern = context[n_ctx - n:]
        windows = np.lib.stride_tricks.sliding_window_view(
            context[:n_ctx - 1], n)
        hits = np.nonzero((windows == pattern).all(axis=1))[0]
        if hits.size:
            start = int(hits[-1]) + n
            drafts = context[start:start + int(k)]
            if drafts.size:
                return drafts.astype(np.int32)
    return np.empty((0,), np.int32)


def _check_sampling(vocab_size: int, top_k, top_p) -> None:
    """Sampling-parameter validation (``validate_request``)."""
    if top_k is not None and top_k != 0 \
            and not 1 <= top_k <= vocab_size:
        raise ValueError(
            f"top_k must be in [1, vocab_size={vocab_size}] "
            f"(or 0/None to disable), got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(
            f"top_p must be in (0, 1] (or None to disable), "
            f"got {top_p}")


def sample_dynamic(logits, keys, temperature, top_k, top_p,
                   vocab_size: int):
    """Branchless per-row sampling with DEVICE-ARRAY parameters.

    The engine's historical sampling tail, now living in
    :func:`apex_tpu.ops.fused_sampling.fused_sample_reference` as the
    golden semantics (and the non-Pallas dispatch target) of the fused
    one-pass sampling kernel — this name stays as the reference entry
    point and delegates verbatim.  Semantics: per row fp32 argmax when
    ``temperature <= 0``, else top-k- and/or nucleus-truncated
    categorical at ``logits/temperature``, mirroring ``generate``'s
    static :func:`~apex_tpu.models.generate.sample_logits` with traced
    parameters; an all-greedy / plain-temperature step now
    ``lax.cond``-skips the whole sort + softmax + cumsum tail at
    runtime (bitwise-equivalent on that predicate — see the ops
    module).  The engine itself calls
    :func:`~apex_tpu.ops.fused_sampling.fused_sample`, which resolves
    to the one-pass Pallas kernel on TPU and to exactly this
    composition elsewhere.
    """
    return fused_sample_reference(logits, keys, temperature, top_k,
                                  top_p, vocab_size)


def _active_sampling_params(state):
    """``(temperature, top_k, top_p)`` with RELEASED slots' filter
    params neutralized.

    ``release_slot`` only clears the active bit — a finished top-k /
    top-p tenant would otherwise leave its stale filter params in the
    slot row forever, and the fused epilogue's runtime sort
    short-circuit (skip the sort + cumsum tail when NO row enables a
    filter) would never fire again for the engine's lifetime.  Masking
    by ``active`` only changes rows whose tokens the emission gates
    already discard, so emitted chains are bit-identical either way —
    but the short-circuit predicate sees the true live traffic.
    """
    return (state.temperature,
            jnp.where(state.active, state.top_k, 0),
            jnp.where(state.active, state.top_p, 0.0))


#: what PagedEngine reads off a model's ``.cfg`` (any model class)
_MODEL_CONTRACT = ("max_seq_len", "vocab_size", "num_heads", "kv_heads",
                   "head_dim", "dtype")


@dataclasses.dataclass
class _Tenant:
    """Host-side record of one slot's tenant (the device never sees
    prompts or block lists — only the tables/cursors built from them)."""

    prompt: np.ndarray          # full prompt tokens
    fed: int = 0                # prompt tokens already fed (chunked)
    cursor: int = 0             # tokens written into the cache
    blocks: List[int] = dataclasses.field(default_factory=list)
    seq: int = 0                # admission order (LIFO preemption key)
    budget: int = 0             # max_new_tokens (host mirror)
    planned: int = 0            # emissions dispatched so far
    emitted: int = 0            # tokens fetched so far (host mirror)
    gen: List[int] = dataclasses.field(default_factory=list)
    #: chain digests of the prompt's full blocks (prefix sharing)
    digests: List[bytes] = dataclasses.field(default_factory=list)
    registered: int = 0         # prompt blocks offered to the trie


@dataclasses.dataclass
class _Flight:
    """One dispatched step whose output the host has not fetched."""

    kind: str                   # the step's span, named by its program
    out: Any                    # the step's packed output, on the device
    #: each slot's tenant when the step was planned: a row of the
    #: output is theirs, whoever holds the slot when it is fetched
    recs: List[Optional[_Tenant]]
    n_tokens: np.ndarray
    preempted: Tuple[int, ...]
    #: the next step has to wait for this one's fetch: a drafted step
    #: (its cursors are the device's to decide), or one in which a
    #: row's dispatched emissions reach its budget (a slot comes free)
    fence: bool
    host_s: float               # seconds of the dispatch half


class PagedEngine:
    """Continuous-batching decode over a PAGED KV-cache pool.

    The engine:

    - stores K/V in fixed-size **pages** of a pool sized in TOKENS
      (``pool_tokens``), shared across tenants through per-slot block
      tables (:class:`~apex_tpu.serving.cache.BlockAllocator`) — HBM
      footprint and per-step attention bytes scale with live tokens,
      not with ``max_slots × max_seq_len``;
    - runs **chunked prefill inside the decode step**: prompts are
      split into ``prefill_chunk``-token pieces that ride the regular
      step beside decoding tenants (ONE fused mixed prefill+decode
      executable), so a long prompt can never head-of-line-block
      co-tenants and per-step latency is bounded by the chunk;
    - the whole ragged batch is ONE model application — per-row
      cursors/block tables in the cache collection let every row
      attend at its own position, and attention goes through
      :func:`apex_tpu.ops.paged_attention`.  The collection holds one
      subtree a LAYER (``.../layer_{i}/attention/paged_key``, ...;
      no leaf has a layer axis, whether the model scans its layers or
      unrolls them): the steps donate the tree, and each layer's
      kernels write its own pool in place
      (:func:`apex_tpu.models.transformer.decode_layers`).

    Exactly FOUR executables for the process lifetime — FIVE with
    speculative decoding on — each under an exact
    :func:`~apex_tpu.utils.tracecheck.retrace_guard` budget of 1:
    ``decode_step`` (width-1 step), ``prefill_step`` (the width-
    ``prefill_chunk`` mixed step — every prompt length replays this
    one shape), the optional ``spec_step``
    (the width-``1 + spec_tokens`` draft/verify step below), ``admit``
    (slot-state scatter; no cache writes — pages are overwritten
    before they become visible and recurrent state is zeroed by the
    step that finds its row at cursor 0, so admission and release
    never touch the cache), and ``release``.

    A model with **recurrent state** (a state-space mixer beside the
    attention: :class:`~apex_tpu.models.falcon_h1.FalconH1Model`)
    keeps, a layer, ``ssm_state`` / ``conv_state`` leaves with one row
    a slot in the same ``"cache"`` collection.  The engine never writes
    them: the model starts a row from zero state when its cursor is 0
    (a fresh admission, and a re-admission after a preemption, which
    re-prefills ``prompt ++ streamed``) and advances a row only over
    its real lanes — ``chunk_lens``, fed every step (a decoding row in
    a mixed step has 1 real lane of ``prefill_chunk``).  A slot's
    previous tenant therefore never needs clearing, as with pages.
    ``share_prefixes``, ``spec_tokens`` and ``mesh`` need a state
    snapshot or a state sharding that does not exist and raise for
    such a model.

    A model with **window layers** (``cfg.kv_window`` states their
    width, whichever of its layers they are) is served as any other: its window layers' reads of the pool start at the
    window's first page (:mod:`apex_tpu.ops.paged_attention`,
    ``window=``) and its pages stay allocated behind the window
    (ROADMAP M1).  ``share_prefixes`` and ``spec_tokens`` hold under a
    window — a page's K/V are a function of the prefix whatever reads
    them, and a verify chunk is a chunk.  ``kv_window_pages`` counts
    what one window layer sweeps beside ``kv_pages_live``.

    A model with an **expert share** (an expert layer that holds some
    of its layer's experts: :class:`~apex_tpu.models.afmoe.AfmoeModel`)
    reports, a layer and a step, the assignments each held expert got;
    they come back in the step's one fetch (every step program returns
    ONE packed array: tokens, accepted counts, finished flags, these
    counts) and feed
    ``expert_assignments``, ``expert_load_max``, ``experts_active`` and
    ``expert_layer_steps``.  ``mesh`` raises for such a model.

    **A step in flight.**  A step is two calls: :meth:`dispatch`
    builds the feed, extends pages, installs admissions and enqueues
    the step's program (JAX returns futures; the cache and the slot
    state chain from step to step on the device), :meth:`collect` makes
    the step's ONE fetch and updates the host mirrors that need it
    (the tokens, the expert counts).  Everything the next plan needs —
    cursors, ``fed``, pages, the trie — advances at dispatch by
    ``n_tokens``, which the host chose, so step n + 1 can be dispatched
    while step n is unfetched and the chip never waits for the host's
    round trip.  At most one step runs ahead, and none where the code
    can see that the step in flight must be collected first
    (:meth:`dispatch` then returns ``False``): it frees a slot (a
    row's dispatched emissions reach its budget — the next prompt
    would wait a whole extra program), the next step drafts (drafts
    are looked up in the tokens of the step in flight), or the plan
    has to preempt (the victim's streamed prefix must hold the token
    in flight).  The device gates a row's emission on its own active
    bit, and the fetched emission mask, not the host's plan, says what
    a row produced: a row that finished by EOS under a step in flight
    emits nothing there.  A row of a step's output belongs to the
    tenant that held the slot when the step was planned.
    :meth:`step` is ``dispatch(); collect()``, the lock-step form;
    ``steps_ahead`` counts the steps dispatched over an unfetched one.

    Block exhaustion preempts the YOUNGEST tenant (its blocks are
    freed, its slot state cleared) and reports it in
    ``StepOutput.preempted``; the scheduler requeues it to continue
    from its streamed prefix (PR 4's fault-recovery machinery).

    **Prefix sharing (``share_prefixes=True``)**: admission hashes the
    prompt block-by-block (:func:`~apex_tpu.serving.cache.
    chain_digests`) against a :class:`~apex_tpu.serving.cache.
    PrefixTrie` of live read-only prompt pages.  Hits are mapped
    refcounted (:meth:`BlockAllocator.incref`) instead of recomputed:
    the tenant's ``fed``/``cursor`` start past the shared prefix, so a
    hot system prompt costs the pool — and the prefill compute — once
    per replica instead of once per tenant.  Only FULL prompt blocks
    are shared and a tenant always re-feeds at least its final prompt
    token (the logits source); when the trie covers the whole prompt,
    the last matched block is **copy-on-write forked**: the tenant
    takes a private page and re-derives the block's KV by re-feeding
    its tokens through the ordinary prefill step (copy-by-recompute —
    bitwise identical, no extra executable), counted on ``cow_forks``.
    Eviction/preemption *decrement* refcounts; a page returns to the
    pool — and drops out of the trie — only when its last tenant
    leaves, so ``blocks_in_use`` stays exact and drains to 0.

    **Speculative decoding (``spec_tokens=K > 0``)**: a host-side
    prompt-lookup drafter (:func:`prompt_lookup_draft` — no second
    model) proposes up to K tokens per decoding row from the tenant's
    own ``prompt ++ streamed`` context; the ``spec_step`` feeds
    ``[current, d_1..d_k]`` through ONE model application (the
    chunked-prefill machinery already handles multi-token rows at
    arbitrary positions), samples at every position with sequentially
    split per-row keys, accepts the longest draft prefix matching the
    sampled chain plus one bonus token, and rolls the host cursor back
    over rejected tails (their pool writes are position-masked garbage
    the next step overwrites).  The rng advance is emission-gated *per
    emitted token* — the k-th produced token always consumes the k-th
    split — so greedy AND sampled chains are token-identical to
    ``generate()`` regardless of the acceptance pattern.

    **Quantized KV pages (``kv_dtype="int8"`` / ``"fp8"``)**: the pool
    stores 1-byte codes with per-(kv_head, page) fp32 amax scales
    riding the cache beside the block table
    (``TransformerConfig.kv_dtype`` — quantize-on-write in the model's
    paged scatter, in-register dequant in the Pallas kernel).  The
    allocator, refcounts, CoW forks, preemption and the trie are
    untouched — a shared or forked page carries its scale with it —
    and ``pool_tokens`` keeps counting TOKENS, which are now ~2×
    (bf16) / ~4× (fp32) cheaper: the default pool converts the byte
    budget of ``max_slots × max_seq_len`` unquantized tokens into
    quantized token capacity, and the
    shared-aware admission gate therefore admits the reclaimed HBM as
    occupancy.  ``kv_dtype="auto"`` adopts the (block_size, kv_dtype)
    pair a joint :func:`~apex_tpu.ops.autotune.tune_paged_attention`
    sweep measured best (unquantized when nothing is cached).

    Numerics contract under quantization: greedy chains agree with
    ``generate()`` within the quantized accuracy band (≥95% token
    agreement on trained models — tests), NOT bitwise; chains remain
    deterministic per (tokens, knobs) and co-tenant-independent.  One
    spec-decoding nuance: write-then-attend puts draft K/V in the pool
    before acceptance is known, so a REJECTED draft's amax legitimately
    stays in its page's monotone running scale — spec-on and spec-off
    quantized chains therefore agree within the band, not bitwise
    (same bounded drift class as rescale-on-append; the rolled-back
    CODES are overwritten next step as usual).

    **Tensor-parallel replica (``mesh=``, ISSUE 13)**: one engine can
    span M chips — the first change that serves a model too big for
    one.  Pass a :func:`tp_mesh` (or an int M) and the whole paged
    datapath shards: weights per their GSPMD annotations
    (ColumnParallel/RowParallel — XLA inserts the per-layer
    all-reduces), the K/V pool (and its quant-scale leaves) on the
    ``kv_heads`` axis through :func:`~apex_tpu.ops.paged_attention`'s
    shard_map path, while block tables, cursors and ``SlotState``
    stay REPLICATED — so the allocator, refcounts, CoW forking,
    preemption, the prefix trie, drafting and the scheduler above are
    byte-for-byte the single-chip host logic.  Prefix sharing,
    speculative decoding and quantized pages therefore ride the
    sharded pool unchanged, at the same 5×1 trace budget (step
    outputs pin their shardings to the committed placement, so the
    signatures reach a fixed point).  ``kv_heads % M != 0`` raises a
    loud ``ValueError`` here, at construction.

    ``block_size=0`` consults the
    :mod:`~apex_tpu.ops.autotune` table (op ``"paged_attention"``,
    keyed on head_dim + the pool's STORAGE dtype + the PER-SHARD
    kv_heads count — a TP engine must not adopt a block size swept at
    full head count) and falls back to 16.
    ``pool_tokens`` defaults to ``max_slots × max_seq_len`` — every
    slot can reach its full context (converted into quantized tokens
    at equal bytes when ``kv_dtype`` is set); shrink it to trade capacity
    for memory (admission token-gates and preemption backstops the
    overcommit).
    """

    def __init__(self, model, params, *, max_slots: int = 4,
                 block_size: int = 0,
                 pool_tokens: Optional[int] = None,
                 prefill_chunk: int = 32,
                 admit_headroom: Optional[int] = None,
                 share_prefixes: bool = False,
                 spec_tokens: int = 0,
                 spec_ngram: int = 3,
                 kv_dtype: Optional[str] = None,
                 mesh=None):
        cfg = getattr(model, "cfg", None)
        missing = [f for f in _MODEL_CONTRACT if not hasattr(cfg, f)]
        if cfg is None or missing:
            raise ValueError(
                "PagedEngine needs a model that keeps the paged-serving "
                "contract: a flax module built as type(model)(cfg=...) "
                "whose dataclass .cfg carries "
                f"{', '.join(_MODEL_CONTRACT)}; applied with "
                "decode=True and a mutable 'cache' collection it "
                "returns (batch, width, vocab) logits — given lanes= "
                "(a models.transformer.Lanes list), (batch, 1, vocab) "
                "of each row's last real lane — and keeps, a "
                "layer, a page pool plus 'block_tables' / 'cursors' "
                "leaves (and any per-slot recurrent state, leading "
                "axis = slots) that the engine overwrites before "
                "every step"
                + (f" — missing on .cfg: {missing}" if cfg is not None
                   else " — the model has no .cfg"))
        if not getattr(cfg, "causal", True):
            raise ValueError("PagedEngine requires a causal model "
                             "(decode=True contract)")
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if spec_tokens < 0:
            raise ValueError(
                f"spec_tokens must be >= 0, got {spec_tokens}")
        if spec_ngram < 1:
            raise ValueError(
                f"spec_ngram must be >= 1, got {spec_ngram}")
        # tensor-parallel replica (ISSUE 13): an int builds a
        # tp-wide mesh over the first tp devices; a Mesh is used as
        # given (the fleet hands each replica its own device slice).
        # A mesh whose tensor axis is 1 is the single-chip engine.
        if isinstance(mesh, int):
            mesh = tp_mesh(mesh) if mesh > 1 else None
        if mesh is not None and TENSOR_AXIS not in mesh.axis_names:
            # loud, like every other TP config mistake: silently
            # serving single-chip on a mesh with no tensor axis would
            # let the user believe they are tensor-parallel
            raise ValueError(
                f"mesh has no {TENSOR_AXIS!r} axis (axes: "
                f"{tuple(mesh.axis_names)}) — build the serving mesh "
                f"with serving.tp_mesh(tp, devices), or pass an int")
        tp = (1 if mesh is None
              else int(dict(mesh.shape).get(TENSOR_AXIS, 1)))
        if tp <= 1:
            mesh, tp = None, 1
        else:
            # the loud config-time gate: kv_heads % tp == 0 (the GQA
            # group→shard mapping), instead of a shape error deep
            # inside shard_map
            tp_head_shards(cfg.num_heads, cfg.kv_heads, tp)
        self.mesh = mesh
        self.tp = tp
        self.model = model
        self.max_slots = int(max_slots)
        self.max_seq_len = int(cfg.max_seq_len)
        self.vocab_size = int(cfg.vocab_size)
        self._chunk = int(prefill_chunk)
        self.share_prefixes = bool(share_prefixes)
        self.spec_tokens = int(spec_tokens)
        self.spec_ngram = int(spec_ngram)
        #: the drafter — swapped for a forced-draft stub during warmup
        #: so the spec executable is traced even when the dummy context
        #: has no n-gram hit
        self._drafter = prompt_lookup_draft
        from apex_tpu.ops import autotune
        from apex_tpu.ops.paged_attention import (
            kv_quant_spec, kv_store_bytes_per_token)
        # autotune entries are keyed on the PER-SHARD kv_heads count:
        # a TP engine's decode step gathers kv_heads/tp heads' pages
        # per chip, so it must never adopt a block size swept at full
        # head count (and vice versa)
        shard_kv_heads = int(cfg.kv_heads) // self.tp
        if kv_dtype == "auto":
            # adopt the (block_size, kv_dtype) pair a joint
            # tune_paged_attention sweep measured best — only together
            # with block_size=0 (an explicit block size means the
            # caller is overriding the tuner, so we don't silently
            # flip their numerics either)
            pair = (autotune.cached_paged_pair(
                int(cfg.head_dim), str(jnp.dtype(cfg.dtype)),
                kv_heads=shard_kv_heads)
                if block_size == 0 else None)
            kv_dtype = pair[1] if pair else None
            if pair and block_size == 0:
                block_size = pair[0]
        store_dt, _qmax = kv_quant_spec(kv_dtype)   # validates name
        self.kv_dtype = kv_dtype
        #: pool storage bits per K/V element (metrics/health gauge)
        self.kv_bits = 8 * (jnp.dtype(cfg.dtype).itemsize
                            if store_dt is None
                            else jnp.dtype(store_dt).itemsize)
        if block_size == 0:
            # per-dtype lookup: a quantized pool's measured best block
            # size is cached under its STORAGE dtype
            key_dt = (str(jnp.dtype(cfg.dtype)) if store_dt is None
                      else str(jnp.dtype(store_dt)))
            block_size = autotune.cached_block_rows(
                "paged_attention", int(cfg.head_dim), key_dt,
                kv_heads=shard_kv_heads) or 16
        if block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {block_size}")
        self.block_size = int(block_size)
        if pool_tokens is None:
            pool_tokens = self.max_slots * self.max_seq_len
            if store_dt is not None:
                # equal-HBM default: the byte budget of
                # max_slots × max_seq_len tokens at the compute
                # dtype buys ~itemsize× the QUANTIZED tokens, scale
                # overhead included — the reclaimed HBM becomes
                # admitted occupancy instead of idle savings (same
                # formula the bench traffic model counts with)
                unq = kv_store_bytes_per_token(
                    cfg.head_dim, self.block_size, dtype=cfg.dtype)
                qnt = kv_store_bytes_per_token(
                    cfg.head_dim, self.block_size, kv_dtype)
                pool_tokens = int(pool_tokens * unq / qnt)
        # the pool bounds the largest ADMISSIBLE request
        # (validate_request rejects anything that could never fit
        # alone); the floor here only covers the warmup tenants — the
        # drafted warmup pass admits chunk+1 prompt tokens with a
        # 2 + spec_tokens budget, so the floor grows with K
        min_tokens = min(self._chunk + 3 + self.spec_tokens,
                         self.max_seq_len)
        if pool_tokens < min_tokens:
            raise ValueError(
                f"pool_tokens ({pool_tokens}) must cover at least the "
                f"warmup tenant ({min_tokens} tokens)")
        num_blocks = slot_cache.blocks_for(pool_tokens,
                                           self.block_size) + 1
        self._alloc = slot_cache.BlockAllocator(num_blocks,
                                                self.block_size)
        self._trie = slot_cache.PrefixTrie()
        #: the lanes a mixed step computes (T, static): half of its
        #: ``max_slots × prefill_chunk``, rounded up to a multiple of
        #: 128, and never fewer than the oldest prompt's chunk beside
        #: every other row decoding; the planner feeds at most this
        #: many real lanes (:meth:`_waiting_rows`)
        rows = self.max_slots * self._chunk
        self.lane_budget = min(rows, max(-(-rows // 256) * 128,
                                         self.max_slots - 1 + self._chunk))
        #: lifetime counters (gauges ride health()/metrics)
        self.cow_forks = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        #: pages the attention kernels' sweeps had to visit, summed
        #: over steps and rows (an empty row sweeps the null page)
        self.kv_pages_live = 0
        #: pages the chunk write of a step wider than one token
        #: touches: the pages spanned by [cursor, cursor + n_tokens)
        #: of every live row (a width-1 step writes inside its
        #: attention kernel and counts none)
        self.kv_write_pages = 0
        #: the width of the model's window layers (``cfg.kv_window``;
        #: None: it has none) and the pages ONE such layer's sweeps had
        #: to visit, the same sum as ``kv_pages_live`` from the window's
        #: first page on: what the window saves is the difference
        self.window = getattr(cfg, "kv_window", None)
        self.kv_window_pages = 0
        self._headroom = (2 * self.block_size if admit_headroom is None
                          else int(admit_headroom))
        self._variables = dict(params)
        if "cache" in self._variables:
            raise ValueError(
                "params must not carry a 'cache' collection — the "
                "engine owns the cache pool")
        # the paged twin: same parameters, paged cache layout — the
        # layout is part of the module hash, so its executables can
        # never collide with those of the model as given (whose
        # dense cache is generate()'s) in any jit cache
        self._paged_model = type(model)(cfg=dataclasses.replace(
            cfg, kv_cache="paged", kv_block_size=self.block_size,
            kv_pool_blocks=num_blocks, kv_dtype=self.kv_dtype,
            kv_mesh=self.mesh,
            kv_shard_axis=(TENSOR_AXIS if self.mesh is not None
                           else None)))
        shapes = cache_shapes(self._paged_model, self.max_slots)
        # recurrent state beside the pages (a state-space mixer's
        # ``ssm_state`` / ``conv_state``, a slot a row): what of it a
        # slot holds is a function of EVERY token the row has seen, so
        # it cannot be shared by page, rolled back over a rejected
        # draft or split over kv heads the way K/V rows can
        self.ssm_state_bytes = slot_cache.recurrent_state_bytes(shapes)
        if self.ssm_state_bytes:
            for on, what, why in (
                    (self.share_prefixes, "share_prefixes=True",
                     "a shared prompt page would need a SNAPSHOT of "
                     "the recurrent state at its last token, and no "
                     "state snapshot exists"),
                    (self.spec_tokens, f"spec_tokens={self.spec_tokens}",
                     "a rejected draft would need the recurrent state "
                     "ROLLED BACK to a snapshot before it, and no "
                     "state snapshot exists"),
                    (self.mesh is not None, "mesh=",
                     "no SHARDING of the recurrent state over the "
                     "tensor axis exists")):
                if on:
                    raise ValueError(
                        f"PagedEngine: {what} is not supported for a "
                        f"model with recurrent state — {why}; serve "
                        "it with share_prefixes=False, spec_tokens=0, "
                        "mesh=None")
        # an expert layer reports the assignments its held experts got
        # a step (``expert_counts``, a layer); the chip's share of the
        # experts has no sharding over the tensor axis
        counts = slot_cache.expert_count_leaves(shapes)
        self.expert_layers = len(counts)
        self._expert_cells = sum(int(np.prod(c.shape)) for c in counts)
        if self.expert_layers and self.mesh is not None:
            raise ValueError(
                "PagedEngine: mesh= is not supported for a model with "
                "an expert share — the experts held here are one chip's "
                "part of an EXPERT-parallel layer, and no sharding of "
                "them over the tensor axis exists; serve it with "
                "mesh=None")
        #: over steps and expert layers (lifetime; 0 without experts):
        #: assignments that landed on held experts, the fullest held
        #: expert's count, the held experts that got any, and the
        #: layer-steps summed over
        self.expert_assignments = 0
        self.expert_load_max = 0
        self.experts_active = 0
        self.expert_layer_steps = 0
        #: rows started from zero state (admissions, and re-admissions
        #: after a preemption) and real lanes advanced through the
        #: recurrence, summed over steps and rows, a step counted once
        #: and not a layer (lifetime; 0 without recurrent state)
        self.ssm_state_resets = 0
        self.ssm_positions = 0
        #: over mixed steps (lifetime): the real lanes fed, the lanes
        #: computed (``lane_budget`` a step) and the prefilling rows the
        #: budget left waiting a step
        self.mixed_lanes_real = 0
        self.mixed_lanes_computed = 0
        self.prefill_rows_deferred = 0
        self.cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        self.state = slot_cache.init_slot_state(self.max_slots)
        if self.mesh is not None:
            # commit the replica onto its mesh: weights per their
            # GSPMD annotations, the pool sharded on kv_heads, block
            # tables / cursors / slot state replicated.  The step
            # functions pin their outputs to the SAME placement, so
            # shardings reach a fixed point and the retrace budgets
            # of 1 hold exactly as on one chip.
            self._variables = _shard_params_for_tp(self._variables,
                                                   self.mesh)
            self.cache = slot_cache.shard_paged_cache(
                self.cache, self.mesh, TENSOR_AXIS)
            self.state = jax.device_put(
                self.state, jax.sharding.NamedSharding(
                    self.mesh, jax.sharding.PartitionSpec()))
        mb = slot_cache.blocks_for(self.max_seq_len, self.block_size)
        self._tables = np.zeros((self.max_slots, mb), np.int32)
        self._cursors = np.zeros((self.max_slots,), np.int32)
        self._tenants: List[Optional[_Tenant]] = [None] * self.max_slots
        self._admit_seq = 0
        #: admissions the device has not seen yet, slot -> (ints,
        #: floats) columns of ``admit_slots``: the next step installs
        #: them all in one call (64 arrivals at once cost one, not 64)
        self._pending: Dict[int, Tuple[tuple, tuple]] = {}
        #: steps dispatched and not yet collected, oldest first (two at
        #: most: the one about to be fetched and one behind it)
        self._flights: Deque[_Flight] = deque()
        #: steps dispatched while their predecessor was unfetched
        self.steps_ahead = 0
        self.spans = SpanTotals((STEP_PREFILL, STEP_DECODE, STEP_SPEC,
                                 PLAN, DISPATCH, FETCH, COMMIT))
        self._build()

    # ------------------------------------------------------------- jits
    def _build(self) -> None:
        model = self._paged_model
        vocab = self.vocab_size
        mesh = self.mesh

        def pin_out(cache, state):
            # TP fixed point: outputs land exactly where the inputs
            # were committed (pool on kv_heads, everything else
            # replicated), so feeding them back never changes the jit
            # signature — the retrace budgets of 1 stay exact
            if mesh is None:
                return cache, state
            return (slot_cache.constrain_paged_cache(
                        cache, mesh, TENSOR_AXIS),
                    _pin_replicated(state, mesh))

        def fetched(cache, *columns):
            # ONE int32 array for the step's one fetch — every array
            # the host reads back is a round trip of its own: the
            # per-slot columns, slot-major, then every expert layer's
            # counts (a model without experts: nothing)
            columns = [c.reshape(c.shape[0], -1).astype(jnp.int32)
                       for c in columns]
            experts = slot_cache.expert_counts(cache)
            tail = ([] if experts is None
                    else [experts.reshape(-1).astype(jnp.int32)])
            return jnp.concatenate(
                [jnp.concatenate(columns, axis=1).reshape(-1)] + tail)

        slots, pages = self._tables.shape
        budget = self.lane_budget

        def fed(packed, n_flags):
            # ``_packed``'s array taken apart: block tables, cursors,
            # the feed — its width is what is left —, n_tokens, then
            # ``n_flags`` rows of flags
            w = packed.shape[0] // slots - pages - 2 - n_flags
            sizes = (slots * pages, slots, slots * w, slots) \
                + (slots,) * (n_flags - 1)
            tables, cursors, feed, n_tokens, *flags = jnp.split(
                packed, np.cumsum(sizes))
            return (tables.reshape(slots, pages), cursors,
                    feed.reshape(slots, w), n_tokens,
                    *[f != 0 for f in flags])

        def step_fn(variables, cache, state, packed):
            tables, cursors, feed, n_tokens, is_prefill, emit = fed(
                packed, 2)
            # the host-authoritative block tables / cursors overwrite
            # their cache leaves (the model never advances them);
            # n_tokens doubles as the quantized pool's chunk_lens so
            # pad lanes can't pollute page scales
            cache = slot_cache.set_paged_leaves(cache, tables, cursors,
                                                n_tokens)
            # one ragged-batch application: prefilling rows feed their
            # chunk, decoding rows their last sampled token (+ pad)
            tok_ids = jnp.zeros_like(feed).at[:, 0].set(state.tok)
            ids = jnp.where(is_prefill[:, None], feed, tok_ids)
            lanes = None
            if feed.shape[1] > 1:
                # the mixed step computes its real lanes only — a live
                # row's first n_tokens — and the head each row's last
                real = (jnp.arange(feed.shape[1])[None]
                        < n_tokens[:, None]) & (is_prefill | emit)[:, None]
                lanes = Lanes.of(real, budget)
            logits, cache = apply_decode(model, variables, cache, ids,
                                         lanes)
            last = logits[:, 0]
            split = jax.vmap(jax.random.split)(state.rng)
            # fused decode epilogue (see ops/fused_sampling): the
            # Pallas kernel reads the (slots, vocab) logits once on
            # TPU; the XLA reference is the historical sample_dynamic.
            # Released slots' stale filter params are masked so the
            # sort short-circuit tracks live traffic.
            temp, top_k, top_p = _active_sampling_params(state)
            nxt = fused_sample(last, split[:, 0], temp, top_k, top_p,
                               vocab_size=vocab)
            # emission is gated on the host plan: a mid-prefill tenant
            # computes but emits nothing, and its rng does NOT advance
            # — the k-th produced token always uses the k-th split, so
            # sampled chains are invariant to chunking
            # — and on the device's own active bit: a row that finished
            # under a step in flight was planned once more and emits
            # nothing; the host reads the mask back with the tokens
            emit = emit & state.active
            produced = state.produced + emit.astype(jnp.int32)
            hit_budget = produced >= state.budget
            hit_eos = (state.eos_id >= 0) & (nxt == state.eos_id)
            finished = emit & (hit_budget | hit_eos)
            state = state._replace(
                tok=jnp.where(emit, nxt, state.tok),
                produced=produced,
                active=state.active & ~finished,
                rng=jnp.where(emit[:, None], split[:, 1], state.rng))
            cache, state = pin_out(cache, state)
            return cache, state, fetched(cache, nxt, emit, finished)

        spec_w = 1 + self.spec_tokens

        def spec_step_fn(variables, cache, state, packed):
            tables, cursors, feed, n_tokens, emit = fed(packed, 1)
            # the draft/verify step: every active row decodes — feed
            # row i is [current_tok, d_1..d_k, pad] with n_tokens[i] =
            # 1 + k real tokens.  ONE model application scores all
            # positions; write-then-attend puts the drafts' K/V in the
            # pool first, and the absolute-position mask gives each
            # draft exactly its sequential context.
            cache = slot_cache.set_paged_leaves(cache, tables, cursors,
                                                n_tokens)
            ids = feed.at[:, 0].set(state.tok)
            logits, cache = apply_decode(model, variables, cache, ids)
            # sequential rng chain: position j samples with the j-th
            # split of the row's key — identical keys to j one-token
            # steps, which is what makes sampled chains
            # acceptance-invariant
            chain = state.rng
            keys, chains = [], [chain]
            for _ in range(spec_w):
                split = jax.vmap(jax.random.split)(chain)
                keys.append(split[:, 0])
                chain = split[:, 1]
                chains.append(chain)
            # ONE width-axis fused-epilogue call scores all 1+K
            # positions (the old path paid spec_w separate sorted
            # sampling tails in this executable); per-position keys
            # ride the width axis, per-slot params broadcast —
            # released slots masked, as in the plain step
            temp, top_k, top_p = _active_sampling_params(state)
            sampled = fused_sample(
                logits[:, :spec_w], jnp.stack(keys, axis=1),
                temp, top_k, top_p,
                vocab_size=vocab)                     # (slots, w)
            idx = jnp.arange(spec_w, dtype=jnp.int32)
            # draft j+1 accepted iff it equals the token the model
            # would have sampled at its position — the longest
            # accepted prefix reproduces the sequential chain exactly
            match = (sampled[:, :-1] == feed[:, 1:]) \
                & (idx[None, 1:] < n_tokens[:, None])
            accept = jnp.sum(jnp.cumprod(
                match.astype(jnp.int32), axis=1), axis=1)
            n_emit = jnp.minimum(accept + 1, n_tokens)
            eos_hit = (state.eos_id[:, None] >= 0) \
                & (sampled == state.eos_id[:, None])
            eos_pos = jnp.min(jnp.where(eos_hit, idx[None, :], spec_w),
                              axis=1)
            n_emit = jnp.minimum(n_emit, eos_pos + 1)
            remaining = jnp.maximum(state.budget - state.produced, 0)
            n_emit = jnp.minimum(n_emit, remaining)
            n_emit = jnp.where(emit & state.active, n_emit, 0)
            produced = state.produced + n_emit
            hit_budget = produced >= state.budget
            hit_eos = eos_pos < n_emit
            finished = (n_emit > 0) & (hit_budget | hit_eos)
            last = jnp.take_along_axis(
                sampled, jnp.maximum(n_emit - 1, 0)[:, None],
                axis=1)[:, 0]
            # rng advance is emission-gated per TOKEN: exactly n_emit
            # splits are consumed, like n_emit one-token steps
            new_rng = jnp.take_along_axis(
                jnp.stack(chains, axis=1), n_emit[:, None, None],
                axis=1)[:, 0]
            state = state._replace(
                tok=jnp.where(n_emit > 0, last, state.tok),
                produced=produced,
                active=state.active & ~finished,
                rng=new_rng)
            cache, state = pin_out(cache, state)
            return cache, state, fetched(cache, sampled, n_emit, finished)

        def admit(state, ints, floats):
            state = slot_cache.admit_slots(state, ints, floats)
            return (state if mesh is None
                    else _pin_replicated(state, mesh))

        def release(state, slot):
            state = slot_cache.release_slot(state, slot)
            return (state if mesh is None
                    else _pin_replicated(state, mesh))

        # exact budgets: decode/spec/admit/release = 1 and every
        # prompt length rides ONE mixed-step shape — any excess trace
        # raises RetraceError
        self._decode = tracecheck.retrace_guard(
            step_fn, max_traces=1, name="serving.decode_step",
            donate_argnums=(1, 2))
        self._prefill = tracecheck.retrace_guard(
            step_fn, max_traces=1, name="serving.prefill_step",
            donate_argnums=(1, 2))
        self._spec = tracecheck.retrace_guard(
            spec_step_fn, max_traces=1, name="serving.spec_step",
            donate_argnums=(1, 2))
        self._admit = tracecheck.retrace_guard(
            admit, max_traces=1, name="serving.admit",
            donate_argnums=(0,))
        self._release = tracecheck.retrace_guard(
            release, max_traces=1, name="serving.release",
            donate_argnums=(0,))

    # ------------------------------------------------------------- host
    def validate_request(self, prompt_len: int, max_new_tokens: int,
                         temperature: float = 0.0,
                         top_k: Optional[int] = None,
                         top_p: Optional[float] = None) -> None:
        """Static admission checks (chunked prefill admits any prompt
        length that fits the cache and the pool)."""
        if prompt_len < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if prompt_len + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt_len ({prompt_len}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq_len "
                f"({self.max_seq_len})")
        need = slot_cache.blocks_for(prompt_len + max_new_tokens,
                                     self.block_size)
        if need > self._alloc.blocks_total:
            raise ValueError(
                f"request needs {need} pages "
                f"({prompt_len}+{max_new_tokens} tokens at "
                f"block_size={self.block_size}) but the whole pool "
                f"holds {self._alloc.blocks_total} — raise pool_tokens")
        _check_sampling(self.vocab_size, top_k, top_p)
        del temperature

    def _sharable_blocks(self, prompt: np.ndarray,
                         digests: Optional[List[bytes]] = None) -> int:
        """Trie-matched prompt blocks this prompt could map, CAPPED so
        at least the final prompt token is always re-fed (the logits
        source): a whole-prompt hit drops its last block — that block
        is re-derived into a private page (the copy-on-write fork)."""
        if not self.share_prefixes:
            return 0
        if digests is None:
            digests = slot_cache.chain_digests(prompt, self.block_size)
        matched = len(self._trie.match(digests))
        return min(matched,
                   (int(prompt.size) - 1) // self.block_size)

    def prefix_hit_blocks(self, prompt) -> int:
        """Pages of ``prompt``'s prefix already resident in this
        engine's trie (0 with sharing off) — the fleet router's
        prefix-affinity routing key, and the admission discount."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        return self._sharable_blocks(prompt)

    def can_admit(self, prompt_len: int, max_new_tokens: int,
                  prompt=None) -> bool:
        """Token-budget admission gate: free pages must cover the
        prompt plus reserved decode headroom (preemption backstops the
        deliberate overcommit beyond the headroom).  SHARED-aware when
        the caller passes the prompt tokens: trie-resident prefix
        pages cost nothing new, so reclaimed pool capacity converts
        directly into admitted occupancy."""
        shared = 0
        if prompt is not None and self.share_prefixes:
            shared = self.prefix_hit_blocks(prompt)
        need = slot_cache.blocks_for(
            prompt_len + min(int(max_new_tokens), self._headroom),
            self.block_size) - shared
        return self._alloc.blocks_free >= need

    def admit(self, slot: int, prompt, *, max_new_tokens: int,
              temperature: float = 0.0, top_k: Optional[int] = None,
              top_p: Optional[float] = None,
              eos_id: Optional[int] = None, seed: int = 0) -> None:
        """Install one request into a free slot.  NO prefill happens
        here — the prompt rides the next steps as chunks; no pages are
        allocated either (the step loop extends tables just ahead of
        the tokens it writes).  With ``share_prefixes``, trie-resident
        prompt-prefix pages ARE mapped here (refcounted, read-only):
        ``fed``/``cursor`` start past them, so their KV is neither
        recomputed nor re-stored.  No device call either: the slot's
        row of the device state (token, budget, sampling, key) waits in
        ``_pending`` and the next step's dispatch installs every
        waiting row in ONE call of the ``admit`` executable — a burst
        of 64 arrivals costs one call, not 64."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.validate_request(prompt.shape[0], max_new_tokens,
                              temperature, top_k, top_p)
        if not 0 <= slot < self.max_slots:
            raise ValueError(
                f"slot must be in [0, {self.max_slots}), got {slot}")
        if self._tenants[slot] is not None:
            raise ValueError(f"slot {slot} is occupied (paged "
                             "admission never silently replaces — the "
                             "tenant owns pool pages)")
        self._admit_seq += 1
        rec = _Tenant(prompt=prompt, seq=self._admit_seq,
                      budget=int(max_new_tokens))
        if self.share_prefixes:
            rec.digests = slot_cache.chain_digests(prompt,
                                                   self.block_size)
            matched = self._trie.match(rec.digests)
            # same cap as _sharable_blocks, without a second trie walk
            n_share = min(len(matched),
                          (int(prompt.size) - 1) // self.block_size)
            if len(matched) > n_share:
                # whole-prompt hit: the dropped tail block will be
                # re-derived into a private page (CoW fork by
                # recompute — see the class docstring)
                self.cow_forks += 1
                counters.inc("serving.cow_fork")
            for page in matched[:n_share]:
                self._alloc.incref(page)
            rec.blocks = list(matched[:n_share])
            self._tables[slot, :n_share] = rec.blocks
            rec.fed = rec.cursor = n_share * self.block_size
            rec.registered = n_share
            self._cursors[slot] = rec.cursor
        self._tenants[slot] = rec
        self._pending[slot] = (
            (1, int(prompt[-1]), int(max_new_tokens), int(top_k or 0),
             -1 if eos_id is None else int(eos_id),
             int(np.array(seed, np.uint32).view(np.int32))),
            (float(temperature), 0.0 if top_p is None else float(top_p)))

    def _install_admissions(self) -> None:
        """Hand the device every admission since the last step, in one
        call of the ``admit`` executable."""
        if not self._pending:
            return
        ints = np.zeros((6, self.max_slots), np.int32)
        floats = np.zeros((2, self.max_slots), np.float32)
        for slot, (i, f) in self._pending.items():
            ints[:, slot] = i
            floats[:, slot] = f
        self._pending.clear()
        self.state = self._admit(self.state, ints, floats)

    def _youngest(self) -> int:
        live = [s for s, t in enumerate(self._tenants) if t is not None]
        return max(live, key=lambda s: self._tenants[s].seq)

    def _free_tenant(self, slot: int) -> None:
        """Return a tenant's pages and clear its host/device state.
        The pool itself is untouched: freed pages are garbage until
        their next owner overwrites them, and the position mask keeps
        garbage unreachable."""
        rec = self._tenants[slot]
        if rec is not None:
            # refcounted free: shared prefix pages survive until their
            # LAST tenant leaves; pages that actually returned to the
            # pool drop out of the trie (it only indexes live KV)
            for page in self._alloc.free(rec.blocks):
                self._trie.forget(page)
            self._tables[slot] = 0
            self._cursors[slot] = 0
            self._tenants[slot] = None
        if self._pending.pop(slot, None) is not None:
            return              # the device never saw this tenant
        self.state = self._release(self.state, np.int32(slot))

    def _read_only(self, page: int) -> bool:
        """A page no tenant may write: mapped by >1 tenant, or indexed
        by the trie (a future tenant may map it any time)."""
        return (self._alloc.refcount(page) > 1
                or self._trie.holds_block(page))

    def _growth(self, rec: _Tenant, n: int) -> int:
        """Pages ``rec``'s table lacks for its next ``n`` tokens —
        capped at the table width: a finished-but-unreleased tenant
        stepped past max_seq_len (possible in raw engine drivers; the
        scheduler releases at the finish boundary) wraps within its
        last page instead of growing the table."""
        return min(slot_cache.blocks_for(rec.cursor + n, self.block_size),
                   self._tables.shape[1]) - len(rec.blocks)

    def _extend(self, slot: int, n: int,
                preempted: List[int]) -> None:
        """Grow ``slot``'s block table to cover its next ``n`` real
        tokens, preempting the youngest tenant on exhaustion.  A
        request is admission-validated to fit the whole pool alone, so
        the loop terminates: in the worst case everyone else (and
        finally the needy slot itself) is preempted.

        Copy-on-write guard: the write range must never touch a
        READ-ONLY page.  By construction it cannot land mid-block in
        one (admission always leaves shared prefixes at a block
        boundary and re-derives a whole-prompt hit's tail block), so
        the only live case is an exact-boundary fork — swap in a fresh
        private page with nothing to copy — and exhaustion there
        preempts through the same loop as a plain extension."""
        rec = self._tenants[slot]
        while rec is not None and rec.cursor % self.block_size == 0:
            wb = rec.cursor // self.block_size
            if wb >= len(rec.blocks) \
                    or not self._read_only(rec.blocks[wb]):
                break
            try:
                got = self._alloc.alloc(1)
            except slot_cache.BlockExhausted:
                victim = self._youngest()
                self._free_tenant(victim)
                preempted.append(victim)
                if victim == slot:
                    return
                continue
            for page in self._alloc.free([rec.blocks[wb]]):
                self._trie.forget(page)
            rec.blocks[wb] = got[0]
            self._tables[slot, wb] = got[0]
            self.cow_forks += 1
            counters.inc("serving.cow_fork")
            break
        while rec is not None:
            need = self._growth(rec, n)
            if need <= 0:
                return
            try:
                got = self._alloc.alloc(need)
            except slot_cache.BlockExhausted:
                victim = self._youngest()
                self._free_tenant(victim)
                preempted.append(victim)
                if victim == slot:
                    return
                continue
            start = len(rec.blocks)
            self._tables[slot, start:start + len(got)] = got
            rec.blocks.extend(got)

    def _register_blocks(self, rec: _Tenant) -> None:
        """Offer a prefilling tenant's newly COMPLETED full prompt
        blocks to the trie: from the moment a block's last prompt
        token is fed (and therefore written), its page is finalized
        read-only KV any same-prefix admission may map."""
        full = min(int(rec.fed), int(rec.prompt.size)) \
            // self.block_size
        limit = min(full, len(rec.digests))
        while rec.registered < limit:
            i = rec.registered
            self._trie.register(rec.digests[i], rec.blocks[i])
            rec.registered += 1

    def _plan_drafts(self) -> List[Optional[np.ndarray]]:
        """Host-side draft proposal for every decoding row: up to
        ``spec_tokens`` prompt-lookup tokens, capped by the remaining
        budget (an accepted run emits ``drafts + 1`` tokens) and the
        cache envelope (every fed token is written at
        ``cursor + offset``)."""
        drafts: List[Optional[np.ndarray]] = [None] * self.max_slots
        for slot, rec in enumerate(self._tenants):
            if rec is None:
                continue
            cap = min(self.spec_tokens,
                      rec.budget - rec.emitted - 1,
                      self.max_seq_len - rec.cursor - 1)
            if cap < 1:
                continue
            context = rec.prompt
            if rec.gen:
                context = np.concatenate(
                    [context, np.asarray(rec.gen, np.int32)])
            proposal = self._drafter(context, cap, self.spec_ngram)
            if proposal.size:
                drafts[slot] = proposal[:cap]
        return drafts

    def _packed(self, feed, n_tokens, *flags) -> np.ndarray:
        """ONE int32 array of everything the host hands a step — the
        block tables, the cursors, ``feed``, ``n_tokens`` and the flag
        rows, in the order the step takes them apart again: a step's
        arguments are transferred one by one, and each transfer costs
        the host the same quarter of a millisecond whatever its size
        (PERF.md section 6, PR 37)."""
        return np.concatenate(
            [self._tables.reshape(-1), self._cursors, feed.reshape(-1),
             n_tokens] + [f.astype(np.int32) for f in flags])

    def _waiting_rows(self, w: int) -> List[int]:
        """The prefilling rows a mixed step of width ``w`` leaves
        waiting: its real lanes — a decoding row's one, a prefilling
        row's chunk — may not pass ``lane_budget``, so the YOUNGEST
        prefilling rows wait a step (the oldest admitted go first, so
        no row starves; the budget always holds the oldest)."""
        lanes, prefilling = 0, []
        for slot, rec in enumerate(self._tenants):
            if rec is None:
                continue
            n = 1
            if rec.fed < rec.prompt.size:
                n = min(w, rec.prompt.size - rec.fed)
                prefilling.append((rec.seq, slot, n))
            lanes += n
        waiting = []
        for _, slot, n in sorted(prefilling, reverse=True):
            if lanes <= self.lane_budget:
                break
            lanes -= n
            waiting.append(slot)
        return waiting

    def _pages_short(self, w: int) -> bool:
        """Would a step of width ``w`` have to preempt?  The pages the
        live rows' next tokens need (:meth:`_extend`'s two demands: a
        private page in place of a read-only one at a block boundary,
        then the table's growth) against the free ones."""
        free = self._alloc.blocks_free
        # no row can ask for more than this: the usual case, no loop
        if free >= self.max_slots * (
                slot_cache.blocks_for(w, self.block_size) + 2):
            return False
        need = 0
        for rec in self._tenants:
            if rec is None:
                continue
            n = (min(w, rec.prompt.size - rec.fed)
                 if rec.fed < rec.prompt.size else 1)
            wb = rec.cursor // self.block_size
            if self.share_prefixes and rec.cursor % self.block_size == 0 \
                    and wb < len(rec.blocks) \
                    and self._read_only(rec.blocks[wb]):
                need += 1
            need += max(self._growth(rec, n), 0)
        return need > free

    @property
    def in_flight(self) -> int:
        """Steps dispatched and not yet collected (0, 1 or 2)."""
        return len(self._flights)

    def dispatch(self) -> bool:  # graftlint: hot-step
        """Plan one fused mixed prefill+decode step over every slot
        and enqueue its program; :meth:`collect` fetches what it made.

        Prefilling tenants consume their next prompt chunk (emitting a
        token only on the final chunk — that token IS the first
        generated one, sampled straight from the prefill logits);
        decoding tenants advance one token — or, in a drafted step
        (``spec_tokens > 0``, no prefill pending, at least one lookup
        hit), verify their draft run and emit the accepted prefix plus
        one bonus token.  Inactive slots compute garbage into the null
        page.  The host mirrors the next plan reads (cursors, ``fed``,
        the trie) advance here, by what the host fed; no host sync.

        With a step in flight this one runs AHEAD of its fetch.  That
        is refused — ``False``, nothing done — where the step in flight
        has to be collected first: it frees a slot or was drafted (its
        ``fence``), this one would be drafted, this one would have to
        preempt, or one step already runs ahead.
        """
        t_top = time.perf_counter()
        any_prefill = any(rec is not None
                          and rec.fed < rec.prompt.size
                          for rec in self._tenants)
        drafting = not any_prefill and self.spec_tokens > 0
        w = self._chunk if any_prefill else 1
        if self._flights and (
                len(self._flights) > 1 or self._flights[-1].fence
                or drafting or self._pages_short(w)):
            return False
        drafts: List[Optional[np.ndarray]] = [None] * self.max_slots
        if drafting:
            drafts = self._plan_drafts()
        any_spec = any(d is not None for d in drafts)
        if any_spec:
            w = 1 + self.spec_tokens
        # the step's span is named by the program it runs, which the
        # drafts decide: plan's seconds count from the top, the step's
        # are this half's and the collect half's, counted there
        kind = (STEP_SPEC if any_spec
                else STEP_PREFILL if any_prefill else STEP_DECODE)
        with jax.profiler.TraceAnnotation(kind):
            with span(self.spans, PLAN, since=t_top):
                feed = np.zeros((self.max_slots, w), np.int32)
                n_tokens = np.ones((self.max_slots,), np.int32)
                is_prefill = np.zeros((self.max_slots,), bool)
                emit = np.zeros((self.max_slots,), bool)
                preempted: List[int] = []
                waiting = (self._waiting_rows(w)
                           if any_prefill and w > 1 else [])
                for slot in range(self.max_slots):
                    rec = self._tenants[slot]
                    if rec is None:
                        continue
                    if slot in waiting:
                        # a waiting row feeds no lane and emits nothing:
                        # its cursor, pages and recurrent state stay
                        n_tokens[slot] = 0
                        continue
                    if rec.fed < rec.prompt.size:
                        n = min(w, rec.prompt.size - rec.fed)
                        feed[slot, :n] = rec.prompt[rec.fed:rec.fed + n]
                        n_tokens[slot] = n
                        is_prefill[slot] = True
                        emit[slot] = rec.fed + n >= rec.prompt.size
                    else:
                        emit[slot] = True
                        if drafts[slot] is not None:
                            d = drafts[slot]
                            feed[slot, 1:1 + d.size] = d
                            n_tokens[slot] = 1 + d.size
                    self._extend(slot, int(n_tokens[slot]), preempted)
                for slot in preempted:
                    feed[slot] = 0
                    n_tokens[slot] = 1
                    is_prefill[slot] = False
                    emit[slot] = False
                if any_prefill and w > 1:
                    self.mixed_lanes_real += int(
                        n_tokens[is_prefill | emit].sum())
                    self.mixed_lanes_computed += self.lane_budget
                    self.prefill_rows_deferred += len(waiting)
                if self.ssm_state_bytes:
                    live = np.fromiter(
                        (rec is not None for rec in self._tenants),
                        bool, self.max_slots) & (n_tokens > 0)
                    self.ssm_positions += int(n_tokens[live].sum())
                    self.ssm_state_resets += int(
                        (self._cursors[live] == 0).sum())
            with span(self.spans, DISPATCH):
                self._install_admissions()
                last_page = self._cursors // self.block_size
                self.kv_pages_live += int((last_page + 1).sum())
                if self.window is not None:
                    first_page = np.maximum(
                        self._cursors - self.window + 1, 0) \
                        // self.block_size
                    self.kv_window_pages += int(
                        (last_page - first_page + 1).sum())
                if w > 1:
                    live = [slot for slot, rec in enumerate(self._tenants)
                            if rec is not None and n_tokens[slot]]
                    first = self._cursors[live]
                    last = first + n_tokens[live] - 1
                    self.kv_write_pages += int(
                        (last // self.block_size
                         - first // self.block_size + 1).sum())
                if any_spec:
                    self.cache, self.state, out = self._spec(
                        self._variables, self.cache, self.state,
                        self._packed(feed, n_tokens, emit))
                else:
                    runner = self._prefill if any_prefill else self._decode
                    self.cache, self.state, out = runner(
                        self._variables, self.cache, self.state,
                        self._packed(feed, n_tokens, is_prefill, emit))
                # the program is queued: now the mirrors the NEXT plan
                # reads.  A plain step advances every row by what the
                # host fed; a drafted step's cursors wait for the
                # accepted counts (collect), and so does the next plan
                fence = any_spec
                if not any_spec:
                    for slot, rec in enumerate(self._tenants):
                        if rec is None:
                            continue
                        n = int(n_tokens[slot])
                        if is_prefill[slot]:
                            rec.fed += n
                            if self.share_prefixes:
                                self._register_blocks(rec)
                        rec.cursor += n
                        self._cursors[slot] = rec.cursor
                        if emit[slot]:
                            rec.planned += 1
                            fence |= rec.planned >= rec.budget
        if self._flights:
            self.steps_ahead += 1
        self._flights.append(_Flight(
            kind, out, list(self._tenants), n_tokens, tuple(preempted),
            fence, time.perf_counter() - t_top))
        return True

    def collect(self) -> StepOutput:  # graftlint: hot-step
        """Fetch the oldest step in flight: its tokens, counts and
        finished flags for the scheduler, and the host mirrors that
        need them.  The single per-step host sync lives here; while the
        host waits in it the chip may already run the step dispatched
        behind this one."""
        if not self._flights:
            raise RuntimeError("collect(): no step is in flight")
        flight = self._flights[0]
        recs, n_tokens = flight.recs, flight.n_tokens
        # one count a device step; its seconds are both halves'
        with span(self.spans, flight.kind,
                  since=time.perf_counter() - flight.host_s):
            with span(self.spans, FETCH):
                # graftlint: unsharded(the paged engine's single per-step host sync — emitted tokens feed the host tenant table, finished flags release slots, verified drafts' accepted-prefix lengths steer host-side cursors, an expert model's counts feed health())
                out = np.asarray(flight.out)
                # per slot: the step's tokens, how many of them the
                # device emitted, the finished flag
                rows = out[:out.size - self._expert_cells].reshape(
                    self.max_slots, -1)
                tokens, counts = rows[:, :-2], rows[:, -2]
                finished = rows[:, -1].astype(bool)
            with span(self.spans, COMMIT):
                if self.expert_layers:
                    experts = out[rows.size:].reshape(self.expert_layers, -1)
                    self.expert_assignments += int(experts.sum())
                    self.expert_load_max += int(experts.max(axis=1).sum())
                    self.experts_active += int((experts > 0).sum())
                    self.expert_layer_steps += experts.shape[0]
                for slot, rec in enumerate(recs):
                    if rec is None:
                        continue
                    kept = int(counts[slot])
                    if flight.kind == STEP_SPEC:
                        # keep only the verified prefix: the cursor
                        # rolls back over rejected draft tails, whose
                        # pool writes are position-masked garbage the
                        # next step overwrites
                        rec.cursor += kept
                        rec.planned += kept
                        if self._tenants[slot] is rec:
                            self._cursors[slot] = rec.cursor
                        proposed = int(n_tokens[slot]) - 1
                        if proposed > 0:
                            self.spec_proposed += proposed
                            self.spec_accepted += max(kept - 1, 0)
                    # host mirrors of the emission (the drafter's
                    # context and budget cap)
                    if kept:
                        rec.emitted += kept
                        rec.gen.extend(int(t) for t in tokens[slot, :kept])
            self._flights.popleft()
            return StepOutput(tokens, finished, counts > 0,
                              flight.preempted, counts)

    def step(self) -> StepOutput:
        """One step in lock-step: :meth:`dispatch`, then
        :meth:`collect` — for direct callers that want a step's output
        before they plan the next (warm-up, tests, batch scripts).
        Needs an empty pipeline: with a step in flight the output
        would be an older step's."""
        if self._flights:
            raise RuntimeError(
                "step() is dispatch() + collect() of ONE step: "
                f"{len(self._flights)} dispatched step(s) are still "
                "uncollected — collect() (or discard()) them first")
        self.dispatch()
        return self.collect()

    def discard(self) -> None:
        """Knowingly drop every step in flight unfetched — for a caller
        that has released every tenant those steps served (a drain, a
        shutdown): nobody is left to hand their tokens to."""
        self._flights.clear()

    def release(self, slot: int) -> None:
        """Free ``slot``: pages back to the pool (refcount-decremented
        — shared prefix pages survive their co-tenants), state
        cleared."""
        self._free_tenant(slot)

    def warmup(self) -> None:
        """Trace every executable: one dummy tenant whose prompt spans
        a full chunk plus a remainder (mixed prefill step) and then
        decodes (width-1 step); with ``spec_tokens`` on, a second
        tenant runs under a forced-draft stub so the drafted step is
        traced even though the dummy context has no n-gram hit.
        Steady state over ANY request mix is retrace-free afterwards —
        and guarded.

        Prompts clamp for small-context models (chunk width larger
        than the context is legal: real chunks are capped by the
        prompt; the executable widths traced are the same either
        way)."""
        drafter = self._drafter

        def run_one(plen: int, budget: int) -> None:
            self.admit(0, np.zeros((plen,), np.int32),
                       max_new_tokens=budget)
            while self._tenants[0] is not None:
                out = self.step()
                if bool(out.finished[0]):
                    break
            self.release(0)

        try:
            # pass 1: prefill + plain decode (drafts suppressed so the
            # width-1 executable is the one traced)
            self._drafter = lambda context, k, ngram: np.empty(
                (0,), np.int32)
            run_one(max(1, min(self._chunk + 1, self.max_seq_len - 2)),
                    2)
            if self.spec_tokens:
                # pass 2: forced drafts so the spec executable traces
                self._drafter = lambda context, k, ngram: np.zeros(
                    (k,), np.int32)
                run_one(
                    max(1, min(self._chunk + 1,
                               self.max_seq_len - 2 - self.spec_tokens)),
                    2 + self.spec_tokens)
        finally:
            self._drafter = drafter

    def compiled_step_text(self, *, prefill: bool = False) -> str:
        """Compiled text of the steady decode step (width 1) — with
        ``prefill``, of the mixed prefill step (chunk width) — at the
        engine's current arguments: what a caller reads to see which
        kernels are really in the program (``tpu_custom_call`` and the
        kernels' scope names).  Compiles a second copy of the
        executable; the retrace budgets are untouched."""
        runner = self._prefill if prefill else self._decode
        feed = np.zeros((self.max_slots, self._chunk if prefill else 1),
                        np.int32)
        ones = np.ones((self.max_slots,), np.int32)
        off = np.zeros((self.max_slots,), bool)
        return runner.lower(
            self._variables, self.cache, self.state,
            self._packed(feed, ones, off, off)).compile().as_text()

    # ------------------------------------------------------------ gauges
    @property
    def chips_per_replica(self) -> int:
        """Chips this ONE replica spans (the tensor-parallel degree;
        1 = the single-chip engine) — per-chip throughput in the
        Gemma-paper serving protocol divides by this."""
        return self.tp

    @property
    def mesh_shape(self) -> Optional[dict]:
        """``{axis: size}`` of the replica's mesh, or ``None`` on a
        single chip (health()/fleet merged-view field)."""
        if self.mesh is None:
            return None
        return {str(k): int(v) for k, v in dict(self.mesh.shape).items()
                if int(v) > 1}

    @property
    def blocks_total(self) -> int:
        return self._alloc.blocks_total

    @property
    def blocks_free(self) -> int:
        return self._alloc.blocks_free

    @property
    def blocks_in_use(self) -> int:
        return self._alloc.blocks_in_use

    @property
    def pool_tokens(self) -> int:
        return self._alloc.tokens_total

    @property
    def live_tokens(self) -> int:
        """Tokens currently written for live tenants (host-side view)
        — a finer utilization numerator than whole pages; surfaced in
        ``InferenceServer.health()``/metrics so a fleet router can see
        real load, not just page-granular occupancy."""
        return int(sum(t.cursor for t in self._tenants
                       if t is not None))

    @property
    def shared_blocks(self) -> int:
        """Physical pages currently mapped by more than one tenant."""
        return self._alloc.shared_blocks

    @property
    def blocks_saved(self) -> int:
        """Pool pages prefix sharing reclaims right now (Σ ref-1)."""
        return self._alloc.blocks_saved

    @property
    def trie_blocks(self) -> int:
        """Live pages indexed by the prefix trie (sharable)."""
        return len(self._trie)

    @property
    def spec_accept_rate(self) -> float:
        """Fraction of proposed draft tokens the verify step accepted
        (lifetime; 0.0 before any drafted step)."""
        if not self.spec_proposed:
            return 0.0
        return self.spec_accepted / self.spec_proposed

    @property
    def trace_counts(self) -> dict:
        """Observed traces per executable (diagnostics / tests).  The
        ``spec_step`` entry appears only when speculative decoding is
        configured — the documented budget is 4 executables, + 1 with
        drafting on."""
        out = {
            "decode_step": self._decode.trace_count,
            "prefill_step": self._prefill.trace_count,
            "admit": self._admit.trace_count,
            "release": self._release.trace_count,
        }
        if self.spec_tokens:
            out["spec_step"] = self._spec.trace_count
        return out
