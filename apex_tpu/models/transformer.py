"""Parallel transformer core — the flagship model building block.

Reference: ``apex/transformer/testing/{standalone_gpt,standalone_bert}.py``
(toy Megatron models the reference's test suite trains) and the layer
recipe of SURVEY.md §3.4: pre-LN → ColumnParallel qkv → RoPE → fused
attention → RowParallel out → residual → pre-LN → ColumnParallel h→ffn
(+GeLU) → RowParallel ffn→h → residual, with ``sequence_parallel``
sharding the LN/residual activations along the sequence.

TPU-first shape: one flax module family under GSPMD — weights carry
``nn.with_partitioning`` specs over the ``tensor`` mesh axis, activations
get ``with_sharding_constraint`` hints, and XLA inserts the same
all-gather/reduce-scatter pairs the reference hand-codes.  Layers are
stacked with ``nn.scan`` (one trace/compile for N layers) and optionally
``nn.remat`` (activation checkpointing ≙
``tensor_parallel.random.checkpoint``, SURVEY.md §2.6 — RNG replay is
free because everything is functional).  A ``decode=True`` application
loops over the layers instead (:func:`decode_layers`): its cache is one
subtree a layer, so the serving pools are updated in place.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.core.mesh import TENSOR_AXIS
from apex_tpu.ops.attention import fused_attention
from apex_tpu.ops.layer_norm import fused_layer_norm, fused_rms_norm
from apex_tpu.ops.paged_attention import (
    kv_quant_spec,
    paged_attention,
    paged_decode_fused,
    paged_write,
    quantize_kv,
    rope_rows as _rope_rows,
    tp_head_shards,
)
from apex_tpu.ops.mlp import resolve_activation
from apex_tpu.ops.rope import fused_rope, rope_cos_sin
from apex_tpu.transformer.layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    maybe_constrain,
)

__all__ = ["TransformerConfig", "ParallelTransformerLayer",
           "ParallelTransformer", "ParallelMLP", "ParallelAttention",
           "Lanes", "decode_layers", "parameters_only"]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Architecture + parallelism knobs shared by the model zoo."""

    vocab_size: int = 50304
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    num_kv_heads: Optional[int] = None      # GQA; None = num_heads
    # width of one attention head where the model states it; None = the
    # classic hidden_size // num_heads.  Models whose attention is
    # narrower than the residual stream state it (Falcon-H1: 20 heads
    # x 128 beside a hidden size of 5120).  Read it as ``cfg.head_dim``.
    kv_channels: Optional[int] = None
    ffn_hidden_size: Optional[int] = None   # None = 4*hidden
    max_seq_len: int = 2048
    # positional scheme: "rope" (GPT-NeoX/Llama) or "learned" (BERT/GPT-2)
    position_embedding: str = "rope"
    rotary_pct: float = 1.0
    rope_base: float = 10000.0
    norm: str = "layernorm"                 # or "rmsnorm"
    layernorm_eps: float = 1e-5
    causal: bool = True
    hidden_dropout: float = 0.0
    attention_dropout: float = 0.0
    activation: str = "gelu"
    # biases on every linear (qkv/out/mlp) — Megatron's add_bias_linear;
    # False for the Llama recipe
    add_bias_linear: bool = True
    # sliding-window attention (Mistral-style; requires causal): each
    # query attends to the last `sliding_window` positions only.  The
    # flash kernel enumerates just the in-band tiles, so long-sequence
    # attention cost scales with window/seq, not seq.
    sliding_window: Optional[int] = None
    # gated-linear-unit MLP (SwiGLU when activation="silu"):
    # act(x·W_gate) * (x·W_up) -> RowParallel down-projection.  The gate
    # and up projections are separate ColumnParallel weights sharded
    # identically, so the elementwise product stays shard-local under TP.
    gated_mlp: bool = False
    # muP-style branch multipliers that sit INSIDE a shared module (the
    # ones around a module are the calling block's): on the keys after
    # the qkv projection, and on the gate's pre-activation in a gated
    # MLP.  1.0 leaves the classic recipes untouched.
    key_multiplier: float = 1.0
    mlp_gate_multiplier: float = 1.0
    # what the afmoe family's attention adds (models/afmoe.py), stated
    # like the fields above: a sigmoid gate on the heads' output,
    # ``W_o (o * sigmoid(W_g x))`` with ``W_g`` hidden -> heads x
    # head_dim and no bias, and an RMS norm over each query and key
    # head's channels (one learned gain vector each, eps
    # ``layernorm_eps``) before the rotation.  A stack whose layers
    # differ in ``sliding_window`` / ``position_embedding`` gives each
    # layer a config of its own (``dataclasses.replace``): neither
    # field shapes a parameter.
    attn_gate: bool = False
    qk_norm: bool = False
    # Mixture-of-Experts FFN (Mixtral-style; beyond-reference — the
    # reference has no EP, SURVEY §2.6 checklist): replaces every
    # layer's dense MLP with `num_moe_experts` experts under top-k
    # token-choice routing (transformer/moe.py — capacity-bounded
    # GShard dispatch; experts shard over `moe_expert_axis` and GSPMD
    # inserts the token all-to-all).  The per-layer load-balance aux
    # loss is sown into the "losses" collection: apply with
    # mutable=["losses"] and add `models.moe_aux_loss(mutated)` to the
    # task loss.  gated_mlp/activation apply to the experts too.
    num_moe_experts: Optional[int] = None
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_weight: float = 1e-2
    moe_expert_axis: Optional[str] = TENSOR_AXIS
    # parallel / compile behavior
    sequence_parallel: bool = False
    remat: bool = False
    # jax.checkpoint policy when remat=True: a jax.checkpoint_policies
    # attr name ("nothing_saveable" = full recompute, min memory;
    # "dots_with_no_batch_dims_saveable" = save GEMM outputs), or
    # "save_only:<name>[,<name>...]" to keep just the named residuals
    # (e.g. "save_only:attn_out" skips recomputing attention in bwd for
    # b·s·h bf16 per layer of memory).
    remat_policy: str = "nothing_saveable"
    # with remat=True and unrolled layers: every k-th layer skips remat
    # entirely (keeps activations, no backward recompute) — 0 disables
    remat_skip_every: int = 0
    # dense-cache steady-decode attention implementation: "einsum"
    # (one-shot masked einsum over the whole cache), "blocked"
    # (online-softmax scan that skips blocks past the live prefix), or
    # "auto" (blocked from 2048 cache slots up — the measured winner,
    # BASELINE.md round 5).  A config field, NOT an env var: the choice
    # is part of the module hash and therefore of every jit/lru cache
    # key, so A/B flips retrace instead of silently replaying the old
    # executable (ADVICE round 5; graftlint env-read-in-trace).
    decode_attn: str = "auto"
    # decode KV-cache layout: "dense" (one (b, max_seq_len, kv_heads,
    # d) slab per layer, the generate()/slotted-engine substrate) or
    # "paged" (a shared (kv_heads, kv_pool_blocks, kv_block_size, d)
    # page pool per layer + per-row block tables/cursors riding the
    # cache collection — the serving engine's token-granular layout;
    # attention goes through ops.paged_attention and positions are
    # per-ROW, so one application serves a ragged batch of tenants).
    # Only apex_tpu.serving.PagedEngine drives the paged mode; block 0
    # of every pool is the null page pad-token writes land in.
    kv_cache: str = "dense"
    kv_block_size: int = 16                 # tokens per page (paged)
    kv_pool_blocks: int = 0                 # pool pages incl. null page
    # paged-pool STORAGE dtype: None stores K/V in the compute dtype;
    # "int8" / "fp8" (float8_e4m3fn, where the jax build has it) store
    # 1-byte codes with one fp32 amax scale per (kv_head, page) riding
    # the cache beside the block table — ~2× (bf16) to ~4× (fp32) the
    # token capacity at equal HBM, dequantized in-register inside
    # ops.paged_attention.  Scales are maintained by the write path
    # (reset at a page's first write, monotone running amax on
    # append), so shared/CoW/preempted pages carry their scale with
    # them and the engine's accounting never changes.  Paged-only: the
    # dense slab and the training path always store the compute dtype.
    kv_dtype: Optional[str] = None
    # tensor-parallel paged serving (ISSUE 13): shard the paged pool on
    # its kv_heads axis over `kv_shard_axis` of `kv_mesh` so ONE
    # serving replica spans the mesh — each chip stores (and attends
    # over) kv_heads / tp heads' pages, with the per-(kv_head, page)
    # quant scales sharding on the same leading axis, while block
    # tables / cursors / chunk_lens stay REPLICATED (the engine's host
    # allocator, refcounts, CoW and trie never learn about the mesh).
    # Attention routes through the shard_map path of
    # ops.paged_attention; the matmuls ride the GSPMD tensor-parallel
    # layers as always.  Config fields, NOT ambient state: the mesh is
    # part of the module hash, so a different topology is a different
    # executable (graftlint trace-hygiene).  Set by
    # serving.PagedEngine(mesh=); paged-only, both-or-neither.
    kv_shard_axis: Optional[str] = None
    kv_mesh: Any = None                     # jax.sharding.Mesh
    # flash-attention kernel tile sizes; None = the kernel's seq-aware
    # default (512 at short seq — isolated-op sweeps can mislead: in
    # the full rematted model 512/512 measures fastest at s=512 — and
    # 1024 from 16k up, 21% faster measured at 32k)
    attention_block_q: Optional[int] = None
    attention_block_k: Optional[int] = None
    # Megatron per-head-grouped qkv layout: keeps the q/k/v split
    # shard-local under TP (without it GSPMD inserts cross-shard
    # permutes in every layer).  Costs extra strided-slice temps that
    # XLA pads 2x at d=64 — at very long sequence on a single chip
    # (no TP benefit) turn it off to save HBM.
    qkv_grouped: bool = True
    scan_layers: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        """Width of one attention head: ``kv_channels`` where stated,
        else ``hidden_size // num_heads``."""
        if self.kv_channels is not None:
            return self.kv_channels
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def ffn_size(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def kv_window(self) -> Optional[int]:
        """The width of the stack's window layers, for the serving
        engine's page counters; ``None``: the stack has none (a family
        whose layers differ overrides this)."""
        return self.sliding_window

    def __post_init__(self):
        if self.kv_channels is not None:
            if self.kv_channels < 1:
                raise ValueError(
                    f"kv_channels must be >= 1, got {self.kv_channels}")
        elif self.hidden_size % self.num_heads:
            raise ValueError(
                f"num_heads ({self.num_heads}) must divide hidden_size "
                f"({self.hidden_size}) unless kv_channels states the "
                f"head width")
        if self.num_kv_heads and self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_kv_heads ({self.num_kv_heads}) must divide "
                f"num_heads ({self.num_heads})")
        if self.position_embedding not in ("rope", "learned", "none"):
            raise ValueError(
                f"position_embedding={self.position_embedding!r} not in "
                "('rope', 'learned', 'none')")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(
                f"norm={self.norm!r} not in ('layernorm', 'rmsnorm')")
        if self.sliding_window is not None:
            if not self.causal:
                raise ValueError(
                    "sliding_window requires causal=True")
            if self.sliding_window < 1:
                raise ValueError(
                    f"sliding_window must be >= 1, got "
                    f"{self.sliding_window}")
        if self.decode_attn not in ("auto", "einsum", "blocked"):
            raise ValueError(
                f"decode_attn={self.decode_attn!r} not in "
                "('auto', 'einsum', 'blocked')")
        if self.kv_cache not in ("dense", "paged"):
            raise ValueError(
                f"kv_cache={self.kv_cache!r} not in ('dense', 'paged')")
        if self.kv_cache == "paged":
            if not self.causal:
                raise ValueError("kv_cache='paged' requires causal=True "
                                 "(it is a decode-cache layout)")
            if self.kv_block_size < 1:
                raise ValueError(
                    f"kv_block_size must be >= 1, got "
                    f"{self.kv_block_size}")
            if self.kv_pool_blocks < 2:
                raise ValueError(
                    "kv_pool_blocks must be >= 2 (block 0 is the "
                    f"reserved null page), got {self.kv_pool_blocks}")
        if self.kv_dtype is not None:
            if self.kv_cache != "paged":
                raise ValueError(
                    "kv_dtype requires kv_cache='paged' — quantized "
                    "KV pages live in the paged pool (per-page scales "
                    "beside the block table); the dense slab stores "
                    "K/V in the compute dtype")
            # unknown names / fp8 on a build without float8_e4m3fn
            # raise here, at config time
            kv_quant_spec(self.kv_dtype)
        if self.kv_shard_axis is not None or self.kv_mesh is not None:
            if self.kv_cache != "paged":
                raise ValueError(
                    "kv_shard_axis / kv_mesh require kv_cache='paged' "
                    "— tensor-parallel serving shards the paged pool "
                    "on its kv_heads axis; the dense slab is "
                    "single-chip")
            if self.kv_shard_axis is None or self.kv_mesh is None:
                raise ValueError(
                    "kv_shard_axis and kv_mesh come together: the "
                    "axis names WHERE the pool shards, the mesh says "
                    "over WHICH chips")
            size = dict(self.kv_mesh.shape).get(self.kv_shard_axis)
            if size is None:
                raise ValueError(
                    f"kv_shard_axis={self.kv_shard_axis!r} is not an "
                    f"axis of kv_mesh (axes: "
                    f"{tuple(self.kv_mesh.axis_names)})")
            # the loud config-time divisibility gate: kv_heads % tp
            # must be 0 (instead of a shape error deep inside
            # shard_map) — the GQA group→shard mapping
            tp_head_shards(self.num_heads, self.kv_heads, size)
        if self.num_moe_experts is not None:
            if self.num_moe_experts < 2:
                raise ValueError(
                    f"num_moe_experts must be >= 2, got "
                    f"{self.num_moe_experts}")
            if self.moe_top_k < 1:
                raise ValueError(
                    f"moe_top_k must be >= 1, got {self.moe_top_k}")
            if self.moe_top_k > self.num_moe_experts:
                raise ValueError(
                    f"moe_top_k ({self.moe_top_k}) cannot exceed "
                    f"num_moe_experts ({self.num_moe_experts})")


def _remat_policy(spec: str):
    if spec.startswith("save_only:"):
        names = spec[len("save_only:"):].split(",")
        return jax.checkpoint_policies.save_only_these_names(*names)
    return getattr(jax.checkpoint_policies, spec)


def _norm(cfg: TransformerConfig, name: str):
    """Fused pre-norm as a parameterized closure over a flax scope."""
    class _Norm(nn.Module):
        @nn.compact
        def __call__(self, x):
            w = self.param("scale", nn.initializers.ones_init(),
                           (cfg.hidden_size,), cfg.param_dtype)
            if cfg.norm == "rmsnorm":
                return fused_rms_norm(x, w, eps=cfg.layernorm_eps)
            b = self.param("bias", nn.initializers.zeros_init(),
                           (cfg.hidden_size,), cfg.param_dtype)
            return fused_layer_norm(x, w, b, eps=cfg.layernorm_eps)
    return _Norm(name=name)


def _cache_attention(q, keys, values, idx, scale, window=None,
                     key_positions=None):
    """Decode-step attention of ``q`` (b, s, h, d) over the KV cache
    (b, S, hk, d): GQA grouped dot, fp32 softmax, positions ``> idx+i``
    (and, with ``window``, ``<= idx+i-window``) masked.  Memory-bound
    (s is the decode chunk, usually 1) — plain XLA is the right tool;
    the flash kernel is for the training path.

    ``key_positions``: per-slot absolute positions (rolling ring-buffer
    cache; -1 marks an empty slot).  Default: slot index IS the
    position (dense cache).
    """
    b, s, h, d = q.shape
    S, hk = keys.shape[1], keys.shape[2]
    rep = h // hk
    qg = q.reshape(b, s, hk, rep, d).astype(jnp.float32)
    scores = jnp.einsum(
        "bsgrd,bkgd->bsgrk", qg, keys.astype(jnp.float32)) * scale
    pos_q = idx + jnp.arange(s)
    k_pos = (jnp.arange(S) if key_positions is None
             else key_positions)[None, :]
    visible = (k_pos >= 0) & (k_pos <= pos_q[:, None])       # (s, S)
    if window is not None:
        visible &= k_pos > pos_q[:, None] - window
    scores = jnp.where(visible[None, :, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bsgrk,bkgd->bsgrd", p, values.astype(jnp.float32))
    return o.reshape(b, s, h, d).astype(q.dtype)


def _cache_attention_blocked(q, keys, values, idx, scale, window=None,
                             key_positions=None, block=1024):
    """Chunk attention of ``q`` (b, s, h, d) over cached keys
    (b, S, hk, d) in an online-softmax scan over key blocks — the jnp
    analogue of the flash kernel's kv sweep, for the decode path where
    keys live in the cache rather than in the chunk.

    The one-shot masked einsum materializes (b, h, s, S) scores — the
    exact O(S²) temp that BASELINE.md shows uncompilable at 32k — while
    this form bounds temps to (b, h, s, block) per step.  With the
    default slot-index positions (dense cache) blocks past the live
    prefix are SKIPPED (``lax.cond`` on ``block_start <= idx+s-1``),
    so compute scales with the filled cache, not ``max_seq_len``;
    with explicit ``key_positions`` (ring concat — arbitrary per-slot
    positions, -1 = dead) every block runs.  ``S`` is padded up to a
    block multiple with dead keys (position -1 / past-the-end slots
    are masked either way), so any cache length works.
    """
    b, s, h, d = q.shape
    S, hk = keys.shape[1], keys.shape[2]
    rep = h // hk
    block = min(block, S)
    pad = -S % block
    if pad:
        kpad = ((0, 0), (0, pad), (0, 0), (0, 0))
        keys = jnp.pad(keys, kpad)
        values = jnp.pad(values, kpad)
        if key_positions is not None:
            key_positions = jnp.pad(key_positions, (0, pad),
                                    constant_values=-1)
        # default positions: padded slots sit at S..S+pad-1, beyond
        # every query position (idx + s <= max_seq_len = S) -> masked
    nblk = (S + pad) // block
    qg = (q.reshape(b, s, hk, rep, d).astype(jnp.float32)
          * jnp.float32(scale))
    pos_q = idx + jnp.arange(s)                       # (s,)
    last_q = idx + s - 1

    def body(carry, start):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(
            keys, start, block, 1).astype(jnp.float32)
        vb = jax.lax.dynamic_slice_in_dim(
            values, start, block, 1).astype(jnp.float32)
        sc = jnp.einsum("bsgrd,bkgd->bsgrk", qg, kb)
        if key_positions is None:
            k_pos = start + jnp.arange(block)
        else:
            k_pos = jax.lax.dynamic_slice_in_dim(
                key_positions, start, block, 0)
        vis = ((k_pos[None, :] >= 0)
               & (k_pos[None, :] <= pos_q[:, None]))  # (s, block)
        if window is not None:
            vis &= k_pos[None, :] > pos_q[:, None] - window
        sc = jnp.where(vis[None, :, None, None, :], sc, -1e30)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        p = jnp.where(sc < -0.5e30, 0.0, p)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = (acc * alpha[..., None]
               + jnp.einsum("bsgrk,bkgd->bsgrd", p, vb))
        return (m_new, l, acc), None

    def step(carry, blk):
        start = blk * block
        if key_positions is None:
            # dense cache: slot index IS the position — blocks wholly
            # past the newest query hold nothing visible
            return jax.lax.cond(
                start <= last_q,
                lambda c: body(c, start)[0], lambda c: c, carry), None
        return body(carry, start)[0], None

    m0 = jnp.full((b, s, hk, rep), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, s, hk, rep), jnp.float32)
    a0 = jnp.zeros((b, s, hk, rep, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), jnp.arange(nblk))
    o = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return o.reshape(b, s, h, d).astype(q.dtype)


def _tp_pin(x, mesh, axis, dim):
    """Pin ``x``'s sharding to ``axis`` on dimension ``dim`` (rest
    replicated) — the paged pool's kv_heads placement under
    tensor-parallel serving.  Keeps the pool scatter shard-local and
    the cache leaves' out-shardings at a fixed point, so the engine's
    retrace guards see one stable signature (the concrete
    NamedSharding form is legal inside plain jit on every supported
    jax)."""
    spec = [None] * x.ndim
    spec[dim] = axis
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(*spec)))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Lanes:
    """The real lanes of a mixed serving step as ONE compact list.

    A step of ``rows`` slots at ``width`` lanes holds, a row, a prefix
    of real lanes (a prompt's chunk, a decoding row's one token) and
    pads.  Everything position-wise — the embedding, the norms, every
    projection, the MLP, the experts, the head — runs on the list,
    ``(1, size, ...)``, so a pad costs nothing; only the sequence
    mixers (the paged write and attention with their rotary positions,
    a state-space convolution and scan) see ``(rows, width, ...)``:
    :meth:`to_rows` lays the list out as rows, pads zero, and
    :meth:`take` gathers it back.

    ``index`` ``(size,)``: entry ``i`` of the list is the flat lane
    ``row * width + lane``, ``rows * width`` where unused; ``slot``
    ``(rows * width,)``: the inverse, ``size`` for a pad; ``last``
    ``(rows,)``: each row's last real lane in the list (a row with none
    points at its predecessor's: its output is nobody's).
    """

    index: jax.Array
    slot: jax.Array
    last: jax.Array
    rows: int = dataclasses.field(metadata=dict(static=True))
    width: int = dataclasses.field(metadata=dict(static=True))

    @classmethod
    def of(cls, real, size: int) -> "Lanes":
        """The list of ``real`` ``(rows, width)`` bool, a prefix of
        each row, at most ``size`` lanes in all."""
        rows, width = real.shape
        flat = real.reshape(-1)
        index = jnp.nonzero(flat, size=size,
                            fill_value=rows * width)[0].astype(jnp.int32)
        slot = jnp.where(flat, jnp.cumsum(flat) - 1, size)
        last = jnp.maximum(jnp.cumsum(real.sum(axis=1)) - 1, 0)
        return cls(index, slot.astype(jnp.int32), last.astype(jnp.int32),
                   rows, width)

    @property
    def valid(self):
        """``(1, size)``: the entries that hold a lane."""
        return (self.index < self.rows * self.width)[None]

    def take(self, x):
        """``(rows, width, ...)`` -> ``(1, size, ...)``."""
        flat = x.reshape((self.rows * self.width,) + x.shape[2:])
        return jnp.take(flat, self.index, axis=0, mode="fill",
                        fill_value=0)[None]

    def to_rows(self, x):
        """``(1, size, ...)`` -> ``(rows, width, ...)``, pads zero."""
        return jnp.take(x[0], self.slot, axis=0, mode="fill",
                        fill_value=0).reshape(
            (self.rows, self.width) + x.shape[2:])

    def last_lanes(self, x):
        """``(1, size, ...)`` -> ``(rows, 1, ...)``: each row's last
        real lane, the one it samples from."""
        return x[0, self.last][:, None]


class ParallelAttention(nn.Module):
    """TP attention block: ColumnParallel qkv → RoPE → flash → RowParallel.

    Head-sharded over the ``tensor`` axis (qkv ColumnParallel shards the
    head dim product; out-proj RowParallel reduces), the reference's
    layer recipe (SURVEY.md §3.4 steps 1-5).

    ``decode=True`` switches to incremental decoding: k/v are appended
    to a ``cache`` collection (``cached_key``/``cached_value`` +
    ``cache_index``) and q attends over the cached prefix, with RoPE
    applied at the absolute cache position.  The cache stores kv
    *heads* (GQA: ``kv_heads`` can be far fewer than ``num_heads`` —
    the cache shrinks with it) and is ``(b, max_seq_len, kv_heads,
    d)`` — except with ``sliding_window``, where it is a
    window-sized RING BUFFER ``(b, window, kv_heads, d)`` plus a
    ``slot_positions`` leaf (position+1 per slot; 0 = empty), so
    decode memory scales with the window, not ``max_seq_len``.
    Multi-token chunks are supported at ANY cache position (chunked
    prefill): the dense cache runs a blocked online-softmax scan over
    the live prefix, the ring cache combines the banded flash kernel
    with a ring-correction einsum for the first ``min(window, s)``
    queries.  ``lanes`` (paged serving's mixed step, :class:`Lanes`):
    ``x`` is the step's compact lane list; the projections run on it
    and only the write and attend see rows.
    """

    cfg: TransformerConfig

    def _paged_decode(self, q, k, v, rot):
        """Chunk/decode attention over the PAGED KV pool
        (``cfg.kv_cache == "paged"``; serving-engine substrate).

        Cache leaves: a shared per-layer page pool ``paged_key`` /
        ``paged_value`` of ``(kv_heads, kv_pool_blocks, kv_block_size,
        d)`` plus per-row ``block_tables`` (logical page → physical
        pool block) and ``cursors`` (tokens already cached).  The
        serving engine OWNS the tables/cursors — it overwrites both
        leaves every step from its host allocator (this module never
        advances them), which is what makes one application serve a
        ragged batch: every row sits at its own position.

        Write-then-attend, like the dense path: the chunk's K/V are
        scattered into the pool at ``cursor + i`` first, then every
        query attends over the pool by absolute position — within-chunk
        causality falls out of the position mask.  Pad tokens beyond a
        row's real chunk write into the null page (block 0, where
        unallocated table entries point) or into positions the next
        real token overwrites before any query can see them.
        """
        cfg = self.cfg
        b, s, hk, d = k.shape
        S = cfg.max_seq_len
        NB, BS = cfg.kv_pool_blocks, cfg.kv_block_size
        MB = -(-S // BS)
        store_dt, qmax = kv_quant_spec(cfg.kv_dtype)
        # tensor-parallel pool (kv_mesh/kv_shard_axis, validated
        # together at config time): pool + scale leaves pin their
        # kv_heads axis to the mesh so the scatter stays shard-local
        # and attention routes through the shard_map path
        tp_on = (cfg.kv_mesh is not None
                 and dict(cfg.kv_mesh.shape).get(cfg.kv_shard_axis,
                                                 1) > 1)
        pin = ((lambda x, dim: _tp_pin(x, cfg.kv_mesh,
                                       cfg.kv_shard_axis, dim))
               if tp_on else (lambda x, dim: x))
        pk = self.variable("cache", "paged_key", jnp.zeros,
                           (hk, NB, BS, d),
                           k.dtype if store_dt is None else store_dt)
        pv = self.variable("cache", "paged_value", jnp.zeros,
                           (hk, NB, BS, d),
                           v.dtype if store_dt is None else store_dt)
        if store_dt is not None:
            # per-(kv_head, page) fp32 amax scales, living beside the
            # block table; page 0's entry is garbage like the null
            # page itself (the position mask keeps both unreachable)
            ksc = self.variable("cache", "key_scales", jnp.zeros,
                                (hk, NB), jnp.float32)
            vsc = self.variable("cache", "value_scales", jnp.zeros,
                                (hk, NB), jnp.float32)
            # per-row REAL lane count for this chunk (engine-owned,
            # like tables/cursors): the unquantized path can let pad
            # lanes write K/V that the next real token overwrites, but
            # the scale scatter-max is MONOTONE — a pad lane's amax
            # would pollute the page scale forever — so pad lanes must
            # be routed to the null page.  Defaults to "every lane
            # real" (max_seq_len) for non-engine callers.
            cl = self.variable("cache", "chunk_lens", jnp.full,
                               (b,), S, jnp.int32)
        bt = self.variable("cache", "block_tables", jnp.zeros,
                           (b, MB), jnp.int32)
        cur = self.variable("cache", "cursors", jnp.zeros,
                            (b,), jnp.int32)
        positions = cur.value[:, None] + jnp.arange(s, dtype=jnp.int32)
        cos_b = sin_b = None
        if cfg.position_embedding == "rope" and rot:
            # per-ROW rope: each tenant rotates at its own absolute
            # position (the shared-table fused_rope cannot express a
            # ragged batch); pad positions clamp into the table — their
            # K/V are unreachable garbage either way
            cos, sin = rope_cos_sin(S, rot, base=cfg.rope_base)
            pc = jnp.minimum(positions, S - 1)
            cos_b = cos[pc][:, :, None, :]
            sin_b = sin[pc][:, :, None, :]
        if s == 1:
            # FUSED decode prologue (ISSUE 14): the width-1 step —
            # the serving engines' steady decode — routes RoPE, the
            # (quantized) row write and the attend through ONE op:
            # on TPU the Pallas kernel rotates/codes/writes the new
            # row in-register on its way into the attend (pool
            # aliased, only the write page moves); elsewhere the
            # dispatch target is the historical unfused XLA sequence
            # verbatim, so this branch is bitwise the old path there.
            # Chunked prefill and the speculative verify (s > 1) write
            # through paged_write below: the touched pages, in place.
            outs = paged_decode_fused(
                q, k, v, pk.value, pv.value, bt.value, cur.value,
                max_seq_len=S, cos_b=cos_b, sin_b=sin_b,
                scale=d ** -0.5,
                k_scales=(ksc.value if store_dt is not None else None),
                v_scales=(vsc.value if store_dt is not None else None),
                chunk_lens=(cl.value if store_dt is not None else None),
                mesh=cfg.kv_mesh, shard_axis=cfg.kv_shard_axis,
                window=cfg.sliding_window)
            if store_dt is None:
                o, kp_new, vp_new = outs
            else:
                o, kp_new, vp_new, ks_new, vs_new = outs
                ksc.value = pin(ks_new, 0)
                vsc.value = pin(vs_new, 0)
            pk.value = pin(kp_new, 0)
            pv.value = pin(vp_new, 0)
            return o
        if cos_b is not None:
            q = _rope_rows(q, cos_b, sin_b)
            k = _rope_rows(k, cos_b, sin_b)
        logical = jnp.minimum(positions // BS, MB - 1)
        phys = jnp.take_along_axis(bt.value, logical, axis=1)  # (b, s)
        # pad positions past max_seq_len go to the NULL page — the
        # clamped logical index above would land them in the row's
        # LAST allocated block, overwriting live (visible) entries
        # when a near-full tenant rides a wide mixed step
        phys = jnp.where(positions < S, phys, 0)
        off = positions % BS

        def write(k_rows, v_rows, phys):
            # the touched pages move, in place, on a TPU; elsewhere
            # (and for a tensor-parallel pool) the one-pass scatter
            kp_new, vp_new = paged_write(
                k_rows, v_rows, pk.value, pv.value, phys, off,
                mesh=cfg.kv_mesh, shard_axis=cfg.kv_shard_axis)
            pk.value = pin(kp_new, 0)
            pv.value = pin(vp_new, 0)

        if store_dt is None:
            write(k, v, phys)
            return paged_attention(q, pk.value, pv.value, bt.value,
                                   cur.value, scale=d ** -0.5,
                                   mesh=cfg.kv_mesh,
                                   shard_axis=cfg.kv_shard_axis,
                                   window=cfg.sliding_window)
        # quantize-on-write (chunked prefill and decode scatter are
        # this one path).  Scale discipline per (kv_head, page):
        # - RESET at a page's first write: pages always begin life at
        #   offset 0 (sequential fill from a block boundary), so the
        #   offset-0 tokens of this chunk mark fresh pages and clear
        #   any stale scale left by the page's previous tenant (the
        #   non-fresh lane of the scatter is routed to the null page);
        # - each token contributes its row's MONOTONE RUNNING AMAX —
        #   cummax over the chunk seeded from the scale of the row's
        #   most recent written page, which by induction is the
        #   running amax of the whole prefix — scatter-MAXed into its
        #   page, so the scale only ever grows and codes already
        #   written never clip and never need rewriting.  Chaining
        #   through the previous page (instead of a per-page region
        #   amax) is what makes rescale-on-append RARE: the running
        #   amax saturates over the prompt, so a partially-filled
        #   page's scale almost never moves under decode appends and
        #   the residual inflation of earlier codes is bounded by the
        #   sequence-level amax drift across one <= block_size-token
        #   page.  Page scales stay a pure function of the row's
        #   tokens 0..page-end — chunk-alignment-invariant, which is
        #   what lets shared/CoW-forked pages reproduce bitwise
        #   (tests/test_paged_serving.py::TestQuantizedKV).
        # pad lanes (>= the row's chunk_lens) route to the NULL page:
        # their K/V would be position-masked and overwritten anyway,
        # but the scale scatter-max below is MONOTONE — one garbage
        # pad amax would stick in a live page's scale forever
        real = (jnp.arange(s, dtype=jnp.int32)[None, :]
                < cl.value[:, None])                         # (b, s)
        phys = jnp.where(real, phys, 0)
        kT = k.transpose(2, 0, 1, 3)             # (hk, b, s, d)
        vT = v.transpose(2, 0, 1, 3)
        ka = jnp.max(jnp.abs(kT.astype(jnp.float32)), axis=-1)
        va = jnp.max(jnp.abs(vT.astype(jnp.float32)), axis=-1)
        ka = jnp.where(real[None], ka, 0.0)                  # (hk, b, s)
        va = jnp.where(real[None], va, 0.0)
        base_logical = jnp.clip((cur.value - 1) // BS, 0, MB - 1)
        base_phys = jnp.take_along_axis(
            bt.value, base_logical[:, None], axis=1)[:, 0]   # (b,)
        has_prefix = cur.value > 0                           # (b,)
        k_base = jnp.where(has_prefix[None, :],
                           ksc.value[:, base_phys], 0.0)     # (hk, b)
        v_base = jnp.where(has_prefix[None, :],
                           vsc.value[:, base_phys], 0.0)
        k_run = jnp.maximum(jax.lax.cummax(ka, axis=2),
                            k_base[:, :, None])              # (hk, b, s)
        v_run = jnp.maximum(jax.lax.cummax(va, axis=2),
                            v_base[:, :, None])
        fresh = jnp.where(off == 0, phys, 0)                 # (b, s)
        ks_new = pin(
            ksc.value.at[:, fresh].set(0.0).at[:, phys].max(k_run), 0)
        vs_new = pin(
            vsc.value.at[:, fresh].set(0.0).at[:, phys].max(v_run), 0)
        ksc.value, vsc.value = ks_new, vs_new
        write(quantize_kv(k, ks_new[:, phys].transpose(1, 2, 0), qmax,
                          store_dt),
              quantize_kv(v, vs_new[:, phys].transpose(1, 2, 0), qmax,
                          store_dt), phys)
        return paged_attention(q, pk.value, pv.value, bt.value,
                               cur.value, scale=d ** -0.5,
                               k_scales=ks_new, v_scales=vs_new,
                               mesh=cfg.kv_mesh,
                               shard_axis=cfg.kv_shard_axis,
                               window=cfg.sliding_window)

    @nn.compact
    def __call__(self, x, *, mask_bias=None, deterministic: bool = True,
                 decode: bool = False, lanes: Optional[Lanes] = None):
        cfg = self.cfg
        b, s, _ = x.shape
        h, hk, d = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        qkv_features = (h + 2 * hk) * d
        qkv = ColumnParallelLinear(
            features=qkv_features, use_bias=cfg.add_bias_linear,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="qkv_proj")(x)
        if lanes is not None:
            # a compact mixed step: the projections ran on its real
            # lanes (``x`` is the list); the paged write and attention
            # take rows, by absolute position
            if not (decode and cfg.kv_cache == "paged"):
                raise ValueError("lanes= is the paged serving step's")
            qkv = lanes.to_rows(qkv)
            b, s = qkv.shape[:2]
        if cfg.qkv_grouped:
            # Megatron qkv layout: features grouped per kv-head —
            # [q_g·rep … q_g·rep+rep-1, k_g, v_g] per group g — so the
            # q/k/v split is a reshape along UNSHARDED dims and stays
            # shard-local under TP (the flat [q|k|v] layout's slice
            # boundaries cross tensor shards, making GSPMD insert
            # cross-shard collective-permutes in every layer).  Head
            # order is unchanged (q heads stay g-major = the standard
            # GQA grouping; for MHA it's the identity).
            rep = h // hk
            grouped = qkv.reshape(b, s, hk, rep + 2, d)
            q = grouped[..., :rep, :].reshape(b, s, h, d)
            k = grouped[..., rep, :]
            v = grouped[..., rep + 1, :]
        else:
            q = qkv[..., : h * d].reshape(b, s, h, d)
            k = qkv[..., h * d: (h + hk) * d].reshape(b, s, hk, d)
            v = qkv[..., (h + hk) * d:].reshape(b, s, hk, d)
        if cfg.key_multiplier != 1.0:
            k = k * jnp.asarray(cfg.key_multiplier, k.dtype)
        if cfg.qk_norm:
            q = self._head_norm("q_norm", q)
            k = self._head_norm("k_norm", k)
        gate = None
        if cfg.attn_gate:
            gate = ColumnParallelLinear(
                features=h * d, use_bias=False,
                sequence_parallel=cfg.sequence_parallel,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="gate_proj")(x)
        rot = int(cfg.rotary_pct * d) // 2 * 2
        if decode:
            if not cfg.causal:
                raise ValueError(
                    "decode=True requires a causal model (the cache "
                    "attends over the generated prefix)")
            if mask_bias is not None:
                raise ValueError(
                    "mask_bias is not supported with decode=True — the "
                    "cache attention masks by absolute position only; "
                    "bucket ragged prompts instead of padding them")
            # contract: the caller must not advance the cache past
            # max_seq_len — the index is traced, so it cannot be
            # validated here; dynamic_update_slice would silently clamp.
            # generate() enforces the bound statically.
            if cfg.kv_cache == "paged":
                o = self._paged_decode(q, k, v, rot).reshape(b, s, h * d)
                if lanes is not None:
                    o = lanes.take(o)
                return RowParallelLinear(
                    features=cfg.hidden_size,
                    use_bias=cfg.add_bias_linear,
                    sequence_parallel=cfg.sequence_parallel,
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                    name="out_proj")(self._gated(o, gate))
            S = cfg.max_seq_len
            # rolling ring-buffer cache (Mistral design): with a
            # sliding window only the last `window` keys are ever
            # visible, so the cache holds exactly that many slots —
            # decode memory scales with window, not max_seq_len
            Wc = (cfg.sliding_window
                  if cfg.sliding_window and cfg.sliding_window < S
                  else None)
            Sc = Wc or S
            ck = self.variable("cache", "cached_key", jnp.zeros,
                               (b, Sc, hk, d), k.dtype)
            cv = self.variable("cache", "cached_value", jnp.zeros,
                               (b, Sc, hk, d), v.dtype)
            ci = self.variable("cache", "cache_index",
                               lambda: jnp.array(0, jnp.int32))
            if Wc is not None:
                # slot_positions stores position+1 (0 = empty slot):
                # the all-zeros encoding keeps init_cache's
                # zeros-from-shape invariant valid for every cache leaf
                cp = self.variable("cache", "slot_positions",
                                   jnp.zeros, (Wc,), jnp.int32)
            idx = ci.value
            if cfg.position_embedding == "rope":
                cos, sin = rope_cos_sin(S, rot, base=cfg.rope_base)
                cos = jax.lax.dynamic_slice_in_dim(cos, idx, s, 0)
                sin = jax.lax.dynamic_slice_in_dim(sin, idx, s, 0)
                q = fused_rope(q, cos, sin)
                k = fused_rope(k, cos, sin)
            scale = d ** -0.5
            if Wc is None:
                keys = jax.lax.dynamic_update_slice_in_dim(
                    ck.value, k, idx, 1)
                values = jax.lax.dynamic_update_slice_in_dim(
                    cv.value, v, idx, 1)
                ck.value, cv.value = keys, values
                # (window is always a no-op here: Wc is None only when
                # sliding_window is unset or >= max_seq_len, and a
                # window covering the whole cache masks nothing)
                if s == 1:
                    # steady decode reads the WHOLE (b, S, hk, d) cache
                    # every token in the one-shot einsum; the blocked
                    # form's lax.cond skip bounds reads to the live
                    # prefix — measured on-chip (decode bench,
                    # BASELINE.md round-5): +30% tokens/s at S=2048
                    # and 2.3x at S=8192 (b=8, llama_1b), so it is the
                    # default from 2048 slots up.  cfg.decode_attn
                    # ∈ {einsum, blocked} overrides for A/B (a config
                    # field so the choice is part of the compile
                    # signature — the old APEX_TPU_DECODE_ATTN env read
                    # here was captured at trace time and a mid-process
                    # flip was a silent no-op).
                    mode = cfg.decode_attn
                    if mode == "blocked" or (
                            mode == "auto" and S >= 2048):
                        o = _cache_attention_blocked(
                            q, keys, values, idx, scale, block=512)
                    else:
                        o = _cache_attention(q, keys, values, idx,
                                             scale)
                else:
                    # prefill / mid-stream chunk: online-softmax block
                    # scan over the cache — the one-shot einsum's
                    # (s, S) score temp is exactly what BASELINE.md
                    # shows uncompilable at 32k prompts
                    o = _cache_attention_blocked(
                        q, keys, values, idx, scale)
            elif s == 1:
                # steady decode: one slot write, attend over the ring
                slot = idx % Wc
                keys = jax.lax.dynamic_update_slice(
                    ck.value, k, (0, slot, 0, 0))
                values = jax.lax.dynamic_update_slice(
                    cv.value, v, (0, slot, 0, 0))
                pos = jax.lax.dynamic_update_slice(
                    cp.value, idx[None] + 1, (slot,))
                ck.value, cv.value, cp.value = keys, values, pos
                o = _cache_attention(q, keys, values, idx, scale,
                                     window=Wc,
                                     key_positions=pos - 1)
            else:
                # multi-token chunk at ANY position.  Only queries in
                # the chunk's first hlen = min(Wc, s) offsets can see
                # ring entries (offset i >= Wc has pos_q - Wc >= idx,
                # putting every ring key out of window), so those head
                # rows run the blocked online-softmax einsum over
                # [ring ‖ chunk-head] with per-slot positions.  When
                # s <= Wc (e.g. 2048-token auto prefill chunks against
                # Mistral's 4096 window) hlen == s and the WHOLE chunk
                # is that blocked einsum — the banded flash kernel is
                # not invoked at all.  Only when s > Wc do the
                # remaining rows (pure in-chunk attention) go through
                # the banded kernel; it computes all s rows and the
                # first hlen are discarded by the [:, hlen:] slice —
                # redundant work bounded by hlen/s <= Wc/s < 1 of the
                # kernel call.  On the first call the ring is empty
                # (slot_positions == 0 → k_pos == -1, masked), so
                # prefill needs no special case.
                hlen = min(Wc, s)
                cat_k = jnp.concatenate([ck.value, k[:, :hlen]], axis=1)
                cat_v = jnp.concatenate([cv.value, v[:, :hlen]], axis=1)
                cat_pos = jnp.concatenate(
                    [cp.value - 1, idx + jnp.arange(hlen)])
                o = _cache_attention_blocked(
                    q[:, :hlen], cat_k, cat_v, idx, scale, window=Wc,
                    key_positions=cat_pos)
                if s > hlen:
                    o_tail = fused_attention(
                        q, k, v, causal=True, scale=scale,
                        window=Wc)[:, hlen:]
                    o = jnp.concatenate([o, o_tail], axis=1)
                tail = min(s, Wc)
                positions = idx + s - tail + jnp.arange(tail)
                slots = positions % Wc
                ck.value = ck.value.at[:, slots].set(k[:, -tail:])
                cv.value = cv.value.at[:, slots].set(v[:, -tail:])
                cp.value = cp.value.at[slots].set(positions + 1)
            ci.value = idx + s
        else:
            if cfg.position_embedding == "rope":
                cos, sin = rope_cos_sin(s, rot, base=cfg.rope_base)
                q = fused_rope(q, cos, sin)
                k = fused_rope(k, cos, sin)
            # attention-prob dropout runs INSIDE the flash kernel
            # (counter-hash mask, regenerated in the backward kernels) —
            # the dropout path no longer bypasses the Pallas attention
            drop = cfg.attention_dropout if (
                cfg.attention_dropout > 0.0 and not deterministic) else 0.0
            o = fused_attention(
                q, k, v, causal=cfg.causal, bias=mask_bias,
                window=cfg.sliding_window,
                dropout_rate=drop,
                dropout_rng=(self.make_rng("dropout") if drop > 0.0
                             else None),
                block_q=cfg.attention_block_q,
                block_k=cfg.attention_block_k)
        # remat_policy="save_only:attn_out,attn_lse" saves the flash
        # kernel's own output/lse residuals — named inside the kernel's
        # fwd rule (ops/attention.py), not here: a second layer-level
        # tag with the same name would store the attention output twice
        o = self._gated(o.reshape(b, s, h * d), gate)
        return RowParallelLinear(
            features=cfg.hidden_size, use_bias=cfg.add_bias_linear,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="out_proj")(o)

    def _head_norm(self, name, x):
        """RMS norm of every head of ``x`` (b, s, heads, d) over its
        ``d`` channels, one learned gain vector for all heads
        (``cfg.qk_norm``)."""
        cfg = self.cfg
        w = self.param(name, nn.initializers.ones_init(),
                       (cfg.head_dim,), cfg.param_dtype)
        xf = x.astype(jnp.float32)
        xf = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.layernorm_eps)
        return (xf * w.astype(jnp.float32)).astype(x.dtype)

    @staticmethod
    def _gated(o, gate):
        """The heads' output under the sigmoid gate (``cfg.attn_gate``);
        ``gate`` None leaves it as it is."""
        if gate is None:
            return o
        return o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)


class ParallelMLP(nn.Module):
    """TP MLP: ColumnParallel h→ffn (+act) → RowParallel ffn→h.

    The reference's ``apex.mlp.MLP``/``FusedDenseGeluDense`` fused into
    the TP recipe — XLA fuses bias+GeLU into the matmul epilogue.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        act = resolve_activation(cfg.activation, gelu_approximate=True)
        y = ColumnParallelLinear(
            features=cfg.ffn_size, use_bias=cfg.add_bias_linear,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="dense_h_to_4h")(x)
        if cfg.gated_mlp:
            # SwiGLU-style GLU: gate and up projections sharded
            # identically over the tensor axis, product shard-local
            gate = ColumnParallelLinear(
                features=cfg.ffn_size, use_bias=cfg.add_bias_linear,
                sequence_parallel=cfg.sequence_parallel,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="dense_h_to_4h_gate")(x)
            if cfg.mlp_gate_multiplier != 1.0:
                gate = gate * jnp.asarray(cfg.mlp_gate_multiplier,
                                          gate.dtype)
            y = act(gate) * y
        else:
            y = act(y)
        return RowParallelLinear(
            features=cfg.hidden_size, use_bias=cfg.add_bias_linear,
            sequence_parallel=cfg.sequence_parallel,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="dense_4h_to_h")(y)


class ParallelTransformerLayer(nn.Module):
    """Pre-LN transformer block (Megatron layer recipe)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, *, mask_bias=None, deterministic: bool = True,
                 decode: bool = False, lanes: Optional[Lanes] = None):
        cfg = self.cfg
        seq_spec = (TENSOR_AXIS if cfg.sequence_parallel else None)
        x = maybe_constrain(x, "data", seq_spec)
        a = _norm(cfg, "input_norm")(x)
        a = ParallelAttention(cfg, name="attention")(
            a, mask_bias=mask_bias, deterministic=deterministic,
            decode=decode, lanes=lanes)
        if cfg.hidden_dropout > 0.0 and not deterministic:
            a = nn.Dropout(rate=cfg.hidden_dropout)(a, deterministic=False)
        x = x + a.astype(x.dtype)
        m = _norm(cfg, "post_attention_norm")(x)
        if cfg.num_moe_experts:
            from apex_tpu.transformer.moe import MoEConfig, MoEMLP

            m, aux = MoEMLP(MoEConfig(
                num_experts=cfg.num_moe_experts,
                top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                hidden_size=cfg.hidden_size,
                ffn_hidden_size=cfg.ffn_size,
                activation=cfg.activation, gated=cfg.gated_mlp,
                expert_axis=cfg.moe_expert_axis,
                aux_loss_weight=cfg.moe_aux_loss_weight,
                use_bias=cfg.add_bias_linear,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype),
                name="moe_mlp")(m)
            # load-balance aux term: a no-op unless the caller applies
            # with mutable=["losses"] (flax drops sows into immutable
            # collections) — models.moe_aux_loss sums them.  Never sown
            # during init: a "losses" leaf in the init dict would ride
            # into optimizer state / checkpoints and double-count on
            # the first apply.
            if not self.is_initializing():
                self.sow("losses", "moe_aux", aux)
        else:
            m = ParallelMLP(cfg, name="mlp")(m)
        if cfg.hidden_dropout > 0.0 and not deterministic:
            m = nn.Dropout(rate=cfg.hidden_dropout)(m, deterministic=False)
        x = x + m.astype(x.dtype)
        return maybe_constrain(x, "data", seq_spec)


class _ScanBlock(nn.Module):
    """One layer in scan-carry form: ``x -> (x', None)``."""

    cfg: TransformerConfig
    deterministic: bool
    decode: bool = False

    @nn.compact
    def __call__(self, x, mask_bias):
        y = ParallelTransformerLayer(self.cfg, name="layer")(
            x, mask_bias=mask_bias, deterministic=self.deterministic,
            decode=self.decode)
        return y, None


def parameters_only(scanned):
    """``scanned`` (an ``nn.scan`` over a layer) for ``init`` under
    ``decode=True``: it makes the stacked parameters as ever, and the
    stacked cache it would leave beside them is dropped —
    :func:`decode_layers` makes the cache, a subtree a layer."""
    return nn.map_variables(scanned, "cache", mutable=True,
                            trans_out_fn=lambda _: {})


def decode_layers(stack: nn.Module, layer, num_layers: int, x, *,
                  scope: str = "layers", first: int = 0,
                  decode: bool = True, layer_args=None,
                  lanes: Optional[Lanes] = None, **kwargs):
    """The layers of a scanned stack under ``decode=True``, WITHOUT a
    scan over the cache.

    ``stack`` is the bound module whose ``layers`` child ``nn.scan``
    made — parameters stacked under ``layers/layer``, a leading layer
    axis on every leaf — and ``layer`` one unbound layer
    (``parent=None``).  Layer ``i`` is applied to slice ``i`` of every
    stacked parameter and to ITS OWN cache subtree, which ``stack``
    keeps as ``cache/layer_{i}``: the cache tree of an unrolled
    (``scan_layers=False``) stack, no layer axis on any leaf, every
    leaf under the name its layer gave it.

    Why not ``nn.scan``: a scan OVER the cache makes every stacked
    pool an input and an output of the loop, so XLA copies the whole
    stack once a step and slices each layer's pool out and back every
    iteration — three passes over the whole cache around kernels that
    move one page or one row (PERF.md, PR 33: most of a serving step's
    device time).  With a leaf a layer the donated buffers
    alias straight through the kernels and nothing pool-shaped is
    left.  The parameters stay stacked (checkpoints, amp and the TP
    annotations see one tree whatever ``decode`` is); their static
    slices fuse into the GEMMs that read them.  What grows is the
    program: a decode step compiles in time linear in depth.

    A model whose layers are of more than one kind calls this once a
    parameter stack (``scope`` names the stack's child; ``first`` is
    the index of its first layer in the model, which names the cache
    subtrees ``layer_{first + i}``) and may hand ``layer`` as a
    sequence, one unbound module a layer: modules that differ in
    static attributes only (a window, a positional scheme) slice the
    same stack, and layers that share a module share its trace.  Such
    a model has no scan to fall back on for its full-sequence forward
    and walks the same loop with ``decode=False``: no cache is read or
    kept.  ``layer_args``: one tuple of further positional arguments a
    layer, traced — what a layer reads that is NOT sliced from the
    stack (a bank of weights that a kernel takes whole: a slice that
    feeds a ``pallas_call`` is a copy, a slice that feeds a GEMM fuses
    into it).  ``lanes``: a compact mixed serving step's lane list
    (:class:`Lanes`), handed to every layer, traced.
    """
    stacked = stack.get_variable("params", scope)["layer"]
    layers = (list(layer) if isinstance(layer, (list, tuple))
              else [layer] * num_layers)
    key = stack.make_rng("dropout") if stack.has_rng("dropout") else None

    # one trace and one lowered function for all the layers: their
    # shapes are the same, and XLA inlines the calls
    def applier(module):
        @jax.jit
        def apply_layer(variables, x, key, args, lanes):
            rngs = None if key is None else {"dropout": key}
            if not decode:
                return module.apply(variables, x, *args, rngs=rngs,
                                    **kwargs), None
            extra = {} if lanes is None else {"lanes": lanes}
            return module.apply(variables, x, *args, decode=True,
                                mutable=["cache"], rngs=rngs, **kwargs,
                                **extra)
        return apply_layer

    appliers = {}
    for i in range(num_layers):
        if id(layers[i]) not in appliers:
            appliers[id(layers[i])] = applier(layers[i])
        apply_layer = appliers[id(layers[i])]
        # the slice keeps a Partitioned box; its names drop the layer
        # axis, as nn.scan's metadata_params does on the way in
        params = nn.meta.remove_axis(
            jax.tree.map(lambda a: a[i], stacked), 0,
            {nn.PARTITION_NAME: None})
        name = f"layer_{first + i}"
        variables = {"params": params}
        if decode and stack.has_variable("cache", name):
            variables["cache"] = stack.get_variable("cache", name)
        x, updated = apply_layer(
            variables, x,
            None if key is None else jax.random.fold_in(key, i),
            () if layer_args is None else layer_args[i], lanes)
        if decode:
            stack.put_variable("cache", name, updated["cache"])
    return x


class ParallelTransformer(nn.Module):
    """N stacked layers via ``nn.scan`` (+ optional ``nn.remat``).

    ``scan_layers=True`` gives the parameters a leading layer axis
    (sharded spec-compatible).  Training and the full-sequence forward
    compile ONE layer and iterate it — compile time stays flat in
    depth.  A ``decode=True`` application never scans over its cache:
    it runs :func:`decode_layers`, a Python loop over slices of the
    same stacked parameters with one cache subtree a layer
    (``cache/layer_{i}``, as ``scan_layers=False`` names them), so the
    KV pools are updated in place and a decode program's size is
    linear in depth.  ``remat=True`` recomputes each layer's
    activations in backward (``jax.checkpoint``), the functional
    equivalent of the reference's ``tensor_parallel.random.checkpoint``.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, *, mask_bias=None, deterministic: bool = True,
                 decode: bool = False, lanes: Optional[Lanes] = None):
        cfg = self.cfg
        if cfg.scan_layers:
            if not decode or self.is_initializing():
                block_cls = _ScanBlock
                if cfg.remat:
                    block_cls = nn.remat(
                        block_cls, prevent_cse=False,
                        policy=_remat_policy(cfg.remat_policy))
                stack = nn.scan(
                    block_cls,
                    variable_axes={"params": 0, "cache": 0, "losses": 0},
                    split_rngs={"params": True, "dropout": True},
                    in_axes=nn.broadcast,
                    length=cfg.num_layers,
                    metadata_params={nn.PARTITION_NAME: None},
                )
                if decode:
                    stack = parameters_only(stack)
                y, _ = stack(cfg, deterministic, decode,
                             name="layers")(x, mask_bias)
            if decode:
                x = decode_layers(
                    self, ParallelTransformerLayer(cfg, parent=None),
                    cfg.num_layers, x, mask_bias=mask_bias,
                    deterministic=deterministic, lanes=lanes)
            else:
                x = y
        else:
            remat_cls = ParallelTransformerLayer
            # decode never remats (inference has no backward) — and the
            # decode kwarg must not reach nn.remat, which would trace
            # the Python bool into a concrete-less tracer
            if cfg.remat and not decode:
                remat_cls = nn.remat(
                    ParallelTransformerLayer, prevent_cse=False,
                    policy=_remat_policy(cfg.remat_policy))
            for i in range(cfg.num_layers):
                # remat_skip_every=k: every k-th layer keeps its
                # activations (no recompute) — trades ~150 MB/layer of
                # HBM for one layer-forward less of backward compute;
                # the memory/FLOPs dial full remat doesn't have
                skip = (cfg.remat_skip_every
                        and i % cfg.remat_skip_every == 0)
                layer_cls = (ParallelTransformerLayer if skip
                             else remat_cls)
                kw = {"decode": True, "lanes": lanes} if decode else {}
                x = layer_cls(cfg, name=f"layer_{i}")(
                    x, mask_bias=mask_bias, deterministic=deterministic,
                    **kw)
        return x
