"""GPT — decoder-only flagship model (tensor/sequence-parallel).

Reference: ``apex/transformer/testing/standalone_gpt.py`` (the toy
Megatron GPT the reference's pipeline/TP tests train) and the GPT-2-1.3B
tensor-parallel config of BASELINE.json (``configs[3]``).

TPU-native: GSPMD end to end — VocabParallelEmbedding (vocab sharded
over ``tensor``), scanned ParallelTransformer stack, final norm, tied or
untied vocab-parallel LM head; loss = memory-saving softmax cross
entropy (``apex.contrib.xentropy`` parity).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.core.mesh import TENSOR_AXIS
from apex_tpu.models.transformer import (
    Lanes,
    ParallelTransformer,
    TransformerConfig,
    _norm,
)
from apex_tpu.ops.xentropy import mean_cross_entropy
from apex_tpu.transformer.layers import (
    ColumnParallelLinear,
    VocabParallelEmbedding,
    maybe_constrain,
)

__all__ = ["GPTConfig", "GPTModel", "gpt_loss_fn", "moe_aux_loss"]


@dataclasses.dataclass(frozen=True)
class GPTConfig(TransformerConfig):
    """GPT architecture presets (reference workload: GPT-2 1.3B TP)."""

    tie_embeddings: bool = True

    @classmethod
    def tiny(cls, **kw) -> "GPTConfig":
        """Test-size config (standalone_gpt scale)."""
        kw.setdefault("vocab_size", 1024)
        kw.setdefault("hidden_size", 256)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 2)
        kw.setdefault("max_seq_len", 256)
        return cls(**kw)

    @classmethod
    def gpt2_1p3b(cls, **kw) -> "GPTConfig":
        """BASELINE.json configs[3]: GPT-2 1.3B (Megatron sizing,
        learned absolute positions like GPT-2/standalone_gpt)."""
        kw.setdefault("vocab_size", 50304)
        kw.setdefault("hidden_size", 2048)
        kw.setdefault("num_layers", 24)
        kw.setdefault("num_heads", 16)
        kw.setdefault("max_seq_len", 2048)
        kw.setdefault("position_embedding", "learned")
        return cls(**kw)


class GPTModel(nn.Module):
    """Decoder-only LM; returns logits ``(batch, seq, vocab)``."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, input_ids, *, deterministic: bool = True,
                 decode: bool = False, lanes: Optional[Lanes] = None):
        """``lanes`` (paged serving's mixed step, :class:`~apex_tpu.
        models.transformer.Lanes`): the model runs on the step's real
        lanes only and returns ``(rows, 1, vocab)``, the logits of each
        row's last real lane."""
        cfg = self.cfg
        width = input_ids.shape[1]
        if lanes is not None:
            input_ids = lanes.take(input_ids)
        emb = VocabParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="embedding")
        x = emb(input_ids)
        if cfg.position_embedding == "learned":
            pos_table = self.param(
                "position_embedding", nn.initializers.normal(0.02),
                (cfg.max_seq_len, cfg.hidden_size), cfg.param_dtype)
            if decode and cfg.kv_cache == "paged":
                # paged serving: positions are PER ROW (a ragged batch
                # of tenants, each at its own cursor).  The engine
                # overwrites this leaf every step alongside the
                # per-layer cursors; pad positions clamp into the table
                # (their outputs are ignored and their K/V unreachable)
                pi = self.variable(
                    "cache", "position_index",
                    lambda: jnp.zeros((x.shape[0],), jnp.int32))
                positions = jnp.minimum(
                    pi.value[:, None]
                    + jnp.arange(width, dtype=jnp.int32),
                    cfg.max_seq_len - 1)
                if lanes is not None:
                    positions = lanes.take(positions)
                x = x + pos_table[positions].astype(x.dtype)
            elif decode:
                # incremental decoding: positions continue from the
                # model-level cache index (the per-layer attention
                # caches track their own — they advance in lockstep)
                pi = self.variable("cache", "position_index",
                                   lambda: jnp.array(0, jnp.int32))
                pos = jax.lax.dynamic_slice_in_dim(
                    pos_table, pi.value, x.shape[1], 0)
                pi.value = pi.value + x.shape[1]
                x = x + pos[None].astype(x.dtype)
            else:
                x = x + pos_table[None, : x.shape[1]].astype(x.dtype)
        x = x.astype(cfg.dtype)
        x = ParallelTransformer(cfg, name="transformer")(
            x, deterministic=deterministic, decode=decode, lanes=lanes)
        if lanes is not None:
            x = lanes.last_lanes(x)
        x = _norm(cfg, "final_norm")(x).astype(cfg.dtype)
        if cfg.tie_embeddings:
            logits = emb.attend(x)
        else:
            logits = ColumnParallelLinear(
                features=cfg.vocab_size, use_bias=False,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                name="lm_head")(x)
        return maybe_constrain(logits, "data", None, TENSOR_AXIS)


def gpt_loss_fn(logits, labels, *, ignore_index: int = -100):
    """Next-token CE averaged over valid tokens (memory-saving
    xentropy, fp32)."""
    return mean_cross_entropy(logits, labels, ignore_index=ignore_index)


def moe_aux_loss(mutated_variables) -> jnp.ndarray:
    """Sum the per-layer MoE load-balance terms a model sowed into the
    ``losses`` collection.

    Usage with ``num_moe_experts`` configs::

        logits, mut = model.apply(params, ids, mutable=["losses"])
        loss = gpt_loss_fn(logits, labels) + moe_aux_loss(mut)

    Each term already carries its ``moe_aux_loss_weight``; a model
    without MoE layers (or applied without ``mutable=["losses"]``)
    contributes 0.
    """
    leaves = jax.tree.leaves(dict(mutated_variables).get("losses", {}))
    total = jnp.asarray(0.0, jnp.float32)
    for leaf in leaves:
        total = total + jnp.sum(leaf.astype(jnp.float32))
    return total
