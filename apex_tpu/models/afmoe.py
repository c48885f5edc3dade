"""AFMoE — Arcee's Trinity family (``model_type`` ``afmoe``): a decoder
whose layers are of more than one kind.  A few leading DENSE layers,
then EXPERT layers; window and full attention in a repeating pattern
(``layer_types``); in every block a gated attention with normed query
and key heads between sandwich norms.

With ``RMS`` an RMSNorm with a learned gain::

    h0  = E[ids] * sqrt(hidden)                         (mup_enabled)
    a   = Attn(RMS_in(h));      h = h + RMS_post_attn(a)
    m   = MLP(RMS_pre_mlp(h));  h = h + RMS_post_mlp(m)
    Attn(x): q, k, v = W_qkv x;  g = W_g x
             q = RMS_q(q), k = RMS_k(k)      a head over its channels
             window layer: RoPE(q, k); key j visible iff i - window < j <= i
             full layer:   no rotary embedding;  j <= i
             Attn = W_o (softmax(q k^T / sqrt(d)) v * sigmoid(g))
    MLP, dense layer:  SwiGLU of width ``ffn_hidden_size``
    MLP, expert layer: :class:`~apex_tpu.transformer.moe.ExpertShareMLP`
    logits = W_head RMS_final(h)

The building blocks are the zoo's: ``ParallelAttention`` (which takes
the gate, the head norms and each layer's window and positional scheme
as config fields), the SwiGLU ``ParallelMLP`` (the dense layers and the
shared expert), the vocab-parallel embedding and head, and the serving
expert layer, told which experts this chip holds (``experts_held``,
``expert_offset``).

Parameters stack BY KIND — ``dense_layers/layer`` and
``expert_layers/layer``, a leading layer axis each — because a dense
and an expert layer have different trees; window and full layers of one
kind share a stack, the difference being two static attributes.  The
routed experts' matrices are stacked once more, into a BANK at the
model's top (``expert_w_in`` ``(expert layers x held, hidden, 2 x
width)``, ``expert_w_down``): the grouped products take the bank whole
and each layer names its groups in it, because a layer's slice of a
stack is a copy when a kernel reads it (1.8 GB a layer a step).  Every
application walks the layers in published order as one Python loop over
slices of the two stacks
(:func:`~apex_tpu.models.transformer.decode_layers`), each layer with a
config of its own; under ``decode=True`` each keeps its own cache
subtree ``cache/layer_{i}``: the KV pages (one pool geometry for all
layers) and, for an expert layer, ``chunk_lens`` — a row's real lanes
this step, engine-owned: pad lanes are routed to no expert — and
``expert_counts`` — the assignments each held expert got in the step,
which the serving engine reads back beside the tokens.

Not here: training (the expert layer's kernel has no backward), the
exchange between the chips of an expert-parallel group (ROADMAP M3).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.core.mesh import TENSOR_AXIS
from apex_tpu.models.llama import LlamaConfig
from apex_tpu.models.transformer import (
    ParallelAttention,
    ParallelMLP,
    _norm,
    decode_layers,
    parameters_only,
)
from apex_tpu.transformer.layers import (
    ColumnParallelLinear,
    VocabParallelEmbedding,
    maybe_constrain,
)
from apex_tpu.transformer.moe import ExpertShareConfig, ExpertShareMLP

__all__ = ["AfmoeConfig", "AfmoeModel"]

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig(LlamaConfig):
    """AFMoE sizes over the Llama recipe.  ``sliding_window`` is the
    width of the WINDOW layers; ``ffn_hidden_size`` the dense layers'
    MLP width, ``moe_ffn_hidden_size`` one expert's.  The router scores
    by a sigmoid and normalises the selected weights, as the family
    does."""

    layernorm_eps: float = 1e-5
    attn_gate: bool = True
    qk_norm: bool = True
    #: one entry a layer, ``"sliding_attention"`` or ``"full_attention"``
    layer_types: Tuple[str, ...] = ()
    num_dense_layers: int = 1
    num_experts: int = 8                  # the router's width
    experts_held: Optional[int] = None    # this chip's share; None = all
    expert_offset: int = 0
    num_experts_per_tok: int = 2
    num_shared_experts: int = 1
    moe_ffn_hidden_size: int = 1024
    route_scale: float = 1.0
    mup_enabled: bool = True

    def __post_init__(self):
        super().__post_init__()
        if len(self.layer_types) != self.num_layers or any(
                t not in (WINDOW, FULL) for t in self.layer_types):
            raise ValueError(
                f"layer_types holds one of {WINDOW!r} / {FULL!r} a layer "
                f"({self.num_layers}), got {self.layer_types}")
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError(
                f"num_dense_layers ({self.num_dense_layers}) must lie "
                f"in [0, num_layers={self.num_layers}]")
        if self.num_moe_experts:
            raise ValueError("AFMoE's experts are num_experts / "
                             "experts_held, not num_moe_experts")
        self.expert_share             # validates the share

    @property
    def expert_share(self) -> ExpertShareConfig:
        return ExpertShareConfig(
            num_experts=self.num_experts, experts_held=self.experts_held,
            expert_offset=self.expert_offset,
            top_k=self.num_experts_per_tok, route_scale=self.route_scale,
            hidden_size=self.hidden_size,
            ffn_hidden_size=self.moe_ffn_hidden_size,
            activation=self.activation, dtype=self.dtype,
            param_dtype=self.param_dtype)

    @property
    def kv_window(self) -> Optional[int]:
        """The window layers' width, if the stack has such a layer."""
        return self.sliding_window if WINDOW in self.layer_types else None

    def layer_config(self, i: int) -> "AfmoeConfig":
        """Layer ``i``'s own config: its window (none on a full layer)
        and its positional scheme (rotary on window layers only)."""
        window = self.layer_types[i] == WINDOW
        if window and self.sliding_window is None:
            raise ValueError("a window layer needs sliding_window")
        return dataclasses.replace(
            self, sliding_window=self.sliding_window if window else None,
            position_embedding="rope" if window else "none")

    @classmethod
    def from_hf(cls, c, **kw) -> "AfmoeConfig":
        """From the keys of a HuggingFace ``afmoe`` config.  A file
        that describes a cut states beside them: ``layers_run``, the
        published indices of the layers it keeps (``layer_types`` stays
        the published list; default: the first ``num_hidden_layers``),
        and ``router_experts``, the router's width where
        ``num_experts`` counts a held share (with ``expert_offset``)."""
        run = c.get("layers_run", range(c["num_hidden_layers"]))
        kinds = tuple(c["layer_types"][i] for i in run)
        if c["score_func"] != "sigmoid" or not c["route_norm"]:
            raise ValueError(
                "the expert layer scores by a sigmoid and normalises the "
                f"selected weights; got score_func={c['score_func']!r}, "
                f"route_norm={c['route_norm']!r}")
        return cls(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
            num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            kv_channels=c["head_dim"],
            ffn_hidden_size=c["intermediate_size"],
            moe_ffn_hidden_size=c["moe_intermediate_size"],
            max_seq_len=c["max_position_embeddings"],
            sliding_window=c["sliding_window"],
            layernorm_eps=c["rms_norm_eps"],
            rope_base=float(c["rope_theta"]),
            layer_types=kinds,
            num_dense_layers=c["num_dense_layers"],
            num_experts=c.get("router_experts", c["num_experts"]),
            experts_held=c["num_experts"],
            expert_offset=c.get("expert_offset", 0),
            num_experts_per_tok=c["num_experts_per_tok"],
            num_shared_experts=c["num_shared_experts"],
            route_scale=c["route_scale"], mup_enabled=c["mup_enabled"],
            **kw)

    @classmethod
    def tiny(cls, **kw) -> "AfmoeConfig":
        """Test size with the family's ratios: six query heads a KV
        head, a head width that is not hidden / heads, one dense layer
        before a window / window / window / full period, a window
        shorter than the context, 4 of 32 experts held."""
        kw.setdefault("vocab_size", 512)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_layers", 5)
        kw.setdefault("num_heads", 12)
        kw.setdefault("num_kv_heads", 2)
        kw.setdefault("kv_channels", 16)
        kw.setdefault("ffn_hidden_size", 128)
        kw.setdefault("moe_ffn_hidden_size", 128)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("sliding_window", 16)
        kw.setdefault("layer_types", (WINDOW,) * 4 + (FULL,))
        kw.setdefault("num_dense_layers", 1)
        kw.setdefault("num_experts", 32)
        kw.setdefault("experts_held", 4)
        kw.setdefault("num_experts_per_tok", 4)
        kw.setdefault("route_scale", 2.448)
        return cls(**kw)


class AfmoeBlock(nn.Module):
    """One block: gated attention and an MLP, dense or of experts,
    between sandwich norms.  ``cfg`` is the LAYER's config
    (:meth:`AfmoeConfig.layer_config`)."""

    cfg: AfmoeConfig
    experts: bool = False

    @nn.compact
    def __call__(self, h, bank=None, first_group=None, *,
                 decode: bool = False):
        cfg = self.cfg
        a = ParallelAttention(cfg, name="attention")(
            _norm(cfg, "input_norm")(h), decode=decode)
        h = h + _norm(cfg, "post_attention_norm")(a).astype(h.dtype)
        x = _norm(cfg, "pre_mlp_norm")(h)
        if self.experts:
            valid = None
            if decode:
                # engine-owned, like the attention's cursors: how many
                # of each row's lanes hold a token this step (default
                # for other callers: every lane)
                lens = self.variable("cache", "chunk_lens", jnp.full,
                                     h.shape[:1], cfg.max_seq_len,
                                     jnp.int32)
                valid = jnp.arange(h.shape[1])[None] < lens.value[:, None]
            # unbound, so that the expert layer adopts it (its
            # parameters sit under moe/shared_expert)
            shared = ParallelMLP(dataclasses.replace(
                cfg, ffn_hidden_size=cfg.num_shared_experts
                * cfg.moe_ffn_hidden_size), parent=None) \
                if cfg.num_shared_experts else None
            m, counts = ExpertShareMLP(cfg.expert_share, shared,
                                       name="moe")(
                x, bank, first_group, valid)
            if decode:
                # what the step routed here, for the engine's counters
                self.variable("cache", "expert_counts", jnp.zeros,
                              counts.shape, counts.dtype).value = counts
        else:
            m = ParallelMLP(cfg, name="mlp")(x)
        return h + _norm(cfg, "post_mlp_norm")(m).astype(h.dtype)


class _ScanBlock(nn.Module):
    cfg: AfmoeConfig
    experts: bool
    decode: bool = False

    @nn.compact
    def __call__(self, h, bank):
        return AfmoeBlock(self.cfg, self.experts, name="layer")(
            h, bank, None if bank is None else jnp.int32(0),
            decode=self.decode), None


class AfmoeModel(nn.Module):
    """Decoder-only LM; returns logits ``(batch, seq, vocab)``."""

    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, input_ids, *, deterministic: bool = True,
                 decode: bool = False):
        del deterministic                   # no dropout in this family
        cfg = self.cfg
        x = VocabParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="embedding")(input_ids)
        if cfg.mup_enabled:
            x = x.astype(jnp.float32) * math.sqrt(cfg.hidden_size)
        x = x.astype(cfg.dtype)
        n_dense = cfg.num_dense_layers
        n_expert = cfg.num_layers - n_dense
        share = cfg.expert_share
        bank = tuple(
            self.param(name, nn.initializers.normal(0.02), shape,
                       cfg.param_dtype)
            for name, shape in zip(
                ("expert_w_in", "expert_w_down"),
                ExpertShareMLP.bank_shapes(share, n_expert))
        ) if n_expert else None
        stacks = (("dense_layers", False, 0, n_dense),
                  ("expert_layers", True, n_dense, n_expert))
        for scope, experts, first, count in stacks:
            if not count:
                continue
            args = [(bank, jnp.int32(i * share.held)) if experts else ()
                    for i in range(count)]
            if self.is_initializing():
                # the scan makes the stacked parameters; neither the
                # window nor the positional scheme shapes one, so layer
                # `first`'s config stands for the whole stack
                stack = nn.scan(
                    _ScanBlock, variable_axes={"params": 0, "cache": 0},
                    split_rngs={"params": True}, in_axes=nn.broadcast,
                    length=count,
                    metadata_params={nn.PARTITION_NAME: None})
                if decode:
                    stack = parameters_only(stack)
                stack(cfg.layer_config(first), experts, decode,
                      name=scope)(x, bank if experts else None)
            # one module a KIND of layer, so that layers of a kind
            # share a trace
            kinds = {kind: AfmoeBlock(cfg.layer_config(first + i), experts,
                                      parent=None)
                     for i, kind in enumerate(
                         cfg.layer_types[first:first + count])}
            layers = [kinds[kind]
                      for kind in cfg.layer_types[first:first + count]]
            x = decode_layers(self, layers, count, x, scope=scope,
                              first=first, decode=decode, layer_args=args)
        x = _norm(cfg, "final_norm")(x).astype(cfg.dtype)
        logits = ColumnParallelLinear(
            features=cfg.vocab_size, use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="lm_head")(x)
        return maybe_constrain(logits, "data", None, TENSOR_AXIS)
