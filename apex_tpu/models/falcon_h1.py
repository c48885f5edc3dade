"""Falcon-H1 — a hybrid decoder: in EVERY block a Mamba-2 (SSD) mixer
and a grouped-query attention read the same normed input in parallel
and both add to the residual, followed by a SwiGLU MLP; muP-style
multipliers stand on every branch (TII, ``model_type`` ``falcon_h1``).

With ``x = RMSNorm(h)``::

    [z | xBC | dt] = (W_in (x * ssm_in_multiplier)) * mu
    xBC = silu(conv1d(xBC))          depthwise, causal, width d_conv
    x_s, B, C = split(xBC)           heads of a group share B and C
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t;  y_t = S_t C_t + D x_t
    m = W_out GroupRMSNorm(y * silu(z)) * ssm_out_multiplier
    a = Attention(x * attention_in_multiplier) * attention_out_multiplier
    h = h + m + a
    h = h + W_down(silu(W_gate y' * g) * W_up y') * mlp_down_multiplier

``mu`` holds ``ssm_multipliers`` on the columns of z, x, B, C and dt;
the keys carry ``key_multiplier`` and the gate ``mlp_gate_multiplier``
inside the shared :class:`ParallelAttention` / :class:`ParallelMLP`.
Every multiplier is applied where it stands, none is folded into a
weight.

The building blocks are the zoo's: RMSNorm, ``ParallelAttention`` (its
paged path unchanged), the SwiGLU ``ParallelMLP``, the vocab-parallel
embedding and head, parameters stacked under one ``nn.scan`` (which a
``decode=True`` application replaces by a loop over its layers,
:func:`~apex_tpu.models.transformer.decode_layers`).  New is the mixer
and its recurrent state: under ``decode=True`` (paged serving only) each
layer keeps, a slot, ``ssm_state`` (heads, d_head, d_state) in float32
and ``conv_state`` (d_conv - 1, channels), beside the KV pages in the
``"cache"`` collection, together with ``cursors`` and ``chunk_lens``
leaves that the serving engine overwrites before every step.  A row
whose cursor is 0 starts from zero state whatever the buffers hold, and
only a row's ``chunk_lens`` real lanes move its state
(:mod:`apex_tpu.ops.ssm`).

Not here: training through the scan (no backward is defined for the
kernels; the XLA reference differentiates), the dense ``generate()``
cache, state snapshots (prefix sharing, speculation) and the state
under tensor parallelism — ROADMAP M6.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.core.mesh import TENSOR_AXIS
from apex_tpu.models.llama import LlamaConfig
from apex_tpu.models.transformer import (
    Lanes,
    ParallelAttention,
    ParallelMLP,
    _norm,
    decode_layers,
    parameters_only,
)
from apex_tpu.ops.ssm import (causal_conv_step, ssd_chunk_scan,
                              ssm_decode_update)
from apex_tpu.transformer.layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    maybe_constrain,
)

__all__ = ["FalconH1Config", "FalconH1Model"]


@dataclasses.dataclass(frozen=True)
class FalconH1Config(LlamaConfig):
    """Falcon-H1 sizes and multipliers over the Llama recipe."""

    layernorm_eps: float = 1e-5
    mamba_d_ssm: int = 1024
    mamba_n_heads: int = 8
    mamba_d_head: int = 128
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    # lanes a call of the scan takes on the full-sequence (non-decode)
    # path; the serving step's width is the engine's prefill_chunk
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    #: on the in-projection's columns of z, x, B, C, dt
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_down_multiplier: float = 1.0

    def __post_init__(self):
        super().__post_init__()
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(
                f"mamba_d_ssm ({self.mamba_d_ssm}) must be mamba_n_heads "
                f"x mamba_d_head ({self.mamba_n_heads} x "
                f"{self.mamba_d_head})")
        if self.mamba_n_heads % self.mamba_n_groups \
                or self.mamba_d_ssm % self.mamba_n_groups:
            raise ValueError(
                f"mamba_n_groups ({self.mamba_n_groups}) must divide "
                f"mamba_n_heads ({self.mamba_n_heads})")
        if len(self.ssm_multipliers) != 5:
            raise ValueError("ssm_multipliers holds five numbers: on "
                             "z, x, B, C and dt")
        if self.num_moe_experts or self.sliding_window is not None:
            raise ValueError("Falcon-H1 has a dense MLP and full "
                             "attention")

    @property
    def conv_channels(self) -> int:
        """Columns the convolution runs over: x, B and C."""
        return self.mamba_d_ssm \
            + 2 * self.mamba_n_groups * self.mamba_d_state

    @classmethod
    def from_hf(cls, c, **kw) -> "FalconH1Config":
        """From the keys of a HuggingFace ``falcon_h1`` config."""
        return cls(
            vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
            num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            kv_channels=c["head_dim"],
            ffn_hidden_size=c["intermediate_size"],
            max_seq_len=c["max_position_embeddings"],
            layernorm_eps=c["rms_norm_eps"],
            rope_base=float(c["rope_theta"]),
            mamba_d_ssm=c["mamba_d_ssm"], mamba_n_heads=c["mamba_n_heads"],
            mamba_d_head=c["mamba_d_head"],
            mamba_d_state=c["mamba_d_state"],
            mamba_n_groups=c["mamba_n_groups"],
            mamba_d_conv=c["mamba_d_conv"],
            mamba_chunk_size=c["mamba_chunk_size"],
            embedding_multiplier=c["embedding_multiplier"],
            lm_head_multiplier=c["lm_head_multiplier"],
            attention_in_multiplier=c["attention_in_multiplier"],
            attention_out_multiplier=c["attention_out_multiplier"],
            key_multiplier=c["key_multiplier"],
            ssm_in_multiplier=c["ssm_in_multiplier"],
            ssm_out_multiplier=c["ssm_out_multiplier"],
            ssm_multipliers=tuple(c["ssm_multipliers"]),
            mlp_gate_multiplier=c["mlp_multipliers"][0],
            mlp_down_multiplier=c["mlp_multipliers"][1], **kw)

    @classmethod
    def tiny(cls, **kw) -> "FalconH1Config":
        """Test size that keeps every ratio of the 34B model: two B/C
        groups, five query heads a KV head, a head width that is not
        hidden / heads, d_conv 4."""
        kw.setdefault("vocab_size", 512)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 10)
        kw.setdefault("num_kv_heads", 2)
        kw.setdefault("kv_channels", 16)
        kw.setdefault("ffn_hidden_size", 128)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("mamba_d_ssm", 64)
        kw.setdefault("mamba_n_heads", 4)
        kw.setdefault("mamba_d_head", 16)
        kw.setdefault("mamba_d_state", 128)
        kw.setdefault("mamba_n_groups", 2)
        kw.setdefault("mamba_chunk_size", 8)
        return cls(**kw)


def _scaled(x, mult):
    return x if mult == 1.0 else x * jnp.asarray(mult, x.dtype)


class FalconH1Mixer(nn.Module):
    """The Mamba-2 mixer of one block (see the module docstring)."""

    cfg: FalconH1Config

    @nn.compact
    def __call__(self, x, *, decode: bool = False,
                 lanes: Optional[Lanes] = None):
        cfg = self.cfg
        b, s, _ = x.shape
        heads, p = cfg.mamba_n_heads, cfg.mamba_d_head
        g, n = cfg.mamba_n_groups, cfg.mamba_d_state
        d_ssm, cc, k = cfg.mamba_d_ssm, cfg.conv_channels, cfg.mamba_d_conv
        f32 = jnp.float32

        proj = ColumnParallelLinear(
            features=d_ssm + cc + heads, use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="in_proj")(_scaled(x, cfg.ssm_in_multiplier))
        mz, mx, mb, mc, mdt = cfg.ssm_multipliers
        if any(m != 1.0 for m in cfg.ssm_multipliers):
            mu = jnp.concatenate([
                jnp.full((d_ssm,), mz), jnp.full((d_ssm,), mx),
                jnp.full((g * n,), mb), jnp.full((g * n,), mc),
                jnp.full((heads,), mdt)]).astype(proj.dtype)
            proj = proj * mu
        z = proj[..., :d_ssm]
        udt = proj[..., d_ssm:]
        if lanes is not None:
            # a compact mixed step: the convolution and the scan run
            # over rows, the projections and the gate on the list
            udt = lanes.to_rows(udt)
            b, s = udt.shape[:2]
        u, dt = udt[..., :cc], udt[..., cc:]

        conv_w = self.param("conv_weight", nn.initializers.normal(0.5),
                            (k, cc), cfg.param_dtype)
        conv_b = self.param("conv_bias", nn.initializers.zeros_init(),
                            (cc,), cfg.param_dtype)
        dt_bias = self.param("dt_bias", nn.initializers.zeros_init(),
                             (heads,), f32)
        a_log = self.param("A_log", nn.initializers.zeros_init(),
                           (heads,), f32)
        d_skip = self.param("D", nn.initializers.ones_init(),
                            (heads,), f32)
        norm_w = self.param("norm_scale", nn.initializers.ones_init(),
                            (d_ssm,), cfg.param_dtype)

        if decode:
            if cfg.kv_cache != "paged":
                raise ValueError(
                    "FalconH1Model decodes through the paged serving "
                    "engine only (PagedEngine, which InferenceServer "
                    "builds): the dense generate() cache keeps no "
                    "recurrent state")
            st = self.variable("cache", "ssm_state", jnp.zeros,
                               (b, heads, p, n), f32)
            win = self.variable("cache", "conv_state", jnp.zeros,
                                (b, k - 1, cc), u.dtype)
            # engine-owned, like the attention's: where each row stands
            # and how many of this step's lanes are real.  Defaults for
            # other callers: position 0 (start from zero), every lane
            cur = self.variable("cache", "cursors", jnp.zeros,
                                (b,), jnp.int32)
            cl = self.variable("cache", "chunk_lens", jnp.full,
                               (b,), cfg.max_seq_len, jnp.int32)
            lens = jnp.minimum(cl.value, s)
            # a row with no real lane (one the step leaves waiting)
            # keeps its state whatever its cursor
            reset = (cur.value == 0) & (lens > 0)
            state, window = st.value, win.value
        else:
            lens = jnp.full((b,), s, jnp.int32)
            reset = jnp.ones((b,), bool)
            state = jnp.zeros((b, heads, p, n), f32)
            window = jnp.zeros((b, k - 1, cc), u.dtype)

        u, window = causal_conv_step(u, window, conv_w, conv_b, lens,
                                     reset)
        u = jax.nn.silu(u).astype(cfg.dtype)
        xs = u[..., :d_ssm].reshape(b, s, heads, p)
        bm = u[..., d_ssm:d_ssm + g * n].reshape(b, s, g, n)
        cm = u[..., d_ssm + g * n:].reshape(b, s, g, n)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
        a = -jnp.exp(a_log)

        if decode and s == 1:
            y, state = ssm_decode_update(
                xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], state, lens,
                reset)
            y = y[:, None]
        elif decode:
            y, state = ssd_chunk_scan(xs, dt, a, bm, cm, state, lens,
                                      reset)
        else:
            y = self._full_sequence(xs, dt, a, bm, cm, state)
        if decode:
            st.value, win.value = state, window

        y = (y + d_skip[:, None] * xs.astype(f32)).reshape(b, s, d_ssm)
        if lanes is not None:
            y = lanes.take(y)
        lead = z.shape[:2]
        y = y * jax.nn.silu(z.astype(f32))
        # gate first, then an RMS norm over each group's columns
        yg = y.reshape(lead + (g, d_ssm // g)).astype(f32)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg.layernorm_eps)
        y = (yg.reshape(lead + (d_ssm,))
             * norm_w.astype(f32)).astype(cfg.dtype)
        out = RowParallelLinear(
            features=cfg.hidden_size, use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="out_proj")(y)
        return _scaled(out, cfg.ssm_out_multiplier)

    def _full_sequence(self, xs, dt, a, bm, cm, state):
        """A whole sequence from zero state, ``mamba_chunk_size`` lanes
        a call of the scan."""
        b, s = xs.shape[:2]
        c = min(self.cfg.mamba_chunk_size, s)
        pad = -s % c
        cut = lambda v: jnp.moveaxis(
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            .reshape((b, (s + pad) // c, c) + v.shape[2:]), 1, 0)
        lens = jnp.clip(s - jnp.arange((s + pad) // c) * c, 0, c)
        never = jnp.zeros((b,), bool)

        def chunk(st, part):
            x_c, dt_c, b_c, c_c, n_real = part
            y, st = ssd_chunk_scan(
                x_c, dt_c, a, b_c, c_c, st,
                jnp.full((b,), n_real, jnp.int32), never)
            return st, y

        _, y = jax.lax.scan(chunk, state, (cut(xs), cut(dt), cut(bm),
                                           cut(cm), lens))
        return jnp.moveaxis(y, 0, 1).reshape(
            (b, s + pad) + y.shape[3:])[:, :s]


class FalconH1Block(nn.Module):
    """Mixer and attention side by side on one normed input, then the
    MLP."""

    cfg: FalconH1Config

    @nn.compact
    def __call__(self, h, *, decode: bool = False,
                 lanes: Optional[Lanes] = None):
        cfg = self.cfg
        x = _norm(cfg, "input_norm")(h)
        m = FalconH1Mixer(cfg, name="mamba")(x, decode=decode, lanes=lanes)
        a = ParallelAttention(cfg, name="attention")(
            _scaled(x, cfg.attention_in_multiplier), decode=decode,
            lanes=lanes)
        a = _scaled(a, cfg.attention_out_multiplier)
        h = h + m.astype(h.dtype) + a.astype(h.dtype)
        y = _norm(cfg, "pre_ff_norm")(h)
        y = _scaled(ParallelMLP(cfg, name="mlp")(y),
                    cfg.mlp_down_multiplier)
        return h + y.astype(h.dtype)


class _ScanBlock(nn.Module):
    cfg: FalconH1Config
    decode: bool = False

    @nn.compact
    def __call__(self, h, _):
        return FalconH1Block(self.cfg, name="layer")(
            h, decode=self.decode), None


class FalconH1Model(nn.Module):
    """Decoder-only LM; returns logits ``(batch, seq, vocab)``."""

    cfg: FalconH1Config

    @nn.compact
    def __call__(self, input_ids, *, deterministic: bool = True,
                 decode: bool = False, lanes: Optional[Lanes] = None):
        """``lanes``: as :class:`~apex_tpu.models.gpt.GPTModel`'s — the
        real lanes of a mixed serving step, ``(rows, 1, vocab)`` back."""
        del deterministic                   # no dropout in this family
        cfg = self.cfg
        if lanes is not None:
            input_ids = lanes.take(input_ids)
        x = VocabParallelEmbedding(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="embedding")(input_ids)
        x = _scaled(x.astype(cfg.dtype), cfg.embedding_multiplier)
        # a decode application never scans over its cache
        # (transformer.decode_layers has why); at init the scan makes
        # the stacked parameters all the same
        if not decode or self.is_initializing():
            stack = nn.scan(
                _ScanBlock,
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True},
                in_axes=nn.broadcast,
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: None},
            )
            if decode:
                stack = parameters_only(stack)
            y, _ = stack(cfg, decode, name="layers")(x, None)
        x = (decode_layers(self, FalconH1Block(cfg, parent=None),
                           cfg.num_layers, x, lanes=lanes)
             if decode else y)
        if lanes is not None:
            x = lanes.last_lanes(x)
        x = _norm(cfg, "final_norm")(x).astype(cfg.dtype)
        logits = ColumnParallelLinear(
            features=cfg.vocab_size, use_bias=False,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="lm_head")(x)
        logits = _scaled(logits, cfg.lm_head_multiplier)
        return maybe_constrain(logits, "data", None, TENSOR_AXIS)
