"""Mixture-of-Experts FFN with expert parallelism.

**Beyond-reference extension** (SURVEY.md §2.6 checklist: "EP / MoE:
ABSENT" in apex) — included because expert parallelism is a
first-class axis of modern TPU training, alongside the ring-attention
context parallelism.

Design (GShard-style dense dispatch, TPU-shaped):

- token-choice top-k gating with load-balancing auxiliary loss;
- capacity-bounded dispatch/combine as einsums against a one-hot
  dispatch mask — dense, static-shaped, MXU-friendly (no ragged
  scatter);
- the stacked expert weights ``(E, ...)`` carry a sharding spec over a
  mesh axis (``expert_axis``); under GSPMD the dispatch einsum lowers
  to the all-to-all that routes tokens to expert shards, exactly where
  a NCCL implementation hand-codes ``all_to_all``.

That is :class:`MoEMLP`, the training zoo's layer.  Beside it stands
the layer that SERVES (:class:`ExpertShareMLP`): drop-free, sorted by
expert, grouped matrix products over ragged groups, and told which of
the layer's experts live on this chip.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.core.mesh import TENSOR_AXIS
from apex_tpu.ops.mlp import resolve_activation
from apex_tpu.transformer.layers import sharded_param

__all__ = ["MoEConfig", "top_k_gating", "MoEMLP",
           "ExpertShareConfig", "ExpertShareMLP", "route_top_k"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    # per-group expert capacity = capacity_factor * S*k/E (group = batch
    # row; bounds dispatch memory linearly in the global token count)
    capacity_factor: float = 1.25
    hidden_size: int = 1024
    ffn_hidden_size: Optional[int] = None
    activation: str = "gelu"
    # gated-linear-unit experts (SwiGLU when activation="silu") — the
    # Mixtral expert shape: act(x·w1) * (x·wg) -> w2
    gated: bool = False
    # expert biases (b1/b2); False for the bias-free Llama/Mixtral
    # recipes (plumbed from TransformerConfig.add_bias_linear)
    use_bias: bool = True
    expert_axis: Optional[str] = TENSOR_AXIS
    aux_loss_weight: float = 1e-2
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @property
    def ffn_size(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size


def top_k_gating(logits: jax.Array, k: int, capacity: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Token-choice top-k routing with capacity.

    ``logits``: (T, E).  Returns ``(dispatch, combine, aux_loss)``:
    ``dispatch`` (T, E, C) one-hot routing mask, ``combine`` (T, E, C)
    = dispatch * gate probability, ``aux_loss`` the Switch/GShard
    load-balancing term (mean_prob · mean_assignment · E).
    Tokens beyond an expert's capacity are dropped (standard GShard
    semantics); position within the expert buffer is assigned in token
    order via a cumulative count.
    """
    t, e = logits.shape
    if k > e:
        raise ValueError(
            f"top_k ({k}) cannot exceed num_experts ({e}) — later "
            f"routing rounds would silently double-route to expert 0")
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    # running per-expert fill count across the k routing rounds
    fill = jnp.zeros((e,), jnp.int32)
    masked = probs
    assign_frac = jnp.zeros((e,), jnp.float32)
    for _ in range(k):
        choice = jnp.argmax(masked, axis=-1)               # (T,)
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.float32)
        assign_frac = assign_frac + jnp.mean(onehot, axis=0)
        # position of each token in its chosen expert's buffer
        pos = jnp.cumsum(onehot, axis=0) - 1.0 + fill[None, :]
        pos_tok = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)
        keep = pos_tok < capacity
        poh = jax.nn.one_hot(pos_tok, capacity, dtype=jnp.float32)
        d = (onehot * keep[:, None].astype(jnp.float32))[..., None] \
            * poh[:, None, :]
        gate = jnp.sum(probs * onehot, axis=-1)            # (T,)
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        fill = fill + jnp.sum(
            onehot * keep[:, None], axis=0).astype(jnp.int32)
        masked = masked * (1.0 - onehot)                   # next round
    # load-balance loss (Switch eq. 4): E * Σ_e mean_prob_e * frac_e
    aux = e * jnp.sum(jnp.mean(probs, axis=0) * assign_frac / k)
    if k > 1:
        # renormalize combine weights over the k selected experts
        denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
        combine = combine / jnp.maximum(denom, 1e-9)
    # k == 1 keeps the raw gate probability as the output scale
    # (Switch semantics) — renormalizing would make it identically 1
    # and cut the router off from the task-loss gradient.
    return dispatch, combine, aux


class MoEMLP(nn.Module):
    """MoE FFN block: gate → dispatch → stacked expert MLPs → combine.

    Drop-in for a dense ``ParallelMLP``; returns ``(y, aux_loss)``.
    Expert weights are stacked ``(E, ...)`` and sharded over
    ``cfg.expert_axis`` — GSPMD inserts the token all-to-all.

    Tokens are routed **per group** (group = batch row, GShard-style):
    per-expert capacity is ``cf·S·k/E`` *per group*, so dispatch/combine
    masks are ``(B, S, E, C)`` — linear in the global token count
    instead of the quadratic blowup of a single flat token pool.
    """

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, h = x.shape
        e = cfg.num_experts
        # ceil, not floor: the documented contract is "at least
        # cf·S·k/E slots"; truncation would drop tokens at nearly
        # double the configured rate at small S
        capacity = max(1, math.ceil(
            cfg.capacity_factor * s * cfg.top_k / e))

        gate_w = self.param("gate", nn.initializers.normal(0.02),
                            (h, e), cfg.param_dtype)
        logits = jnp.einsum("gsh,he->gse", x.astype(jnp.float32),
                            gate_w.astype(jnp.float32))
        dispatch, combine, aux = jax.vmap(
            lambda lg: top_k_gating(lg, cfg.top_k, capacity))(logits)
        aux = jnp.mean(aux)

        def expert_param(name, init, shape):
            if not cfg.expert_axis:
                return self.param(name, init, shape, cfg.param_dtype)
            names = (cfg.expert_axis,) + (None,) * (len(shape) - 1)
            return sharded_param(self, name, init, names, shape,
                                 cfg.param_dtype)

        w1 = expert_param("w1", nn.initializers.he_normal(),
                          (e, h, cfg.ffn_size))
        w2 = expert_param("w2", nn.initializers.he_normal(),
                          (e, cfg.ffn_size, h))
        if cfg.use_bias:
            b1 = expert_param("b1", nn.initializers.zeros_init(),
                              (e, cfg.ffn_size))
            b2 = expert_param("b2", nn.initializers.zeros_init(),
                              (e, h))

        # dispatch: (G,S,E,C) x (G,S,H) -> (G,E,C,H); GSPMD turns the
        # E-sharded contraction into the token all-to-all
        xin = jnp.einsum("gsec,gsh->gech", dispatch.astype(cfg.dtype),
                         x.astype(cfg.dtype))
        act = resolve_activation(cfg.activation, gelu_approximate=True)
        pre = jnp.einsum(
            "gech,ehf->gecf", xin, w1.astype(cfg.dtype),
            preferred_element_type=jnp.float32)
        if cfg.use_bias:
            pre = pre + b1[None, :, None].astype(jnp.float32)
        hmid = act(pre)
        if cfg.gated:
            # SwiGLU-style experts (Mixtral): elementwise gate from a
            # third expert matrix, sharded identically over the
            # expert axis (no bias, as the Llama-family recipe)
            wg = expert_param("wg", nn.initializers.he_normal(),
                              (e, h, cfg.ffn_size))
            hmid = hmid * jnp.einsum(
                "gech,ehf->gecf", xin, wg.astype(cfg.dtype),
                preferred_element_type=jnp.float32)
        yout = jnp.einsum(
            "gecf,efh->gech", hmid.astype(cfg.dtype),
            w2.astype(cfg.dtype),
            preferred_element_type=jnp.float32)
        if cfg.use_bias:
            yout = yout + b2[None, :, None].astype(jnp.float32)
        y = jnp.einsum("gsec,gech->gsh", combine, yout)
        return y.astype(x.dtype), cfg.aux_loss_weight * aux


# --------------------------------------------------------------------- #
# the serving layer: drop-free, one chip's share of the experts
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ExpertShareConfig:
    """An expert layer as one chip of an expert-parallel deployment
    holds it.

    The ROUTER is whole: ``num_experts`` sigmoid scores a token, the
    ``top_k`` best are selected and their weights normalised over all
    of them, held here or not.  The EXPERTS are a share: the
    ``experts_held`` consecutive experts from ``expert_offset`` on live
    on this chip, and the layer returns what THEY add for the tokens
    routed to them — a partial sum; what the absent experts would have
    added is the other chips' to compute and the exchange's to add (not
    here: a single chip serves its share alone, docs/serving.md).
    """

    num_experts: int = 8                 # the router's width
    experts_held: Optional[int] = None   # None = all of them
    expert_offset: int = 0
    top_k: int = 2
    route_scale: float = 1.0
    hidden_size: int = 1024
    ffn_hidden_size: int = 1024          # one expert's width
    activation: str = "silu"
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @property
    def held(self) -> int:
        return (self.num_experts if self.experts_held is None
                else self.experts_held)

    def __post_init__(self):
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(
                f"top_k ({self.top_k}) must lie in [1, num_experts="
                f"{self.num_experts}]")
        if self.held < 1 or self.expert_offset < 0 \
                or self.expert_offset + self.held > self.num_experts:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.held}) are not among the layer's "
                f"{self.num_experts}")


def route_top_k(cfg: ExpertShareConfig, x, router_w, bias):
    """The router, in float32: ``(ids, weights)``, each ``(tokens,
    top_k)``.  ``scores = sigmoid(x W_r)``; the ``top_k`` experts are
    SELECTED by ``scores + bias`` and WEIGHTED by ``scores``:
    ``route_scale * s_e / (sum of the selected s + 1e-20)``."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), cfg.top_k)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return ids, picked * cfg.route_scale


class ExpertShareMLP(nn.Module):
    """The expert layer that serves; returns ``(y, counts)``.

    The tokens of a step are flattened; their ``tokens x top_k``
    assignments are sorted by expert, the assignments to absent
    experts behind those to held ones, as one tail group that no
    product visits (they are MASKED there, not compacted away: the
    sorted buffer always holds ``tokens x top_k`` rows, padded to the
    kernel's row tile).  The gate/up and the down projections run as
    grouped matrix products over the ragged groups
    (:func:`apex_tpu.ops.expert_gmm.expert_gmm`); the results return to
    assignment order, take their weights and add up a token; the shared
    expert's output is added.  NO token is dropped and NO capacity
    exists: an expert that every token chose multiplies every token,
    and an expert that no token chose costs nothing — its group is
    empty and its matrices are never read.  ``counts`` ``(held,)``
    int32 says how many assignments each held expert got this call.

    Parameters: ``router`` ``(hidden, num_experts)`` and
    ``expert_bias`` ``(num_experts,)`` float32 (selection only).  The
    experts' matrices come from outside, as a BANK: ``bank = (w_in,
    w_down)``, ``w_in`` ``(groups, hidden, 2 x ffn)`` with columns
    ``[gate | up]`` and ``w_down`` ``(groups, ffn, hidden)``, of which
    this layer's are the ``held`` groups from ``first_group`` (a traced
    scalar) on.  The products take the bank whole, with every other
    group empty.  A model stacks the banks of all its expert layers
    into one (:meth:`bank_shapes`): a layer's slice of a stacked
    parameter is a COPY when a kernel reads it (1.8 GB a layer a step
    at Trinity's widths), where a GEMM would fuse it.

    ``shared_expert`` is the module every token passes beside the
    routed experts (the model's own SwiGLU; ``None``: no shared
    expert); its parameters sit under this layer's ``shared_expert``.

    ``valid`` ``(batch, seq)`` bool marks the positions that hold a
    token; the others — the pad lanes of a serving step, most of a
    mixed step's — are routed nowhere: they are not tokens, they would
    all be the same one, and whichever experts that one chose would
    carry hundreds of rows for nobody.  Their routed part is 0.
    """

    cfg: ExpertShareConfig
    shared_expert: Optional[nn.Module] = None

    @staticmethod
    def bank_shapes(cfg: ExpertShareConfig, layers: int):
        """Shapes of a bank for ``layers`` layers: ``(w_in, w_down)``."""
        h, f, g = cfg.hidden_size, cfg.ffn_hidden_size, layers * cfg.held
        return (g, h, 2 * f), (g, f, h)

    @nn.compact
    def __call__(self, x, bank, first_group=0, valid=None):
        from apex_tpu.ops.expert_gmm import ROW_TILE, expert_gmm

        cfg = self.cfg
        b, s, h = x.shape
        k, held, f = cfg.top_k, cfg.held, cfg.ffn_hidden_size
        router_w = self.param("router", nn.initializers.normal(0.02),
                              (h, cfg.num_experts), cfg.param_dtype)
        bias = self.param("expert_bias", nn.initializers.zeros_init(),
                          (cfg.num_experts,), jnp.float32)
        w_in, w_down = bank
        tokens = x.reshape(b * s, h).astype(cfg.dtype)
        ids, weights = route_top_k(cfg, tokens, router_w, bias)

        # assignment a = (token a // k, its (a % k)-th choice); held
        # experts sort to the front by their local id, absent ones
        # into one tail group
        local = ids.reshape(-1) - cfg.expert_offset
        is_held = (local >= 0) & (local < held)
        if valid is not None:
            is_held &= jnp.repeat(valid.reshape(-1), k)
        group = jnp.where(is_held, local, held)
        counts = jnp.sum(
            group[:, None] == jnp.arange(held, dtype=group.dtype)[None],
            axis=0, dtype=jnp.int32)
        order = jnp.argsort(group, stable=True)
        rows = tokens[order // k]
        pad = -rows.shape[0] % ROW_TILE
        if pad:
            rows = jnp.pad(rows, ((0, pad), (0, 0)))
        act = resolve_activation(cfg.activation, gelu_approximate=True)
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((w_in.shape[0],), jnp.int32), counts, (first_group,))
        y = expert_gmm(rows, w_in.astype(cfg.dtype), sizes)
        y = expert_gmm(act(y[:, :f]) * y[:, f:], w_down.astype(cfg.dtype),
                       sizes)
        # back to assignment order; a row no product visited holds
        # anything at all, so it is selected away, not multiplied by 0
        y = jnp.where(is_held[:, None],
                      y[jnp.argsort(order)].astype(jnp.float32), 0.0)
        out = jnp.sum(y.reshape(b * s, k, h) * weights[..., None], axis=1)
        out = out.astype(cfg.dtype).reshape(b, s, h)
        if self.shared_expert is not None:
            out = out + self.shared_expert(x).astype(cfg.dtype)
        return out, counts
