"""Weights of an AFMoE configuration from ``--seed``, made on the device
in one jitted call, and the same values under the reference's names.

In a sandwich-norm block every branch ends in an RMS norm, so what a
branch adds to the residual stream is its POST-norm's gain and nothing
else; the scales below are chosen so that every branch speaks and the
router's scores spread:

- the embedding has the standard deviation 1 / sqrt(hidden): under the
  published ``sqrt(hidden)`` scale the residual stream starts at 1;
- every projection that reads a normed input (qkv, the attention gate,
  the MLPs' gate and up, each expert's) is N(0, 1 / hidden): unit
  outputs; down and out projections N(0, 1 / fan-in);
- the head norms' gains are ``sqrt(SCORE_STD)`` each, so that a score
  has the standard deviation ``SCORE_STD`` = 2 after 1 / sqrt(d) (unit
  gains would leave the softmax near uniform over thousands of keys);
- the post-attention and post-MLP gains are ``BRANCH`` = 0.4 (what the
  family's "depth-scaled" initialisation sets is this gain; its value
  there is not published with the config): ten branches take the
  stream from 1 to about 1.6; the other norms' gains are 1;
- the router is N(0, ``ROUTER_STD``^2 / hidden): logits of standard
  deviation 1, sigmoid scores spread over 0.12-0.88, the four selected
  near 0.9 (the top 4 of 256 lie 2.15 deviations out, where the
  sigmoid's slope is 0.09);
- the selection bias ``b`` is N(0, ``BIAS_STD``^2), 0.002: not zero,
  and small beside the spread of the scores that compete for the fourth
  place, which is what a bias that BALANCES the load leaves behind — an
  expert's load goes as exp(2.15 b / 0.09), so 0.002 moves it by 5 %
  and the 32 held experts' sum by under 1 %.  (At 0.1, with logits of
  deviation 1.5, the scores at the top saturate within 0.03 of each
  other and ``b`` alone selected: every token the same few experts,
  which of them are held the seed's luck, and the step's time with it:
  PERF.md section 6, PR 37.)  The rehearsal's sizes behave alike;
- the head gives logits a standard deviation of about 1.4 for a final
  stream of size 1, the size the accepted serve cells' logits have.

The rule is by the leaf's path, in the leaf's own dtype."""

import math

import jax
import jax.numpy as jnp

SCORE_STD = 2.0
BRANCH = 0.4
ROUTER_STD = 1.0
BIAS_STD = 0.002
LOGIT_STD = 1.4


def seed32(seed):
    """``--seed`` may exceed 32 signed bits; fold it into a key seed."""
    return int(seed) % (2 ** 31 - 1)


def _keys(path):
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    return [n for n in names if n != "value"]


def _rule(names, shape, c):
    """``("normal", std)`` or ``("const", value)`` for one leaf."""
    h = c["hidden_size"]
    last = names[-1]
    owner = names[-2] if last in ("kernel", "scale", "embedding") else last
    if owner == "embedding":
        return "normal", 1.0 / math.sqrt(h)
    if owner in ("q_norm", "k_norm"):
        return "const", math.sqrt(SCORE_STD)
    if owner in ("post_attention_norm", "post_mlp_norm"):
        return "const", BRANCH
    if last == "scale":
        return "const", 1.0
    if owner == "router":
        return "normal", ROUTER_STD / math.sqrt(h)
    if owner == "expert_bias":
        return "normal", BIAS_STD
    if owner == "lm_head":
        return "normal", LOGIT_STD / math.sqrt(h)
    if owner in ("qkv_proj", "gate_proj", "dense_h_to_4h",
                 "dense_h_to_4h_gate", "expert_w_in"):
        return "normal", 1.0 / math.sqrt(h)
    if owner in ("out_proj", "dense_4h_to_h", "expert_w_down"):
        return "normal", 1.0 / math.sqrt(shape[-2])
    raise ValueError(f"no rule for the leaf {names}")


def make_weights(shapes, seed, config):
    """A tree like ``shapes`` (of ``ShapeDtypeStruct``), filled from
    ``seed``.  Call under ``jax.jit`` with ``shapes`` and ``config``
    closed over."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        kind, value = _rule(_keys(path), leaf.shape, config)
        if kind == "const":
            val = jnp.full(leaf.shape, value, jnp.float32)
        else:
            val = value * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, jnp.float32)
        out.append(val.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def reference_weights(params, config):
    """The program's parameters under the reference's names: a list,
    one dict a layer, in published order."""
    p = params["params"]
    val = lambda x: getattr(x, "value", x)

    def one(lay, i, experts):
        att = lay["attention"]
        mlp = lay["moe"]["shared_expert"] if experts else lay["mlp"]
        w = {
            "ln_in": lay["input_norm"]["scale"],
            "ln_post_attn": lay["post_attention_norm"]["scale"],
            "ln_pre_mlp": lay["pre_mlp_norm"]["scale"],
            "ln_post_mlp": lay["post_mlp_norm"]["scale"],
            "qkv": att["qkv_proj"]["kernel"],
            "q_norm": att["q_norm"], "k_norm": att["k_norm"],
            "gate_proj": att["gate_proj"]["kernel"],
            "out": att["out_proj"]["kernel"],
            "gate": mlp["dense_h_to_4h_gate"]["kernel"],
            "up": mlp["dense_h_to_4h"]["kernel"],
            "down": mlp["dense_4h_to_h"]["kernel"]}
        if experts:
            moe = lay["moe"]
            w.update(router=moe["router"], bias=moe["expert_bias"])
        w = {k: val(v)[i] for k, v in w.items()}
        if experts:
            # the bank whole and where this layer's experts start in it
            w.update(w_in=val(p["expert_w_in"]),
                     w_down=val(p["expert_w_down"]),
                     first=jnp.int32(i * config["num_experts"]))
        return w

    n_dense = config["num_dense_layers"]
    layers = [one(p["dense_layers"]["layer"], i, False)
              for i in range(n_dense)]
    layers += [one(p["expert_layers"]["layer"], i, True)
               for i in range(config["num_hidden_layers"] - n_dense)]
    return {"emb": val(p["embedding"]["embedding"]),
            "head": val(p["lm_head"]["kernel"]),
            "final_norm": val(p["final_norm"]["scale"]),
            "layers": layers}
