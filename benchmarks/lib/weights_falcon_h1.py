"""Weights of a Falcon-H1 configuration from ``--seed``, made on the
device in one jitted call, and the same values under the reference's
names.

``lib/weights.py``'s rule (N(0, 0.02), vectors 0) would silence this
model: under ``key_multiplier`` 0.011 every attention score is 0 and the
softmax uniform, ``A`` is -1 and ``dt`` 0.69 on every head, ``D`` is 0.
A muP model carries its multipliers because its weights live at other
scales, so the scale of each leaf is chosen here so that, WITH the
published multipliers applied, every branch speaks:

- the embedding has the standard deviation 1 / ``embedding_multiplier``:
  the residual stream starts at 1;
- ``in_proj`` puts a standard deviation of 4 in front of
  ``ssm_multipliers`` (z 1.4, x 1, B 0.7, C 2, dt 1.4 behind them);
- the convolution's weights are N(0, 1/4) (four taps keep the size),
  its bias 0; ``dt_bias`` is the inverse softplus of a log-uniform step
  in [1e-3, 1e-1], ``A_log`` the logarithm of U[1, 16], ``D`` 1;
- queries and keys are sized so that a score's standard deviation is
  ``SCORE_STD`` = 2 after ``key_multiplier`` and 1 / sqrt(d);
- the three output projections are sized so that mixer, attention and
  MLP each add about ``BRANCH`` = 0.4 to a residual stream of size 1
  (the driver reads what they add on the chip: ``branch_rms`` in the
  notes);
- the head gives logits a standard deviation of about 1.4, the size the
  accepted serve cells' logits have (0.02 x sqrt(4096)).

Norm scales are 1.  The rule is by the leaf's path, in the leaf's own
dtype."""

import math

import jax
import jax.numpy as jnp

SCORE_STD = 2.0
BRANCH = 0.4
LOGIT_STD = 1.4


def seed32(seed):
    """``--seed`` may exceed 32 signed bits; fold it into a key seed."""
    return int(seed) % (2 ** 31 - 1)


def _stds(c):
    """Standard deviation of each matrix leaf's entries, by the module
    that holds it and its name there (a ``kernel`` goes by its layer's
    name)."""
    h, ffn = c["hidden_size"], c["intermediate_size"]
    qk = math.sqrt(SCORE_STD / c["key_multiplier"])    # q, k, v alike
    gate, up = 1.0 / c["mlp_multipliers"][0], 1.0
    return {
        ("embedding", "embedding"): 1.0 / c["embedding_multiplier"],
        ("mamba", "in_proj"): 4.0 / c["ssm_in_multiplier"] / math.sqrt(h),
        ("mamba", "conv_weight"): 0.5,
        # behind the gated group norm the mixer's inner signal is 1
        ("mamba", "out_proj"): BRANCH / c["ssm_out_multiplier"]
        / math.sqrt(c["mamba_d_ssm"]),
        ("attention", "qkv_proj"): qk / c["attention_in_multiplier"]
        / math.sqrt(h),
        # softmax over a few keys at a time: the heads' output keeps
        # about half of v's size
        ("attention", "out_proj"): BRANCH / c["attention_out_multiplier"]
        / (0.5 * qk) / math.sqrt(c["num_attention_heads"] * c["head_dim"]),
        ("mlp", "dense_h_to_4h_gate"): gate / math.sqrt(h),
        ("mlp", "dense_h_to_4h"): up / math.sqrt(h),
        # silu(N(0,1)) * N(0,1) has a standard deviation of 0.6
        ("mlp", "dense_4h_to_h"): BRANCH / c["mlp_multipliers"][1] / 0.6
        / math.sqrt(ffn),
        ("params", "lm_head"): LOGIT_STD / c["lm_head_multiplier"]
        / math.sqrt(h),
    }


def _keys(path):
    """The path's keys as strings, a partitioned leaf's ``value`` box
    left out."""
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    return [n for n in names if n != "value"]


def make_weights(shapes, seed, config):
    """A tree like ``shapes`` (of ``ShapeDtypeStruct``), filled from
    ``seed``.  Call under ``jax.jit`` with ``shapes`` and ``config``
    closed over."""
    stds = _stds(config)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        names = _keys(path)
        k = jax.random.fold_in(key, i)
        last = names[-1]
        std = stds.get(tuple(names[-3:-1] if last == "kernel"
                             else names[-2:]))
        if std is not None:
            val = std * jax.random.normal(k, leaf.shape, jnp.float32)
        elif last == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                k, leaf.shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            val = jnp.log(jnp.expm1(step))
        elif last == "A_log":
            val = jnp.log(jax.random.uniform(k, leaf.shape, jnp.float32,
                                             1.0, 16.0))
        elif last == "D" or "scale" in last:
            val = jnp.ones(leaf.shape, jnp.float32)
        elif last == "conv_bias":
            val = jnp.zeros(leaf.shape, jnp.float32)
        else:
            raise ValueError(f"no rule for the leaf {names}")
        out.append(val.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def reference_weights(params):
    """The program's parameters under the reference's names."""
    p = params["params"]
    lay = p["layers"]["layer"]
    val = lambda x: getattr(x, "value", x)
    mam, att, mlp = lay["mamba"], lay["attention"], lay["mlp"]
    return {
        "emb": val(p["embedding"]["embedding"]),
        "head": val(p["lm_head"]["kernel"]),
        "final_norm": val(p["final_norm"]["scale"]),
        "layers": {
            "ln1": val(lay["input_norm"]["scale"]),
            "ln2": val(lay["pre_ff_norm"]["scale"]),
            "in_proj": val(mam["in_proj"]["kernel"]),
            "conv_w": val(mam["conv_weight"]),
            "conv_b": val(mam["conv_bias"]),
            "dt_bias": val(mam["dt_bias"]),
            "A_log": val(mam["A_log"]),
            "D": val(mam["D"]),
            "mnorm": val(mam["norm_scale"]),
            "out_proj": val(mam["out_proj"]["kernel"]),
            "qkv": val(att["qkv_proj"]["kernel"]),
            "out": val(att["out_proj"]["kernel"]),
            "gate": val(mlp["dense_h_to_4h_gate"]["kernel"]),
            "up": val(mlp["dense_h_to_4h"]["kernel"]),
            "down": val(mlp["dense_4h_to_h"]["kernel"]),
        }}
