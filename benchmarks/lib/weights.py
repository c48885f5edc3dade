"""Weights from ``--seed``, made on the device in one jitted call.

The benchmark, not the program, decides the values: the program only
says which leaves exist (``jax.eval_shape`` of its ``init``).  The rule
is by the leaf's last path key and rank, the classic transformer
initialisation: ``*scale`` leaves are 1, other vectors (biases) 0,
everything of rank >= 2 is N(0, 0.02), in the leaf's own dtype."""

import jax
import jax.numpy as jnp

STD = 0.02


def _kind(path, leaf):
    if "scale" in jax.tree_util.keystr(path).split("[")[-1]:
        return "ones"
    return "normal" if leaf.ndim >= 2 else "zeros"


def make_weights(shapes, seed):
    """A tree like ``shapes`` (of ``ShapeDtypeStruct``), filled from
    ``seed``.  Call under ``jax.jit`` with ``shapes`` closed over."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        kind = _kind(path, leaf)
        if kind == "ones":
            out.append(jnp.ones(leaf.shape, leaf.dtype))
        elif kind == "zeros":
            out.append(jnp.zeros(leaf.shape, leaf.dtype))
        else:
            out.append((STD * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, jnp.float32)
            ).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def seed32(seed):
    """``--seed`` may exceed 32 signed bits; fold it into a key seed."""
    return int(seed) % (2 ** 31 - 1)


def flat_names(tree):
    """``{path string: leaf}`` in flatten order."""
    return {jax.tree_util.keystr(p): l
            for p, l in jax.tree_util.tree_flatten_with_path(tree)[0]}
