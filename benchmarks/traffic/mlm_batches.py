"""BERT pretraining batches from a seed (after ``bench.mlm_batch``).

Parameters: ``batch``, ``seq``, ``mlm_positions``, ``distinct`` (how
many different batches the window cycles through).  Every row of every
batch differs.  As in the program's own generator the label of a masked
position is the token that stands there; no token is replaced, so the
loss starts near ln(vocab).  Made on the device in one jitted call."""

import jax
import jax.numpy as jnp


def generate(params, seed, vocab):
    b, s = params["batch"], params["seq"]
    p, n = params["mlm_positions"], params["distinct"]

    def make(key):
        k_ids, k_pos = jax.random.split(key)
        ids = jax.random.randint(k_ids, (n, b, s), 0, vocab, jnp.int32)
        order = jnp.argsort(jax.random.uniform(k_pos, (n, b, s)), axis=-1)
        positions = order[..., :p].astype(jnp.int32)
        labels = jnp.take_along_axis(ids, positions, axis=-1)
        return ids, positions, labels

    ids, positions, labels = jax.jit(make)(jax.random.PRNGKey(seed))
    return [(ids[i], positions[i], labels[i]) for i in range(n)]
