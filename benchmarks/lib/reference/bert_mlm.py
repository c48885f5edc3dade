"""Plain float32 BERT masked-LM pretraining: forward, loss, gradients
and Adam, in straightforward ``jax.numpy``.

The encoder is the pre-norm block (Megatron's BERT, the one this repo
trains): ``x += attn(ln1(x)); x += mlp(ln2(x))``, learned positions and
token types, a layer norm after the embeddings, tanh-GELU, and the MLM
head ``dense -> gelu -> ln -> tied decoder + bias`` on the gathered
masked positions.  The loss is the mean cross entropy over them.

Parameters are a flat dict; q, k and v have a (hidden, hidden) matrix
and a bias each, their columns running head by head.  Each block is under ``jax.checkpoint`` so
that the float32 activations of 24 layers fit beside the weights."""

import jax
import jax.numpy as jnp

from .quant import einsum, matmul


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(x, p, heads, eps, lower):
    b, s, h = x.shape
    d = h // heads
    a = layer_norm(x, p["ln1.w"], p["ln1.b"], eps)
    q, k, v = ((matmul(a, p[f"{n}.w"], lower) + p[f"{n}.b"]
                ).reshape(b, s, heads, d) for n in "qkv")
    scores = einsum("bqhd,bkhd->bhqk", q, k, lower) * d ** -0.5
    probs = jax.nn.softmax(scores, axis=-1)
    o = einsum("bhqk,bkhd->bqhd", probs, v, lower).reshape(b, s, h)
    x = x + matmul(o, p["out.w"], lower) + p["out.b"]
    m = layer_norm(x, p["ln2.w"], p["ln2.b"], eps)
    m = gelu(matmul(m, p["fc1.w"], lower) + p["fc1.b"])
    return x + matmul(m, p["fc2.w"], lower) + p["fc2.b"]


def loss_fn(params, ids, positions, labels, *, layers, heads, eps,
            lower=None):
    s = ids.shape[1]
    x = params["tok_emb"][ids] + params["pos_emb"][None, :s] \
        + params["type_emb"][0]
    x = layer_norm(x, params["emb_ln.w"], params["emb_ln.b"], eps)
    # one block, scanned over the stacked layers (compiles once)
    names = [k[len("layer0."):] for k in params if k.startswith("layer0.")]
    stacked = {n: jnp.stack([params[f"layer{i}.{n}"]
                             for i in range(layers)]) for n in names}
    blk = jax.checkpoint(lambda x, p: (block(x, p, heads, eps, lower), None))
    x, _ = jax.lax.scan(blk, x, stacked)
    x = jnp.take_along_axis(x, positions[..., None], axis=1)
    hdn = gelu(matmul(x, params["head.dense.w"], lower)
               + params["head.dense.b"])
    hdn = layer_norm(hdn, params["head.ln.w"], params["head.ln.b"], eps)
    logits = matmul(hdn, params["tok_emb"].T, lower) + params["head.bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def adam(params, grads, m, v, count, lr, b1=0.9, b2=0.999, eps=1e-8):
    """One bias-corrected Adam step, no weight decay."""
    c = jnp.float32(count)
    bc1, bc2 = 1.0 - b1 ** c, 1.0 - b2 ** c
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps),
        params, m, v)
    return params, m, v


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(x))) for k, x in tree.items()}


def train_readings(params, batches, *, layers, heads, eps, lr,
                   lower=None, frozen=False):
    """Follow the first ``len(batches)`` steps from ``params``.
    Returns the losses, the per-leaf norm of the first gradient, and
    the per-leaf norm of the parameters' change after the last step.
    ``frozen`` plants the fault of a step that returns its state
    unchanged: every step starts from ``params`` again."""
    step = jax.jit(lambda p, m, v, c, ids, pos, lab: _step(
        p, m, v, c, ids, pos, lab, layers, heads, eps, lr, lower))
    zeros = jax.tree.map(jnp.zeros_like, params)
    p, m, v = params, zeros, zeros
    losses, grad_norms = [], None
    for i, (ids, pos, lab) in enumerate(batches):
        new = step(p, m, v, i + 1, ids, pos, lab)
        loss, gn = new[3:]
        if not frozen:
            p, m, v = new[:3]
        losses.append(loss)
        if i == 0:
            grad_norms = gn
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(p, params)
    return ([float(l) for l in losses],
            {k: float(x) for k, x in grad_norms.items()},
            {k: float(x) for k, x in delta.items()})


def _step(p, m, v, c, ids, pos, lab, layers, heads, eps, lr, lower):
    loss, g = jax.value_and_grad(loss_fn)(
        p, ids, pos, lab, layers=layers, heads=heads, eps=eps,
        lower=lower)
    p, m, v = adam(p, g, m, v, c, lr)
    return p, m, v, loss, leaf_norms(g)
