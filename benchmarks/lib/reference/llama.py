"""Plain float32 forward of a Llama/Mistral decoder, teacher-forced.

Pre-norm blocks with RMSNorm, rotary positions in the half-rotation
(NeoX) layout over the whole head, grouped-query attention with a
causal mask, a SwiGLU MLP ``down(silu(gate(x)) * up(x))``, a final
RMSNorm and an untied output head.  No cache, no batching, no kernels:
one sequence at a time, one layer at a time, so that float32 copies of
one layer's weights are all that lives beside the sequence.

Weights: ``emb`` (vocab, hidden), ``head`` (hidden, vocab),
``final_norm`` (hidden), and per layer ``ln1``, ``ln2`` (hidden),
``qkv`` (hidden, (heads + 2 kv) * d) with columns running kv-group by
kv-group, each group its ``heads/kv`` query heads, then its k, then its
v: ``[group][q..q|k|v][d]``; ``out`` (heads*d, hidden); ``gate``,
``up`` (hidden, ffn); ``down`` (ffn, hidden).  The per-layer arrays
come stacked on a leading layer axis."""

import functools

import jax
import jax.numpy as jnp

from .quant import einsum, matmul


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, base):
    """x: (s, heads, d); rotate the two halves of d by position."""
    s, _, d = x.shape
    inv = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv", "d", "eps", "base", "lower"))
def layer(x, w, *, heads, kv, d, eps, base, lower):
    f32 = lambda a: a.astype(jnp.float32)
    s = x.shape[0]
    rep = heads // kv
    a = rms_norm(x, f32(w["ln1"]), eps)
    qkv = matmul(a, f32(w["qkv"]), lower).reshape(s, kv, rep + 2, d)
    q = rope(qkv[:, :, :rep].reshape(s, heads, d), base)
    k = rope(qkv[:, :, rep], base)
    v = qkv[:, :, rep + 1]
    qg = q.reshape(s, kv, rep, d)
    scores = einsum("qgrd,kgd->grqk", qg, k, lower) * d ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    o = einsum("grqk,kgd->qgrd", probs, v, lower).reshape(s, heads * d)
    x = x + matmul(o, f32(w["out"]), lower)
    m = rms_norm(x, f32(w["ln2"]), eps)
    m = jax.nn.silu(matmul(m, f32(w["gate"]), lower)) \
        * matmul(m, f32(w["up"]), lower)
    return x + matmul(m, f32(w["down"]), lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def head(x, norm_w, head_w, *, eps, lower):
    x = rms_norm(x, norm_w.astype(jnp.float32), eps)
    return matmul(x, head_w.astype(jnp.float32), lower)


def logits(weights, ids, *, layers, heads, kv, d, eps, base, lower=None):
    """(len(ids), vocab) float32 logits of one sequence."""
    x = weights["emb"][ids].astype(jnp.float32)
    for i in range(layers):
        w = {k: weights["layers"][k][i] for k in weights["layers"]}
        x = layer(x, w, heads=heads, kv=kv, d=d, eps=eps, base=base,
                  lower=lower)
    return head(x, weights["final_norm"], weights["head"], eps=eps,
                lower=lower)


@jax.jit
def gaps(ref_logits, other_logits, tokens):
    """Per position p (the row that predicts token p+1):
    how far the reference's logit of (a) the served token and (b) the
    other computation's best token lies below the reference's best."""
    best = jnp.max(ref_logits, -1)
    nxt = jnp.roll(tokens, -1)
    served = best - jnp.take_along_axis(ref_logits, nxt[:, None], -1)[:, 0]
    other = best - jnp.take_along_axis(
        ref_logits, jnp.argmax(other_logits, -1)[:, None], -1)[:, 0]
    return served, other
