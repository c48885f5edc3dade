"""Plain float32 forward of a Falcon-H1 decoder, teacher-forced.

Every block reads ``x = RMSNorm(h)`` twice, in parallel: a Mamba-2
mixer and a grouped-query attention, both added to the residual, then
a SwiGLU MLP; a multiplier stands on every branch (``mult``, the
published numbers, applied where they stand)::

    [z | u | dt] = (W_in (x * ssm_in)) * mu     mu: ssm_multipliers on the
                                                 columns of z, x, B, C, dt
    u = silu(conv1d(u))                          depthwise, causal, width K
    x_s, B, C = split(u);  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t;  y_t = S_t C_t + D x_t
    m = W_out GroupRMSNorm(y * silu(z)) * ssm_out
    q, k, v = W_qkv (x * attention_in);  k = k * key_multiplier
    a = W_o softmax(rope(q) rope(k)^T / sqrt(d)) v * attention_out
    h = h + m + a
    h = h + W_down(silu(W_gate y * mlp_gate) * W_up y) * mlp_down
    logits = W_head RMSNorm(h_L) * lm_head        h_0 = E[ids] * embedding

No cache, no batching, no kernels: one sequence at a time, one layer at
a time, the recurrence as a plain ``lax.scan`` over the positions that
carries the state ``S`` (heads, d_head, d_state) and the last ``K - 1``
inputs of the convolution.  The head runs over blocks of the
vocabulary, so that beside the sequence only one block's float32 copy
of the head lives.

Weights: ``emb`` (vocab, hidden), ``head`` (hidden, vocab),
``final_norm``; per layer, stacked on a leading layer axis: ``ln1``,
``ln2``; ``in_proj`` (hidden, d_ssm + C + H) with columns ``[z | x | B
| C | dt]`` (``C`` = d_ssm + 2 groups d_state); ``conv_w`` (K, C), the
last row on the current input, ``conv_b``; ``dt_bias``, ``A_log``, ``D``
(H); ``mnorm`` (d_ssm); ``out_proj`` (d_ssm, hidden); ``qkv``, ``out``,
``gate``, ``up``, ``down`` as in ``llama.py``.

Two deliberately WRONG variants serve as controls of the comparison
(the benchmark's, no switch in the program): ``restart_every=r`` starts
the recurrence and the convolution's history from zero at every r-th
position, which is what a serving step that failed to carry state
across chunks would compute; ``init`` starts them from given values,
another request's final ones, which is what a slot that was not reset
would compute.  ``hidden`` returns each layer's final state for that.
"""

import functools

import jax
import jax.numpy as jnp

from .llama import gaps, rms_norm, rope       # noqa: F401  (gaps: re-export)
from .quant import einsum, matmul

#: widest block of the vocabulary the head takes at once
HEAD_BLOCK = 32768


def dims_of(config):
    """The static sizes, from a configuration's published keys."""
    c = config
    return dict(
        heads=c["num_attention_heads"], kv=c["num_key_value_heads"],
        d=c["head_dim"], m_heads=c["mamba_n_heads"],
        m_p=c["mamba_d_head"], m_n=c["mamba_d_state"],
        m_g=c["mamba_n_groups"], eps=c["rms_norm_eps"],
        base=float(c["rope_theta"]))


def mult_of(config):
    """The multipliers, hashable, from the published keys."""
    c = config
    return (("embedding", c["embedding_multiplier"]),
            ("lm_head", c["lm_head_multiplier"]),
            ("attention_in", c["attention_in_multiplier"]),
            ("attention_out", c["attention_out_multiplier"]),
            ("key", c["key_multiplier"]),
            ("ssm_in", c["ssm_in_multiplier"]),
            ("ssm_out", c["ssm_out_multiplier"]),
            ("ssm", tuple(c["ssm_multipliers"])),
            ("mlp_gate", c["mlp_multipliers"][0]),
            ("mlp_down", c["mlp_multipliers"][1]))


def mixer(x, w, init, *, m_heads, m_p, m_n, m_g, eps, mult, lower,
          restart_every):
    """The Mamba-2 branch over one sequence ``x`` (s, hidden) of normed
    input; returns its output and the final (state, conv history)."""
    f32 = lambda a: a.astype(jnp.float32)
    s = x.shape[0]
    d_ssm, gn = m_heads * m_p, m_g * m_n
    mz, mx, mb, mc, mdt = mult["ssm"]
    mu = jnp.concatenate([
        jnp.full((d_ssm,), mz), jnp.full((d_ssm,), mx),
        jnp.full((gn,), mb), jnp.full((gn,), mc),
        jnp.full((m_heads,), mdt)]).astype(jnp.float32)
    proj = matmul(x * mult["ssm_in"], f32(w["in_proj"]), lower) * mu
    z, u, dt = (proj[:, :d_ssm], proj[:, d_ssm:2 * d_ssm + 2 * gn],
                proj[:, 2 * d_ssm + 2 * gn:])
    dt = jax.nn.softplus(dt + f32(w["dt_bias"]))
    a = -jnp.exp(f32(w["A_log"]))
    conv_w, conv_b, skip = f32(w["conv_w"]), f32(w["conv_b"]), f32(w["D"])
    restart = (jnp.arange(s) % restart_every == 0) if restart_every \
        else jnp.zeros((s,), bool)
    rep = m_heads // m_g

    def step(carry, lane):
        state, hist = carry
        u_t, dt_t, fresh = lane
        state = jnp.where(fresh, 0.0, state)
        hist = jnp.where(fresh, 0.0, hist)
        full = jnp.concatenate([hist, u_t[None]], axis=0)       # (K, C)
        act = jax.nn.silu(jnp.sum(full * conv_w, axis=0) + conv_b)
        xs = act[:d_ssm].reshape(m_heads, m_p)
        bm = jnp.repeat(act[d_ssm:d_ssm + gn].reshape(m_g, m_n), rep, 0)
        cm = jnp.repeat(act[d_ssm + gn:].reshape(m_g, m_n), rep, 0)
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * xs)[:, :, None] * bm[:, None, :]
        y = jnp.sum(state * cm[:, None, :], axis=-1) + skip[:, None] * xs
        return (state, full[1:]), y.reshape(d_ssm)

    final, y = jax.lax.scan(step, init, (u, dt, restart))
    y = y * jax.nn.silu(z)
    yg = y.reshape(s, m_g, d_ssm // m_g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
    y = yg.reshape(s, d_ssm) * f32(w["mnorm"])
    return matmul(y, f32(w["out_proj"]), lower) * mult["ssm_out"], final


def attention(x, w, *, heads, kv, d, base, mult, lower):
    """The attention branch over one sequence ``x`` (s, hidden)."""
    f32 = lambda a: a.astype(jnp.float32)
    s = x.shape[0]
    rep = heads // kv
    qkv = matmul(x * mult["attention_in"], f32(w["qkv"]), lower) \
        .reshape(s, kv, rep + 2, d)
    q = rope(qkv[:, :, :rep].reshape(s, heads, d), base)
    k = rope(qkv[:, :, rep] * mult["key"], base)
    v = qkv[:, :, rep + 1]
    scores = einsum("qgrd,kgd->grqk", q.reshape(s, kv, rep, d), k,
                    lower) * d ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    o = einsum("grqk,kgd->qgrd", probs, v, lower).reshape(s, heads * d)
    return matmul(o, f32(w["out"]), lower) * mult["attention_out"]


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv", "d", "m_heads", "m_p", "m_n", "m_g", "eps", "base",
    "mult", "lower", "restart_every"))
def layer(h, w, init, *, heads, kv, d, m_heads, m_p, m_n, m_g, eps, base,
          mult, lower, restart_every):
    f32 = lambda a: a.astype(jnp.float32)
    mult = dict(mult)
    x = rms_norm(h, f32(w["ln1"]), eps)
    m, final = mixer(x, w, init, m_heads=m_heads, m_p=m_p, m_n=m_n,
                     m_g=m_g, eps=eps, mult=mult, lower=lower,
                     restart_every=restart_every)
    a = attention(x, w, heads=heads, kv=kv, d=d, base=base, mult=mult,
                  lower=lower)
    rms = lambda v: jnp.sqrt(jnp.mean(v * v))
    sizes = [rms(h), rms(m), rms(a)]
    h = h + m + a
    y = rms_norm(h, f32(w["ln2"]), eps)
    y = jax.nn.silu(matmul(y, f32(w["gate"]), lower) * mult["mlp_gate"]) \
        * matmul(y, f32(w["up"]), lower)
    y = matmul(y, f32(w["down"]), lower) * mult["mlp_down"]
    # what came in and what mixer, attention and MLP each add
    return h + y, final, jnp.stack(sizes + [rms(y)])


def zero_state(weights, dims):
    """What every layer's recurrence starts from: nothing."""
    k, c = weights["layers"]["conv_w"].shape[1:]
    return (jnp.zeros((dims["m_heads"], dims["m_p"], dims["m_n"]),
                      jnp.float32), jnp.zeros((k - 1, c), jnp.float32))


def hidden(weights, ids, *, layers, dims, mult, lower=None, init=None,
           restart_every=None):
    """(len(ids), hidden) float32 output of the last block; each
    layer's final (state, conv history); and a (layers, 4) array of
    root mean squares: the residual stream entering each block and
    what its mixer, attention and MLP add."""
    h = weights["emb"][ids].astype(jnp.float32) * dict(mult)["embedding"]
    finals, sizes = [], []
    for i in range(layers):
        w = {k: weights["layers"][k][i] for k in weights["layers"]}
        h, final, size = layer(
            h, w, zero_state(weights, dims) if init is None else init[i],
            mult=mult, lower=lower, restart_every=restart_every, **dims)
        finals.append(final)
        sizes.append(size)
    return h, finals, jnp.stack(sizes)


def _vocab_blocks(vocab):
    """(blocks, width): the fewest equal blocks of at most
    ``HEAD_BLOCK`` columns."""
    n = -(-vocab // HEAD_BLOCK)
    while vocab % n:
        n += 1
    return n, vocab // n


@functools.partial(jax.jit, static_argnames=("width", "lower"))
def _head_block(x, head_w, start, *, width, lower):
    w = jax.lax.dynamic_slice_in_dim(head_w, start, width, axis=1)
    return matmul(x, w.astype(jnp.float32), lower)


def head(h, weights, *, eps, mult, lower=None):
    """(len(h), vocab) float32 logits, a block of the vocabulary at a
    time (the fp8 control scales each block on its own)."""
    x = rms_norm(h, weights["final_norm"].astype(jnp.float32), eps)
    n, width = _vocab_blocks(weights["head"].shape[1])
    out = [_head_block(x, weights["head"], i * width, width=width,
                       lower=lower) for i in range(n)]
    return jnp.concatenate(out, axis=1) * dict(mult)["lm_head"]


def logits(weights, ids, *, layers, dims, mult, lower=None, init=None,
           restart_every=None):
    """(len(ids), vocab) float32 logits of one sequence."""
    h, _, _ = hidden(weights, ids, layers=layers, dims=dims, mult=mult,
                     lower=lower, init=init, restart_every=restart_every)
    return head(h, weights, eps=dims["eps"], mult=mult, lower=lower)


@functools.partial(jax.jit, static_argnames=("width", "lower"))
def _gap_block(carry, x_ref, x_low, head_w, start, nxt, scale, *, width,
               lower):
    best, served, low_best, ref_at_low = carry
    w = jax.lax.dynamic_slice_in_dim(head_w, start, width, axis=1) \
        .astype(jnp.float32)
    ref = matmul(x_ref, w, None) * scale
    low = matmul(x_low, w, lower) * scale
    best = jnp.maximum(best, jnp.max(ref, -1))
    at = jnp.clip(nxt - start, 0, width - 1)
    served = jnp.where((nxt >= start) & (nxt < start + width),
                       jnp.take_along_axis(ref, at[:, None], -1)[:, 0],
                       served)
    top = jnp.max(low, -1)
    ref_there = jnp.take_along_axis(
        ref, jnp.argmax(low, -1)[:, None], -1)[:, 0]
    ref_at_low = jnp.where(top > low_best, ref_there, ref_at_low)
    return best, served, jnp.maximum(low_best, top), ref_at_low


def gaps_by_block(weights, h_ref, h_low, ids, *, eps, mult, lower=None):
    """``gaps`` of ``head(h_ref)`` and ``head(h_low, lower)`` without
    either (len, vocab) array: per position p (the row that predicts
    token p+1), how far the reference's logit of the served token, and
    of the other computation's first choice, lies below the
    reference's best.  One block of the vocabulary at a time."""
    norm_w = weights["final_norm"].astype(jnp.float32)
    x_ref, x_low = rms_norm(h_ref, norm_w, eps), rms_norm(h_low, norm_w, eps)
    n, width = _vocab_blocks(weights["head"].shape[1])
    low0 = jnp.full((len(ids),), -jnp.inf, jnp.float32)
    carry = (low0, jnp.zeros_like(low0), low0, jnp.zeros_like(low0))
    nxt = jnp.roll(ids, -1)
    for i in range(n):
        carry = _gap_block(carry, x_ref, x_low, weights["head"], i * width,
                           nxt, dict(mult)["lm_head"], width=width,
                           lower=lower)
    best, served, _, ref_at_low = carry
    return best - served, best - ref_at_low
