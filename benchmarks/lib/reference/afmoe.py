"""Plain float32 forward of an AFMoE decoder (Arcee's Trinity family,
``model_type`` ``afmoe``), teacher-forced, as ONE chip of an
expert-parallel deployment computes it.

``h0 = E[ids] * sqrt(hidden)`` (``mup_enabled``).  A block, with ``RMS``
an RMSNorm (eps ``rms_norm_eps``, a learned gain)::

    a   = Attn(RMS_in(h));     h = h + RMS_post_attn(a)
    m   = MLP(RMS_pre_mlp(h)); h = h + RMS_post_mlp(m)        (sandwich: four norms a block)
    Attn(x): q = W_q x (heads x d), k = W_k x, v = W_v x (kv heads x d), g = W_g x (heads x d)
             q = RMS_q(q), k = RMS_k(k)        per head, over its d channels, one gain vector each
             window layer:  RoPE(q, k), theta ``rope_theta``, half-rotation over the whole head;
                            key j visible to query i  iff  i - window < j <= i
             full layer:    NO rotary embedding;  j <= i
             o = softmax(q k^T / sqrt(d)) v ;  Attn = W_o (o * sigmoid(g))
    MLP, dense layer:   W_down(silu(W_gate x) * W_up x),  width ``intermediate_size``
    MLP, expert layer:  s = sigmoid(W_r x) in float32 (one score an expert of the WHOLE layer)
                        T = top-k of (s + b)        b: the selection bias, used to SELECT only
                        w_e = route_scale * s_e / (sum_{e' in T} s_{e'} + 1e-20)   for e in T
                        MLP = Shared(x) + sum_{e in T and held} w_e Expert_e(x)
                        Shared, Expert_e: SwiGLU of width ``moe_intermediate_size``

then ``logits = W_head RMS_final(h)`` (untied).

**The share.**  ``held`` = the ``num_experts`` consecutive experts from
``expert_offset`` on that this chip holds; the router, the top-k and
the denominator run over all ``router_experts``; what the absent
experts would have added is LEFT OUT and the partial result goes on to
the next layer (model-configs section 4).  The shared expert is computed
on every chip.  The vocabulary slice is a smaller vocabulary.

**Departures from the published modeling code, each an assumption**
(no network here; the configuration file lists them under ``assumed``):
the gate's shape and place (hidden -> heads x d, no bias, before
``W_o``); the head norms before the rotation; rotary embedding on the
window layers only; the ``sqrt(hidden)`` embedding scale; selection by
``s + b`` and weights from ``s``; "depth-scaled" sandwich norm is an
initial value of the post-norm gains and no equation.

No cache, no batching, no kernels: one sequence at a time, one layer at
a time with its weights cast up then, one expert at a time (every held
expert multiplies EVERY position and the routing weights, zero where
the expert was not chosen, pick what counts), attention a block of
queries at a time so that 6.6k positions fit.

Weights: ``emb`` (vocab, hidden), ``head`` (hidden, vocab),
``final_norm``; ``layers``: a list, one dict a layer, of ``ln_in``,
``ln_post_attn``, ``ln_pre_mlp``, ``ln_post_mlp`` (hidden), ``qkv``
(hidden, (heads + 2 kv) d; columns ``[group][q..q|k|v][d]`` as
``llama.py``), ``q_norm``, ``k_norm`` (d), ``gate_proj`` (hidden, heads
d), ``out`` (heads d, hidden), and ``gate``, ``up``, ``down`` — the
dense MLP's, or the shared expert's beside ``router`` (hidden,
router_experts), ``bias`` (router_experts), and the routed experts'
matrices as the program keeps them, one bank for all expert layers:
``w_in`` (expert layers x held, hidden, 2 width; columns ``[gate |
up]``), ``w_down`` (expert layers x held, width, hidden), ``first`` (the
index of this layer's first expert in the bank).  An expert's matrices
are taken out of the bank, and cast up, when its turn comes.

Three deliberately WRONG variants are the controls of the comparison
(the benchmark's, no switch in the program), ``wrong=``:
``window_ignored`` (full attention in the window layers: what kernels
that never learnt the window would serve), ``rope_everywhere`` (the
rotation on the full layers too: one positional scheme for the stack),
``experts_dropped`` (the shared expert alone: what an expert layer that
lost its routed part would serve).
"""

import functools

import jax
import jax.numpy as jnp

from .falcon_h1 import gaps_by_block                     # noqa: F401
from .llama import rms_norm, rope
from .quant import einsum, matmul

#: queries the attention takes at once
QUERY_BLOCK = 256
WRONG = ("window_ignored", "rope_everywhere", "experts_dropped")
WINDOW = "sliding_attention"


def dims_of(config):
    """The static sizes, hashable, from a configuration's keys."""
    c = config
    return (("heads", c["num_attention_heads"]),
            ("kv", c["num_key_value_heads"]), ("d", c["head_dim"]),
            ("eps", c["rms_norm_eps"]), ("base", float(c["rope_theta"])),
            ("window", c["sliding_window"]),
            ("top_k", c["num_experts_per_tok"]),
            ("route_scale", c["route_scale"]),
            ("route_norm", c["route_norm"]),
            ("offset", c.get("expert_offset", 0)),
            ("held", c["num_experts"]))


def layer_kinds(config):
    """``(window layer?, expert layer?)`` of every layer."""
    run = config.get("layers_run", range(config["num_hidden_layers"]))
    return [(config["layer_types"][at] == WINDOW,
             i >= config["num_dense_layers"]) for i, at in enumerate(run)]


def attention(x, w, *, heads, kv, d, eps, base, window, rotary, lower):
    """Gated attention over one sequence ``x`` (s, hidden) of normed
    input; ``window`` None on a full layer."""
    f32 = lambda a: a.astype(jnp.float32)
    s = x.shape[0]
    rep = heads // kv
    qkv = matmul(x, f32(w["qkv"]), lower).reshape(s, kv, rep + 2, d)
    q = rms_norm(qkv[:, :, :rep].reshape(s, heads, d), f32(w["q_norm"]), eps)
    k = rms_norm(qkv[:, :, rep], f32(w["k_norm"]), eps)
    v = qkv[:, :, rep + 1]
    if rotary:
        q, k = rope(q, base), rope(k, base)
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, kv, rep, d)
    k_pos = jnp.arange(s)

    def block(args):
        q_blk, start = args
        q_pos = start + jnp.arange(qb)
        scores = einsum("qgrd,kgd->grqk", q_blk, k, lower) * d ** -0.5
        seen = k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            seen &= k_pos[None, :] > q_pos[:, None] - window
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return einsum("grqk,kgd->qgrd", probs, v, lower)

    o = jax.lax.map(block, (qg, jnp.arange(qg.shape[0]) * qb))
    o = o.reshape(-1, heads * d)[:s]
    o = o * jax.nn.sigmoid(matmul(x, f32(w["gate_proj"]), lower))
    return matmul(o, f32(w["out"]), lower)


def swiglu(x, gate, up, down, lower):
    f32 = lambda a: a.astype(jnp.float32)
    return matmul(jax.nn.silu(matmul(x, f32(gate), lower))
                  * matmul(x, f32(up), lower), f32(down), lower)


def routing(x, w, *, top_k, route_scale, route_norm, offset, held):
    """(s, held) float32: the weight of each HELD expert at each
    position, 0 where the router did not choose it.  The router is
    float32 whatever precision the control lowers the rest to: an fp8
    path keeps it so."""
    f32 = lambda a: a.astype(jnp.float32)
    scores = jax.nn.sigmoid(matmul(x, f32(w["router"])))
    _, ids = jax.lax.top_k(scores + f32(w["bias"]), top_k)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    if route_norm:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    chosen = ids[:, :, None] - offset == jnp.arange(held)[None, None, :]
    return jnp.sum(jnp.where(chosen, (picked * route_scale)[:, :, None],
                             0.0), axis=1)


def experts(x, w, *, lower, wrong, **route):
    """The expert layer's MLP over one sequence; returns the sum and
    the root mean squares of its routed and shared parts."""
    shared = swiglu(x, w["gate"], w["up"], w["down"], lower)
    weight = routing(x, w, **route)                       # (s, held)
    width = w["w_down"].shape[1]

    def one(total, args):
        at, col = args
        w_in, w_down = w["w_in"][at], w["w_down"][at]
        y = swiglu(x, w_in[:, :width], w_in[:, width:], w_down, lower)
        return total + col[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (w["first"] + jnp.arange(route["held"]), weight.T))
    if wrong == "experts_dropped":
        routed = jnp.zeros_like(routed)
    rms = lambda v: jnp.sqrt(jnp.mean(v * v))
    return shared + routed, rms(routed), rms(shared)


@functools.partial(jax.jit, static_argnames=(
    "dims", "windowed", "expert_layer", "lower", "wrong"))
def layer(h, w, *, dims, windowed, expert_layer, lower, wrong):
    f32 = lambda a: a.astype(jnp.float32)
    dims = dict(dims)
    eps = dims["eps"]
    a = attention(
        rms_norm(h, f32(w["ln_in"]), eps), w, heads=dims["heads"],
        kv=dims["kv"], d=dims["d"], eps=eps, base=dims["base"],
        window=dims["window"] if windowed and wrong != "window_ignored"
        else None,
        rotary=windowed or wrong == "rope_everywhere", lower=lower)
    a = rms_norm(a, f32(w["ln_post_attn"]), eps)
    rms = lambda v: jnp.sqrt(jnp.mean(v * v))
    sizes = [rms(h), rms(a)]
    h = h + a
    x = rms_norm(h, f32(w["ln_pre_mlp"]), eps)
    if expert_layer:
        m, routed, shared = experts(
            x, w, lower=lower, wrong=wrong,
            **{k: dims[k] for k in ("top_k", "route_scale", "route_norm",
                                    "offset", "held")})
    else:
        m = swiglu(x, w["gate"], w["up"], w["down"], lower)
        routed, shared = jnp.float32(0.0), rms(m)
    m = rms_norm(m, f32(w["ln_post_mlp"]), eps)
    # what came in, what attention and MLP add, and inside the MLP
    # (before its post-norm) the routed and the shared part
    return h + m, jnp.stack(sizes + [rms(m), routed, shared])


def hidden(weights, ids, *, kinds, dims, lower=None, wrong=None):
    """(len(ids), hidden) float32 output of the last block, and a
    (layers, 5) array of root mean squares (see ``layer``)."""
    hidden_size = weights["emb"].shape[1]
    h = weights["emb"][ids].astype(jnp.float32) * hidden_size ** 0.5
    sizes = []
    for w, (windowed, expert_layer) in zip(weights["layers"], kinds):
        h, size = layer(h, w, dims=dims, windowed=windowed,
                        expert_layer=expert_layer, lower=lower, wrong=wrong)
        sizes.append(size)
    return h, jnp.stack(sizes)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def head(h, norm_w, head_w, *, eps, lower=None):
    x = rms_norm(h, norm_w.astype(jnp.float32), eps)
    return matmul(x, head_w.astype(jnp.float32), lower)


def logits(weights, ids, *, kinds, dims, lower=None, wrong=None):
    """(len(ids), vocab) float32 logits of one sequence."""
    h, _ = hidden(weights, ids, kinds=kinds, dims=dims, lower=lower,
                  wrong=wrong)
    return head(h, weights["final_norm"], weights["head"],
                eps=dict(dims)["eps"], lower=lower)
