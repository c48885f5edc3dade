"""Driver of the training cells: one donated jitted step, in a loop.

The step is the one ``bench.py`` and ``chip_smoke.py`` run: the
program's MLM loss (``bench.mlm_loss_of``: amp policy cast, the model's
forward with its kernels, cross entropy, loss scaling), its backward,
and ``MixedPrecisionTrainState.apply_gradients`` (unscale, FusedAdam).
Set-up builds ONE such step and state from ``--seed``, drives it through
its first three steps with the window's own call and feed, keeps what
the comparison reads of them (three losses, the first gradient's norm
per leaf out of Adam's first moment, the parameters' change per leaf;
the cell's ``limits`` say which of the numbers are held to a limit),
and hands the same object to the window.  After the window has closed
and the state is freed, the plain float32 reference follows the same
three steps from the same weights and batches.

``ctx.fault``: ``state_unchanged`` (the step returns the state it got),
``half_batch`` (the second half of every batch's rows repeats the
first, so the mean is over half the rows).  ``ctx.control`` puts the
reference at fp8 in the program's place; with the fault
``state_unchanged`` beside it, the float32 reference with that fault
planted (the program's own step, not donated, wants the state twice)."""

import re
import statistics
import time

B1 = 0.9

#: program leaf -> the reference's name for it
NAMES = [
    (r"embedding/embedding", "tok_emb"),
    (r"position_embedding", "pos_emb"),
    (r"token_type_embedding", "type_emb"),
    (r"emb_norm_scale", "emb_ln.w"), (r"emb_norm_bias", "emb_ln.b"),
    (r"mlm_dense/kernel", "head.dense.w"),
    (r"mlm_dense/bias", "head.dense.b"),
    (r"mlm_norm/scale", "head.ln.w"), (r"mlm_norm/bias", "head.ln.b"),
    (r"mlm_bias", "head.bias"),
    (r"pooler/kernel", "pooler.w"), (r"pooler/bias", "pooler.b"),
    (r"transformer/layer_(\d+)/input_norm/scale", r"layer\1.ln1.w"),
    (r"transformer/layer_(\d+)/input_norm/bias", r"layer\1.ln1.b"),
    (r"transformer/layer_(\d+)/attention/qkv_proj/kernel", r"layer\1.qkv.w"),
    (r"transformer/layer_(\d+)/attention/qkv_proj/bias", r"layer\1.qkv.b"),
    (r"transformer/layer_(\d+)/attention/out_proj/kernel", r"layer\1.out.w"),
    (r"transformer/layer_(\d+)/attention/out_proj/bias", r"layer\1.out.b"),
    (r"transformer/layer_(\d+)/post_attention_norm/scale", r"layer\1.ln2.w"),
    (r"transformer/layer_(\d+)/post_attention_norm/bias", r"layer\1.ln2.b"),
    (r"transformer/layer_(\d+)/mlp/dense_h_to_4h/kernel", r"layer\1.fc1.w"),
    (r"transformer/layer_(\d+)/mlp/dense_h_to_4h/bias", r"layer\1.fc1.b"),
    (r"transformer/layer_(\d+)/mlp/dense_4h_to_h/kernel", r"layer\1.fc2.w"),
    (r"transformer/layer_(\d+)/mlp/dense_4h_to_h/bias", r"layer\1.fc2.b"),
]


def ref_name(path):
    keys = re.findall(r"\['([^']+)'\]", path)
    key = "/".join(keys[1:] if keys[0] == "params" else keys)
    for pat, name in NAMES:
        m = re.fullmatch(pat, key)
        if m:
            return m.expand(name)
    raise KeyError(f"no reference name for program leaf {path}")


def pieces(tree, heads):
    """The program's parameter-shaped ``tree`` as the reference's flat
    dict.  The program keeps q, k and v in one matrix and one bias whose
    columns run ``[head][q|k|v][d]``; the reference keeps three, so each
    fused leaf is cut into its three pieces here.  (The key's bias has no
    gradient under softmax: as a piece of its own it falls under the
    rule for leaves that move by round-off alone.)"""
    from lib import weights

    out = {}
    for path, x in weights.flat_names(tree).items():
        name = ref_name(path)
        if ".qkv." not in name:
            out[name] = x
            continue
        cut = x.reshape(x.shape[:-1] + (heads, 3, x.shape[-1] // (3 * heads)))
        for i, part in enumerate("qkv"):
            out[name.replace(".qkv.", f".{part}.")] = \
                cut[..., i, :].reshape(x.shape[:-1] + (-1,))
    return out


def leaf_gaps(got, want, keep=None):
    """|got - want| of every leaf's norm, each against the reference's
    norm of that leaf or of the median leaf, whichever is larger;
    sorted, largest last: [(gap, leaf)]."""
    floor = statistics.median(want.values())
    return sorted((abs(got[k] - want[k]) / max(want[k], floor), k)
                  for k in want if keep is None or k in keep)


def spread_of(gaps):
    vals = [g for g, _ in gaps]
    return {"worst": vals[-1], "worst_leaf": gaps[-1][1],
            "p90": vals[int(0.9 * (len(vals) - 1))],
            "median": statistics.median(vals), "leaves": len(vals)}


def compare(prog, ref, limits):
    losses, grads, delta = prog
    r_losses, r_grads, r_delta = ref
    checks = {}
    for i, (a, b) in enumerate(zip(losses, r_losses)):
        checks[f"loss_step{i + 1}_rel"] = abs(a - b) / abs(b)
    g_gaps = leaf_gaps(grads, r_grads)
    # leaves whose gradient is nought to rounding in the reference move
    # under Adam by round-off alone: left out of the change, by the
    # reference's gradient and not by name
    moving = {k for k, v in r_grads.items()
              if v >= 1e-3 * statistics.median(r_grads.values())}
    d_gaps = leaf_gaps(delta, r_delta, keep=moving)
    checks["grad_norm_worst_leaf"] = g_gaps[-1][0]
    checks["update_norm_worst_leaf"] = d_gaps[-1][0]
    # steady from seed to seed where the worst leaf is one small leaf's
    # noise: the median leaf's change
    checks["update_norm_median_leaf"] = statistics.median(
        g for g, _ in d_gaps)
    notes = {"grad": spread_of(g_gaps), "update": spread_of(d_gaps),
             "losses": list(losses), "reference_losses": list(r_losses),
             "update_top": [(round(g, 4), k, r_grads[k], r_delta[k])
                            for g, k in d_gaps[-4:]],
             "median_ref_grad_norm": statistics.median(r_grads.values()),
             "median_ref_update_norm": statistics.median(r_delta.values())}
    # a number is compared where the cell's file gives it a limit; the
    # others are read and kept in the notes only (PERF.md says which)
    notes["not_compared"] = {k: v for k, v in checks.items()
                             if k not in limits}
    return ({k: {"value": v, "limit": limits[k]}
             for k, v in checks.items() if k in limits}, notes)


def run(ctx):
    import jax
    import jax.numpy as jnp

    import bench
    from apex_tpu import amp
    from apex_tpu.models import BertConfig, BertModel
    from apex_tpu.optim import fused_adam
    from lib import counts, weights
    from lib.reference import bert_mlm

    c, train, tp = ctx.config, ctx.config["train"], ctx.cell["traffic_params"]
    seed = weights.seed32(ctx.seed)
    cfg = BertConfig(
        vocab_size=c["padded_vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        ffn_hidden_size=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"],
        layernorm_eps=c["layer_norm_eps"], remat=train["remat"],
        scan_layers=train["scan_layers"], dtype=jnp.bfloat16)
    model = BertModel(cfg)
    batches = ctx.load("traffic", ctx.cell["generator"]).generate(
        tp, seed, c["vocab_size"])
    first = batches[:3]                  # what the reference follows
    if ctx.fault == "half_batch":
        half = tp["batch"] // 2
        batches = [tuple(jnp.concatenate([x[:half], x[:half]]) for x in b)
                   for b in batches]
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            batches[0][0][:2])
    make = jax.jit(lambda s: weights.make_weights(shapes, s))
    ref_args = dict(layers=cfg.num_layers, heads=cfg.num_heads,
                    eps=cfg.layernorm_eps, lr=train["learning_rate"])

    heads = cfg.num_heads

    def reference(**kw):
        return bert_mlm.train_readings(
            jax.jit(lambda t: pieces(t, heads))(make(seed)["params"]),
            first, **kw, **ref_args)

    if ctx.control:
        ctx.open_window()
        stand_in = dict(frozen=True) if ctx.fault == "state_unchanged" \
            else dict(lower="float8_e4m3fn")
        checks, notes = compare(reference(**stand_in), reference(),
                                ctx.cell["limits"])
        return {"attempted": 0, "failed": 0, "checks": checks,
                "end_to_end": {}, "notes": notes}

    state = amp.initialize(
        model.apply, make(seed), fused_adam(train["learning_rate"]),
        opt_level=train["opt_level"], half_dtype=jnp.bfloat16)
    jax.block_until_ready(state)
    ctx.mark("state")

    def step_fn(state, ids, positions, labels):
        grads, loss = jax.grad(
            lambda p: bench.mlm_loss_of(state, p, ids, positions, labels),
            has_aux=True)(state.params)
        new_state, finite = state.apply_gradients(grads=grads)
        return new_state, loss, finite

    if ctx.fault == "state_unchanged":
        real = jax.jit(step_fn)

        def step(state, *batch):
            return (state,) + real(state, *batch)[1:]
    else:
        step = jax.jit(step_fn, donate_argnums=(0,))

    norms = jax.jit(lambda t: {
        k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for k, x in pieces(t, heads).items()})
    change = jax.jit(lambda p, s: norms(jax.tree.map(
        jnp.subtract, p, weights.make_weights(shapes, s))))

    def floats(d):
        return {k: float(v) for k, v in d.items()}

    # the first three steps, through the window's own call and feed
    losses, fed = [], 0
    for batch in batches[:3]:
        state, loss, _ = step(state, *batch)
        fed += 1
        losses.append(loss)
        if fed == 1:
            jax.block_until_ready(loss)
            ctx.mark("first_step")       # trace, lower, compile or load
            grad_norms = norms(state.opt_state.exp_avg["params"])
    delta = floats(change(state.params, seed))
    prog = ([float(l) for l in losses],
            {k: v / (1.0 - B1) for k, v in floats(grad_norms).items()},
            delta)
    for _ in range(2):                       # warm: the call is steady
        state, loss, _ = step(state, *batches[fed % len(batches)])
        fed += 1
    jax.block_until_ready(state)
    ctx.mark("checked_steps")

    t0 = ctx.open_window()
    steps, inflight = 0, []
    with ctx.annotate("window"):
        while time.perf_counter() - t0 < ctx.seconds:
            with ctx.annotate("dispatch"):
                state, loss, finite = step(state,
                                           *batches[fed % len(batches)])
            fed += 1
            steps += 1
            inflight.append(loss)
            if len(inflight) > 2:            # keep two steps in flight
                with ctx.annotate("wait_step"):
                    inflight.pop(0).block_until_ready()
        with ctx.annotate("wait_last"):
            jax.block_until_ready(state)
    window = time.perf_counter() - t0
    last_loss, finite = float(loss), bool(finite)
    tokens = steps * tp["batch"] * tp["seq"]
    ctx.read_memory_peak()
    if ctx.tracer is not None:
        ctx.tracer.finish()
    del state, loss, inflight, grad_norms
    # a step whose gradients were not finite did no training
    failed = 0 if finite and last_loss == last_loss else steps
    checks, notes = compare(prog, reference(), ctx.cell["limits"])
    notes["last_loss"] = last_loss
    facts = dict(steps=steps, measured_s=window, tokens=tokens,
                 model_flops=steps * counts.bert_train_step_flops(
                     c, tp["batch"], tp["seq"], tp["mlm_positions"],
                     c["vocab_size"]),
                 attention=dict(batch=tp["batch"], heads=cfg.num_heads,
                                seq=tp["seq"], d=cfg.head_dim,
                                layers=cfg.num_layers))
    return {"attempted": steps, "failed": failed, "checks": checks,
            "end_to_end": {"train_tokens_per_s": tokens / window},
            "facts": facts, "notes": notes}
