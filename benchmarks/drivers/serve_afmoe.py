"""Driver of the AFMoE serving cells, and the closed-loop window as a
function of a model's hooks.

:func:`serve_closed_loop` is ``serve_server.py``'s closed loop — the
warm requests, the window, the stamps, the drain, the ``facts`` keys
and the three end-to-end definitions, as that file's docstring
describes them — with everything that knows the model behind
``hooks``:

``build_server(ctx, seed)``      -> ``(cfg, server, make)``: the model's
                                   config, the started server and the
                                   jitted function that makes the
                                   weights from a seed
``reference_weights(ctx, params)``  the weights under the reference's names
``logit_gaps(ctx, weights, sample, pad_to, others)``
                                 -> ``(served, {other: gaps}, tokens,
                                   notes)``: ``(widest, mean)`` gap of
                                   the served tokens in the float32
                                   reference, and for each name in
                                   ``others`` those of the tokens THAT
                                   computation puts first
``counts(ctx, cfg, server, window)``  -> facts of the model's own:
                                   ``model_flops`` and what its
                                   per-layer readers want; ``window``
                                   holds what the loop recorded
``wrong_references``             names ``ctx.fault`` may take beside
                                   ``token_altered``

PERF.md section 7 (4) asks for this refactor of the two older drivers; they
stay as they are (a PR may not edit them) and this one borrows what is
a function there: ``Sent``, ``percentile``, ``check_sample``.

``ctx.control`` reports, at the served positions, the gap of the token
an fp8 reference puts first; ``ctx.fault`` names a WRONG reference that
stands in the same place (``lib/reference/afmoe.py``:
``window_ignored``, ``rope_everywhere``, ``experts_dropped``) or
``token_altered``, which changes one token of every greedy stream where
the benchmark receives it.  The cell's ``check`` entry may name
``also``: further of these readings to take in the same run, into the
notes, for the builder's readings of the limit.

**Two numbers are compared**, each with its limit, because a router is
not continuous: where a token's fourth and fifth expert score within
rounding of each other, a bfloat16 program and the float32 reference
pick different experts, and that one token's logits move by several
tenths — in ANY bfloat16 computation (the ``bf16`` reading shows the
same gap at the same token).  ``served_logit_gap_max`` therefore has
room for such a token and catches what is wrong at SOME token by a
wide margin (a wrong mask, a lost expert, an altered token);
``served_logit_gap_mean``, the mean gap over the served tokens, hardly
moves with a flipped token and catches what is a little wrong at EVERY
token: a lower precision."""

import queue
import time

import numpy as np

DRAIN_S = 60.0
#: readings that put a LOWER precision in the reference's place: the
#: control (``fp8``, the step below what the configuration states) and,
#: for the builder's reading of the floor, ``bf16``: what another
#: computation in the stated precision reads
LOWER = {"fp8": "float8_e4m3fn", "bf16": "bfloat16"}


# ------------------------------------------------------------ the model
def build_server(ctx, seed):
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import AfmoeConfig, AfmoeModel
    from apex_tpu.serving import InferenceServer
    from lib import weights_afmoe as weights

    c = ctx.config
    cfg = AfmoeConfig.from_hf(c, dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16)
    model = AfmoeModel(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    shapes = {"params": shapes["params"]}
    make = jax.jit(lambda s: weights.make_weights(shapes, s, c))
    params = jax.block_until_ready(make(seed))
    ctx.mark("weights")
    server = InferenceServer(model, params, **c["serve"]["server"])
    server.start()
    ctx.mark("server_started")
    return cfg, server, make


def reference_weights(ctx, params):
    from lib import weights_afmoe as weights

    return weights.reference_weights(params, ctx.config)


def logit_gaps(ctx, weights_ref, sample, pad_to, others):
    import jax.numpy as jnp

    from lib.reference import afmoe as ref

    c = ctx.config
    kw = dict(kinds=ref.layer_kinds(c), dims=ref.dims_of(c))
    head = dict(eps=c["rms_norm_eps"], mult=(("lm_head", 1.0),))
    served_max, served_sum, n_tokens, sizes = 0.0, 0.0, 0, None
    other_max = {name: 0.0 for name in others}
    other_sum = {name: 0.0 for name in others}
    by_request = []
    for s in sample:
        p = len(s.req["prompt"])
        seq = np.concatenate([s.req["prompt"],
                              np.asarray(s.tokens, np.int32)])
        ids = np.zeros(pad_to, np.int32)
        ids[: len(seq)] = seq
        ids = jnp.asarray(ids)
        rows = slice(p - 1, len(seq) - 1)
        h, size = ref.hidden(weights_ref, ids, **kw)
        sizes = size if sizes is None else sizes
        served, _ = ref.gaps_by_block(weights_ref, h, h, ids, **head)
        served_max = max(served_max, float(jnp.max(served[rows])))
        served_sum += float(jnp.sum(served[rows]))
        n_tokens += len(s.tokens)
        # [prompt, served tokens, widest gap, the token it stands at]
        by_request.append([p, len(s.tokens),
                           round(float(jnp.max(served[rows])), 4),
                           int(jnp.argmax(served[rows]))])
        for name in others:
            lower = LOWER.get(name)
            low, _ = ref.hidden(weights_ref, ids, lower=lower,
                                wrong=None if lower else name, **kw)
            _, other = ref.gaps_by_block(weights_ref, h, low, ids,
                                         lower=lower, **head)
            other_max[name] = max(other_max[name],
                                  float(jnp.max(other[rows])))
            other_sum[name] += float(jnp.sum(other[rows]))
    notes = {"gap_by_request": by_request,
             "branch_rms": [[round(float(v), 4) for v in row]
                            for row in np.asarray(sizes)]}
    return ((served_max, served_sum / n_tokens),
            {name: (other_max[name], other_sum[name] / n_tokens)
             for name in others}, n_tokens, notes)


def counts(ctx, cfg, server, window):
    """``model_flops`` of what the window processed: from the shapes,
    the contexts the clients saw and the assignments the program
    counted."""
    from lib import counts_afmoe

    c = ctx.config
    w = c["sliding_window"]
    full = sum(counts_afmoe.prompt_pairs(p) for p in window["prefilled"])
    windowed = sum(counts_afmoe.prompt_pairs(p, w)
                   for p in window["prefilled"])
    dec = window["decode_ctx"]
    full += int(dec.sum())
    windowed += int(np.minimum(dec, w).sum())
    assigned = window["health_after"].get("expert_assignments", 0) \
        - window["health_before"].get("expert_assignments", 0)
    return {"model_flops": counts_afmoe.decoder_forward_flops(
        c, sum(window["prefilled"]) + len(dec), full, windowed,
        window["tokens_in_window"], assigned)}


class Hooks:
    build_server = staticmethod(build_server)
    reference_weights = staticmethod(reference_weights)
    logit_gaps = staticmethod(logit_gaps)
    counts = staticmethod(counts)
    wrong_references = ("window_ignored", "rope_everywhere",
                        "experts_dropped")


# ------------------------------------------------------------- the loop
def serve_closed_loop(ctx, hooks):
    from lib import weights as seeds

    base = ctx.load("drivers", "serve_server")
    Sent, percentile = base.Sent, base.percentile
    seed = seeds.seed32(ctx.seed)
    cfg, server, make = hooks.build_server(ctx, seed)
    traffic = ctx.load("traffic", ctx.cell["generator"]).generate(
        ctx.cell["traffic_params"], seed, ctx.seconds, cfg.vocab_size)
    if traffic["mode"] != "closed":
        raise ValueError("serve_closed_loop drives closed-loop traffic "
                         f"only; generator {ctx.cell['generator']!r} is "
                         f"{traffic['mode']!r}")
    events = queue.Queue()
    alter = ctx.fault == "token_altered"

    def submit(sent, now):
        def tap(token, finished, error, s=sent):
            t = time.perf_counter()
            if token is not None:
                if alter and s.req["greedy"] and len(s.tokens) == 2:
                    token = (token + 1) % cfg.vocab_size
                s.tokens.append(token)
                s.times.append(t)
            if error is not None:
                s.error = error
            if finished:
                s.done = True
                events.put((s, t))

        sent.sent = now
        try:
            with ctx.annotate("submit"):
                server.submit(sent.req["prompt"],
                              max_new_tokens=sent.req["max_new_tokens"],
                              seed=sent.req["seed"], block=False, tap=tap,
                              **sent.req["sampling"])
        except Exception as e:                    # refused: a failure
            sent.error, sent.done = e, True

    # warm requests: the longest prompt shape and a sampled row
    rng = np.random.default_rng(seed)
    warm = []
    for n, kw in ((traffic["max_tokens"] // 2, {}),
                  (40, dict(ctx.cell["traffic_params"]["sampling"]))):
        s = Sent({"prompt": rng.integers(0, cfg.vocab_size, size=n,
                                         dtype=np.int32),
                  "max_new_tokens": 8, "seed": 1, "sampling": kw,
                  "greedy": not kw}, 0.0)
        submit(s, time.perf_counter())
        warm.append(s)
    for s in warm:
        events.get(timeout=600)
    if any(s.error is not None for s in warm):
        raise RuntimeError(f"warm request failed: {[s.error for s in warm]}")

    ctx.mark("warm_requests")
    h0 = server.health()
    t0 = ctx.open_window()
    t_close = t0 + ctx.seconds
    sents = []
    with ctx.annotate("window"):
        nxt = [0] * len(traffic["clients"])
        owner = {}

        def send(ci, due):
            s = Sent(traffic["clients"][ci][nxt[ci]], due)
            nxt[ci] += 1
            owner[id(s)] = ci
            sents.append(s)
            submit(s, time.perf_counter())
            if s.done and s.error is not None:   # refused at once
                events.put((s, time.perf_counter()))

        for ci in range(len(nxt)):
            send(ci, t0)
        while True:
            left = t_close - time.perf_counter()
            if left <= 0:
                break
            try:
                with ctx.annotate("wait_completion"):
                    s, t = events.get(timeout=left)
            except queue.Empty:
                break
            ci = owner[id(s)]
            if nxt[ci] < len(traffic["clients"][ci]) and t < t_close:
                send(ci, t)
    h1 = server.health()
    # late is late, not wrong: wait for what is still in flight
    deadline = time.perf_counter() + DRAIN_S
    while not all(s.done for s in sents) and time.perf_counter() < deadline:
        time.sleep(0.05)
    ctx.read_memory_peak()
    if ctx.tracer is not None:
        ctx.tracer.finish()
    health_end = server.health()
    server.shutdown()

    finished = [s for s in sents if s.done and s.error is None
                and len(s.tokens) == s.req["max_new_tokens"]]
    failed = len(sents) - len(finished)
    ttft = [(s.times[0] - s.due) * 1e3 for s in sents if s.times]
    itl = [(s.times[-1] - s.times[0]) / (len(s.times) - 1) * 1e3
           for s in finished if len(s.times) > 1]
    in_window = sum(1 for s in sents for t in s.times if t <= t_close)
    late = [(s.sent - s.due) * 1e3 for s in sents]

    # what the window processed, for the readers (client-side records)
    dec_t, dec_ctx, prefilled = [], [], []
    for s in sents:
        p = len(s.req["prompt"])
        if s.times and s.times[0] <= t_close:
            prefilled.append(p)
        for j, t in enumerate(s.times[1:], start=1):
            dec_t.append(t - t0)
            dec_ctx.append(p + j)
    dec_t, dec_ctx = np.asarray(dec_t), np.asarray(dec_ctx, np.int64)
    inside = dec_t <= ctx.seconds
    facts = {
        "measured_s": ctx.seconds, "requests": len(sents),
        "finished": len(finished), "tokens_in_window": in_window,
        "health_before": h0, "health_after": h1,
        "blocks_in_use_end": health_end.get("blocks_in_use"),
        "max_slots": ctx.config["serve"]["server"]["max_slots"],
        "late_ms": late, "decode_t": dec_t, "decode_ctx": dec_ctx,
        "ttft_n": len(ttft), "itl_n": len(itl),
    }
    facts.update(hooks.counts(ctx, cfg, server, dict(
        prefilled=prefilled, decode_ctx=dec_ctx[inside],
        tokens_in_window=in_window, health_before=h0, health_after=h1)))
    end_to_end = {"serve_tokens_per_s": in_window / ctx.seconds,
                  "ttft_p95_ms": percentile(ttft, 95),
                  "itl_p95_ms": percentile(itl, 95)}

    # the server is gone: free it, then run the reference
    check = ctx.cell["check"]
    sample = base.check_sample(finished, seed, check["tokens"],
                               check["requests"])
    del server
    checks, ref_notes = {}, {}
    if sample:
        judged = "fp8" if ctx.control else ctx.fault \
            if ctx.fault in hooks.wrong_references else None
        others = ([judged] if judged else []) + [
            name for name in check.get("also", []) if name != judged]
        wref = hooks.reference_weights(ctx, make(seed))
        served, other, n, ref_notes = hooks.logit_gaps(
            ctx, wref, sample, traffic["max_tokens"], others)
        facts.update(check_requests=len(sample), check_tokens=n,
                     check_longest=max(len(s.req["prompt"])
                                       + len(s.tokens) for s in sample),
                     served_logit_gap_max=served[0],
                     served_logit_gap_mean=served[1],
                     control_gap_max=other.get("fp8", (0.0, 0.0))[0],
                     other_gap_max={k: v[0] for k, v in other.items()},
                     other_gap_mean={k: v[1] for k, v in other.items()})
        for i, name in enumerate(("served_logit_gap_max",
                                  "served_logit_gap_mean")):
            checks[name] = {
                "value": other[judged][i] if judged else served[i],
                "limit": ctx.cell["limits"][name]}
    notes = {k: facts.get(k) for k in (
        "requests", "finished", "tokens_in_window", "ttft_n", "itl_n",
        "check_requests", "check_tokens", "check_longest",
        "served_logit_gap_max", "served_logit_gap_mean",
        "control_gap_max", "blocks_in_use_end", "other_gap_max",
        "other_gap_mean")}
    notes.update(ref_notes)
    notes["ttft_p50_ms"] = percentile(ttft, 50)
    notes["itl_p50_ms"] = percentile(itl, 50)
    notes["queue_depth_end"] = h1.get("queue_depth")
    return {"attempted": len(sents), "failed": failed, "checks": checks,
            "end_to_end": end_to_end, "facts": facts, "notes": notes}


def run(ctx):
    return serve_closed_loop(ctx, Hooks)
