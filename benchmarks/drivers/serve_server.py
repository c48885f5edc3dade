"""Driver of the serving cells: ``InferenceServer.submit()`` and the
streams it hands back, timed from the client's side.

Set-up makes the weights on the device from ``--seed`` (the benchmark's
own values, in the type they are served in), builds the server the
configuration's ``serve.server`` entry describes, starts it (its
warm-up traces every executable) and serves a few warm requests.  The
window offers the cell's traffic from ONE thread: an open loop sends
each request when it is due, a closed loop sends a client's next request
when its last one completed.  A refused submission is a failure.  Every
token is stamped when the server hands it over (the handle's ``tap``).
After the window has closed the driver waits for what is still in
flight (late is late, not wrong), reads the memory peak, shuts the
server down and frees it, and only then teacher-forces a sample of the
finished greedy requests, the longest among them, through the plain
float32 reference: ``served_logit_gap_max`` is the widest gap by which a
served token's reference logit lies below the reference's best.

``ctx.fault``: ``token_altered`` changes one token of every greedy
stream where the benchmark receives it.  ``ctx.control`` reports, at the
same positions, the gap of the token an fp8 reference puts first."""

import queue
import time

import numpy as np

DRAIN_S = 60.0


class Sent:
    """One request as the client saw it."""

    __slots__ = ("req", "due", "sent", "times", "tokens", "done", "error")

    def __init__(self, req, due):
        self.req, self.due, self.sent = req, due, None
        self.times, self.tokens = [], []
        self.done, self.error = False, None


def percentile(values, q):
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


def build_server(ctx, seed):
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import LlamaConfig, LlamaModel
    from apex_tpu.serving import InferenceServer
    from lib import weights

    c = ctx.config
    cfg = LlamaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        ffn_hidden_size=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"],
        sliding_window=c["sliding_window"],
        layernorm_eps=c["rms_norm_eps"], rope_base=c["rope_theta"],
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    model = LlamaModel(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 4), jnp.int32))
    shapes = {"params": shapes["params"]}
    make = jax.jit(lambda s: weights.make_weights(shapes, s))
    params = jax.block_until_ready(make(seed))
    ctx.mark("weights")
    server = InferenceServer(model, params, **c["serve"]["server"])
    server.start()
    ctx.mark("server_started")
    return cfg, server, make


def reference_weights(params):
    """The benchmark's weights under the reference's names."""
    p = params["params"]
    lay = p["transformer"]["layers"]["layer"]
    val = lambda x: getattr(x, "value", x)
    return {
        "emb": val(p["embedding"]["embedding"]),
        "head": val(p["lm_head"]["kernel"]),
        "final_norm": val(p["final_norm"]["scale"]),
        "layers": {
            "ln1": val(lay["input_norm"]["scale"]),
            "ln2": val(lay["post_attention_norm"]["scale"]),
            "qkv": val(lay["attention"]["qkv_proj"]["kernel"]),
            "out": val(lay["attention"]["out_proj"]["kernel"]),
            "gate": val(lay["mlp"]["dense_h_to_4h_gate"]["kernel"]),
            "up": val(lay["mlp"]["dense_h_to_4h"]["kernel"]),
            "down": val(lay["mlp"]["dense_4h_to_h"]["kernel"]),
        }}


def check_sample(finished, seed, want_tokens, most):
    """Finished greedy requests to compare: the longest, then others
    drawn from the seed until ``want_tokens`` served tokens are in."""
    greedy = [s for s in finished if s.req["greedy"] and len(s.tokens) > 0]
    if not greedy:
        return []
    greedy.sort(key=lambda s: -(len(s.req["prompt"]) + len(s.tokens)))
    picked, rest = [greedy[0]], greedy[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    for i in order:
        if sum(len(s.tokens) for s in picked) >= want_tokens \
                or len(picked) >= most:
            break
        picked.append(rest[i])
    return picked


def logit_gaps(cfg, weights_ref, sample, pad_to, control):
    """Widest gap over the sample's served tokens (and, for the
    control, of the fp8 reference's first choice at those positions)."""
    import jax.numpy as jnp

    from lib.reference import llama

    kw = dict(layers=cfg.num_layers, heads=cfg.num_heads, kv=cfg.kv_heads,
              d=cfg.head_dim, eps=cfg.layernorm_eps, base=cfg.rope_base)
    served_max = other_max = 0.0
    n_tokens = 0
    for s in sample:
        p = len(s.req["prompt"])
        ids = np.zeros(pad_to, np.int32)
        seq = np.concatenate([s.req["prompt"], np.asarray(s.tokens, np.int32)])
        ids[: len(seq)] = seq
        ids = jnp.asarray(ids)
        ref = llama.logits(weights_ref, ids, **kw)
        low = llama.logits(weights_ref, ids, lower="float8_e4m3fn", **kw) \
            if control else ref
        served, other = llama.gaps(ref, low, ids)
        rows = slice(p - 1, len(seq) - 1)
        served_max = max(served_max, float(jnp.max(served[rows])))
        other_max = max(other_max, float(jnp.max(other[rows])))
        n_tokens += len(s.tokens)
    return served_max, other_max, n_tokens


def run(ctx):
    import jax

    from lib import counts, weights

    seed = weights.seed32(ctx.seed)
    cfg, server, make = build_server(ctx, seed)
    traffic = ctx.load("traffic", ctx.cell["generator"]).generate(
        ctx.cell["traffic_params"], seed, ctx.seconds, cfg.vocab_size)
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
        if traffic["mode"] == "open":
            for req in traffic["requests"]:
                due = t0 + req["due_s"]
                while True:
                    wait = due - time.perf_counter()
                    if wait <= 0:
                        break
                    with ctx.annotate("wait_due"):
                        time.sleep(min(wait, 0.02) if wait > 0.002 else 0)
                s = Sent(req, due)
                sents.append(s)
                submit(s, time.perf_counter())
            with ctx.annotate("wait_close"):
                time.sleep(max(0.0, t_close - time.perf_counter()))
        else:
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
    window = time.perf_counter() - t0
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
    dec_t, dec_ctx, prefill_tokens, prefill_pairs = [], [], 0, 0
    for s in sents:
        p = len(s.req["prompt"])
        if s.times and s.times[0] <= t_close:
            prefill_tokens += p
            prefill_pairs += p * (p + 1) // 2
        for j, t in enumerate(s.times[1:], start=1):
            dec_t.append(t - t0)
            dec_ctx.append(p + j)
    dec_t, dec_ctx = np.asarray(dec_t), np.asarray(dec_ctx)
    inside = dec_t <= ctx.seconds
    facts = {
        "measured_s": ctx.seconds, "requests": len(sents),
        "finished": len(finished), "tokens_in_window": in_window,
        "health_before": h0, "health_after": h1,
        "blocks_in_use_end": health_end.get("blocks_in_use"),
        "max_slots": ctx.config["serve"]["server"]["max_slots"],
        "late_ms": late, "decode_t": dec_t, "decode_ctx": dec_ctx,
        "model_flops": counts.decoder_forward_flops(
            ctx.config, prefill_tokens + int(inside.sum()),
            prefill_pairs + int(dec_ctx[inside].sum()),
            in_window),
        "ttft_n": len(ttft), "itl_n": len(itl),
    }
    end_to_end = {"serve_tokens_per_s": in_window / ctx.seconds,
                  "ttft_p95_ms": percentile(ttft, 95),
                  "itl_p95_ms": percentile(itl, 95)}

    # the server is gone: free it, then run the reference
    sample = check_sample(finished, seed, ctx.cell["check"]["tokens"],
                          ctx.cell["check"]["requests"])
    del server
    checks = {}
    if sample:
        wref = reference_weights(make(seed))
        served, other, n = logit_gaps(cfg, wref, sample,
                                      traffic["max_tokens"], ctx.control)
        facts.update(check_requests=len(sample), check_tokens=n,
                     served_logit_gap_max=served, control_gap_max=other)
        checks["served_logit_gap_max"] = {
            "value": other if ctx.control else served,
            "limit": ctx.cell["limits"]["served_logit_gap_max"]}
    notes = {k: facts.get(k) for k in (
        "requests", "finished", "tokens_in_window", "ttft_n", "itl_n",
        "check_requests", "check_tokens", "served_logit_gap_max",
        "control_gap_max", "blocks_in_use_end")}
    notes["ttft_p50_ms"] = percentile(ttft, 50)
    notes["itl_p50_ms"] = percentile(itl, 50)
    notes["queue_depth_end"] = h1.get("queue_depth")
    return {"attempted": len(sents), "failed": failed, "checks": checks,
            "end_to_end": end_to_end, "facts": facts, "notes": notes}
