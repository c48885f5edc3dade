"""Driver of the Falcon-H1 serving cells: ``serve_server.py``'s window,
stamps, drain, facts and end-to-end definitions (that file's docstring
describes them), around this model's server, weights and reference.

``serve_server.run`` reaches its model through module globals, so the
window loop cannot be borrowed without editing that file: this driver
holds its own ``run`` — the same warm requests, closed-loop window,
stamps, drain, ``facts`` keys and the same three end-to-end
definitions, line for line (``benchmarks/tests/test_falcon_h1_cell.py`` holds the two
drivers' keys equal) — and borrows what is a function there (``Sent``,
``percentile``, ``check_sample``).

What differs: the model (``FalconH1Model``), the weights
(``lib/weights_falcon_h1.py``: scales at which every branch speaks
under the published multipliers), the reference
(``lib/reference/falcon_h1.py``), the FLOP count
(``lib/counts_falcon_h1.py``) and the controls of the comparison.

``ctx.control`` reports, at the served positions, the gap of the token
an fp8 reference puts first.  ``ctx.fault`` names a WRONG reference that
stands in the same place: ``state_dropped`` restarts the recurrence
from zero at every chunk's first position (what a mixed step that
failed to carry state would serve), ``state_inherited`` starts it from
another request's final state (what a slot that was not reset would
serve).  ``token_altered`` is ``serve_server``'s.  The cell's ``check``
entry may name ``also``: further of these readings to take in the same
run, into the notes, for the builder's readings of the limit."""

import queue
import time

import numpy as np

DRAIN_S = 60.0
WRONG_REFERENCES = ("state_dropped", "state_inherited")


def build_server(ctx, seed):
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import FalconH1Config, FalconH1Model
    from apex_tpu.serving import InferenceServer
    from lib import weights_falcon_h1 as weights

    c = ctx.config
    cfg = FalconH1Config.from_hf(c, dtype=jnp.bfloat16,
                                 param_dtype=jnp.bfloat16)
    model = FalconH1Model(cfg)
    # 8 positions: the narrowest chunk the scan's kernel takes
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


def logit_gaps(ctx, weights_ref, sample, pad_to, others, chunk):
    """Widest gap over the sample's served tokens in the float32
    reference, and for each of ``others`` (``fp8``, ``state_dropped``,
    ``state_inherited``) the widest gap of the token that computation
    puts first at those positions."""
    import jax.numpy as jnp

    from lib.reference import falcon_h1 as ref

    c = ctx.config
    kw = dict(layers=c["num_hidden_layers"], dims=ref.dims_of(c),
              mult=ref.mult_of(c))
    head = dict(eps=c["rms_norm_eps"], mult=kw["mult"])
    padded = []
    for s in sample:
        ids = np.zeros(pad_to, np.int32)
        seq = np.concatenate([s.req["prompt"],
                              np.asarray(s.tokens, np.int32)])
        ids[: len(seq)] = seq
        padded.append((jnp.asarray(ids), len(s.req["prompt"]), len(seq)))
    served_max, n_tokens, sizes = 0.0, 0, None
    other_max = {name: 0.0 for name in others}
    finals = None
    if "state_inherited" in others:      # the request before, cyclically
        finals = [ref.hidden(weights_ref, ids, **kw)[1]
                  for ids, _, _ in padded]
    for i, (ids, p, n) in enumerate(padded):
        rows = slice(p - 1, n - 1)
        h, _, size = ref.hidden(weights_ref, ids, **kw)
        sizes = size if sizes is None else sizes
        served, _ = ref.gaps_by_block(weights_ref, h, h, ids, **head)
        served_max = max(served_max, float(jnp.max(served[rows])))
        n_tokens += n - p
        for name in others:
            lower = None
            if name == "fp8":
                lower = "float8_e4m3fn"
                low = ref.hidden(weights_ref, ids, lower=lower, **kw)[0]
            elif name == "state_dropped":
                low = ref.hidden(weights_ref, ids, restart_every=chunk,
                                 **kw)[0]
            else:
                low = ref.hidden(weights_ref, ids, init=finals[i - 1],
                                 **kw)[0]
            _, other = ref.gaps_by_block(weights_ref, h, low, ids,
                                         lower=lower, **head)
            other_max[name] = max(other_max[name],
                                  float(jnp.max(other[rows])))
    return served_max, other_max, n_tokens, np.asarray(sizes)


def run(ctx):
    from lib import counts_falcon_h1 as counts
    from lib import weights_falcon_h1 as weights

    base = ctx.load("drivers", "serve_server")
    Sent, percentile = base.Sent, base.percentile
    seed = weights.seed32(ctx.seed)
    cfg, server, make = build_server(ctx, seed)
    chunk = server.engine._chunk
    traffic = ctx.load("traffic", ctx.cell["generator"]).generate(
        ctx.cell["traffic_params"], seed, ctx.seconds, cfg.vocab_size)
    if traffic["mode"] != "closed":
        raise ValueError("serve_falcon_h1 drives closed-loop traffic only; "
                         f"generator {ctx.cell['generator']!r} is "
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
    c = ctx.config
    max_slots = c["serve"]["server"]["max_slots"]
    mixer = dict(slots=max_slots, heads=c["mamba_n_heads"],
                 d_head=c["mamba_d_head"], d_state=c["mamba_d_state"],
                 groups=c["mamba_n_groups"])
    facts = {
        "measured_s": ctx.seconds, "requests": len(sents),
        "finished": len(finished), "tokens_in_window": in_window,
        "health_before": h0, "health_after": h1,
        "blocks_in_use_end": health_end.get("blocks_in_use"),
        "max_slots": max_slots,
        "late_ms": late, "decode_t": dec_t, "decode_ctx": dec_ctx,
        "model_flops": counts.decoder_forward_flops(
            ctx.config, prefill_tokens + int(inside.sum()),
            prefill_pairs + int(dec_ctx[inside].sum()),
            in_window),
        "ttft_n": len(ttft), "itl_n": len(itl),
        # the shapes of the two state-space kernels' calls, one a layer
        "ssm_decode_shape": mixer,
        "ssd_chunk_shape": dict(mixer, width=chunk),
    }
    end_to_end = {"serve_tokens_per_s": in_window / ctx.seconds,
                  "ttft_p95_ms": percentile(ttft, 95),
                  "itl_p95_ms": percentile(itl, 95)}

    # the server is gone: free it, then run the reference
    sample = base.check_sample(finished, seed, ctx.cell["check"]["tokens"],
                               ctx.cell["check"]["requests"])
    del server
    checks = {}
    if sample:
        judged = "fp8" if ctx.control else ctx.fault \
            if ctx.fault in WRONG_REFERENCES else None
        others = [judged] if judged else []
        wref = weights.reference_weights(make(seed))
        served, other, n, sizes = logit_gaps(
            ctx, wref, sample, traffic["max_tokens"], others, chunk)
        facts.update(check_requests=len(sample), check_tokens=n,
                     served_logit_gap_max=served,
                     control_gap_max=other.get("fp8", 0.0),
                     other_gap_max=other,
                     branch_rms=[[round(float(v), 4) for v in row]
                                 for row in sizes])
        checks["served_logit_gap_max"] = {
            "value": other[judged] if judged else served,
            "limit": ctx.cell["limits"]["served_logit_gap_max"]}
    notes = {k: facts.get(k) for k in (
        "requests", "finished", "tokens_in_window", "ttft_n", "itl_n",
        "check_requests", "check_tokens", "served_logit_gap_max",
        "control_gap_max", "blocks_in_use_end", "other_gap_max",
        "branch_rms")}
    notes["ttft_p50_ms"] = percentile(ttft, 50)
    notes["itl_p50_ms"] = percentile(itl, 50)
    notes["queue_depth_end"] = h1.get("queue_depth")
    return {"attempted": len(sents), "failed": failed, "checks": checks,
            "end_to_end": end_to_end, "facts": facts, "notes": notes}
