"""Closed-loop traffic of two kinds of client in one queue: ``clients``
in all, of which ``long_clients`` send long prompts and the others
short ones; each sends its next request when its last one completes.

Parameters: ``clients``, ``long_clients``; ``prompt`` (the short
range) and ``long_prompt``, ``output``: each ``{min, max}``, uniform;
``per_client`` (requests made ready for each client, more than a window
can use); ``greedy_share``; ``sampling``; ``shape_seed``.

``closed_loop_uniform.py``'s contract: the sizes, their order, which
clients are long and which requests are greedy come from ``shape_seed``
and are the same for every ``--seed``, which draws the token ids and
the sampling seeds — runs differ by the system, not the work."""

import numpy as np


def generate(params, seed, seconds, vocab):
    c, k = params["clients"], params["per_client"]
    n = c * k
    shape = np.random.default_rng(params["shape_seed"])
    long_client = np.zeros(c, bool)
    long_client[shape.permutation(c)[: params["long_clients"]]] = True
    span = lambda r, size: shape.integers(r["min"], r["max"] + 1, size=size)
    prompts = np.where(np.repeat(long_client, k),
                       span(params["long_prompt"], n),
                       span(params["prompt"], n))
    outputs = span(params["output"], n)
    n_greedy = int(round(params["greedy_share"] * n))
    greedy = np.zeros(n, bool)
    greedy[shape.permutation(n)[:n_greedy]] = True
    rng = np.random.default_rng(seed)
    clients = []
    for ci in range(c):
        rows = []
        for j in range(k):
            i = ci * k + j
            kw = {} if greedy[i] else dict(params["sampling"])
            rows.append({
                "greedy": bool(greedy[i]),
                "prompt": rng.integers(0, vocab, size=int(prompts[i]),
                                       dtype=np.int32),
                "max_new_tokens": int(outputs[i]),
                "seed": int(rng.integers(0, 2 ** 31 - 1)),
                "sampling": kw})
        clients.append(rows)
    return {"mode": "closed", "clients": clients,
            "max_tokens": int(params["long_prompt"]["max"]
                              + params["output"]["max"])}
