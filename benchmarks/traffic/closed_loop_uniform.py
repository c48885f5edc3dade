"""Closed-loop traffic: a fixed number of clients, each sending its next
request when its last one completes; uniform prompt and answer lengths.

Parameters: ``clients``; ``prompt`` and ``output`` each ``{min, max}``;
``per_client`` (requests made ready for each client, more than a window
can use); ``greedy_share``; ``sampling``; ``shape_seed``.

As in the open loop the sizes, their order and which requests are
greedy come from ``shape_seed`` and are the same for every ``--seed``,
which draws the token ids and the sampling seeds.  (Over two other
draws on the chip all three serve metrics stayed within a third of
their bounds, PERF.md section 6.)"""

import numpy as np


def generate(params, seed, seconds, vocab):
    c, k = params["clients"], params["per_client"]
    n = c * k
    shape = np.random.default_rng(params["shape_seed"])
    prompts = shape.integers(params["prompt"]["min"],
                             params["prompt"]["max"] + 1, size=n)
    outputs = shape.integers(params["output"]["min"],
                             params["output"]["max"] + 1, size=n)
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
            "max_tokens": int(params["prompt"]["max"]
                              + params["output"]["max"])}
