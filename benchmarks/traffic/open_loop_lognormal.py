"""Open-loop chat traffic: independent users at a fixed rate, lognormal
prompt and answer lengths, one fixed schedule a cell.

Parameters: ``rate_per_s``; ``prompt`` and ``output`` each ``{median,
sigma, min, max}``; ``greedy_share``; ``sampling`` (the keyword
arguments of the sampled requests); ``shape_seed``.

The schedule is the cell's and the same for every ``--seed``:
``rate_per_s * seconds`` requests whose sizes, exponential gaps and
greedy flags are drawn ONCE, from ``shape_seed``, the gaps scaled so
that they fill the window exactly (so the count is fixed: a draw from a
Poisson process given its count, not the process).  ``--seed`` draws
what the requests say: the token ids and the sampling seeds.  Every
seed offers the same work at the same moments, and what differs between
runs is the system.  Drawn from ``--seed`` instead, the tail would be
each draw's own: over three other draws on the chip ``ttft_p95_ms``
moved by -21 to +29 % and the tokens per second by up to 3.7 % (PERF.md
section 6), more than any bound the contract admits.  Another draw is
another cell: a data file with another ``shape_seed``."""

import numpy as np


def _lengths(rng, n, spec):
    x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], size=n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def generate(params, seed, seconds, vocab):
    n = max(1, int(round(params["rate_per_s"] * seconds)))
    shape = np.random.default_rng(params["shape_seed"])
    prompts = _lengths(shape, n, params["prompt"])
    outputs = _lengths(shape, n, params["output"])
    gaps = shape.exponential(1.0, size=n)
    gaps *= seconds / gaps.sum()
    n_greedy = int(round(params["greedy_share"] * n))

    due = np.cumsum(gaps) - gaps[0]
    greedy = np.zeros(n, bool)
    greedy[shape.permutation(n)[:n_greedy]] = True
    rng = np.random.default_rng(seed)
    requests = []
    for i in range(n):
        kw = {} if greedy[i] else dict(params["sampling"])
        requests.append({
            "due_s": float(due[i]), "greedy": bool(greedy[i]),
            "prompt": rng.integers(0, vocab, size=int(prompts[i]),
                                   dtype=np.int32),
            "max_new_tokens": int(outputs[i]),
            "seed": int(rng.integers(0, 2 ** 31 - 1)), "sampling": kw})
    return {"mode": "open", "requests": requests,
            "max_tokens": int(params["prompt"]["max"]
                              + params["output"]["max"])}
