"""The one general generator of serving traffic. A mix is a data file of
parameters (``traffic/<mix>.json``); this turns it and ``--seed`` into
requests. Host-side numpy only.

Every seed gets the same work in another order: the *set* of (prompt
length, output length) pairs and the *set* of gaps between arrivals are
drawn once from the mix's own ``shape_seed``; ``--seed`` shuffles both and
draws the token ids. So two seeds offer the same number of requests, the
same prompt and output tokens in total and the same last arrival, and
differ in which request meets which.

Mix parameters: ``rate_rps`` (offered load, requests a second),
``arrivals`` ("poisson": exponential gaps; "uniform": equal gaps),
``prompt`` and ``output`` (``median``, ``sigma`` of a lognormal, ``min``,
``max`` clips), ``max_total`` (prompt + output at most this), ``greedy``
(always true today: the correctness check reads greedy tokens),
``shape_seed``.
"""

import math

import numpy as np


def _lengths(rng, spec, n):
    raw = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def shapes(mix, duration_s):
    """The seed-independent part: gaps and length pairs of the requests due
    inside ``duration_s``."""
    rng = np.random.default_rng(mix["shape_seed"])
    rate = float(mix["rate_rps"])
    # Enough draws that the cumulative gaps pass the duration.
    n_draw = int(rate * duration_s * 2 + 50)
    if mix.get("arrivals", "poisson") == "poisson":
        gaps = rng.exponential(1.0 / rate, n_draw)
    else:
        gaps = np.full(n_draw, 1.0 / rate)
    due = np.cumsum(gaps)
    n = int(np.searchsorted(due, duration_s))
    prompts = _lengths(rng, mix["prompt"], n_draw)[:n]
    outputs = _lengths(rng, mix["output"], n_draw)[:n]
    outputs = np.minimum(outputs, mix["max_total"] - prompts)
    return gaps[:n], prompts, outputs


def requests(mix, seed, duration_s, vocab_size):
    """``[{id, due_s, prompt (list of ids), max_new_tokens}]`` in order of
    arrival."""
    gaps, prompts, outputs = shapes(mix, duration_s)
    rng = np.random.default_rng([int(seed), 0x7261])
    gaps = rng.permutation(gaps)
    order = rng.permutation(len(prompts))
    due = np.cumsum(gaps)
    out = []
    for i, j in enumerate(order):
        # Log-uniform ids, as the training batches: p(k) ~ 1/k.
        u = rng.random(int(prompts[j]))
        ids = np.clip(np.exp(u * math.log(vocab_size)).astype(int) - 1,
                      0, vocab_size - 1)
        out.append({"id": f"r{i}", "due_s": float(due[i]),
                    "prompt": [int(t) for t in ids],
                    "max_new_tokens": int(outputs[j])})
    return out
