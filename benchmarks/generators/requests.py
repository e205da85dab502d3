"""The one general generator of serving traffic.  A traffic mix is a data
file of parameters; this file reads them and nothing else decides the
load.

    loop          "open" (arrivals on a schedule) or "closed" (clients wait)
    rate_per_s    open loop: mean arrivals per second, fixed in the file
    clients       closed loop: requests kept in flight
    warm_seconds  the same traffic before the window (set-up, not measured)
    prompt_len    {"dist": "lognormal", "median", "sigma", "min", "max"} or
    output_len    {"dist": "uniform", "min", "max"}
    pool          closed loop: distinct requests to cycle through
    schedule_seed optional (default 0): orders the lengths and the gaps

The schedule is stratified and fixed, not drawn: the lengths are their
distribution's evenly spaced quantiles and an open loop's inter-arrival
gaps are the exponential distribution's (the gaps a Poisson process would
have, every quantile once), in the one order that ``schedule_seed`` in the
mix's file draws.  Every run seed gets that same schedule; the run's
``--seed`` draws the token ids (and, in the runner, the weights).  So a
cell sees one arrival pattern, not the spread of patterns a Poisson process
gives.  PR 23 first gave each seed another order of the same sizes: with 44
requests in a window, which long answers fall near its edges then moved
tokens/s by 13 % and the p95 by 13 % from seed to seed, the same code, so
the order is part of the mix.
"""
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _quantiles(spec, n):
    """``n`` lengths: the distribution's evenly spaced quantiles, clipped."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(np.rint(v), spec.get("min", 1),
                   spec.get("max", 1 << 30)).astype(np.int64)


def generate(traffic, cfg, seed, seconds):
    """A list of requests ``{"due_s", "prompt", "max_new_tokens"}``;
    ``due_s`` is relative to the window's start (negative: warm-up) and is
    None in a closed loop."""
    rng = np.random.default_rng(int(seed))  # token ids
    order = np.random.default_rng(int(traffic.get("schedule_seed", 0)))
    vocab = cfg["vocab_size"]
    warm = float(traffic.get("warm_seconds", 0.0))
    if traffic["loop"] == "open":
        rate = float(traffic["rate_per_s"])
        n = max(int(round(rate * (warm + seconds))), 1)
        u = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-u)
        gaps *= n / gaps.sum()  # unit mean: the run spans warm + seconds
        t_unit = np.cumsum(order.permutation(gaps))
        due = t_unit / rate - warm
        due = np.minimum(due, seconds - 1e-6)
    elif traffic["loop"] == "closed":
        n = int(traffic["pool"])
        due = [None] * n
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    p_len = order.permutation(_quantiles(traffic["prompt_len"], n))
    o_len = order.permutation(_quantiles(traffic["output_len"], n))
    return [{"due_s": None if due[i] is None else float(due[i]),
             "prompt": rng.integers(1, vocab,
                                    size=int(p_len[i])).astype(np.int32),
             "max_new_tokens": int(o_len[i])} for i in range(n)]


def summary(requests, seconds):
    p = [len(r["prompt"]) for r in requests]
    o = [r["max_new_tokens"] for r in requests]
    due = [r["due_s"] for r in requests if r["due_s"] is not None]
    s = {"requests": len(requests),
         "prompt_len": [min(p), int(np.median(p)), max(p)],
         "output_len": [min(o), int(np.median(o)), max(o)],
         "prompt_tokens": int(sum(p)), "output_tokens": int(sum(o))}
    if due:
        s.update(due_in_window=sum(1 for d in due if d >= 0),
                 first_due_s=min(due), last_due_s=max(due))
    return s
