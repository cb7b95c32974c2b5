"""Open-loop chat traffic from a mix's parameters: for each phase of a
run (warm-up, window, traced span) a schedule of requests, each with its
arrival time, its prompt's token ids and its output budget.

**Every seed gets the same multiset.**  A phase of ``seconds`` at
``rate`` requests a second holds ``N = round(rate x seconds)`` requests.
Their prompt lengths are the N quantiles ``(i + 1/2) / N`` of the mix's
prompt distribution, their budgets those of the output distribution,
the gaps between arrivals those of the gap distribution (a gamma renewal
process; shape 1 is Poisson), scaled so that they sum to ``seconds``.
The seed draws the three orders, each over the whole phase, and the
token ids: total work, padding and offered rate of a phase are equal
across seeds to the token, and a seed never used before is still new
traffic.  A mix that names an ``order_seed`` replays ONE schedule
instead: the three orders come from that number in every run and the
seed draws the ids alone (the stretch of a queue that a window serves
is then the same work in every run, as a replayed trace's is).

Plain numpy on the host; nothing of the program.
"""

import math
from statistics import NormalDist

import numpy as np


def _midpoints(n):
    return (np.arange(n) + 0.5) / n


def lognormal_quantiles(n, median, sigma, low, high):
    """The n mid-quantiles of a log-normal, clipped, as whole numbers."""
    normal = NormalDist()
    values = [median * math.exp(sigma * normal.inv_cdf(float(u)))
              for u in _midpoints(n)]
    return np.clip(np.rint(values), low, high).astype(np.int64)


def gamma_gap_quantiles(n, shape, total):
    """The n mid-quantiles of the gap of a gamma renewal process of
    ``shape``, scaled to sum to ``total``.  Shape 1 (exponential gaps)
    has a closed form; another shape is inverted by bisection on the
    regularised incomplete gamma function's series."""
    u = _midpoints(n)
    if shape == 1:
        gaps = -np.log1p(-u)
    else:
        gaps = np.array([_gamma_inv(float(p), shape) for p in u])
    return gaps * (total / gaps.sum())


def _gamma_cdf(x, shape):
    term = total = 1.0 / shape
    for k in range(1, 400):
        term *= x / (shape + k)
        total += term
        if term < 1e-16 * total:
            break
    return total * math.exp(-x + shape * math.log(x) - math.lgamma(shape))


def _gamma_inv(p, shape):
    low, high = 0.0, shape + 40.0 * math.sqrt(shape) + 40.0
    for _ in range(200):
        mid = (low + high) / 2
        if mid > 0 and _gamma_cdf(mid, shape) < p:
            low = mid
        else:
            high = mid
    return (low + high) / 2


def lengths(spec, n):
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return lognormal_quantiles(n, spec["median"], spec["sigma"],
                               spec["min"], spec["max"])


def phase(order, ids, traffic, seconds, rate, vocab_size):
    """One phase's requests, arrival times from the phase's start:
    ``order`` draws the orders of lengths, budgets and gaps over the
    whole phase, ``ids`` the prompts' tokens."""
    n = max(1, round(rate * seconds))
    arrival = traffic["arrival"]
    if arrival["dist"] != "gamma":
        raise ValueError(f"unknown arrival process {arrival['dist']!r}")
    prompts = order.permutation(lengths(traffic["prompt"], n))
    budgets = order.permutation(lengths(traffic["output"], n))
    # a pair too long for the context gives up prompt, never budget
    prompts = np.minimum(prompts, traffic["max_total"] - budgets)
    gaps = order.permutation(
        gamma_gap_quantiles(n, arrival["shape"], seconds))
    # a request arrives its own gap after the one before it; the first
    # gap is split between the phase's two ends
    at = np.cumsum(gaps) - gaps[0] / 2
    return [{"at": float(t), "gap": float(g), "budget": int(b),
             "prompt": ids.integers(0, vocab_size, int(p), dtype=np.int32)}
            for t, g, p, b in zip(at, gaps, prompts, budgets)]


def make(seed, config, traffic, phases, rate=None):
    """{phase name: [request]} for ``phases`` = [(name, seconds)], one
    after another; a request's ``at`` counts from the first phase's
    start and ``phase`` names its own."""
    ids = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    order = np.random.default_rng(traffic["order_seed"]) \
        if "order_seed" in traffic else ids
    rate = traffic["rate"] if rate is None else rate
    out, start = [], 0.0
    for name, seconds in phases:
        if seconds <= 0:
            continue
        for request in phase(order, ids, traffic, seconds, rate,
                             config["vocab_size"]):
            request["at"] += start
            request["phase"] = name
            out.append(request)
        start += seconds
    return out


def fixed(seed, config, pairs):
    """The check's requests: the mix's fixed (prompt length, budget)
    pairs with ids from the seed."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    return [{"at": 0.0, "phase": "check", "budget": int(budget),
             "prompt": rng.integers(0, config["vocab_size"], int(length),
                                    dtype=np.int32)}
            for length, budget in pairs]
