"""Traffic generation: fixed multisets, permuted by the seed.

Never imports JAX (the load generator process imports this module).

A traffic file gives each length distribution and the number of requests
per minute of schedule. A distribution becomes a *fixed multiset*: its
evenly spaced quantiles, as many as requests are needed. ``--seed`` decides
only the order of that multiset (and the token ids), so every run offers
the same work; which request meets which neighbour varies.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

_NORMAL = NormalDist()


def _mid_quantiles(n: int) -> list[float]:
    """n evenly spaced probabilities, the midpoints of n equal bins."""
    return [(i + 0.5) / n for i in range(n)]


def quantile_multiset(dist: dict, n: int) -> list[float]:
    """The n evenly spaced quantiles of ``dist``, ascending.

    ``dist["kind"]`` is ``lognormal`` (``median``, ``sigma``), ``uniform``,
    ``exponential`` (``mean``) or ``constant`` (``value``). ``min`` and
    ``max`` clip. Values are floats; callers round where they need lengths.
    """
    kind = dist["kind"]
    out = []
    for p in _mid_quantiles(n):
        if kind == "lognormal":
            x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(p))
        elif kind == "uniform":
            x = dist["min"] + (dist["max"] - dist["min"]) * p
        elif kind == "exponential":
            x = -dist["mean"] * math.log1p(-p)
        elif kind == "constant":
            x = dist["value"]
        else:
            raise ValueError(f"unknown distribution kind {kind!r}")
        if "min" in dist:
            x = max(x, dist["min"])
        if "max" in dist:
            x = min(x, dist["max"])
        out.append(x)
    return out


def length_multiset(dist: dict, n: int) -> list[int]:
    return [int(round(x)) for x in quantile_multiset(dist, n)]


def permuted(values: list, seed: int, salt: str) -> list:
    """``values`` in an order that depends only on (seed, salt)."""
    out = list(values)
    random.Random(f"{seed}:{salt}").shuffle(out)
    return out


def prompt_ids(seed: int, index: int, length: int, vocab: int,
               reserved: int = 259) -> list[int]:
    """``length`` token ids for request ``index``. The first id is unique
    to the request (so no two prompts share a prefix, not even one token)
    and the rest are uniform over the ids the byte tokenizer does not
    reserve (0..258 are bytes and specials)."""
    rng = random.Random(f"{seed}:prompt:{index}")
    span = vocab - reserved
    ids = [reserved + rng.randrange(span) for _ in range(length)]
    ids[0] = reserved + index % span
    return ids


def _requests(traffic: dict, n: int, seed: int, salt: str,
              first_index: int) -> list[dict]:
    """n requests: the n evenly spaced quantiles of the prompt lengths,
    each paired with one of the n quantiles of ``max_tokens`` by a pairing
    that no seed changes, in an order that the seed decides. Every seed
    sends the same set of requests, in another order."""
    prompts = length_multiset(traffic["prompt_tokens"], n)
    outs = permuted(length_multiset(traffic["max_tokens"], n), 0, "pairing")
    pairs = permuted(list(zip(prompts, outs)), seed, salt)
    return [{"index": first_index + i, "prompt_tokens": p, "max_tokens": o}
            for i, (p, o) in enumerate(pairs)]


def _with_due_times(reqs: list[dict], rate_per_s: float, span_s: float,
                    seed: int, salt: str) -> list[dict]:
    """Poisson arrivals made the same way: the quantiles of the exponential
    at the cell's rate, permuted, and scaled so that they fill ``span_s``
    exactly (the last request is due a mean half-gap before its end)."""
    n = len(reqs)
    gaps = permuted(quantile_multiset(
        {"kind": "exponential", "mean": 1.0 / rate_per_s}, n),
        seed, salt + ":g")
    scale = span_s / sum(gaps) * n / (n + 0.5)
    t = 0.0
    for r, g in zip(reqs, gaps):
        t += g * scale
        r["due_s"] = t
    return reqs


def warmup_requests(traffic: dict) -> list[dict]:
    """The lone requests that warm the cell's shapes, from the traffic
    file; the same for every seed."""
    return [{"index": i, **w} for i, w in enumerate(traffic["warmup"])]


def open_loop_plan(traffic: dict, seed: int, seconds: float) -> dict:
    """Ramp and window of an open loop. The window's multiset depends on
    the traffic file and ``seconds`` alone: round(rate x seconds) requests
    due inside it. The ramp before it (``ramp_s``, about one request
    lifetime) is made the same way and brings the slots to steady state."""
    rate = traffic["rate_per_s"]
    n_ramp = max(1, round(rate * traffic["ramp_s"]))
    n_win = max(1, round(rate * seconds))
    ramp = _with_due_times(_requests(traffic, n_ramp, seed, "ramp", 1000),
                           rate, traffic["ramp_s"], seed, "ramp")
    window = _with_due_times(_requests(traffic, n_win, seed, "win", 10000),
                             rate, seconds, seed, "win")
    return {"ramp": ramp, "window": window, "ramp_s": traffic["ramp_s"]}


def closed_loop_plan(traffic: dict, seed: int, seconds: float) -> dict:
    """The request list of a closed loop: cycles of ``cycle_requests``
    requests, every cycle the same set in an order of its own; enough
    cycles for ramp, window and drain at several times the expected rate.

    The order is the seed's, and it matters: two runs of one seed agree
    within 1% on ``serve_tok_s`` while seeds differ by up to 4%, and one
    fixed "balanced" order (small and large requests in turn) entered at a
    point chosen by the seed ran 8% slower and spread wider (my chip runs,
    PR 23). How requests line up in the scheduler is part of what the
    cell measures."""
    n = traffic["cycle_requests"]
    cycles = 2 + int(seconds * traffic["max_requests_per_s"] / n)
    reqs: list[dict] = []
    for c in range(cycles):
        reqs += _requests(traffic, n, seed, f"cycle{c}", 1000 + c * n)
    return {"requests": reqs, "clients": traffic["clients"]}


def train_batch_seed(seed: int, step: int) -> list[int]:
    """Entropy for numpy's ``default_rng`` for the batch of one step."""
    return [int(seed) & 0xFFFFFFFF, int(seed) >> 32, int(step) & 0xFFFFFFFF]
