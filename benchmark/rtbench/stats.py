"""Metric arithmetic kept with the yardstick. Never imports JAX."""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default). Raises on an empty list: a metric with
    no sample is not reported as 0."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def pro_rata_tokens(intervals: list[tuple[float, float, float]],
                    w0: float, w1: float) -> float:
    """Tokens served inside the window [w0, w1]. Each entry is (start, end,
    tokens): a request's service interval from send to last frame and its
    token count (prompt + output). It contributes its tokens times the
    share of its interval that lies inside the window."""
    total = 0.0
    for start, end, tokens in intervals:
        span = end - start
        if span <= 0:
            if w0 <= start <= w1:
                total += tokens
            continue
        total += tokens * overlap(start, end, w0, w1) / span
    return total


def whole_request_tokens(intervals: list[tuple[float, float, float]],
                         w0: float, w1: float) -> float:
    """The count pro rata replaces: tokens of requests that *ended* inside
    the window. Steps by a whole request as the window's edge moves."""
    return float(sum(t for _s, e, t in intervals if w0 <= e <= w1))


def tpot_ms(first_token_t: float, last_token_t: float, n_tokens: int) -> float | None:
    """Time per output token of one request: (last - first) / (n - 1), in
    milliseconds; None for fewer than two tokens."""
    if n_tokens < 2:
        return None
    return (last_token_t - first_token_t) / (n_tokens - 1) * 1e3
