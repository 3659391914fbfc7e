"""Helpers of the serving readers that join the device trace with the
clients' records over the traced span."""

from rtbench import stats


def program_seconds(trace, programs) -> float:
    mods = trace.module_seconds()
    return sum(v for k, v in mods.items()
               if any(k.startswith(p) for p in programs))


def output_tokens_in(records, t0, t1) -> float:
    """Output tokens the clients received in [t0, t1], a request's tokens
    after its first spread evenly from its first frame to its last."""
    total = 0.0
    for r in records:
        if r["first_t"] is None or r["frames"] < 1:
            continue
        if t0 <= r["first_t"] <= t1:
            total += 1
        if r["frames"] > 1:
            total += stats.pro_rata_tokens(
                [(r["first_t"], r["last_t"], r["frames"] - 1)], t0, t1)
    return total


def mean_decoding(records, t0, t1) -> float:
    """Mean number of requests between their first and last token."""
    return sum(stats.overlap(r["first_t"], r["last_t"], t0, t1)
               for r in records
               if r["first_t"] is not None and r["frames"] > 1) / (t1 - t0)


def mean_live_kv_tokens(records, t0, t1) -> float:
    """Time-mean over [t0, t1] of the cached positions live in the batch:
    each decoding request holds its prompt plus the tokens it has made."""
    total = 0.0
    for r in records:
        if r["first_t"] is None or r["frames"] < 2:
            continue
        a, b = max(r["first_t"], t0), min(r["last_t"], t1)
        if b <= a:
            continue
        span = r["last_t"] - r["first_t"]
        mid_progress = ((a + b) / 2 - r["first_t"]) / span * r["frames"]
        total += (r["prompt_tokens"] + mid_progress) * (b - a)
    return total / (t1 - t0)


def prompt_tokens_prefilled(records, t0, t1) -> float:
    """Prompt tokens prefilled in [t0, t1]: a request's prompt spread
    evenly from the time it was sent to its first token."""
    return stats.pro_rata_tokens(
        [(r["send_t"], r["first_t"], r["prompt_tokens"]) for r in records
         if r["first_t"] is not None and r["send_t"] is not None], t0, t1)
