"""Autotune search driver: predict -> prune -> rank -> measure -> bank.

The driver never compiles a pruned candidate: the analytic HBM model
(autotune/model.py) prices the whole space for free, candidates over the
device budget (times a safety margin) are dropped at analysis time, and
only the top few survivors — ranked by a throughput prior plus any cached
measurements — are handed to the caller's ``measure_fn``. Every decision
lands in the search trace (``SearchResult.trace``) so a bench round's
``tried`` list shows WHY each config was measured, skipped, or pruned.
"""

from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import dataclass, field

from ray_tpu.autotune.model import predict_hbm, remat_flops_factor
from ray_tpu.autotune.space import Candidate


class AutotuneCache:
    """Measured-throughput cache, keyed by device kind + geometry + label.

    A JSON file next to the bench (or RTPU_AUTOTUNE_CACHE): measurements
    from earlier runs on this machine order the candidates, so the sweep
    spends its budget on the unexplored frontier instead of re-measuring
    known configs; the best cached config is still re-measured each run
    (it keeps the cache honest against regressions). A cached number only
    ranks: the search never reports one as its result.

    Per-machine state, gitignored: a fresh checkout starts empty."""

    def __init__(self, path: str | None = None):
        self.path = path or os.environ.get("RTPU_AUTOTUNE_CACHE") or \
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))),
                "AUTOTUNE_CACHE.json")
        self._data: dict[str, dict] = {}
        try:
            with open(self.path) as f:
                self._data = json.load(f)
        except Exception:
            self._data = {}

    @staticmethod
    def key(device_kind: str, geometry: str, label: str) -> str:
        return f"{device_kind}|{geometry}|{label}"

    def get(self, device_kind: str, geometry: str, label: str) -> dict | None:
        return self._data.get(self.key(device_kind, geometry, label))

    def put(self, device_kind: str, geometry: str, label: str,
            record: dict, flush: bool = True) -> None:
        """``flush=False`` defers the file write (bulk seeding); call
        :meth:`flush` once afterwards."""
        rec = dict(record)
        rec["ts"] = time.time()
        self._data[self.key(device_kind, geometry, label)] = rec
        if flush:
            self.flush()

    def flush(self) -> None:
        try:
            with open(self.path, "w") as f:
                json.dump(self._data, f, indent=1, sort_keys=True)
        except Exception:
            pass  # cache is an optimization, never a failure


def geometry_sig(cfg, seq: int, n_devices: int = 1) -> str:
    return (f"L{cfg.num_layers}h{cfg.hidden_size}H{cfg.num_heads}"
            f"kv{cfg.num_kv_heads}d{cfg.head_dim}v{cfg.vocab_size}"
            f"s{seq}n{n_devices}")


@dataclass
class SearchResult:
    winner: str | None = None
    tokens_per_sec: float = 0.0
    trace: list[dict] = field(default_factory=list)
    space_size: int = 0
    pruned: int = 0
    measured: int = 0          # successful measurements only
    failed: int = 0            # measure attempts that raised
    analysis_seconds: float = 0.0

    def tried_rows(self) -> list[dict]:
        """The bench's ``tried`` spelling of the trace (one row per
        candidate, measured rows carrying throughput + HBM provenance)."""
        return self.trace


def _score(cand: Candidate, cfg, predicted_bytes: int,
           budget: int | None) -> float:
    """Throughput prior for ranking (NOT a prediction of tok/s): larger
    microbatches amortize per-step overhead with diminishing returns,
    recompute-heavy remat policies pay their FLOPs factor, grad
    accumulation adds per-microbatch launch overhead, and HBM pressure
    derates: configs predicted past ~82% of budget underperform on chip
    (r05: b8/attn 9% and b4/dots 3% slower than b4/attn while the lighter
    b4/attn+ was fastest — XLA trades speed for fit as headroom shrinks).
    The derate constants are fit to exactly that measured r05 ordering."""
    mb = max(1, cand.batch // max(1, cand.grad_accum))
    eff = mb / (mb + 0.35)
    flops = remat_flops_factor(cand.remat, cfg.num_layers)
    accum = 0.99 ** (cand.grad_accum - 1)
    zero1 = 0.995 if cand.zero1 else 1.0
    score = eff * accum * zero1 * cand.batch ** 0.02 / flops
    if budget:
        frac = predicted_bytes / budget
        if frac > 0.82:
            score *= max(0.6, 1.0 - 1.2 * (frac - 0.82))
    return score


def autotune_train_configs(
    cfg,
    seq: int,
    candidates: list[Candidate],
    *,
    hbm_budget_bytes: int | None,
    measure_fn=None,
    max_measure: int = 6,
    cache: AutotuneCache | None = None,
    device_kind: str = "unknown",
    n_devices: int = 1,
    prune_margin: float = 1.05,
) -> SearchResult:
    """Run the search. ``measure_fn(cand) -> dict`` measures one candidate
    (keys: ``tokens_per_sec`` and optionally ``measured_hbm_bytes``,
    ``hbm_source``; raise on failure) — pass None for analysis-only mode
    (CI smoke / CPU hosts): everything is predicted, pruned and ranked,
    nothing measured.

    ``prune_margin``: a candidate is pruned only when its prediction
    exceeds budget * margin — the analytic model overestimates by design
    (see autotune/model.py), and a kept-but-OOM candidate costs one failed
    AOT attempt while a wrongly pruned one silently loses the win."""
    t0 = time.monotonic()
    res = SearchResult(space_size=len(candidates))
    geo = geometry_sig(cfg, seq, n_devices)
    scored: list[tuple[float, Candidate, dict]] = []

    for cand in candidates:
        pred = predict_hbm(cfg, seq, cand, data_shards=n_devices)
        row: dict = {"config": cand.label,
                     "predicted_hbm_gb": pred.total_gb}
        if hbm_budget_bytes and \
                pred.total_bytes > hbm_budget_bytes * prune_margin:
            row["pruned"] = True
            res.pruned += 1
            res.trace.append(row)
            continue
        cached = cache.get(device_kind, geo, cand.label) if cache else None
        if cached and cached.get("tokens_per_sec"):
            row["cached_tokens_per_sec"] = cached["tokens_per_sec"]
        row["score"] = round(_score(cand, cfg, pred.total_bytes,
                                    hbm_budget_bytes), 4)
        scored.append((row["score"], cand, row))
        res.trace.append(row)
    res.analysis_seconds = round(time.monotonic() - t0, 3)

    if measure_fn is None:
        # analysis-only: rank by prior (cached measurements win first).
        # The winner is a ranking, so tokens_per_sec stays 0: nothing ran.
        scored.sort(key=lambda t: (t[2].get("cached_tokens_per_sec", 0.0),
                                   t[0]), reverse=True)
        if scored:
            res.winner = scored[0][1].label
        return res

    # Measurement order: the best CACHED config first (a known-good
    # config is measured before the budget goes to unknowns), then the
    # unmeasured frontier by prior.
    cached_rows = [t for t in scored if "cached_tokens_per_sec" in t[2]]
    fresh_rows = [t for t in scored if "cached_tokens_per_sec" not in t[2]]
    cached_rows.sort(key=lambda t: t[2]["cached_tokens_per_sec"],
                     reverse=True)
    fresh_rows.sort(key=lambda t: t[0], reverse=True)
    order = cached_rows[:1] + fresh_rows + cached_rows[1:]

    best = (0.0, None)
    for _, cand, row in order[:max_measure]:
        try:
            m = measure_fn(cand)
        except Exception as e:  # noqa: BLE001 - one candidate, not the sweep
            row["error"] = str(e)[:160]
            res.failed += 1
            # surface live (the trace row is truncated and only lands in
            # the final record): an operator watching a TPU round needs
            # the OOM/compile error as it happens
            print(f"autotune candidate {cand.label} failed: {str(e)[:400]}",
                  file=sys.stderr)
            continue
        res.measured += 1
        row.update({k: v for k, v in m.items() if v is not None})
        tps = float(m.get("tokens_per_sec") or 0.0)
        if cache is not None and tps > 0:
            cache.put(device_kind, geo, cand.label, m)
        if tps > best[0]:
            best = (tps, cand.label)
    # provenance for rows that were in budget but not measured this round
    for _, _cand, row in order[max_measure:]:
        row.setdefault("skipped", "measure_budget")

    # Every measurement failed: no winner. A cached number is not a result.
    res.tokens_per_sec, res.winner = best
    return res
