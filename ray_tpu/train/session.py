"""Per-worker training session: context + report API.

Capability parity with the reference's session (reference:
ray.train.get_context / ray.train.report — python/ray/train/v2/_internal/
execution/context.py shapes; report flows to the controller's checkpoint
manager, SURVEY.md §3.4 step 4).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any

from ray_tpu.util import tracing

# Per-worker step-time window feeding straggler attribution (the head ranks
# workers from the decile summaries streamed with every telemetry push).
_STEP_WINDOW = 256


@dataclass
class TrainContext:
    world_rank: int = 0
    world_size: int = 1
    local_rank: int = 0
    node_rank: int = 0
    experiment_name: str = "train"
    storage_path: str | None = None
    trial_dir: str | None = None
    coordinator_addr: str | None = None
    restart_count: int = 0
    latest_checkpoint: str | None = None  # dir path, set on restore
    # Multi-slice topology (from JaxBackendConfig.num_slices): lets a
    # train_fn build its hybrid mesh / pick dcn_axes for the spmd step
    # without re-deriving the slice count from MEGASCALE env.
    num_slices: int = 1
    # Replica plane wiring from the controller (None = replication off):
    # {"run": store name prefix, "every": push every N steps,
    #  "num_slices": buddy-mapping slice count,
    #  "restore_step": step to restore from on a fast restart (None unless
    #  the controller chose the replica tier)}.
    replica: dict | None = None

    # filled by the worker harness
    dataset_shards: dict = field(default_factory=dict)  # name -> DataIterator
    _replica_writer: Any = None  # lazy ReplicaWriter (train/replica.py)
    # Goodput RankLedger (observability/goodput.py), attached by
    # set_context when the ledger gate is on; its snapshot rides this
    # rank's train-stats row with every telemetry push.
    _goodput: Any = None
    _reports: list[dict] = field(default_factory=list)
    _report_lock: threading.Lock = field(default_factory=threading.Lock)
    _last_report_ts: float = 0.0  # monotonic ts of the previous report()
    # Rolling per-step timing window: (step_time, sync_s, compute_s) per
    # report(); summarized into deciles for the head's straggler table.
    _step_window: deque = field(
        default_factory=lambda: deque(maxlen=_STEP_WINDOW))
    _steps_total: int = 0

    def get_world_rank(self) -> int:
        return self.world_rank

    def get_world_size(self) -> int:
        return self.world_size

    def get_local_rank(self) -> int:
        return self.local_rank

    def get_num_slices(self) -> int:
        return self.num_slices

    def get_checkpoint(self) -> str | None:
        return self.latest_checkpoint

    def get_replica_state(self):
        """On a replica-tier fast restart: this rank's in-cluster state
        shard as a :class:`ray_tpu.train.replica.ReplicaState` (``.step``,
        ``.state``); None otherwise. Check it BEFORE get_checkpoint() —
        replicas are newer than (or equal to) the latest checkpoint
        whenever the controller picked this tier."""
        rep = self.replica
        if not rep or rep.get("restore_step") is None:
            return None
        from ray_tpu.train.replica import fetch_replica_state

        return fetch_replica_state(rep, self.world_rank, self.world_size)

    def get_dataset_shard(self, name: str = "train"):
        """This worker's streaming split of a Trainer dataset (reference:
        ray.train.get_dataset_shard — v2 DataParallelTrainer datasets= are
        streaming_split across the worker group)."""
        if name not in self.dataset_shards:
            raise KeyError(
                f"no dataset {name!r}; Trainer(datasets={{...}}) keys: "
                f"{sorted(self.dataset_shards)}")
        return self.dataset_shards[name]


_local = threading.local()

# rank -> its LIVE TrainContext (last-write-wins across restarts): the
# telemetry flusher reads step-stat summaries from here without holding a
# reference into any particular worker thread. Only live contexts are held
# strongly — a finished run is summarized into a plain row at
# set_context(None) time (below), never pinned (a TrainContext holds the
# run's dataset shards).
_stats_registry: dict[int, TrainContext] = {}
# rank -> (monotonic finish time, final summary row). The final window
# stays streamable for a bounded grace (a short run can end before the
# flusher's next tick — dropping it instantly would lose the run's stats
# entirely), then the rank is evicted so the telemetry idle-skip resumes
# and the head row ages out of the straggler report instead of being
# re-stamped forever.
_stats_final: dict[int, tuple[float, dict]] = {}
_FINISHED_GRACE_S = 60.0
_stats_lock = threading.Lock()


def _prune_final_locked(now_m: float) -> None:
    for rank, (t0, _row) in list(_stats_final.items()):
        if now_m - t0 > _FINISHED_GRACE_S:
            _stats_final.pop(rank)


def set_context(ctx: TrainContext | None) -> None:
    import time as _time

    prev = getattr(_local, "ctx", None)
    _local.ctx = ctx
    # Goodput ledger lifecycle, BEFORE the final-row summarize below so a
    # finishing run's row carries its closed (tail → idle) ledger.
    try:
        from ray_tpu.observability import goodput as _goodput

        if prev is not None and prev is not ctx:
            _goodput.detach(prev)
        if ctx is not None and ctx is not prev and ctx._goodput is None:
            _goodput.attach(ctx)
    except Exception:
        pass  # the ledger must never break context setup
    now_m = _time.monotonic()
    with _stats_lock:
        _prune_final_locked(now_m)
        if ctx is not None:
            _stats_registry[ctx.world_rank] = ctx
            _stats_final.pop(ctx.world_rank, None)
        elif prev is not None and \
                _stats_registry.get(prev.world_rank) is prev:
            # Guarded so a restart that already took the rank
            # (last-write-wins) isn't evicted by the old run's cleanup.
            _stats_registry.pop(prev.world_rank)
            row = _summarize_steps(prev)
            if row is not None:
                _stats_final[prev.world_rank] = (now_m, row)


def get_context() -> TrainContext:
    ctx = getattr(_local, "ctx", None)
    if ctx is None:
        raise RuntimeError("ray_tpu.train.get_context() called outside a train worker")
    return ctx


_train_metrics = None
_train_metrics_lock = threading.Lock()


def _get_train_metrics():
    """Lazy singletons: the gauges every report() updates. Created on the
    worker that actually trains, so the federated /metrics shows them under
    that worker's node_id (reference capability: the per-chip tokens/sec and
    MFU numbers papers headline — PAPERS.md Gemma-on-TPU — readable off one
    endpoint instead of living in code comments)."""
    global _train_metrics
    with _train_metrics_lock:
        if _train_metrics is None:
            from ray_tpu.util.metrics import Counter, Gauge

            _train_metrics = {
                "step_time": Gauge(
                    "train_step_time_s",
                    "seconds between consecutive session.report() calls "
                    "(the per-step wall time when reporting per step)",
                    tag_keys=("rank",)),
                "tokens_per_s": Gauge(
                    "train_tokens_per_s",
                    "training throughput: reported tokens / step time",
                    tag_keys=("rank",)),
                "mfu": Gauge(
                    "train_mfu",
                    "achieved model FLOPs utilization (0..1): reported "
                    "flops / step time / peak_flops",
                    tag_keys=("rank",)),
                "reports": Counter(
                    "train_reports_total", "session.report() calls",
                    tag_keys=("rank",)),
            }
        return _train_metrics


def _instrument_report(ctx: TrainContext, metrics: dict[str, Any]) -> None:
    """Derive step-time / tokens-per-sec / MFU gauges from a report.
    Recognized keys: ``tokens`` (or ``tokens_per_step``) per step, ``flops``
    (or ``flops_per_step``) per step, ``peak_flops`` (else the
    accelerators/flops.py registry: RTPU_PEAK_FLOPS override or the
    generation table keyed by the backend's device_kind), and direct
    ``tokens_per_s`` / ``mfu`` passthroughs. Goodput keys (all optional,
    seconds within this step): ``sync_time_s`` → collective_wait,
    ``compute_time_s`` → step_compute (remainder → idle),
    ``input_wait_s``, ``compile_time_s``, ``checkpoint_time_s``."""
    import time

    m = _get_train_metrics()
    rank = {"rank": str(ctx.world_rank)}
    m["reports"].inc(tags=rank)
    now = time.monotonic()
    last, ctx._last_report_ts = ctx._last_report_ts, now
    step_time = (now - last) if last else 0.0
    sync = metrics.get("sync_time_s")
    compute = metrics.get("compute_time_s")
    if step_time > 0:
        m["step_time"].set(step_time, tags=rank)
        # _report_lock: the telemetry flusher snapshots this window from
        # another thread, and list(deque) raises if an append lands
        # mid-iteration once the window is full.
        with ctx._report_lock:
            ctx._step_window.append((
                step_time,
                float(sync) if sync is not None else None,
                float(compute) if compute is not None else None,
            ))
            ctx._steps_total += 1
    if ctx._goodput is not None:
        # Close this report's ledger interval: explicit per-step keys
        # merge with seconds the hooks (compile listener, checkpoint
        # writer, replicate, input_wait) stamped since the last close.
        ctx._goodput.close_interval(parts={
            "collective_wait": sync,
            "step_compute": compute,
            "input_wait": metrics.get("input_wait_s"),
            "compile": metrics.get("compile_time_s"),
            "checkpoint": metrics.get("checkpoint_time_s"),
        })
    if "tokens_per_s" in metrics:
        m["tokens_per_s"].set(float(metrics["tokens_per_s"]), tags=rank)
    elif step_time > 0:
        tokens = metrics.get("tokens", metrics.get("tokens_per_step"))
        if tokens:
            m["tokens_per_s"].set(float(tokens) / step_time, tags=rank)
    if "mfu" in metrics:
        m["mfu"].set(float(metrics["mfu"]), tags=rank)
    elif step_time > 0:
        flops = metrics.get("flops", metrics.get("flops_per_step"))
        peak = metrics.get("peak_flops")
        if flops and not peak:
            from ray_tpu.accelerators.flops import resolve_peak_flops

            peak = resolve_peak_flops()
        if flops and peak:
            m["mfu"].set(float(flops) / step_time / float(peak), tags=rank)


def report(metrics: dict[str, Any], checkpoint: str | None = None) -> None:
    """Report metrics (and optionally a checkpoint directory the worker has
    already written) to the controller. Non-blocking; the controller collects
    reports when it polls. Also feeds the train gauges
    (train_step_time_s / train_tokens_per_s / train_mfu) so throughput is
    readable off /metrics, not just the report stream."""
    ctx = get_context()
    # The one piece of host work this library does between two steps: as
    # a phase it shows on a profiler's timeline between the step programs.
    with tracing.phase("train.report"):
        _maybe_chaos(ctx, metrics)
        try:
            _instrument_report(ctx, metrics)
        except Exception:
            pass  # metrics must never fail a training step
        with ctx._report_lock:
            # "ts" is the worker-stamped report instant: the controller
            # closes restart-downtime windows on it instead of its own
            # observation time, so poll/RPC delivery lag never inflates
            # the attribution.
            ctx._reports.append({"metrics": dict(metrics),
                                 "checkpoint": checkpoint,
                                 "ts": time.time()})


def _maybe_chaos(ctx: TrainContext, metrics: dict[str, Any]) -> None:
    """train.step fault-injection probe: every report() is a step boundary,
    so a scheduled worker/slice kill — or a delay rule, i.e. an injected
    straggler — lands here, mid-run, inside the target process. Attrs
    exposed to rule predicates: rank, slice, step, restart."""
    from ray_tpu.chaos import injector as _chaos

    if not _chaos.ACTIVE:
        return
    from ray_tpu.train.replica import slice_of

    _chaos.maybe_kill(
        "train.step",
        rank=ctx.world_rank,
        slice=slice_of(ctx.world_rank, ctx.world_size, ctx.num_slices),
        step=metrics.get("step", ctx._steps_total),
        restart=ctx.restart_count,
    )


def replicate(state: Any, step: int) -> bool:
    """Replicate this rank's training state to its buddy slice's
    :class:`~ray_tpu.train.replica.ReplicaStore` through the object plane.
    Cheap by construction: the state is snapshotted to host memory inline
    (donation-safe) and pushed from a background thread — the train step
    never waits on the wire. Honors the controller's ``replicate_every``
    cadence (CheckpointConfig.replicate_every; steps off-cadence are
    skipped). Under ZeRO-1 pass the optimizer/param shards this worker
    owns (e.g. ``spmd.replica_payload(state)``) — they are already 1/N of
    the run's state, so replication costs one buddy hop of the same bytes
    the DCN all-gather moves every step. Returns True when a push was
    queued."""
    ctx = get_context()
    rep = ctx.replica
    if not rep or int(rep.get("every", 0) or 0) <= 0:
        return False
    if int(step) % int(rep["every"]) != 0:
        return False
    if ctx._replica_writer is None:
        from ray_tpu.train.replica import ReplicaWriter

        ctx._replica_writer = ReplicaWriter(
            rep["run"], ctx.world_rank, ctx.world_size,
            int(rep.get("num_slices", ctx.num_slices)))
    # The push itself is async; only the inline host snapshot + queue
    # time is the step's replication cost — stamp it on the ledger.
    import time as _time

    t0 = _time.perf_counter()
    try:
        return ctx._replica_writer.put(state, step)
    finally:
        if ctx._goodput is not None:
            ctx._goodput.add_pending(
                "replication_push", _time.perf_counter() - t0)


def drain_reports(ctx: TrainContext) -> list[dict]:
    with ctx._report_lock:
        out, ctx._reports = ctx._reports, []
    return out


def collect_train_stats() -> dict:
    """Per-rank step-time/sync-time summaries for the head's straggler
    table, streamed with every telemetry push. Deciles are computed over
    the rolling window (p0..p100 inclusive, 11 values); sync/compute shares
    come from ``sync_time_s``/``compute_time_s`` keys passed to report()
    when the train loop measures them (None when it doesn't)."""
    import time as _time

    out: dict[str, dict] = {}
    now_m = _time.monotonic()
    with _stats_lock:
        _prune_final_locked(now_m)
        contexts = dict(_stats_registry)
        finals = {rank: row for rank, (_t0, row) in _stats_final.items()}
    for rank, ctx in contexts.items():
        row = _summarize_steps(ctx)
        if row is not None:
            out[str(rank)] = row
    for rank, row in finals.items():
        out.setdefault(str(rank), row)
    return out


def _summarize_steps(ctx: TrainContext) -> dict | None:
    """One rank's summary row from its rolling step window (None when the
    run never reported a timed step)."""
    import time as _time

    with ctx._report_lock:  # pairs with the append in _instrument_report
        window = list(ctx._step_window)
    if not window:
        return None
    ts = sorted(t for t, _, _ in window)
    n = len(ts)
    deciles = [ts[min(n - 1, round(q * (n - 1) / 10))]
               for q in range(11)]
    # Shares are ratios over only the steps that REPORTED the numerator
    # — a loop that instruments sync_time_s every Nth step must not get
    # its share diluted by the uninstrumented steps' time (which would
    # misattribute a collective-wait victim as compute-bound).
    syncs = [(t, s) for t, s, _ in window if s is not None]
    computes = [(t, c) for t, _, c in window if c is not None]

    def share(pairs):
        denom = sum(t for t, _ in pairs)
        return (sum(v for _, v in pairs) / denom) if denom else None

    total = sum(ts)
    row = {
        "world_size": ctx.world_size,
        "steps": ctx._steps_total,
        "mean_step_s": total / n,
        "median_step_s": deciles[5],
        "deciles": deciles,
        "sync_share": share(syncs),
        "compute_share": share(computes),
        "run": ctx.experiment_name,
        "ts": _time.time(),
    }
    # Goodput piggyback: the rank's cumulative ledger snapshot rides the
    # same row (no new RPC — the head's train-stats table carries it to
    # the GoodputStore rollup).
    if ctx._goodput is not None:
        try:
            row["goodput"] = ctx._goodput.snapshot()
        except Exception:  # noqa: BLE001 - accounting never breaks stats
            pass
    return row


def get_dataset_shard(name: str = "train"):
    """Module-level alias (reference: ray.train.get_dataset_shard)."""
    return get_context().get_dataset_shard(name)
