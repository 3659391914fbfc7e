"""Worker group: one actor per training worker.

Capability parity with the reference's WorkerGroup (reference:
python/ray/train/v2/_internal/execution/worker_group/worker_group.py:113 —
actors placed via placement group, train_fn runs on a thread inside each
actor (thread_runner.py), poll_status :609 aggregates worker states).

Recovery additions: ``poll_status`` distinguishes DEAD workers (actor
process gone — ActorDiedError on the poll) from application errors, per
rank, so the controller can attribute a failure to a worker/slice and pick
a restart tier; groups can be built from ``recycled`` pre-warmed spare
actors (hot-spare promotion: the fork+import seconds are already paid) via
``TrainWorker.reconfigure``.
"""

from __future__ import annotations

import threading
import traceback
from dataclasses import dataclass, field
from typing import Any, Callable

import ray_tpu
from ray_tpu.devtools.annotations import guarded_by
from ray_tpu.core.exceptions import GetTimeoutError
from ray_tpu.train.session import TrainContext, drain_reports, set_context
from ray_tpu.util import tracing


@guarded_by("_res_lock", "_result", "_error")
class TrainWorker:
    """Actor hosting one training worker; the user's train_fn runs on a
    dedicated thread so poll() stays responsive (max_concurrency=4)."""

    def __init__(self, rank: int, world_size: int, experiment: str,
                 storage_path: str | None, env: dict[str, str] | None = None):
        import os

        for k, v in (env or {}).items():
            os.environ[k] = v
        self.ctx = TrainContext(
            world_rank=rank, world_size=world_size, experiment_name=experiment,
            storage_path=storage_path, local_rank=0,
        )
        self._thread: threading.Thread | None = None
        self._status = "IDLE"  # IDLE | RUNNING | FINISHED | ERRORED
        # Result/context handoff train-fn thread -> actor-call thread
        # (rtlint R1): poll() must never see a half-published result.
        self._res_lock = threading.Lock()
        self._result: Any = None
        self._error: str | None = None

    def reconfigure(self, rank: int, world_size: int, experiment: str,
                    storage_path: str | None) -> bool:
        """Re-rank a pre-warmed spare (or a finished worker) into a new
        group: fresh context, clean status. The process — with its imported
        framework and warmed jax backend — is the asset being recycled."""
        if self._status == "RUNNING":
            raise RuntimeError("cannot reconfigure a running worker")
        old_writer = getattr(self.ctx, "_replica_writer", None)
        if old_writer is not None:
            try:
                old_writer.close()  # don't strand a push thread per restart
            except Exception:
                pass
        with self._res_lock:
            self.ctx = TrainContext(
                world_rank=rank, world_size=world_size,
                experiment_name=experiment,
                storage_path=storage_path, local_rank=0,
            )
            self._thread = None
            self._status = "IDLE"
            self._result = None
            self._error = None
        return True

    def setup_env(self, coordinator_addr: str | None, restart_count: int,
                  latest_checkpoint: str | None, num_slices: int = 1,
                  replica: dict | None = None):
        self.ctx.coordinator_addr = coordinator_addr
        self.ctx.restart_count = restart_count
        self.ctx.latest_checkpoint = latest_checkpoint
        self.ctx.num_slices = max(1, int(num_slices))
        self.ctx.replica = dict(replica) if replica else None
        return True

    def set_dataset_shards(self, shards: dict) -> bool:
        self.ctx.dataset_shards = dict(shards)
        return True

    def run(self, train_fn: Callable, config: dict | None) -> bool:
        if self._status == "RUNNING":
            raise RuntimeError("worker already running")
        self._status = "RUNNING"

        def main():
            import inspect

            tracing.name_thread()   # a profiler's line reads train-fn-<rank>
            set_context(self.ctx)
            try:
                if len(inspect.signature(train_fn).parameters) >= 1:
                    result = train_fn(config if config is not None else {})
                else:
                    result = train_fn()
                with self._res_lock:
                    self._result = result
                    self._status = "FINISHED"
            except BaseException:  # noqa: BLE001
                with self._res_lock:
                    self._error = traceback.format_exc()
                    self._status = "ERRORED"
            finally:
                set_context(None)

        self._thread = threading.Thread(target=main, daemon=True,
                                        name=f"train-fn-{self.ctx.world_rank}")
        self._thread.start()
        return True

    def poll(self) -> dict:
        return {
            "rank": self.ctx.world_rank,
            "status": self._status,
            "reports": drain_reports(self.ctx),
            "error": self._error,
        }

    def get_result(self):
        return self._result

    def ping(self) -> str:
        return "pong"

    def exec_fn(self, fn, *args, **kwargs):
        """Run an arbitrary function in this worker (backend setup hooks)."""
        return fn(*args, **kwargs)


@dataclass
class WorkerStatus:
    finished: bool = False
    errors: dict[int, str] = field(default_factory=dict)
    # rank -> death reason: the actor itself is gone (process killed, node
    # lost), as opposed to an error the train_fn raised and reported.
    dead: dict[int, str] = field(default_factory=dict)
    reports: list[dict] = field(default_factory=list)


def _actor_options(scaling) -> dict[str, Any]:
    res = scaling.worker_resources()
    opts: dict[str, Any] = {"max_concurrency": 4}
    opts["num_cpus"] = res.get("CPU", 0)
    opts["num_tpus"] = res.get("TPU", 0)
    extra = {k: v for k, v in res.items() if k not in ("CPU", "TPU")}
    if extra:
        opts["resources"] = extra
    return opts


def create_spare(scaling, experiment: str, storage_path: str | None,
                 env: dict[str, str] | None = None):
    """A hot-spare TrainWorker actor outside any group (rank -1): its
    process boots (framework + jax import — the seconds that dominate a
    cold restart) while training runs, and a later group recycles it via
    reconfigure()."""
    WorkerActor = ray_tpu.remote(TrainWorker)
    return WorkerActor.options(**_actor_options(scaling)).remote(
        -1, 0, experiment, storage_path, env)


class WorkerGroup:
    def __init__(self, scaling, experiment: str, storage_path: str | None,
                 env: dict[str, str] | None = None,
                 num_workers: int | None = None,
                 recycled: list | None = None):
        self.scaling = scaling
        n = num_workers if num_workers is not None else scaling.num_workers
        self.num_workers = n
        opts = _actor_options(scaling)
        WorkerActor = ray_tpu.remote(TrainWorker)
        spares = list(recycled or [])
        self.recycled_count = 0
        self.workers = []
        for rank in range(n):
            handle = None
            while spares and handle is None:
                cand = spares.pop(0)
                try:
                    ray_tpu.get([cand.reconfigure.remote(
                        rank, n, experiment, storage_path)], timeout=30)
                    handle = cand
                    self.recycled_count += 1
                except Exception:  # noqa: BLE001 - spare died while idle
                    try:
                        ray_tpu.kill(cand)
                    except Exception:
                        pass
            if handle is None:
                handle = WorkerActor.options(**opts).remote(
                    rank, n, experiment, storage_path, env)
            self.workers.append(handle)

    def setup(self, coordinator_addr: str | None, restart_count: int,
              latest_checkpoint: str | None, num_slices: int = 1,
              replica: dict | None = None):
        ray_tpu.get([
            w.setup_env.remote(coordinator_addr, restart_count,
                               latest_checkpoint, num_slices, replica)
            for w in self.workers
        ], timeout=120)

    def assign_dataset_shards(self, per_rank: list[dict]) -> None:
        """per_rank[i] = {name: DataIterator} for worker rank i."""
        ray_tpu.get([w.set_dataset_shards.remote(per_rank[i])
                     for i, w in enumerate(self.workers)], timeout=120)

    def run(self, train_fn: Callable, config: dict | None):
        ray_tpu.get([w.run.remote(train_fn, config) for w in self.workers],
                    timeout=120)

    def poll_status(self, timeout: float = 30.0) -> WorkerStatus:
        status = WorkerStatus()
        refs = [w.poll.remote() for w in self.workers]
        polls: list[dict | None] = []
        for rank, ref in enumerate(refs):
            try:
                polls.append(ray_tpu.get([ref], timeout=timeout)[0])
            except GetTimeoutError:
                raise  # poll stall is the caller's timeout, not a death
            except Exception as e:  # noqa: BLE001 - ActorDied/connection
                status.dead[rank] = f"{type(e).__name__}: {e}"
                polls.append(None)
        states = [p["status"] for p in polls if p is not None]
        for p in polls:
            if p is None:
                continue
            status.reports.extend(
                {**r, "rank": p["rank"]} for r in p["reports"])
            if p["error"]:
                status.errors[p["rank"]] = p["error"]
        status.finished = (not status.dead
                           and all(s == "FINISHED" for s in states))
        return status

    def results(self) -> list:
        return ray_tpu.get([w.get_result.remote() for w in self.workers],
                           timeout=120)

    def shutdown(self):
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass


class SparePool:
    """Controller-owned reserve of pre-warmed TrainWorker actors. fill()
    creates them without blocking (actor creation is async; each spare's
    process boots in the background and we fire a ping to force the spawn);
    take() hands alive spares to the next WorkerGroup, which promotes them
    via reconfigure()."""

    def __init__(self, scaling, experiment: str, storage_path: str | None,
                 size: int, env: dict[str, str] | None = None,
                 warmup: Callable | None = None):
        self.scaling = scaling
        self.experiment = experiment
        self.storage_path = storage_path
        self.size = max(0, int(size))
        self.env = env
        self.warmup = warmup
        self._spares: list = []

    def fill(self) -> None:
        while len(self._spares) < self.size:
            h = create_spare(self.scaling, self.experiment,
                             self.storage_path, self.env)
            if self.warmup is not None:
                # Run the user's warmup (imports, mesh, compile) in the
                # spare NOW, in the background — promotion later finds the
                # process hot. Result/errors discarded: a broken warmup
                # degrades promotion back to first-step cost, not failure.
                h.exec_fn.remote(self.warmup)
            else:
                h.ping.remote()  # force the process spawn; result discarded
            self._spares.append(h)

    def take(self, k: int) -> list:
        out, self._spares = self._spares[:k], self._spares[k:]
        return out

    def available(self) -> int:
        return len(self._spares)

    def shutdown(self) -> None:
        for h in self._spares:
            try:
                ray_tpu.kill(h)
            except Exception:  # noqa: BLE001
                pass
        self._spares.clear()
