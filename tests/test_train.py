"""Train layer: trainer/controller/worker-group E2E, reports, checkpoints,
failure recovery. (Reference shapes: python/ray/train/v2/tests/.)"""

import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu.train import (
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
    get_context,
    report,
    restore_pytree,
    save_pytree,
)


def test_single_worker_report_flow(rt_start, tmp_path):
    def train_fn(config):
        ctx = get_context()
        for step in range(3):
            report({"step": step, "loss": 1.0 / (step + 1),
                    "rank": ctx.get_world_rank()})
        return "done"

    trainer = JaxTrainer(
        train_fn, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t1", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.ok, result.error
    assert result.metrics["step"] == 2
    assert len(result.metrics_history) == 3


def test_multi_worker_ddp_with_host_collective(rt_start, tmp_path):
    """BASELINE config 1 shape: 2-worker CPU data-parallel with allreduce
    gradient sync through the host collective backend."""

    def train_fn(config):
        import numpy as np

        import ray_tpu.collective as col

        ctx = get_context()
        rank, world = ctx.get_world_rank(), ctx.get_world_size()
        g = col.init_collective_group(world_size=world, rank=rank,
                                      backend="host", group_name="ddp")
        # toy quadratic: minimize |w - 3|^2 with per-worker data shards
        w = np.zeros(4, np.float32)
        losses = []
        for step in range(5):
            target = np.full(4, 3.0 + 0.1 * rank, np.float32)
            grad = 2 * (w - target)
            grad = g.allreduce(grad) / world  # DDP gradient average
            w -= 0.3 * grad
            losses.append(float(((w - 3.05) ** 2).sum()))
            report({"step": step, "loss": losses[-1]})
        return w.tolist()

    trainer = JaxTrainer(
        train_fn, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="ddp", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.ok, result.error
    # loss decreased and both workers converged to the same averaged target
    losses = [m["loss"] for m in result.metrics_history if m.get("step") == 4]
    assert all(l < 1.0 for l in losses)


def test_checkpoint_save_restore_roundtrip(tmp_path):
    import jax.numpy as jnp

    tree = {"w": jnp.arange(8, dtype=jnp.float32).reshape(2, 4),
            "opt": {"mu": jnp.ones((3,))}}
    d = save_pytree(tree, str(tmp_path / "ck1"), step=7)
    out = restore_pytree(d)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(tree["w"]))
    np.testing.assert_allclose(np.asarray(out["opt"]["mu"]), 1.0)


def test_checkpoint_reported_and_retained(rt_start, tmp_path):
    def train_fn(config):
        import numpy as np

        ctx = get_context()
        for step in range(4):
            ck = None
            if ctx.get_world_rank() == 0:
                ck_dir = os.path.join(ctx.storage_path, f"checkpoint_{step:08d}")
                os.makedirs(ck_dir, exist_ok=True)
                np.save(os.path.join(ck_dir, "w.npy"), np.full(2, step))
                ck = ck_dir
            report({"step": step}, checkpoint=ck)

    trainer = JaxTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="ckpt", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.ok, result.error
    assert result.checkpoint is not None
    w = np.load(os.path.join(result.checkpoint.path, "w.npy"))
    np.testing.assert_allclose(w, 3.0)


def test_failure_restart_from_checkpoint(rt_start, tmp_path):
    """Worker crashes once; FailurePolicy restarts the group, which resumes
    from the latest reported checkpoint (reference: failure_handling/)."""
    marker = str(tmp_path / "crashed_once")

    def train_fn(config):
        import numpy as np

        ctx = get_context()
        start = 0
        if ctx.get_checkpoint():
            start = int(np.load(os.path.join(ctx.get_checkpoint(), "step.npy"))) + 1
        for step in range(start, 4):
            if step == 2 and not os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                raise RuntimeError("transient failure at step 2")
            ck = None
            if ctx.get_world_rank() == 0:
                ck_dir = os.path.join(ctx.storage_path, f"ck_{step}_{ctx.restart_count}")
                os.makedirs(ck_dir, exist_ok=True)
                np.save(os.path.join(ck_dir, "step.npy"), np.array(step))
                ck = ck_dir
            report({"step": step, "restart": ctx.restart_count}, checkpoint=ck)

    trainer = JaxTrainer(
        train_fn, train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="recover", storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=2)),
    )
    result = trainer.fit()
    assert result.ok, result.error
    steps = [m["step"] for m in result.metrics_history]
    assert steps[-1] == 3
    # resumed (restart_count 1) from step 2, not from scratch
    restarts = [m["restart"] for m in result.metrics_history]
    assert max(restarts) == 1
    resumed_steps = [m["step"] for m in result.metrics_history if m["restart"] == 1]
    assert min(resumed_steps) == 2


def test_failure_budget_unified(rt_start, tmp_path):
    """max_failures is ONE budget: a run allowed 1 restart restarts exactly
    once, and the second failure ends the run with the structured per-rank
    error (regression: _poll_until_done used to track an undecremented
    failures_left while run() counted restart_count separately, so the
    budget-exhausted path lost the rank attribution)."""
    attempts = str(tmp_path / "attempts")
    os.makedirs(attempts, exist_ok=True)

    def train_fn(config):
        import os as _os

        from ray_tpu.train import get_context

        ctx = get_context()
        open(_os.path.join(config["attempts"],
                           f"a{ctx.restart_count}"), "w").close()
        raise RuntimeError(f"always fails (restart {ctx.restart_count})")

    trainer = JaxTrainer(
        train_fn, train_loop_config={"attempts": attempts},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="budget", storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=1)),
    )
    result = trainer.fit()
    assert not result.ok
    # exactly 2 attempts: the original + the single budgeted restart
    assert sorted(os.listdir(attempts)) == ["a0", "a1"]
    # the terminal error is the structured per-rank map, not a controller
    # traceback wrapper
    assert "rank 0" in result.error and "always fails" in result.error
    # the restart decision was recorded with its tier
    assert len(result.restarts) == 1
    assert result.restarts[0]["tier"] in ("checkpoint", "replica")
    assert result.restarts[0]["trigger"] == "worker_error"


def test_async_checkpoint_writer(tmp_path):
    """Write-behind checkpointing: save() returns before the write lands,
    the next save() barriers on the previous one, completed() releases
    directories only after their writes finished, and restore sees the
    LAST snapshot's values even though the tree mutated right after
    save() returned (donation-safety: the snapshot is taken inline)."""
    import jax.numpy as jnp

    from ray_tpu.train import AsyncCheckpointWriter

    writer = AsyncCheckpointWriter()
    tree = {"w": jnp.zeros(4), "step": jnp.int32(0)}
    d1 = writer.save(tree, str(tmp_path / "ck1"), step=1)
    # mutate immediately — the async write must hold the old snapshot
    tree = {"w": jnp.full(4, 9.0), "step": jnp.int32(2)}
    d2 = writer.save(tree, str(tmp_path / "ck2"), step=2)  # barriers on d1
    assert d1 in writer.completed()  # d1 finished before d2 started
    writer.wait()
    assert writer.completed() == [d2]
    r1 = restore_pytree(d1)
    np.testing.assert_allclose(np.asarray(r1["w"]), 0.0)
    r2 = restore_pytree(d2)
    np.testing.assert_allclose(np.asarray(r2["w"]), 9.0)
    # a completed directory carries the meta file (write-finished sentinel)
    from ray_tpu.train import Checkpoint

    assert Checkpoint(d2).metadata()["step"] == 2


def test_async_checkpoint_writer_surfaces_errors(tmp_path):
    from ray_tpu.train import AsyncCheckpointWriter

    writer = AsyncCheckpointWriter()
    blocked = tmp_path / "blocked"
    blocked.write_text("a file where the checkpoint dir should go")
    writer.save({"w": np.ones(2)}, str(blocked / "ck"), step=0)
    with pytest.raises(Exception):
        writer.wait()
    assert writer.completed() == []


def test_jax_train_on_virtual_mesh(rt_start, tmp_path):
    """Tiny llama step inside a train worker on the 8-device CPU mesh —
    the single-process SPMD shape of the TPU fine-tune workload."""

    def train_fn(config):
        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh
        from ray_tpu.parallel.sharding import shard_params
        from ray_tpu.models.llama import param_logical_axes

        cfg = LlamaConfig.tiny()
        params = init_params(cfg, jax.random.PRNGKey(0))
        mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
        params = shard_params(params, mesh, param_logical_axes(cfg))
        opt = optax.adamw(1e-2)
        opt_state = opt.init(params)

        @jax.jit
        def step(params, opt_state, tokens, targets):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(cfg, p, tokens, targets,
                                  attn_impl="blockwise"))(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        key = jax.random.PRNGKey(1)
        tokens = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
        targets = jnp.roll(tokens, -1, axis=1)
        losses = []
        for i in range(3):
            params, opt_state, loss = step(params, opt_state, tokens, targets)
            losses.append(float(loss))
            report({"step": i, "loss": losses[-1]})
        assert losses[-1] < losses[0]

    trainer = JaxTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="llama-tiny", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.ok, result.error
    assert result.metrics_history[-1]["loss"] < result.metrics_history[0]["loss"]


def test_spmd_train_step_factory(cpu_mesh_devices):
    import jax
    import numpy as np
    import optax

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train.spmd import make_llama_train_step

    cfg = LlamaConfig.tiny()
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2), cpu_mesh_devices)
    step_fn, init_state, shard = make_llama_train_step(
        cfg, mesh, optimizer=optax.adamw(1e-2), attn_impl="blockwise",
        remat=False)
    state = init_state()
    rng = np.random.default_rng(0)
    tokens = shard(rng.integers(0, cfg.vocab_size, (4, 32), dtype=np.int32))
    targets = shard(np.roll(np.asarray(tokens), -1, axis=1))
    state, m1 = step_fn(state, tokens, targets)
    state, m2 = step_fn(state, tokens, targets)
    assert float(m2["loss"]) < float(m1["loss"])
    assert int(state.step) == 2
    # params stayed sharded per rules
    from jax.sharding import PartitionSpec as P

    assert state.params["layers"]["wq"].sharding.spec == \
        P(None, ("fsdp",), "tp")


def test_elastic_restart_at_smaller_world_size(tmp_path):
    """Chaos: kill a node mid-run; the elastic policy resumes training at a
    smaller world size from the latest checkpoint (reference:
    scaling_policy/elastic.py:29 + failure_handling restart)."""
    import threading
    import time

    from ray_tpu.core.worker import global_worker
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.train.backend import JaxBackendConfig
    from ray_tpu.train.controller import TrainController
    from ray_tpu.utils import config as config_mod
    from ray_tpu.utils.ids import JobID

    os.environ["RTPU_HEALTH_CHECK_PERIOD_S"] = "0.2"
    config_mod.set_config(config_mod.Config.load())
    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=8, resources={"trainslot": 1.0})
    doomed = c.add_node(num_cpus=2, resources={"trainslot": 1.0})
    rt = c.connect()
    global_worker.runtime = rt
    global_worker.worker_id = rt.worker_id
    global_worker.node_id = rt.node_id
    global_worker.job_id = JobID.from_random()
    global_worker.mode = "cluster"
    try:
        progress = str(tmp_path / "progress")
        os.makedirs(progress, exist_ok=True)

        def train_fn(config):
            import os
            import time

            import numpy as np

            from ray_tpu.train import get_context, report

            ctx = get_context()
            start = 0
            if ctx.get_checkpoint():
                start = int(np.load(os.path.join(ctx.get_checkpoint(),
                                                 "step.npy"))) + 1
            for step in range(start, 6):
                time.sleep(0.4)
                ck = None
                if ctx.get_world_rank() == 0:
                    d = os.path.join(ctx.storage_path,
                                     f"ck_{step}_{ctx.restart_count}")
                    os.makedirs(d, exist_ok=True)
                    np.save(os.path.join(d, "step.npy"), np.array(step))
                    ck = d
                    open(os.path.join(config["progress"],
                                      f"step_{step}"), "w").close()
                report({"step": step, "world": ctx.get_world_size(),
                        "restart": ctx.restart_count}, checkpoint=ck)

        controller = TrainController(
            train_fn, {"progress": progress},
            ScalingConfig(num_workers=2, min_workers=1, max_workers=2,
                          resources_per_worker={"trainslot": 1.0,
                                                "CPU": 1.0}),
            RunConfig(name="elastic", storage_path=str(tmp_path),
                      failure_config=FailureConfig(max_failures=3)),
            JaxBackendConfig(distributed=False),
        )

        def chaos():
            # wait for training to reach step 2, then kill the second node
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if os.path.exists(os.path.join(progress, "step_2")):
                    break
                time.sleep(0.1)
            c.remove_node(doomed)

        killer = threading.Thread(target=chaos)
        killer.start()
        result = controller.run()
        killer.join()

        assert result.ok, result.error
        worlds = [(m["restart"], m["world"], m["step"])
                  for m in result.metrics_history]
        # started at world 2 ...
        assert any(w == 2 for _, w, _ in worlds)
        # ... and a later restart ran at world 1 (elastic downsize)
        downsized = [(r, w, s) for r, w, s in worlds if w == 1]
        assert downsized, f"never downsized: {worlds}"
        # resumed from checkpoint, not from scratch
        assert min(s for _, _, s in downsized) >= 2
        # and training finished
        assert max(s for _, _, s in worlds) == 5
    finally:
        rt.shutdown()
        c.shutdown()
        global_worker.runtime = None
        config_mod.set_config(config_mod.Config.load())


def test_checkpoint_restore_at_different_world_size(cpu_mesh_devices, tmp_path):
    """A checkpoint sharded over 8 devices restores onto a 4-device mesh
    (the elastic-downsize reload path — reference: restore-from-checkpoint
    at new world size, orbax resharded load)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh8 = build_mesh(MeshSpec(dp=8), cpu_mesh_devices[:8])
    x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh8, P("dp")))
    tree = {"w": x, "step": jnp.int32(5)}
    d = save_pytree(tree, str(tmp_path / "ck8"), step=5)

    mesh4 = build_mesh(MeshSpec(dp=4), cpu_mesh_devices[:4])
    template = {
        "w": jax.ShapeDtypeStruct((8, 8), jnp.float32,
                                  sharding=NamedSharding(mesh4, P("dp"))),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    restored = restore_pytree(d, template)
    assert restored["w"].sharding.mesh.devices.size == 4
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.arange(64.0).reshape(8, 8))
    assert int(restored["step"]) == 5


def test_trainer_dataset_ingest(tmp_path):
    """datasets= are streaming_split across the worker group and consumed
    via get_dataset_shard (reference: DataParallelTrainer datasets= +
    ray.train.get_dataset_shard; VERDICT M1 ingest wiring)."""
    import ray_tpu.data as rdata
    from ray_tpu.train import JaxTrainer
    from ray_tpu.train.config import RunConfig, ScalingConfig

    def loop(config):
        from ray_tpu.train import get_dataset_shard, session

        it = get_dataset_shard("train")
        seen = []
        for batch in it.iter_batches(batch_size=8):
            seen.extend(int(v) for v in batch["id"])
        session.report({"n": len(seen), "sum": sum(seen)})

    ray_tpu.init(num_cpus=4)
    try:
        ds = rdata.range(64, parallelism=8)
        trainer = JaxTrainer(
            loop, datasets={"train": ds},
            scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(name="ingest", storage_path=str(tmp_path)))
        result = trainer.fit()
        assert result.ok, result.error
        # both ranks together see every row exactly once
        reports = result.metrics_history
        assert sum(r["n"] for r in reports) == 64
        assert sum(r["sum"] for r in reports) == sum(range(64))
        # equal split: each worker got half
        assert {r["n"] for r in reports} == {32}
    finally:
        ray_tpu.shutdown()
