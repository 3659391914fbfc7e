"""Can the 1B train step run with remat='none' (no recompute) on one v5e?

If the full activation set fits in HBM the backward drops the recompute
entirely. Probes batch 2, 4 and 8.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.train.optim import adamw_lowmem
from ray_tpu.train.spmd import make_llama_train_step

cfg = LlamaConfig(
    vocab_size=32128, hidden_size=2048, intermediate_size=8192,
    num_layers=16, num_heads=32, num_kv_heads=8, head_dim=64,
    max_seq_len=2048, tie_embeddings=True, dtype="bfloat16",
)
SEQ = 2048
mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
rng = np.random.default_rng(0)

for batch, remat in [(2, "none"), (4, "none"), (8, "none")]:
    try:
        step_fn, init_state, shard = make_llama_train_step(
            cfg, mesh, optimizer=adamw_lowmem(3e-4, weight_decay=0.1),
            attn_impl="flash", remat=remat)
        state = init_state()
        tokens = shard(rng.integers(0, cfg.vocab_size, (batch, SEQ),
                                    dtype=np.int32))
        targets = shard(np.roll(np.asarray(tokens), -1, axis=1))
        for _ in range(2):
            state, m = step_fn(state, tokens, targets)
        float(m["loss"])
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(8):
                state, m = step_fn(state, tokens, targets)
            float(m["loss"])
            dt = (time.perf_counter() - t0) / 8
            if best is None or dt < best:
                best = dt
        tps = batch * SEQ / best
        print(f"b{batch}/{remat}: {best*1e3:.1f} ms/step  {tps:.0f} tok/s  "
              f"vs_baseline={6*cfg.num_params()*tps/1.59e14:.3f}", flush=True)
    except Exception as e:  # noqa: BLE001
        print(f"b{batch}/{remat} FAILED: {str(e)[:140]}", flush=True)
    finally:
        state = step_fn = None
        for buf in jax.live_arrays():
            buf.delete()
        jax.clear_caches()
