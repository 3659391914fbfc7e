"""Minimal repro for the dots+ remat TPU compile failure."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.train.spmd import make_llama_train_step

remat = sys.argv[1] if len(sys.argv) > 1 else "dots+"
batch = int(sys.argv[2]) if len(sys.argv) > 2 else 4
layers = int(sys.argv[3]) if len(sys.argv) > 3 else 16
opt_name = sys.argv[4] if len(sys.argv) > 4 else "adamw"

cfg = LlamaConfig(
    vocab_size=32128, hidden_size=2048, intermediate_size=8192,
    num_layers=layers, num_heads=32, num_kv_heads=8, head_dim=64,
    max_seq_len=2048, tie_embeddings=True, dtype="bfloat16",
)
seq = 2048
mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])
if opt_name == "lowmem":
    from ray_tpu.train.optim import adamw_lowmem

    opt = adamw_lowmem(3e-4, weight_decay=0.1)
else:
    opt = optax.adamw(3e-4, weight_decay=0.1, mu_dtype=jnp.bfloat16)
step_fn, init_state, shard = make_llama_train_step(
    cfg, mesh, optimizer=opt, attn_impl="flash", remat=remat,
)
state = init_state()
rng = np.random.default_rng(0)
tokens = shard(rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32))
targets = shard(np.roll(np.asarray(tokens), -1, axis=1))
print("lowering...", flush=True)
lowered = step_fn.lower(state, tokens, targets)
print("compiling...", flush=True)
compiled = lowered.compile()
print("COMPILE OK", flush=True)
mem = compiled.memory_analysis()
print("peak bytes:", getattr(mem, "temp_size_in_bytes", None),
      getattr(mem, "argument_size_in_bytes", None), flush=True)
