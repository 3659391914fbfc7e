"""Benchmark: Llama causal-LM training-step throughput, tokens/sec/chip.

Prints exactly ONE JSON line:
  {"metric": ..., "value": ..., "unit": ..., "device": {...}, ...}

It measures on a TPU or not at all: when JAX finds no TPU, or when no
candidate yields a fresh measurement, it exits non-zero and prints no metric
line. Nothing is read from an earlier run's record.

Measurement strategy: the sweep is driven by the memory-model-guided
autotuner (ray_tpu/autotune) instead of a hand-enumerated candidate list.
The full config space (batch x remat — incl. per-layer save-lists — x
ZeRO-1 x grad accumulation x kernel block/chunk knobs) is priced by the
analytic HBM model; candidates predicted over the device budget are pruned
at analysis time (zero compile attempts spent on them), the survivors are
ranked, and the measurement budget goes to the best cached config first,
then the unexplored frontier. Measured rows record predicted-vs-actual HBM
(actual from the AOT module's memory_analysis / hlo_stats liveness estimate)
and persist in AUTOTUNE_CACHE.json (per-machine, gitignored) so a later run
on the same machine continues the search; the cache only orders the
candidates, and a number is printed only if it was measured in this run.
"""

from __future__ import annotations

import json
import os
import sys
import time

METRIC = "llama_1b_train_tokens_per_sec_per_chip"


def _emit(value: float, device, extra: dict) -> None:
    import jax

    rec = {"metric": METRIC, "value": round(value, 1),
           "unit": "tokens/sec/chip",
           "device": {"platform": device.platform,
                      "kind": device.device_kind,
                      "count": len(jax.devices())}}
    rec.update(extra)
    print(json.dumps(rec))


def _make_measure_fn(cfg, seq, steps, warmup):
    """One-candidate measurement closure for the autotune search driver:
    build the step under the candidate's kernel-env knobs, AOT-compile it
    (the compiled module's memory analysis is the 'actual' HBM the
    prediction is scored against), time the step, and clean up every live
    buffer so an OOM cannot poison the next candidate."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.parallel.hlo_stats import compiled_hbm_bytes
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train.optim import adamw_lowmem
    from ray_tpu.train.spmd import make_llama_train_step

    mesh = build_mesh(MeshSpec(dp=1), jax.devices()[:1])

    def measure(cand):
        state = compiled = None
        try:
            if cand.opt == "lowmem":
                opt = adamw_lowmem(3e-4, weight_decay=0.1)
            else:
                opt = optax.adamw(3e-4, weight_decay=0.1,
                                  mu_dtype=jnp.bfloat16)
            with cand.applied_env():
                step_fn, init_state, shard = make_llama_train_step(
                    cfg, mesh, optimizer=opt, attn_impl=cand.attn,
                    remat=cand.remat, **cand.step_options(),
                )
                state = init_state()
                rng = np.random.default_rng(0)
                tokens = shard(rng.integers(0, cfg.vocab_size,
                                            (cand.batch, seq),
                                            dtype=np.int32))
                targets = shard(np.roll(np.asarray(tokens), -1, axis=1))
                compiled = step_fn.lower(state, tokens, targets).compile()
            hbm, hbm_src = None, None
            try:
                hbm, hbm_src = compiled_hbm_bytes(compiled)
            except Exception:
                pass
            for _ in range(warmup):
                state, m = compiled(state, tokens, targets)
            jax.block_until_ready(m["loss"])
            t0 = time.perf_counter()
            for _ in range(steps):
                state, m = compiled(state, tokens, targets)
            jax.block_until_ready(m["loss"])
            dt = (time.perf_counter() - t0) / steps
            return {
                "tokens_per_sec": round(cand.batch * seq / dt, 1),
                "measured_hbm_gb": (round(hbm / (1 << 30), 3)
                                    if hbm else None),
                "hbm_source": hbm_src,
            }
        finally:
            # Drop every live buffer before the next candidate allocates —
            # a single OOM leaks ~9 GB of params/optimizer state otherwise.
            state = compiled = None  # noqa: F841
            for buf in jax.live_arrays():
                buf.delete()
            jax.clear_caches()

    return measure


def main() -> int:
    from ray_tpu.accelerators.tpu import require_tpu
    from ray_tpu.utils.compile_cache import ensure_compile_cache

    device = require_tpu("bench")
    ensure_compile_cache()

    from ray_tpu.models.llama import LlamaConfig

    # ~1.1B-param geometry (Llama-3.2-1B-like), bf16, remat.
    cfg = LlamaConfig(
        vocab_size=32128, hidden_size=2048, intermediate_size=8192,
        num_layers=16, num_heads=32, num_kv_heads=8, head_dim=64,
        max_seq_len=2048, tie_embeddings=True, dtype="bfloat16",
    )
    seq = 2048
    # Autotuned sweep (ray_tpu/autotune): the analytic HBM model prices
    # the full candidate space — batch x remat (incl. per-layer
    # save-lists) x zero1 x grad_accum x kernel block/chunk knobs — and
    # prunes over-budget configs before any compile (the r04 OOM rows
    # b16/attn, b8/dots, b4/dots+ are auto-pruned instead of hand-dropped).
    # The best cached config measures first; the rest of the measurement
    # budget explores the predicted frontier.
    from ray_tpu.autotune import (
        autotune_train_configs,
        candidate_space,
        device_hbm_budget_bytes,
    )
    from ray_tpu.autotune.search import AutotuneCache

    device_kind = device.device_kind
    cache = AutotuneCache()
    res = autotune_train_configs(
        cfg, seq, candidate_space(cfg.num_layers),
        hbm_budget_bytes=device_hbm_budget_bytes(),
        measure_fn=_make_measure_fn(cfg, seq, steps=10, warmup=2),
        max_measure=int(os.environ.get("RTPU_BENCH_MAX_MEASURE", "6")),
        cache=cache, device_kind=device_kind,
    )
    tok_per_sec, config, tried = res.tokens_per_sec, res.winner, \
        res.tried_rows()
    autotune_info = {"space": res.space_size, "pruned": res.pruned,
                     "measured": res.measured, "failed": res.failed,
                     "analysis_seconds": res.analysis_seconds}

    # "tokens_per_sec" lands on a trace row only when a FRESH measurement
    # succeeded (cached-only rows carry cached_tokens_per_sec).
    if tok_per_sec <= 0 or not any("tokens_per_sec" in r for r in tried):
        print("bench: no candidate yielded a fresh measurement:\n"
              + json.dumps({"tried": tried, "autotune": autotune_info},
                           indent=1), file=sys.stderr)
        return 1

    _emit(tok_per_sec, device, {"config": config, "tried": tried,
                                "autotune": autotune_info})
    return 0


if __name__ == "__main__":
    sys.exit(main())
