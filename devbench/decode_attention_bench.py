"""The decode-attention kernels and the decode program, timed on the chip.

    chiprun -- python3 devbench/decode_attention_bench.py [kernel] [burst]
                                          [prefill_kernel] [prefill]

- ``kernel``: ``ops.decode_attention`` and ``kv_row_write`` alone over all
  layers of a stacked cache at the serving shapes of the benchmark (chat 32
  slots x 2,048, docqa 16 x 3,200, reason 32 x 3,072 with every slot busy,
  at Mistral-7B widths; Ouro's 192 lines of 8 x 768 at 16 KV heads and one
  query head each), each at a few block sizes: microseconds a call beside
  the live blocks, the dead blocks and the empty slots of that call, and
  three patterns at the cell's own block (no line, one block a line, every
  line whole) from which a block's exposed fetch, a live step's compute
  and a dead step's cost can be fitted; the bytes of the live K/V at 819
  GB/s; and the kernel's result against the jnp reference. The same file
  runs against an older tree laid in ``.parent/`` (copy it in).
- ``burst``: ``llama_serving.decode_burst(steps=8)`` as the engine calls it,
  random weights, ms a step, with the weight bytes' floor beside it.
- ``prefill_kernel``: ``ops.prefill_attention`` alone over all layers for a
  chunk of 512 at 0 / 1,024 / 2,560 cached rows, at a few tile sizes, and
  its result against the jnp reference.
- ``prefill``: ``llama_serving.prefill_chunk(512)`` as the engine calls it at
  the same cached rows (docqa's and reason's shapes), ms a chunk, with the time
  its matmuls need at the chip's peak beside it.

Prints one JSON object as its last line. Times are host clock around
``block_until_ready`` over repeated calls of one jitted program.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = 819e9  # TPU v5e, Google Cloud documentation
PEAK_FLOPS = 197e12      # bf16, same source

# Mistral-7B widths; the kernel's shapes below carry their own heads.
WIDTHS = dict(hidden_size=4096, intermediate_size=14336, num_heads=32,
              num_kv_heads=8, head_dim=128, vocab_size=32768)
# Per cell: layers as served (Ouro: cache lines, one a (pass, layer)),
# slots, max_seq, live lengths drawn from [lo, hi), share of busy slots,
# blocks to sweep, KV heads and query heads a KV head.
SHAPES = {"chat": (12, 32, 2048, (64, 900), 0.72, (128, 256, 512, 1024),
                   8, 4),
          "docqa": (16, 16, 3200, (1100, 3100), 0.8, (128, 640), 8, 4),
          "reason": (12, 32, 3072, (1024, 2900), 1.0, (128, 256, 512, 768),
                     8, 4),
          "ouro": (192, 8, 768, (200, 700), 1.0, (128, 256, 384, 768), 16, 1)}
BURST_SHAPES = ("chat", "docqa")
# The prefill side: layers as served, slots, max_seq; a chunk of 512 behind
# each of CACHED_ROWS (2,560 + 512 is the end of reason's line).
PREFILL_SHAPES = {"docqa": (16, 16, 3200), "reason": (12, 32, 3072)}
CHUNK, CACHED_ROWS = 512, (0, 1024, 2560)


def _time(fn, *args, reps: int = 20):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _lengths(rng, slots: int, lo: int, hi: int, busy: float):
    import numpy as np

    lens = rng.integers(lo, hi, size=slots).astype(np.int32)
    lens[rng.random(slots) > busy] = 0
    return lens


def _block_counts(lens, slots: int, s: int, block: int) -> dict:
    """What the terms of a call's time count (PERF.md section 6, PR 35):
    blocks that hold live positions, the other blocks of the rectangle
    slots x (max_seq // block), and slots with no live position."""
    live = int((-(-lens // block)).sum())
    return {"live_blocks": live, "dead_blocks": slots * (s // block) - live,
            "empty_slots": int((lens == 0).sum())}


def bench_kernel() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops.kernels import force_kernel_backend

    out = {}
    for name, (layers, slots, s, (lo, hi), busy, blocks, hkv,
               g) in SHAPES.items():
        d = WIDTHS["head_dim"]
        rng = np.random.default_rng(0)
        lens = _lengths(rng, slots, lo, hi, busy)
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(keys[0], (slots, hkv * g, 1, d), jnp.bfloat16)
        # One line's worth of random rows under every layer: the time does
        # not read the values, and 192 lines drawn at once do not fit.
        kc, vc = (jnp.tile(jax.random.normal(key, (1, slots, hkv, s, d),
                                             jnp.bfloat16),
                           (layers, 1, 1, 1, 1)) for key in keys[1:])
        default = da.decode_kv_block(s, d)
        live_bytes = int(lens.sum()) * layers * 2 * hkv * d * 2
        row = {"layers": layers, "slots": slots, "max_seq": s,
               "kv_heads": hkv, "group": g, "default_block": default,
               "live_positions": int(lens.sum()),
               "floor_ms": 1e3 * live_bytes / HBM_BYTES_PER_S}

        def all_layers(q, kc, vc, lens, pos, block):
            # A tree with the flat walk builds its plan once a step, like
            # the engine; the tree before it has none to build.
            plan = ({"plan": da.decode_plan(lens, block, s)}
                    if hasattr(da, "decode_plan") else {})

            def body(layer, q):
                return da.decode_attention(q, kc, vc, layer, lens, pos,
                                           block=block, **plan)
            return lax.fori_loop(0, layers, body, q)

        def us_a_call(lens, block):
            lens_d = jnp.asarray(lens)
            fn = jax.jit(partial(all_layers, block=block))
            return 1e6 * _time(fn, q, kc, vc, lens_d,
                               jnp.maximum(lens_d - 1, 0)) / layers

        # The cell's lengths at every block; then, at the block the cell
        # runs, three patterns that isolate a term each: no line at all,
        # one block a line, every line whole.
        for block in blocks:
            row[f"block{block}"] = {
                "us_a_call": us_a_call(lens, block),
                "block_us_at_hbm": 1e6 * 2 * hkv * block * d * 2
                / HBM_BYTES_PER_S,
                "read_positions": int(da.kv_positions_read(lens, block).sum()),
                **_block_counts(lens, slots, s, block)}
        for what, value in (("empty", 0), ("one_block", default),
                            ("whole", s)):
            pat = np.full(slots, value, np.int32)
            row[f"pattern_{what}"] = {
                "us_a_call": us_a_call(pat, default),
                **_block_counts(pat, slots, s, default)}
        lens_d = jnp.asarray(lens)
        pos = jnp.maximum(lens_d - 1, 0)
        got = jax.jit(da.decode_attention)(q, kc, vc, 3, lens_d, pos)
        with force_kernel_backend("reference"):
            want = jax.jit(da.decode_attention)(q, kc, vc, 3, lens_d, pos)
        row["max_abs_diff_vs_reference"] = float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - want.astype(jnp.float32))))

        for k_tok in (1, 5):
            nk = jax.random.normal(keys[0], (slots, hkv, k_tok, d),
                                   jnp.bfloat16)
            mask = lens_d > 0

            def write_all(kc, vc, nk, pos, mask):
                def body(layer, c):
                    return da.kv_row_write(c[0], c[1], nk, nk, layer, pos,
                                           mask)
                return lax.fori_loop(0, layers, body, (kc, vc))

            fn = jax.jit(write_all, donate_argnums=(0, 1))
            kc, vc = fn(kc, vc, nk, pos, mask)
            jax.block_until_ready(kc)
            t0 = time.perf_counter()
            for _ in range(10):
                kc, vc = fn(kc, vc, nk, pos, mask)
            jax.block_until_ready(kc)
            row[f"write_ms_k{k_tok}"] = 1e3 * (time.perf_counter() - t0) / 10
            with force_kernel_backend("reference"):
                wk, _ = jax.jit(da.kv_row_write)(
                    kc[:1], vc[:1], nk + 1, nk, 0, pos, mask)
            gk, _ = jax.jit(da.kv_row_write)(kc[:1], vc[:1], nk + 1, nk, 0,
                                             pos, mask)
            row[f"write_matches_reference_k{k_tok}"] = bool(
                jnp.array_equal(wk, gk))
        out[name] = row
        del kc, vc
    return out


def bench_burst() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import llama_serving
    from ray_tpu.models.llama import LlamaConfig, init_params

    out = {}
    for name in BURST_SHAPES:
        layers, slots, s, (lo, hi), busy = SHAPES[name][:5]
        cfg = LlamaConfig(
            num_layers=layers, max_seq_len=s, dtype="bfloat16",
            tie_embeddings=False, rope_theta=1e6, **WIDTHS)
        params = jax.jit(partial(init_params, cfg))(jax.random.PRNGKey(0))
        # as the engine places it: the programs read the fused leaf
        served = llama_serving.program_params(cfg, params)
        cache = llama_serving.init_kv_cache(cfg, slots, s)
        rng = np.random.default_rng(0)
        lens = _lengths(rng, slots, lo, hi, busy)
        write = jnp.asarray(lens > 0)
        pos = jnp.asarray(np.maximum(lens - 1, 0))
        tok = jnp.zeros((slots,), jnp.int32)
        temps = jnp.zeros((slots,), jnp.float32)
        top_ps = jnp.ones((slots,), jnp.float32)
        key = jax.random.PRNGKey(1)
        steps = 8

        def run(cache):
            return llama_serving.decode_burst(
                cfg, served, cache, tok, pos, write, temps, top_ps, key,
                steps, False)

        cache, toks = run(cache)
        jax.block_until_ready(toks)
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            cache, toks = run(cache)
        jax.block_until_ready(toks)
        ms_step = 1e3 * (time.perf_counter() - t0) / reps / steps
        weight_bytes = 2 * sum(
            int(np.prod(a.shape)) for a in jax.tree.leaves(params)
        ) - 2 * cfg.vocab_size * cfg.hidden_size  # the embedding is gathered
        kv_bytes = (int(lens.sum()) * layers * 2 * cfg.num_kv_heads
                    * cfg.head_dim * 2)
        out[name] = {
            "layers": layers, "slots": slots, "max_seq": s,
            "ms_per_step": ms_step,
            "floor_ms": 1e3 * (weight_bytes + kv_bytes) / HBM_BYTES_PER_S,
            "memory_peak_bytes": (jax.devices()[0].memory_stats() or {}).get(
                "peak_bytes_in_use")}
        del params, served, cache
    return out


def bench_prefill_kernel() -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ray_tpu.ops import prefill_attention as pa
    from ray_tpu.ops.decode_attention import decode_kv_block
    from ray_tpu.ops.kernels import force_kernel_backend

    out = {}
    hkv, d, h = WIDTHS["num_kv_heads"], WIDTHS["head_dim"], WIDTHS["num_heads"]
    for name, (layers, slots, s) in PREFILL_SHAPES.items():
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(keys[0], (h, CHUNK, d), jnp.bfloat16)
        kc = jax.random.normal(keys[1], (layers, slots, hkv, s, d),
                               jnp.bfloat16)
        vc = jax.random.normal(keys[2], (layers, slots, hkv, s, d),
                               jnp.bfloat16)
        blocks_k = [b for b in (128, 256, 512, 640) if s % b == 0]
        row = {"default_block_q": pa.prefill_q_block(CHUNK, h // hkv),
               "default_block_k": decode_kv_block(s, d)}

        def all_layers(q, kc, vc, kv_len, bq, bk):
            def body(layer, q):
                return pa.prefill_attention(q, kc, vc, layer, 3, kv_len,
                                            kv_len + CHUNK, block_q=bq,
                                            block_k=bk)
            return lax.fori_loop(0, layers, body, q)

        for bq in (128, 256, 512):
            for bk in blocks_k:
                fn = jax.jit(partial(all_layers, bq=bq, bk=bk))
                for kv_len in CACHED_ROWS:
                    row[f"attn_ms_q{bq}_k{bk}_at{kv_len}"] = 1e3 * _time(
                        fn, q, kc, vc, jnp.int32(kv_len), reps=10)
        for kv_len in CACHED_ROWS:
            # Causal: a query sees the cached rows and half the chunk.
            flops = 4 * h * CHUNK * (kv_len + CHUNK / 2) * d * layers
            row[f"floor_ms_at{kv_len}"] = 1e3 * flops / PEAK_FLOPS
        args = (q, kc, vc, 2, 3, 1061, 1061 + CHUNK - 7)
        got = jax.jit(pa.prefill_attention)(*args)
        with force_kernel_backend("reference"):
            want = jax.jit(pa.prefill_attention)(*args)
        row["max_abs_diff_vs_reference"] = float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - want.astype(jnp.float32))))
        out[name] = row
        del kc, vc
    return out


def bench_prefill() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import llama_serving
    from ray_tpu.models.llama import LlamaConfig, init_params

    out = {}
    for name, (layers, slots, s) in PREFILL_SHAPES.items():
        cfg = LlamaConfig(
            num_layers=layers, max_seq_len=s, dtype="bfloat16",
            tie_embeddings=False, rope_theta=1e6, **WIDTHS)
        params = jax.jit(partial(init_params, cfg))(jax.random.PRNGKey(0))
        # as the engine places it: the programs read the fused leaf
        served = llama_serving.program_params(cfg, params)
        cache = llama_serving.init_kv_cache(cfg, slots, s)
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=CHUNK).astype(np.int32))
        layer_params = sum(int(np.prod(a.shape))
                           for a in jax.tree.leaves(params["layers"]))
        row = {"layers": layers, "slots": slots, "max_seq": s,
               "matmul_floor_ms": 1e3 * 2 * CHUNK * layer_params / PEAK_FLOPS}
        for kv_len in CACHED_ROWS:
            def run(cache):
                return llama_serving.prefill_chunk(
                    cfg, served, cache, toks, jnp.int32(kv_len),
                    jnp.int32(kv_len + CHUNK), jnp.int32(3))

            cache, logits = run(cache)
            jax.block_until_ready(logits)
            t0 = time.perf_counter()
            reps = 10
            for _ in range(reps):
                cache, logits = run(cache)
            jax.block_until_ready(logits)
            row[f"chunk_ms_at{kv_len}"] = (
                1e3 * (time.perf_counter() - t0) / reps)
        row["memory_peak_bytes"] = (
            jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
        out[name] = row
        del params, served, cache
    return out


def main(argv: list[str]) -> int:
    import jax

    which = argv or ["kernel", "burst"]
    out = {"device_kind": jax.devices()[0].device_kind,
           "platform": jax.devices()[0].platform}
    if out["platform"] != "tpu":
        print(json.dumps({**out, "error": "needs a TPU"}))
        return 1
    if "kernel" in which:
        out["kernel"] = bench_kernel()
    if "burst" in which:
        out["burst"] = bench_burst()
    if "prefill_kernel" in which:
        out["prefill_kernel"] = bench_prefill_kernel()
    if "prefill" in which:
        out["prefill"] = bench_prefill()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
