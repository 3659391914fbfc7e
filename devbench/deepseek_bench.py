"""DeepSeek-V2's serving programs at the shapes of
``deepseek-v2-serve-longdoc-16k`` (one dense and seven routed layers at the
published widths, one group of 20 experts, 16 slots x 16,384): compiled for
a described v5e with no chip, and timed on one.

    python3 devbench/deepseek_bench.py aot        # no chip, about a minute
    DEEPSEEK_LAYERS=9 python3 devbench/deepseek_bench.py aot
    chiprun -- python3 devbench/deepseek_bench.py step parity
    chiprun -- python3 devbench/deepseek_bench.py prefill_attention
    chiprun -- python3 devbench/deepseek_bench.py mixed

``aot``: ``llm/deepseek_serving.py``'s ``prefill_chunk(512)``,
``decode_burst(8)`` and ``mixed_burst(8)``, compiled for ``v5e:2x2``'s first
device (nothing runs: no time comes out of it): XLA's ``memory_analysis``
(arguments, temporaries, their sum against the chip's 15.75 GiB), the
Mosaic calls, and every instruction whose result has the shape of the whole
cache or of a stacked leaf, by opcode. ``step``: wall milliseconds of one decode step
inside a burst of 8 at 16 lines of 4,096, 8,192 and 15,360 live positions
(every line prefilled with tokens of its own first, so the router sees
what a served step does), and of a prefill chunk of 512 against 0, 4,096,
8,192 and 15,360 cached rows (the clock stops on a host read of the
result). ``prefill_attention``:
``ops/latent_attention.latent_prefill_attention`` alone, a chunk of 512
against the same four cached lengths of one layer's line, the kernel and
the XLA reference, at 128 heads and at LongCat's 64: milliseconds a call,
TFLOP/s from ``adapters/deepseek.prefill_attention_flops`` (live rows
rounded up to the block of 512) and their share of the chip's peak.
``mixed``: one decode step that carries a chunk of 512
(``deepseek_serving._mixed_impl``, a jit of its own) against
``prefill_chunk`` and ``decode_step`` apart, the chunk against 4,096, 8,192
and 15,360 cached rows of slot 0 beside the 15 other lines at as many live
positions (16 slots as in the cell: a slot mid-prefill does not decode):
wall milliseconds a call, device milliseconds a call and each program's
parts from a device trace a length, and the routed layers' counts; the
whole, with each program's largest operations, goes to
``chiprun_out/deepseek_mixed.json``.
``parity``: the programs in bfloat16 against
``benchmark/reference/deepseek.py`` over a prompt of 1,024 in two chunks
and 16 decoded tokens, the number a run's ``correct`` compares (the
reference's top logit minus its logit of the program's token, worst over
the decoded positions) and the largest logit difference. One JSON object
a mode. The configuration is the benchmark's file through its adapter;
``DEEPSEEK_LAYERS`` overrides the depth.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)

from devbench.lfm2_bench import (  # noqa: E402
    GIB,
    opcodes_with_shape,
    program_times,
)

SLOTS, MAX_SEQ = 16, 16384


def config_json() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek-v2.json")) as f:
        c = json.load(f)
    if "DEEPSEEK_LAYERS" in os.environ:
        c["num_hidden_layers"] = int(os.environ["DEEPSEEK_LAYERS"])
    return c


def config():
    from rtbench.adapters import deepseek as adapter

    return adapter.model_config(config_json(), "serve_longdoc", MAX_SEQ)


def shapes(cfg, place):
    import jax

    from ray_tpu.llm import deepseek_serving as serving
    from ray_tpu.models import deepseek

    params = place(jax.eval_shape(partial(deepseek.init_params, cfg),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(partial(serving.init_cache, cfg, SLOTS,
                                         MAX_SEQ)))
    return params, cache


def lowerings(cfg, params, cache, arg) -> dict:
    """{name: a function that lowers that program} at the cell's shapes."""
    import jax.numpy as jnp

    from ray_tpu.llm import deepseek_serving as serving

    burst = (cfg, params, cache, arg((SLOTS,)), arg((SLOTS,)),
             arg((SLOTS,), jnp.bool_), arg((SLOTS,), jnp.float32),
             arg((SLOTS,), jnp.float32), arg((2,), jnp.uint32))
    riders = (arg((8, 512)), arg((8,)), arg((8,)), arg((8,)), arg(()))
    return {
        "prefill_chunk(512)": lambda: serving.prefill_chunk.lower(
            cfg, params, cache, arg((512,)), arg(()), arg(()), arg(())),
        "decode_burst(8)": lambda: serving.decode_burst.lower(
            *burst, 8, False),
        "mixed_burst(8)": lambda: serving.mixed_burst.lower(
            *burst, riders, 8, False)}


def big_shapes(cfg) -> dict:
    """The shapes no instruction should produce but a parameter, a loop's
    tuple, a kernel's in-place operand or an update in place: the cache, a
    layer or a slot of it, and each stacked leaf of the routed layers and
    of the attentions."""
    L, nm, h = cfg.num_layers, cfg.num_routed_layers, cfg.hidden_size
    E, fe, fs = (cfg.experts_held, cfg.moe_intermediate_size,
                 cfg.shared_width)
    row = f"{MAX_SEQ},{cfg.latent_row}]"
    return {"cache": f"bf16[{L},{SLOTS},{row}",
            "cache_layer": f"bf16[1,{SLOTS},{row}",
            "cache_slot": f"bf16[{L},1,{row}",
            "we_in": f"bf16[{nm},{E},{h},{fe}]",
            "we_down": f"bf16[{nm},{E},{fe},{h}]",
            "ws_in": f"bf16[{nm},{h},{fs}]",
            "ws_down": f"bf16[{nm},{fs},{h}]",
            "wq_b": f"bf16[{L},{cfg.q_lora_rank},"
                    f"{cfg.num_heads * cfg.qk_head_dim}]",
            "wkv_b": f"bf16[{L},{cfg.num_heads},{cfg.kv_lora_rank},"
                     f"{cfg.qk_nope_head_dim + cfg.v_head_dim}]",
            "wo": f"bf16[{L},{cfg.num_heads * cfg.v_head_dim},{h}]"}


def aot() -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.kernels import force_kernel_backend
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    devices = topologies.get_topology_desc("v5e:2x2", "tpu").devices
    cfg = config()
    out = {"mode": "aot", "layers": cfg.num_layers, "slots": SLOTS,
           "max_seq": MAX_SEQ, "params": cfg.num_params(), "programs": {}}
    with force_kernel_backend("mosaic", devices[0].device_kind):
        dev = NamedSharding(build_mesh(MeshSpec(), devices[:1]), P())

        def place(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=dev), tree)

        def arg(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

        params, cache = shapes(cfg, place)
        for name, lower in lowerings(cfg, params, cache, arg).items():
            t0 = time.monotonic()
            compiled = lower().compile()
            text = compiled.as_text()
            mem = compiled.memory_analysis()
            out["programs"][name] = {
                "compile_s": round(time.monotonic() - t0, 1),
                "arguments_gib": round(mem.argument_size_in_bytes / GIB, 3),
                "temporaries_gib": round(mem.temp_size_in_bytes / GIB, 3),
                "sum_gib": round((mem.argument_size_in_bytes
                                  + mem.temp_size_in_bytes) / GIB, 3),
                "mosaic_calls": text.count(
                    'custom_call_target="tpu_custom_call"'),
                "big": {k: opcodes_with_shape(text, s)
                        for k, s in big_shapes(cfg).items()}}
    return out


def _prefilled(cfg, params, cache, live: int, slots):
    """Every slot of ``slots`` prefilled with ``live`` tokens of its own."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import deepseek_serving as serving

    i32 = jnp.int32
    for slot in slots:
        ids = jax.random.randint(jax.random.PRNGKey(100 + slot), (live,),
                                 259, cfg.vocab_size, i32)
        for start in range(0, live, 512):
            cache, logits, _ = serving.prefill_chunk(
                cfg, params, cache, ids[start:start + 512], i32(start),
                i32(live), i32(slot))
    return cache, logits


def step() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import deepseek_serving as serving
    from ray_tpu.models import deepseek

    cfg = config()
    params = jax.jit(deepseek.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    cache = serving.init_cache(cfg, SLOTS, MAX_SEQ)
    i32 = jnp.int32
    out = {"mode": "step", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "decode_ms_per_step": {},
           "prefill_chunk_ms": {}}
    ids = jax.random.randint(jax.random.PRNGKey(7), (512,), 259,
                             cfg.vocab_size, i32)
    for cached in (0, 4096, 8192, 15360):
        times = []
        for _ in range(4):
            t0 = time.monotonic()
            cache, logits, counts = serving.prefill_chunk(
                cfg, params, cache, ids, i32(cached), i32(cached + 512),
                i32(0))
            np.asarray(logits[:1])
            times.append((time.monotonic() - t0) * 1e3)
        out["prefill_chunk_ms"][cached] = round(min(times[1:]), 2)
        out["prefill_counts"] = [int(n) for n in counts]
    temps = jnp.zeros((SLOTS,), jnp.float32)
    t0 = time.monotonic()
    cache, logits = _prefilled(cfg, params, cache, 4096, range(SLOTS))
    np.asarray(logits[:1])
    out["prefill_16_x_4096_s"] = round(time.monotonic() - t0, 2)
    tok = jax.random.randint(jax.random.PRNGKey(8), (SLOTS,), 259,
                             cfg.vocab_size, i32)
    # Past 4,096 the rows are what earlier calls left or zeros: the
    # kernel's time does not depend on their values, the router's counts
    # at 4,096 are the served ones.
    for live in (4096, 8192, 15360):
        times = []
        for _ in range(4):
            t0 = time.monotonic()
            cache, toks, counts = serving.decode_burst(
                cfg, params, cache, tok, jnp.full((SLOTS,), live, i32),
                jnp.ones((SLOTS,), bool), temps, temps + 1.0,
                jax.random.PRNGKey(1), 8, False)
            np.asarray(toks)
            times.append((time.monotonic() - t0) * 1e3 / 8)
        out["decode_ms_per_step"][live] = round(min(times[1:]), 2)
        out[f"decode_counts_{live}"] = [int(n) for n in counts]
    return out


def prefill_attention() -> dict:
    import jax
    import jax.numpy as jnp
    from rtbench.adapters import deepseek as adapter

    from devbench.longcat_bench import timed
    from ray_tpu.ops import latent_attention as la
    from ray_tpu.ops.kernels import force_kernel_backend

    cfg, cj = config(), config_json()
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peak = json.load(f)[jax.devices()[0].device_kind]["bf16_flops_per_s"]
    dt, i32, chunk = cfg.jnp_dtype, jnp.int32, 512
    rank, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                        cfg.qk_rope_head_dim, cfg.v_head_dim)
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    cache = jax.random.normal(keys[0], (2, 2, MAX_SEQ, cfg.latent_row), dt)
    block = la.latent_kv_block(MAX_SEQ, 512)
    out = {"mode": "prefill_attention",
           "device": jax.devices()[0].device_kind, "chunk": chunk,
           "block": block, "peak_tflops": peak / 1e12, "rows": []}
    for heads in (cfg.num_heads, 64):
        q_n = jax.random.normal(keys[1], (chunk, heads, dn), dt)
        q_r = jax.random.normal(keys[2], (chunk, heads, dr), dt)
        w_kb = jax.random.normal(keys[3], (rank, heads, dn), dt) * rank ** -.5
        w_vb = jax.random.normal(keys[4], (rank, heads, dv), dt) * rank ** -.5
        for backend in ("mosaic", "reference"):
            with force_kernel_backend(backend):
                op = jax.jit(partial(la.latent_prefill_attention,
                                     rope_dim=dr, sm_scale=cfg.sm_scale))
                for cached in (0, 4096, 8192, 15360):
                    scalars = (i32(1), i32(1), i32(cached),
                               i32(cached + chunk))
                    sec = timed(lambda: op(q_n, q_r, cache, w_kb, w_vb,
                                           *scalars), 10)
                    live = -(-(cached + chunk) // block) * block
                    flops = adapter.prefill_attention_flops(
                        {**cj, "num_attention_heads": heads}, chunk, live)
                    out["rows"].append({
                        "heads": heads, "backend": backend, "cached": cached,
                        "ms": round(sec * 1e3, 3),
                        "tflops": round(flops / sec / 1e12, 1),
                        "peak_share": round(flops / sec / peak, 3)})
    return out


def parity() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from reference import deepseek as reference
    from rtbench.adapters import deepseek as adapter

    from ray_tpu.llm import deepseek_serving as serving
    from ray_tpu.models import deepseek

    cfg, cj = config(), config_json()
    params = jax.jit(deepseek.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(3))
    slots, prompt, steps = 2, 1024, 16
    cache = serving.init_cache(cfg, slots, 2048)
    i32 = jnp.int32
    ids = jax.random.randint(jax.random.PRNGKey(4), (prompt,), 259,
                             cfg.vocab_size, i32)
    for start in (0, 512):
        cache, logits, _ = serving.prefill_chunk(
            cfg, params, cache, ids[start:start + 512], i32(start),
            i32(prompt), i32(1))
    rows, seq = [np.asarray(logits)], [int(x) for x in np.asarray(ids)]
    write = jnp.array([False, True])
    for p in range(prompt, prompt + steps):
        seq.append(int(rows[-1].argmax()))
        cache, logits, _ = serving.decode_step(
            cfg, params, cache, jnp.array([0, seq[-1]], i32),
            jnp.array([0, p], i32), write)
        rows.append(np.asarray(logits[1]))
    del cache
    padded = seq + [0] * (-len(seq) % 512)
    want = np.asarray(reference.logits(
        cj, adapter.reference_weights(params),
        jnp.asarray(padded, i32)))[prompt - 1:prompt - 1 + len(rows)]
    got = np.stack(rows)
    chosen = want[np.arange(steps), np.asarray(seq[prompt:prompt + steps])]
    return {"mode": "parity", "device": jax.devices()[0].device_kind,
            "layers": cfg.num_layers,
            "worst_margin": float((want[:steps].max(axis=1) - chosen).max()),
            "max_logit_diff": float(np.abs(got - want).max()),
            "logit_scale": float(np.abs(want).max())}


def margins() -> dict:
    """What a sound run's margin is made of: the serving programs in
    bfloat16, teacher-forced a token a step over 1,536 positions after a
    prompt of 512, against the float32 reference on the same weights, with
    the routed experts' down-projections at 2, 1 and 0 times their seeded
    scale (twice is models/lfm2.py's 1 / sqrt(2 x routed layers), the
    scale the first 13 runs of PR 45 were made at). The number is the run's: the reference's top logit minus its
    logit of the program's top token, over the decoded positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from reference import deepseek as reference
    from rtbench.adapters import deepseek as adapter

    from ray_tpu.llm import deepseek_serving as serving
    from ray_tpu.models import deepseek

    cfg, cj = config(), config_json()
    i32 = jnp.int32
    prompt, steps = 512, 1536
    out = {"mode": "margins", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "prompt": prompt, "steps": steps,
           "rows": []}
    init = jax.jit(deepseek.init_params, static_argnums=0)
    scale = jax.jit(lambda a, s: (a.astype(jnp.float32) * s).astype(a.dtype),
                    donate_argnums=0)
    for seed in (11, 12):
        for factor in (2.0, 1.0, 0.0):
            params = init(cfg, jax.random.PRNGKey(seed))
            params["layers"]["we_down"] = scale(
                params["layers"]["we_down"], factor)
            ids = jax.random.randint(jax.random.PRNGKey(100 + seed),
                                     (prompt + steps,), 259, cfg.vocab_size,
                                     i32)
            cache = serving.init_cache(cfg, 2, 2048)
            cache, logits, _ = serving.prefill_chunk(
                cfg, params, cache, ids[:prompt], i32(0), i32(prompt), i32(1))
            picks = [int(np.asarray(logits).argmax())]
            write = jnp.array([False, True])
            host_ids = np.asarray(ids)
            for p in range(prompt, prompt + steps - 1):
                cache, logits, _ = serving.decode_step(
                    cfg, params, cache, jnp.array([0, host_ids[p]], i32),
                    jnp.array([0, p], i32), write)
                picks.append(int(np.asarray(logits[1]).argmax()))
            del cache
            want = np.asarray(reference.logits(
                cj, adapter.reference_weights(params), ids))[prompt - 1:-1]
            gaps = want.max(axis=1) - want[np.arange(len(picks)),
                                           np.asarray(picks)]
            out["rows"].append({
                "seed": seed, "we_down_factor": factor,
                "worst": float(gaps.max()),
                "p99": float(np.percentile(gaps, 99)),
                "mean": float(gaps.mean()),
                "over_0.2": int((gaps > 0.2).sum()),
                "swapped": int((gaps > 0).sum())})
            print(json.dumps(out["rows"][-1]), flush=True)
            del params, want
    return out


def mixed(calls: int = 10, ops: int = 40) -> dict:
    import shutil

    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import deepseek_serving as serving
    from ray_tpu.models import deepseek

    cfg = config()
    params = jax.jit(deepseek.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    i32, rows = jnp.int32, 512
    cache, _ = _prefilled(cfg, params,
                          serving.init_cache(cfg, SLOTS, MAX_SEQ), 4096,
                          range(SLOTS))
    chunk = jax.random.randint(jax.random.PRNGKey(7), (rows,), 259,
                               cfg.vocab_size, i32)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (SLOTS,), 259,
                                cfg.vocab_size, i32)
    # The chunk's slot is 0 and does not decode; the others do.
    write = jnp.arange(SLOTS) >= 1

    # ``params`` is an argument: closed over, its 9.6 GB are captured as
    # constants at lowering (devbench/lfm2_bench.py's finding).
    @partial(jax.jit, static_argnums=0, donate_argnums=2)
    def mixed_step(cfg, params, cache, positions, kv_len, length):
        return serving._mixed_impl(cfg, params, cache, tokens, positions,
                                   write, chunk, kv_len, length, i32(0))

    out = {"mode": "mixed", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "rows": rows, "lines": SLOTS - 1,
           "calls": calls, "at": {}}
    for cached in (4096, 8192, 15360):
        positions = jnp.full((SLOTS,), cached, i32)
        kv_len, length = i32(cached), i32(cached + 2 * rows)
        programs = {
            "prefill_chunk": lambda c: serving.prefill_chunk(
                cfg, params, c, chunk, kv_len, length, i32(0)),
            "decode_step": lambda c: serving.decode_step(
                cfg, params, c, tokens, positions, write),
            "mixed_step": lambda c: mixed_step(cfg, params, c, positions,
                                               kv_len, length)}

        def run(name, cache):
            for _ in range(calls):
                cache, _, counts = programs[name](cache)
            return jax.block_until_ready(cache), counts

        row = out["at"][cached] = {"wall_ms": {}, "counts": {}}
        for name in programs:
            cache, counts = run(name, cache)              # compiles, warms
            t0 = time.monotonic()
            cache, counts = run(name, cache)
            row["wall_ms"][name] = round(
                (time.monotonic() - t0) * 1e3 / calls, 3)
            row["counts"][name] = [int(n) for n in counts]
        trace_dir = os.path.join(ROOT, ".chipwork",
                                 f"deepseek_mixed_{cached}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        for name in programs:
            cache, _ = run(name, cache)
        jax.profiler.stop_trace()
        row.update(program_times(trace_dir, programs, calls, ops))
        print(json.dumps({cached: {k: v for k, v in row.items()
                                   if k != "top_ops_ms"}}), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "deepseek_mixed.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return {k: v for k, v in out.items() if k != "at"}


MODES = {"aot": aot, "step": step, "prefill_attention": prefill_attention,
         "parity": parity, "margins": margins, "mixed": mixed}

if __name__ == "__main__":
    for mode in sys.argv[1:] or ["aot"]:
        print(json.dumps(MODES[mode]()), flush=True)
