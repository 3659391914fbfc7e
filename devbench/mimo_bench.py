"""MiMo-V2.5's serving programs at the shapes of ``mimo-v2.5-serve-mixed-32k``
(7 layers at the published widths, 16 of 256 experts held, an eighth of the
vocabulary, 24 slots x 32,768): compiled for a described v5e with no chip,
and timed on one.

    python3 devbench/mimo_bench.py aot          # no chip, about a minute
    chiprun -- python3 devbench/mimo_bench.py step
    chiprun -- python3 devbench/mimo_bench.py margins

``aot``: ``llm/mimo_serving.py``'s ``prefill_chunk(512)`` and
``decode_burst(8)``, compiled for ``v5e:2x2``'s first device (nothing runs:
no time comes out of it): XLA's ``memory_analysis`` (arguments,
temporaries, their sum against the chip's 15.75 GiB), the Mosaic calls, and
every instruction whose result has the shape of a cache leaf or of a
stacked weight, by opcode. ``step``: a decode step inside a burst of 8 at
24 lines of 2,048 / 8,192 / 30,720 live positions and a prefill chunk of
512 against 0 / 8,192 / 30,208 cached rows: wall milliseconds, and from a
device trace the device milliseconds a call with the programs' parts in %
(``window_attn`` apart from the full layers' ``attn``) and the largest
operations. ``MIMO_FULL_BLOCK`` (script only) overrides the full lines'
block of positions, to weigh it. ``margins``: the serving programs in
bfloat16, a prompt of 2,048 in chunks of 512 and then 512 positions
teacher-forced a token a step, against ``benchmark/reference/mimo.py`` on
the same weights; and the same picks against that reference with the sinks
left out, with a window of 127 and with one of 129 (what the comparison
should not pass: the number is a run's, the reference's top logit minus its
logit of the program's top token, worst over the decoded positions).
``reference``: seconds of the plain reference at lengths a run's check
meets. One JSON object a mode. The configuration is the benchmark's file
through its adapter.
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

from devbench.lfm2_bench import GIB, opcodes_with_shape, program_times  # noqa: E402

SLOTS, MAX_SEQ, CHUNK = 24, 32768, 512


def config_json() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2.5.json")) as f:
        return json.load(f)


def config(max_seq: int = MAX_SEQ):
    from rtbench.adapters import mimo as adapter

    return adapter.model_config(config_json(), "serve_mixed", max_seq)


def lowerings(cfg, params, cache, arg, slots: int = SLOTS) -> dict:
    """{name: a function that lowers that program} at the cell's shapes."""
    import jax.numpy as jnp

    from ray_tpu.llm import mimo_serving as serving

    return {
        "prefill_chunk(512)": lambda: serving.prefill_chunk.lower(
            cfg, params, cache, arg((CHUNK,)), arg(()), arg(()), arg(())),
        "decode_burst(8)": lambda: serving.decode_burst.lower(
            cfg, params, cache, arg((slots,)), arg((slots,)),
            arg((slots,), jnp.bool_), arg((slots,), jnp.float32),
            arg((slots,), jnp.float32), arg((2,), jnp.uint32), 8, False)}


def big_shapes(cfg, slots: int = SLOTS, max_seq: int = MAX_SEQ) -> dict:
    """The shapes no instruction should produce but a parameter, a loop's
    tuple, a kernel's in-place operand or an update in place: the cache's
    leaves and the large stacked weights."""
    h = cfg.hidden_size
    return {
        "kv": f"bf16[{cfg.full_lines},{slots},{cfg.num_kv_heads},{max_seq},"
              f"{cfg.kv_row}]",
        "ring": f"bf16[{cfg.window_lines},{slots},{cfg.swa_num_kv_heads},"
                f"{cfg.sliding_window},{cfg.kv_row}]",
        "wqkv_window": f"bf16[{cfg.window_lines},{h},{cfg.qkv_width(1)}]",
        "wqkv_full": f"bf16[{cfg.full_lines},{h},{cfg.qkv_width(0)}]",
        "wo": f"bf16[{cfg.num_layers},{cfg.num_heads * cfg.v_head_dim},{h}]",
        "we_gate": f"bf16[{cfg.num_routed_layers},{cfg.experts_held},{h},"
                   f"{cfg.moe_intermediate_size}]",
        "we_down": f"bf16[{cfg.num_routed_layers},{cfg.experts_held},"
                   f"{cfg.moe_intermediate_size},{h}]",
        "embed": f"bf16[{cfg.vocab_size},{h}]"}


def compile_programs(cfg, slots: int = SLOTS, max_seq: int = MAX_SEQ,
                     only: str | None = None) -> dict:
    """The programs (or the one named) compiled for a described v5e: {name:
    (memory analysis, HLO text, seconds)}. tests/test_tpu_aot.py reads the
    same."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.llm import mimo_serving as serving
    from ray_tpu.models import mimo
    from ray_tpu.ops.kernels import force_kernel_backend
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    devices = topologies.get_topology_desc("v5e:2x2", "tpu").devices
    out = {}
    with force_kernel_backend("mosaic", devices[0].device_kind):
        dev = NamedSharding(build_mesh(MeshSpec(), devices[:1]), P())

        def place(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=dev), tree)

        def arg(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

        params = place(jax.eval_shape(partial(mimo.init_params, cfg),
                                      jax.random.PRNGKey(0)))
        cache = place(jax.eval_shape(partial(serving.init_cache, cfg, slots,
                                             max_seq)))
        for name, lower in lowerings(cfg, params, cache, arg, slots).items():
            if only not in (None, name):
                continue
            t0 = time.monotonic()
            compiled = lower().compile()
            out[name] = (compiled.memory_analysis(), compiled.as_text(),
                         time.monotonic() - t0)
    return out


def aot() -> dict:
    cfg, slots = config(), SLOTS
    out = {"mode": "aot", "layers": cfg.num_layers, "slots": slots,
           "max_seq": MAX_SEQ, "params": cfg.num_params(), "programs": {}}
    for name, (mem, text, seconds) in compile_programs(cfg, slots).items():
        out["programs"][name] = {
            "compile_s": round(seconds, 1),
            "arguments_gib": round(mem.argument_size_in_bytes / GIB, 3),
            "temporaries_gib": round(mem.temp_size_in_bytes / GIB, 3),
            "sum_gib": round((mem.argument_size_in_bytes
                              + mem.temp_size_in_bytes) / GIB, 3),
            "mosaic_calls": text.count(
                'custom_call_target="tpu_custom_call"'),
            "big": {k: opcodes_with_shape(text, s)
                    for k, s in big_shapes(cfg, slots).items()}}
    return out


def step(calls: int = 4, ops: int = 24) -> dict:
    import shutil

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import mimo_serving as serving
    from ray_tpu.models import mimo

    cfg, slots = config(), SLOTS
    params = jax.jit(mimo.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    cache = serving.init_cache(cfg, slots, MAX_SEQ)
    i32 = jnp.int32
    out = {"mode": "step", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "slots": slots,
           "full_block": serving.full_kv_block(cfg, MAX_SEQ), "rows": []}
    ids = jax.random.randint(jax.random.PRNGKey(7), (CHUNK,), 259,
                             cfg.vocab_size, i32)
    tok = jax.random.randint(jax.random.PRNGKey(8), (slots,), 259,
                             cfg.vocab_size, i32)
    temps = jnp.zeros((slots,), jnp.float32)

    def chunk(cache, cached):
        cache, logits, counts = serving.prefill_chunk(
            cfg, params, cache, ids, i32(cached), i32(cached + CHUNK), i32(0))
        return cache, logits[:1], counts

    def burst(cache, live):
        # The rows are what earlier calls left or zeros: the kernels' time
        # does not depend on their values.
        cache, toks, counts = serving.decode_burst(
            cfg, params, cache, tok, jnp.full((slots,), live, i32),
            jnp.ones((slots,), bool), temps, temps + 1.0,
            jax.random.PRNGKey(1), 8, False)
        return cache, toks, counts

    for name, program, fn, sizes, per in (
            ("prefill_chunk", "prefill_chunk", chunk, (0, 8192, 30208), 1),
            ("decode_step", "decode_burst", burst, (2048, 8192, 30720), 8)):
        for size in sizes:
            cache, got, counts = fn(cache, size)          # warm
            np.asarray(got)
            times = []
            for _ in range(3):
                t0 = time.monotonic()
                cache, got, counts = fn(cache, size)
                np.asarray(got)
                times.append((time.monotonic() - t0) * 1e3 / per)
            row = {"program": name, "at": size,
                   "wall_ms": round(min(times), 3),
                   "counts": [int(n) for n in counts]}
            trace_dir = os.path.join(ROOT, ".chipwork", f"mimo_{name}_{size}")
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            for _ in range(calls):
                cache, got, counts = fn(cache, size)
            np.asarray(got)
            jax.profiler.stop_trace()
            traced = program_times(trace_dir, (program,), calls * per, ops)
            row["device_ms"] = traced["device_ms"].get(program)
            row["part_share_pct"] = traced["part_share_pct"].get(program)
            row["top_ops_ms"] = traced.get("top_ops_ms", {}).get(program)
            if "trace_error" in traced:
                row["trace_error"] = traced["trace_error"]
            out["rows"].append(row)
            print(json.dumps(row), flush=True)
    return out


def margins(seeds=(11, 12)) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from reference import mimo as reference
    from rtbench.adapters import mimo as adapter

    from ray_tpu.llm import mimo_serving as serving
    from ray_tpu.models import mimo

    cfg, cj = config(4096), config_json()
    i32 = jnp.int32
    prompt, steps = 2048, 512
    out = {"mode": "margins", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "prompt": prompt, "steps": steps,
           "rows": []}
    init = jax.jit(mimo.init_params, static_argnums=0)
    # The references the program's picks are held against: the
    # configuration's own, and three that are another model.
    references = {
        "seeded": cj,
        "no_sink": {**cj, "add_swa_attention_sink_bias": False},
        "window_127": {**cj, "sliding_window": 127},
        "window_129": {**cj, "sliding_window": 129}}
    for seed in seeds:
        params = init(cfg, jax.random.PRNGKey(seed))
        weights = adapter.reference_weights(params)
        ids = jax.random.randint(jax.random.PRNGKey(100 + seed),
                                 (prompt + steps,), 259, cfg.vocab_size, i32)
        cache = serving.init_cache(cfg, 2, 4096)
        for start in range(0, prompt, CHUNK):
            cache, logits, _ = serving.prefill_chunk(
                cfg, params, cache, ids[start:start + CHUNK], i32(start),
                i32(prompt), i32(1))
        picks = [int(np.asarray(logits).argmax())]
        write = jnp.array([False, True])
        host_ids = np.asarray(ids)
        for p in range(prompt, prompt + steps - 1):
            cache, logits, _ = serving.decode_step(
                cfg, params, cache, jnp.array([0, host_ids[p]], i32),
                jnp.array([0, p], i32), write)
            picks.append(int(np.asarray(logits[1]).argmax()))
        del cache
        for name, c in references.items():
            want = reference.logits(c, weights, ids)[prompt - 1:-1]
            gaps = want.max(axis=1) - want[np.arange(len(picks)),
                                           np.asarray(picks)]
            out["rows"].append({
                "seed": seed, "reference": name, "worst": float(gaps.max()),
                "p99": float(np.percentile(gaps, 99)),
                "mean": float(gaps.mean()),
                "swapped": int((gaps > 0).sum()),
                "logit_std": float(want.std())})
            print(json.dumps(out["rows"][-1]), flush=True)
        del params, weights, want
    return out


def reference_time() -> dict:
    """Seconds of ``benchmark/reference/mimo.logits`` at lengths a run's
    check meets (the first call of a length compiles)."""
    import jax
    import jax.numpy as jnp
    from reference import mimo as reference
    from rtbench.adapters import mimo as adapter

    from ray_tpu.models import mimo

    cfg, cj = config(4096), config_json()
    params = jax.jit(mimo.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(3))
    jax.block_until_ready(params)
    out = {"mode": "reference", "device": jax.devices()[0].device_kind,
           "calls": []}
    weights = adapter.reference_weights(params)
    for length in (2048, 8192, 8192, 32768):
        ids = jax.random.randint(jax.random.PRNGKey(length), (length,), 259,
                                 cfg.vocab_size, jnp.int32)
        t0 = time.monotonic()
        got = reference.logits(cj, weights, ids)
        stats = jax.devices()[0].memory_stats() or {}
        out["calls"].append({
            "length": length, "rows": len(got),
            "s": round(time.monotonic() - t0, 1),
            "peak_gib": round(stats.get("peak_bytes_in_use", 0) / GIB, 2)})
        print(json.dumps(out["calls"][-1]), flush=True)
    return out


MODES = {"aot": aot, "step": step, "margins": margins,
         "reference": reference_time}

if __name__ == "__main__":
    if "MIMO_FULL_BLOCK" in os.environ:
        from ray_tpu.llm import mimo_serving

        block = int(os.environ["MIMO_FULL_BLOCK"])
        mimo_serving.full_kv_block = lambda cfg, max_seq: block
    for mode in sys.argv[1:] or ["aot"]:
        print(json.dumps(MODES[mode]()), flush=True)
