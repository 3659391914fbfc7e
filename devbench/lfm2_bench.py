"""The LFM2-MoE serving programs at the shapes of ``lfm2-24b-serve-extract-8k``
(the first 10 layers of LFM2-24B-A2B with all 64 experts, 64 slots x 8,192):
compiled for a described v5e with no chip, and timed on one.

    python3 devbench/lfm2_bench.py aot            # no chip, about a minute
    chiprun -- python3 devbench/lfm2_bench.py step parity
    chiprun -- python3 devbench/lfm2_bench.py tile       # about 3 minutes
    chiprun -- python3 devbench/lfm2_bench.py mixed      # about 4 minutes

``aot``: ``llm/lfm2_serving.py``'s ``prefill_chunk`` at the buckets 16 and
512 and ``decode_burst(8)``, compiled for ``v5e:2x2``'s first device
(nothing runs: no time comes out of it): XLA's ``memory_analysis``
(arguments, temporaries, their sum against the chip's 15.75 GiB), the bytes
a cached position takes (the ``kv`` leaf over slots x positions), the
Mosaic calls, and every instruction whose result has the shape of the
cache, of the convolutions' state or of a stacked weight, by opcode (a copy
of one of those is up to 4.5 GiB moved a program). ``step``: wall
milliseconds of one decode step inside a burst of 8 at 64 lines of 1,024,
3,072 and 6,144 live positions, and of a prefill chunk of 512 at 0 and
4,096 cached rows (the clock stops on a host read of the result).
``parity``: the programs against ``models/lfm2.forward`` in bfloat16 and
both against the float32 reference over 1,024 tokens (prefill in chunks of
512, then 8 decode steps), as the reference's top logit minus its logit of
the program's top token. ``tile``: the fit behind ``models/routed.row_tile``.
Every row tile of ``routed.ROW_TILES`` put in the rule's place, at a prefill
chunk of 512 tokens and at a decode step of 64 lines: one routed layer
(``moe_block`` whole, and its two ``grouped_matmul`` calls alone on the same
plan) with tiles a call, experts touched and the share of the touched
experts' bytes at 819 GB/s in the two calls' time; then the whole
``prefill_chunk(512)`` and a step of ``decode_burst(8)``. ``mixed``: the fit
behind ``ServedModel.mixed_burst``. One step that carries a chunk
(``lfm2_serving._mixed_impl``: 512 chunk rows against 2,048 cached and 63
lines at 4,096 live rows through every layer as one array; other sizes by
``LFM2_MIXED="rows lines live cached"``) against ``prefill_chunk`` and
``decode_step`` apart on the same cache: wall milliseconds a call (calls
chained on the donated cache, the clock stopped on the last), and from one
device trace of the three programs each one's device milliseconds a call
with the share of its ``tracing.part`` scopes (``moe_experts``: the routed
layers' two kernel calls). The mixed step runs at the row tile
``routed.row_tile`` picks for its tokens and at the next smaller one, so that
what the tile costs 36 rows an expert is read beside it. One JSON object a
mode.
"""

from __future__ import annotations

import collections
import json
import os
import re
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

SLOTS, MAX_SEQ = 64, 8192
BUCKETS = (16, 512)
GIB = float(1 << 30)


def config_json() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-24b-a2b.json")) as f:
        return json.load(f)


def config(slots_seq: int = MAX_SEQ):
    from rtbench.adapters import lfm2 as adapter

    return adapter.model_config(config_json(), "serve_extract", slots_seq)


def shapes(cfg, place, slots: int = SLOTS):
    import jax

    from ray_tpu.llm import lfm2_serving as serving
    from ray_tpu.models import lfm2

    params = place(jax.eval_shape(partial(lfm2.init_params, cfg),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(partial(serving.init_cache, cfg, slots,
                                         MAX_SEQ)))
    return params, cache


def lowerings(cfg, params, cache, arg, slots: int = SLOTS) -> dict:
    """{name: a function that lowers that program} at the cell's shapes."""
    import jax.numpy as jnp

    from ray_tpu.llm import lfm2_serving as serving

    def chunk(b):
        return lambda: serving.prefill_chunk.lower(
            cfg, params, cache, arg((b,)), arg(()), arg(()), arg(()))

    out = {f"prefill_chunk({b})": chunk(b) for b in BUCKETS}
    burst = (cfg, params, cache, arg((slots,)), arg((slots,)),
             arg((slots,), jnp.bool_), arg((slots,), jnp.float32),
             arg((slots,), jnp.float32), arg((2,), jnp.uint32))
    out["decode_burst(8)"] = lambda: serving.decode_burst.lower(
        *burst, 8, False)
    riders = (arg((8, BUCKETS[-1])), arg((8,)), arg((8,)), arg((8,)), arg(()))
    out["mixed_burst(8)"] = lambda: serving.mixed_burst.lower(
        *burst, riders, 8, False)
    return out


def big_shapes(cfg, slots: int = SLOTS) -> dict:
    """The shapes no instruction should produce: the cache's two leaves,
    one line of each, and each stacked matrix."""
    h, f, fe = (cfg.hidden_size, cfg.intermediate_size,
                cfg.moe_intermediate_size)
    line = f"{slots},{cfg.num_kv_heads},{MAX_SEQ},{2 * cfg.head_dim}]"
    state = f"{slots},{(cfg.conv_L_cache - 1) * h}]"
    nm, e = cfg.num_routed_layers, cfg.experts_held
    return {"kv": f"bf16[{cfg.attention_lines},{line}",
            "kv_line": f"bf16[{line}",
            "kv_slot": f"bf16[1,{cfg.num_kv_heads},{MAX_SEQ},"
                       f"{2 * cfg.head_dim}]",
            "conv": f"bf16[{cfg.conv_lines},{state}",
            "experts_up": f"bf16[{nm},{e},{h},{fe}]",
            "experts_down": f"bf16[{nm},{e},{fe},{h}]",
            "experts_layer_up": f"bf16[{e},{h},{fe}]",
            "experts_layer_down": f"bf16[{e},{fe},{h}]",
            "conv_in": f"bf16[{cfg.conv_lines},{h},{3 * h}]",
            "conv_out": f"bf16[{cfg.conv_lines},{h},{h}]",
            "dense_up": f"bf16[{cfg.num_dense_layers},{h},{f}]",
            "dense_down": f"bf16[{cfg.num_dense_layers},{f},{h}]",
            "embed": f"bf16[{cfg.vocab_size},{h}]",
            "embed_f32": f"f32[{cfg.vocab_size},{h}]"}


def opcodes_with_shape(text: str, shape: str) -> dict:
    ops: collections.Counter = collections.Counter()
    for line in text.splitlines():
        head = line.split(" = ", 1)
        if len(head) == 2 and shape in head[1].split("(", 1)[0]:
            m = re.search(r"\s([a-z][a-z-]*)\(", " " + head[1])
            if m:
                ops[m.group(1)] += 1
    return dict(ops)


def aot(slots: int = SLOTS) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.kernels import force_kernel_backend
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    devices = topologies.get_topology_desc("v5e:2x2", "tpu").devices
    cfg = config()
    out = {"mode": "aot", "slots": slots, "max_seq": MAX_SEQ, "programs": {}}
    with force_kernel_backend("mosaic", devices[0].device_kind):
        dev = NamedSharding(build_mesh(MeshSpec(), devices[:1]), P())

        def place(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=dev), tree)

        def arg(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

        params, cache = shapes(cfg, place, slots)
        kv = cache["kv"]
        out["cached_position_bytes"] = (
            kv.size * kv.dtype.itemsize // (slots * MAX_SEQ))
        for name, lower in lowerings(cfg, params, cache, arg, slots).items():
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
                        for k, s in big_shapes(cfg, slots).items()}}
            if os.environ.get("LFM2_BENCH_HLO"):
                with open(os.path.join(os.environ["LFM2_BENCH_HLO"],
                                       name + ".hlo.txt"), "w") as f:
                    f.write(text)
    return out


def _programs():
    import jax

    from ray_tpu.llm import lfm2_serving as serving
    from ray_tpu.models import lfm2

    cfg = config()
    params = jax.jit(lfm2.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    return cfg, params, serving, serving.init_cache(cfg, SLOTS, MAX_SEQ)


def _time_chunk(serving, cfg, params, cache, kv_len: int = 0):
    """(cache, ms, counts) of ``prefill_chunk(512)`` against ``kv_len``
    cached rows: the best of three calls after a first that may compile."""
    import jax.numpy as jnp
    import numpy as np

    i32, times = jnp.int32, []
    for _ in range(4):
        t0 = time.monotonic()
        cache, logits, counts = serving.prefill_chunk(
            cfg, params, cache, jnp.arange(512, dtype=i32) + 300,
            i32(kv_len), i32(kv_len + 512), i32(0))
        np.asarray(logits[:1])
        times.append((time.monotonic() - t0) * 1e3)
    return cache, round(min(times[1:]), 2), counts


def _time_step(serving, cfg, params, cache, live: int):
    """(cache, ms a step, counts) of ``decode_burst(8)`` at every line
    ``live`` long, timed as ``_time_chunk`` does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    i32, times = jnp.int32, []
    temps = jnp.zeros((SLOTS,), jnp.float32)
    for _ in range(4):
        t0 = time.monotonic()
        cache, toks, counts = serving.decode_burst(
            cfg, params, cache, jnp.arange(SLOTS, dtype=i32) + 300,
            jnp.full((SLOTS,), live, i32), jnp.ones((SLOTS,), bool),
            temps, temps + 1.0, jax.random.PRNGKey(1), 8, False)
        np.asarray(toks)
        times.append((time.monotonic() - t0) * 1e3 / 8)
    return cache, round(min(times[1:]), 2), counts


def step() -> dict:
    import jax

    cfg, params, serving, cache = _programs()
    out = {"mode": "step", "device": jax.devices()[0].device_kind,
           "decode_ms_per_step": {}, "prefill_chunk_ms": {}}
    for kv_len in (0, 4096):
        cache, out["prefill_chunk_ms"][kv_len], counts = _time_chunk(
            serving, cfg, params, cache, kv_len)
        out["prefill_counts"] = [int(n) for n in counts]
    for live in (1024, 3072, 6144):
        cache, out["decode_ms_per_step"][live], counts = _time_step(
            serving, cfg, params, cache, live)
        out["decode_counts"] = [int(n) for n in counts]
    return out


def parity(tokens: int = 1024, seed: int = 7) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from reference import lfm2 as reference
    from rtbench import gen
    from rtbench.adapters import lfm2 as adapter

    from ray_tpu.models import lfm2

    cfg, params, serving, cache = _programs()
    cj = config_json()
    ids = np.asarray(gen.prompt_ids(seed, 1, tokens + 8, cj["vocab_size"]),
                     np.int32)
    want = np.asarray(reference.logits(
        cj, adapter.reference_weights(params), jnp.asarray(ids)))

    def margin(got, rows):
        pick = np.asarray(got).argmax(axis=-1)
        w = want[rows]
        return float((w.max(axis=1) - w[np.arange(len(pick)), pick]).max())

    fwd, _ = jax.jit(lfm2.forward, static_argnums=0)(cfg, params,
                                                     jnp.asarray(ids)[None])
    out = {"mode": "parity", "tokens": tokens,
           "forward_margin": margin(fwd[0], np.arange(tokens + 8))}
    slot, last = 3, []
    for a in range(0, tokens, 512):
        cache, logits, _ = serving.prefill_chunk(
            cfg, params, cache, jnp.asarray(ids[a:a + 512]), jnp.int32(a),
            jnp.int32(tokens), jnp.int32(slot))
        last.append(np.asarray(logits))
    out["prefill_margin"] = margin(last[-1][None], np.array([tokens - 1]))
    write = np.zeros(SLOTS, bool)
    write[slot] = True
    rows = []
    for p in range(tokens, tokens + 8):
        tok = np.zeros(SLOTS, np.int32)
        pos = np.zeros(SLOTS, np.int32)
        tok[slot], pos[slot] = ids[p], p
        cache, logits, _ = serving.decode_step(
            cfg, params, cache, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(write))
        rows.append(np.asarray(logits[slot]))
    out["decode_margin"] = margin(np.stack(rows),
                                  np.arange(tokens, tokens + 8))
    return out


def _ms_a_call(fn, calls: int = 20) -> float:
    """Wall milliseconds a call of ``calls`` dispatched back to back (the
    device runs them in order; the clock stops when the last is done)."""
    import jax

    jax.block_until_ready(fn())
    t0 = time.monotonic()
    for _ in range(calls - 1):
        fn()
    jax.block_until_ready(fn())
    return (time.monotonic() - t0) * 1e3 / calls


def tile() -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import routed
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    cfg, params, serving, cache = _programs()
    rule, layers = cfg.router_rule, params["layers"]
    expert_bytes = 3 * cfg.hidden_size * cfg.moe_intermediate_size * 2
    i32 = jnp.int32
    shapes_ = {"chunk(512)": 512, f"decode({SLOTS})": SLOTS}
    out = {"mode": "tile", "device": jax.devices()[0].device_kind,
           "rule_picks": {name: routed.row_tile(t, rule.topk, rule.outputs)
                          for name, t in shapes_.items()},
           "layer": {name: {} for name in shapes_}, "prefill_chunk_ms": {},
           "decode_ms_per_step": {}}
    rule_s_own = routed.row_tile
    try:
        for tm in routed.ROW_TILES:
            routed.row_tile = lambda *_, tm=tm: tm
            jax.clear_caches()
            for name, t in shapes_.items():
                u = jax.random.normal(jax.random.PRNGKey(t),
                                      (t, cfg.hidden_size), jnp.bfloat16)
                valid = jnp.ones((t,), bool)
                block = jax.jit(partial(routed.moe_block, rule))

                @jax.jit
                def plan(layers, u):
                    idx, _ = routed.route(
                        rule, layers["router"][1], layers["router_bias"][1],
                        u)
                    pick_of_row, _, tile_expert, n_live, sizes = \
                        routed.dispatch_plan(idx.reshape(-1).astype(i32),
                                             rule.held, tm)
                    return (u[jnp.maximum(pick_of_row, 0) // rule.topk],
                            tile_expert, n_live, (sizes > 0).sum())

                @jax.jit
                def kernels(layers, x_rows, tile_expert, n_live):
                    hidden = grouped_matmul(
                        x_rows, layers["we_gate"], 1, tile_expert, n_live,
                        tm=tm, w2=layers["we_up"])
                    return grouped_matmul(hidden, layers["we_down"], 1,
                                          tile_expert, n_live, tm=tm)

                x_rows, tile_expert, n_live, touched = plan(layers, u)
                kernels_ms = _ms_a_call(
                    lambda: kernels(layers, x_rows, tile_expert, n_live))
                layer_ms = _ms_a_call(
                    lambda: block(layers, i32(1), u, valid))
                floor_ms = int(touched) * expert_bytes / 819e9 * 1e3
                out["layer"][name][tm] = {
                    "moe_block_ms": round(layer_ms, 3),
                    "kernels_ms": round(kernels_ms, 3),
                    "tiles": int(n_live), "experts_touched": int(touched),
                    "rows_held": int(x_rows.shape[0]),
                    "bytes_share_pct": round(100 * floor_ms / kernels_ms, 1)}
            for key, timed in (
                    ("prefill_chunk_ms", _time_chunk),
                    ("decode_ms_per_step", partial(_time_step, live=3072))):
                cache, ms, counts = timed(serving, cfg, params, cache)
                out[key][tm] = {"ms": ms, "tiles_per_expert": round(
                    int(counts[5]) / int(counts[3]), 3)}
    finally:
        routed.row_tile = rule_s_own
        jax.clear_caches()
    return out


def program_times(trace_dir: str, names, calls: int, ops: int = 0) -> dict:
    """A device trace of ``calls`` calls of each jitted program in ``names``
    (found as ``jit_<name>``), reduced: device milliseconds a call, each
    program's parts in % of it and, where ``ops`` is given, its ``ops``
    largest operations as [operation, part, source line, calls a call of
    the program, milliseconds a call]. A trace that cannot be read leaves
    ``trace_error`` and the tables empty: the wall times stand alone."""
    from rtbench import trace_reduce, xplane_meta

    import trace_parts

    out = {"device_ms": {}, "part_share_pct": {}}
    if ops:
        out["top_ops_ms"] = {}
    try:
        tables = trace_parts.tables(xplane_meta.load(
            trace_reduce.find_xplane(trace_dir)), top=max(20, 10 * ops))
    except Exception as e:  # noqa: BLE001 - the wall times stand alone
        out["trace_error"] = repr(e)
        return out
    for program, parts in tables["programs"].items():
        name = program.removeprefix("jit_")
        if name not in names:
            continue
        total = sum(parts.values())
        out["device_ms"][name] = round(total * 1e3 / calls, 3)
        out["part_share_pct"][name] = {
            k: round(100 * v / total, 1) for k, v in
            sorted(parts.items(), key=lambda kv: -kv[1])}
        if ops:
            out["top_ops_ms"][name] = [
                [op["op"], op["part"], op["source"], op["count"] // calls,
                 round(op["self_s"] * 1e3 / calls, 3)]
                for op in tables["top_ops"] if op["program"] == program][:ops]
    return out


def mixed(calls: int = 10) -> dict:
    import shutil

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import routed

    rows, lines, live, cached = (
        int(n) for n in os.environ.get("LFM2_MIXED",
                                       "512 63 4096 2048").split())
    cfg, params, serving, cache = _programs()
    rule, i32 = cfg.router_rule, jnp.int32
    chunk = jnp.arange(rows, dtype=i32) + 300
    tokens = jnp.arange(SLOTS, dtype=i32) + 900
    # The chunk's slot is 0 and does not decode; the next ``lines`` do.
    write = (jnp.arange(SLOTS) >= 1) & (jnp.arange(SLOTS) <= lines)
    positions = jnp.full((SLOTS,), live, i32)
    kv_len, length = i32(cached), i32(cached + 2 * rows)
    rule_s_own = routed.row_tile
    rule_s_tile = rule_s_own(rows + SLOTS, rule.topk, rule.outputs)
    out = {"mode": "mixed", "device": jax.devices()[0].device_kind,
           "rows": rows, "lines": lines, "live": live, "cached": cached,
           "rule_picks": rule_s_tile, "calls": calls, "counts": {}}
    programs = {
        "prefill_chunk": lambda c: serving.prefill_chunk(
            cfg, params, c, chunk, kv_len, length, i32(0)),
        "decode_step": lambda c: serving.decode_step(
            cfg, params, c, tokens, positions, write)}
    for tm in [t for t in routed.ROW_TILES if t <= rule_s_tile][:-3:-1]:
        # A jit a tile under a name of its own (the trace is read by
        # program name), traced while the tile stands in the rule's place
        # for this program's token count alone. ``params`` is an argument:
        # closed over, 9.66 GB of constants are captured at lowering.
        def mixed_step(cfg, params, cache):
            return serving._mixed_impl(cfg, params, cache, tokens, positions,
                                       write, chunk, kv_len, length, i32(0))

        mixed_step.__name__ = f"mixed_step_tile{tm}"
        jitted = jax.jit(mixed_step, static_argnums=0, donate_argnums=2)
        routed.row_tile = lambda t, *a, tm=tm: (
            tm if t == rows + SLOTS else rule_s_own(t, *a))
        try:
            cache = jax.block_until_ready(jitted(cfg, params, cache))[0]
        finally:
            routed.row_tile = rule_s_own
        programs[mixed_step.__name__] = (
            lambda c, jitted=jitted: jitted(cfg, params, c))

    def run(name, cache):
        for _ in range(calls):
            cache, _, counts = programs[name](cache)
        return jax.block_until_ready(cache), counts

    out["wall_ms"] = {}
    for name in programs:
        cache, counts = run(name, cache)                  # compiles, warms
        t0 = time.monotonic()
        cache, counts = run(name, cache)
        out["wall_ms"][name] = round(
            (time.monotonic() - t0) * 1e3 / calls, 3)
        out["counts"][name] = [int(n) for n in counts]
    trace_dir = os.path.join(ROOT, ".chipwork", "lfm2_mixed")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for name in programs:
        cache, _ = run(name, cache)
    jax.profiler.stop_trace()
    out.update(program_times(trace_dir, programs, calls))
    return out


MODES = {"aot": aot, "step": step, "parity": parity, "tile": tile,
         "mixed": mixed}

if __name__ == "__main__":
    for mode in sys.argv[1:] or ["aot"]:
        print(json.dumps(MODES[mode]()), flush=True)
