"""The dense Llama module's serving programs at the shapes of the Mistral-7B
serve cells (``mistral7b-serve-docqa``: 16 layers at the published widths,
16 slots x 3,200; ``-reason``: 12 layers, 32 x 3,072): compiled for a
described v5e with no chip, and timed on one.

    python3 devbench/llama_bench.py aot           # no chip, about a minute
    LLAMA_USE=serve_reason python3 devbench/llama_bench.py aot
    chiprun -- python3 devbench/llama_bench.py mixed
    chiprun -- python3 devbench/llama_bench.py step

``aot``: ``llm/llama_serving.py``'s ``prefill_chunk(512)``,
``decode_burst(8)`` and ``mixed_burst(8)``, compiled for ``v5e:2x2``'s first
device (nothing runs: no time comes out of it): XLA's ``memory_analysis``
(arguments, temporaries, their sum against the chip's 15.75 GiB), the
Mosaic calls, and every instruction whose result has the shape of the
stacked cache or of a stacked weight, by opcode. ``mixed``: one decode step
that carries a chunk of 512 (``llama_serving._mixed_impl``, a jit of its
own) against ``prefill_chunk(512)`` and a step of ``decode_burst(8)`` apart,
the chunk against 1,024, 2,048 and 3,072 cached rows of slot 0 (the last
clamped to the line's 3,200) beside the 15 other lines at as many live
positions (16 slots as in the cell: a slot mid-prefill does not decode):
wall milliseconds a call, device milliseconds a call and each program's
parts from a device trace a length; the whole, with each program's largest
operations, goes to ``chiprun_out/llama_mixed.json``. ``step``: a step of
``decode_burst(8)`` and ``prefill_chunk(512)`` alone at the three uses (one
where ``LLAMA_USE`` names it), the same numbers and each program's largest
operations, to ``chiprun_out/llama_step.json``: run from two trees in one
call (the older laid in ``.parent/``, this file copied into its
``devbench/``), it says what a change of the programs did to the step apart
from the schedule. Every mode hands the programs the tree as the engine
places it (``ServedModel.program_params``). The configuration is
the benchmark's file through its adapter; ``LLAMA_USE`` names the use whose
depth and cache it takes (``serve_docqa``, ``serve_reason``,
``serve_chat``).
"""

from __future__ import annotations

import dataclasses
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

# (slots, positions a line) of the cell that states each use; ``tiny`` is
# models/llama.py's test size, to rehearse a mode on the CPU.
CELLS = {"serve_docqa": (16, 3200), "serve_reason": (32, 3072),
         "serve_chat": (32, 2048), "tiny": (4, 1280)}
BURST, ROWS = 8, 512


def config(use: str | None = None):
    """(LlamaConfig, slots, max_seq) of ``use``'s cell."""
    from rtbench.adapters import llama as adapter

    use = use or os.environ.get("LLAMA_USE", "serve_docqa")
    slots, max_seq = CELLS[use]
    if use == "tiny":
        from ray_tpu.models.llama import LlamaConfig

        return (dataclasses.replace(LlamaConfig.tiny(), vocab_size=512,
                                    max_seq_len=max_seq, dtype="bfloat16"),
                slots, max_seq)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mistral-7b-v0.3.json")) as f:
        c = json.load(f)
    return adapter.model_config(c, use, max_seq), slots, max_seq


def shapes(cfg, slots: int, max_seq: int, place):
    """The tree as the engine hands it over (``program_params`` of
    ``init_params``' tree) and the cell's cache, as shapes."""
    import jax

    from ray_tpu.llm import llama_serving as serving
    from ray_tpu.models import llama

    params = place(jax.eval_shape(
        lambda key: served_tree(serving, cfg, llama.init_params(cfg, key)),
        jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(partial(serving.init_kv_cache, cfg, slots,
                                         max_seq)))
    return params, cache


def served_tree(serving, cfg, params):
    """``params`` as the engine places it. A tree laid in ``.parent/`` from
    before the contract had the entry is served as it is."""
    make = getattr(serving.SERVED, "program_params", None)
    return make(cfg, params) if make else params


def lowerings(cfg, params, cache, arg) -> dict:
    """{name: a function that lowers that program} at the cell's shapes."""
    import jax.numpy as jnp

    from ray_tpu.llm import llama_serving as serving

    slots = cache["k"].shape[1]
    burst = (cfg, params, cache, arg((slots,)), arg((slots,)),
             arg((slots,), jnp.bool_), arg((slots,), jnp.float32),
             arg((slots,), jnp.float32), arg((2,), jnp.uint32))
    riders = (arg((BURST, ROWS)), arg((BURST,)), arg((BURST,)),
              arg((BURST,)), arg(()))
    return {
        f"prefill_chunk({ROWS})": lambda: serving.prefill_chunk.lower(
            cfg, params, cache, arg((ROWS,)), arg(()), arg(()), arg(())),
        f"decode_burst({BURST})": lambda: serving.decode_burst.lower(
            *burst, BURST, False),
        f"mixed_burst({BURST})": lambda: serving.mixed_burst.lower(
            *burst, riders, BURST, False)}


def big_shapes(cfg, slots: int, max_seq: int) -> dict:
    """The shapes no instruction should produce but a parameter, a loop's
    tuple, a kernel's in-place operand or an update in place: the stacked
    cache, a layer of it, each stacked weight (``wv``'s is ``wk``'s) and a
    layer's slice of each projection (``<leaf>_layer``; a quarter of a
    stack, which a refetch from the fast memory comes in, is found by the
    opcode: ``slice-done``)."""
    L, h, f = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    nq, nkv = (n * cfg.head_dim for n in (cfg.num_heads, cfg.num_kv_heads))
    line = f"{slots},{cfg.num_kv_heads},{max_seq},{cfg.head_dim}]"
    big = {"cache": f"bf16[{L},{line}", "cache_layer": f"bf16[{line}",
           "w_gate": f"bf16[{L},{h},{f}]", "w_down": f"bf16[{L},{f},{h}]"}
    for leaf, (rows, cols) in {"wq": (h, nq), "wk": (h, nkv), "wo": (nq, h),
                               "wqkv": (h, nq + 2 * nkv)}.items():
        big[leaf] = f"bf16[{L},{rows},{cols}]"
        big[f"{leaf}_layer"] = f"bf16[1,{rows},{cols}]"
    return big


def aot() -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.kernels import force_kernel_backend
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    devices = topologies.get_topology_desc("v5e:2x2", "tpu").devices
    cfg, slots, max_seq = config()
    out = {"mode": "aot", "layers": cfg.num_layers, "slots": slots,
           "max_seq": max_seq, "programs": {}}
    with force_kernel_backend("mosaic", devices[0].device_kind):
        dev = NamedSharding(build_mesh(MeshSpec(), devices[:1]), P())

        def place(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=dev), tree)

        def arg(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

        params, cache = shapes(cfg, slots, max_seq, place)
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
                "big": {k: opcodes_with_shape(text, s) for k, s in
                        big_shapes(cfg, slots, max_seq).items()}}
    return out


def mixed_step_of(impl, name: str):
    """``impl`` (a ``_mixed_impl``) as a jit of its own under ``name`` (a
    trace is read by program name), the cache donated. ``params`` is an
    argument: closed over, the weights are captured as constants at
    lowering (devbench/lfm2_bench.py's finding)."""
    import jax

    def mixed_step(cfg, params, cache, tokens, positions, write, chunk,
                   kv_len, length, slot):
        return impl(cfg, params, cache, tokens, positions, write, chunk,
                    kv_len, length, slot)

    mixed_step.__name__ = name
    return jax.jit(mixed_step, static_argnums=0, donate_argnums=2)


def on_device(cfg, slots: int, max_seq: int):
    """(params, cache, chunk, tokens) of a cell on the chip: the tree as
    the engine places it, a zeroed cache, ``ROWS`` prompt ids and an id a
    slot."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import llama_serving as serving
    from ray_tpu.models import llama

    params = served_tree(serving, cfg, jax.jit(
        llama.init_params, static_argnums=0)(cfg, jax.random.PRNGKey(0)))
    cache = serving.init_kv_cache(cfg, slots, max_seq)
    chunk = jax.random.randint(jax.random.PRNGKey(7), (ROWS,), 259,
                               cfg.vocab_size, jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (slots,), 259,
                                cfg.vocab_size, jnp.int32)
    return params, cache, chunk, tokens


def timed(programs: dict, cache, calls: int, ops: int, trace: str):
    """(cache, row): each of ``programs`` ({name: cache -> cache}) warmed,
    then ``calls`` calls of it by the host's clock and as many under a
    device trace (``.chipwork/<trace>``): wall and device milliseconds a
    call, each program's parts and its ``ops`` largest operations; a
    ``decode_burst`` is a burst of ``BURST``, and ``decode_step`` its time
    over that."""
    import shutil

    import jax

    def run(name, cache):
        for _ in range(calls):
            cache = programs[name](cache)
        return jax.block_until_ready(cache)

    row = {"wall_ms": {}}
    for name in programs:
        cache = run(name, cache)                      # compiles, warms
        t0 = time.monotonic()
        cache = run(name, cache)
        row["wall_ms"][name] = round(
            (time.monotonic() - t0) * 1e3 / calls, 3)
    trace_dir = os.path.join(ROOT, ".chipwork", trace)
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for name in programs:
        cache = run(name, cache)
    jax.profiler.stop_trace()
    row.update(program_times(trace_dir, programs, calls, ops))
    for table in (row["wall_ms"], row["device_ms"]):
        if "decode_burst" in table:
            table["decode_step"] = round(table["decode_burst"] / BURST, 3)
    return cache, row


def mixed(calls: int = 10, ops: int = 40, impls: dict | None = None) -> dict:
    """``impls``: further mixed steps to time beside the module's, {name:
    a ``_mixed_impl``} (a variant tried on the chip; each is traced under
    the program name ``mixed_step_<name>``)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import llama_serving as serving

    cfg, slots, max_seq = config()
    params, cache, chunk, tokens = on_device(cfg, slots, max_seq)
    i32 = jnp.int32
    # The chunk's slot is 0 and does not decode; the others do.
    write = jnp.arange(slots) >= 1
    steps = {"mixed_step": serving._mixed_impl}
    for name, impl in (impls or {}).items():
        steps[f"mixed_step_{name}"] = impl
    jits = {name: mixed_step_of(impl, name) for name, impl in steps.items()}
    burst = (jnp.zeros((slots,)), jnp.ones((slots,)), jax.random.PRNGKey(0))

    out = {"mode": "mixed", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "slots": slots, "max_seq": max_seq,
           "rows": ROWS, "lines": slots - 1, "calls": calls, "at": {}}
    for cached in (1024, 2048, 3072):
        # The lines' rows up to their position and the chunk's slot's up to
        # ``kv_len`` are read, never what they hold: zeros cost what
        # prefilled rows cost. The last length leaves the chunk's line room
        # for its 512 rows (a rider of the cell starts at 2,560 at most).
        kv_len = i32(min(cached, max_seq - ROWS))
        length = kv_len + 2 * ROWS
        positions = jnp.full((slots,), min(cached, max_seq - BURST - 1), i32)
        programs = {
            "prefill_chunk": lambda c: serving.prefill_chunk(
                cfg, params, c, chunk, kv_len, length, i32(0))[0],
            # a burst of 8: its time a call is divided by 8 below
            "decode_burst": lambda c: serving.decode_burst(
                cfg, params, c, tokens, positions, write, *burst, BURST,
                False)[0]}
        for name, jitted in jits.items():
            programs[name] = partial(
                lambda c, f: f(cfg, params, c, tokens, positions, write,
                               chunk, kv_len, length, i32(0))[0], f=jitted)

        cache, row = timed(programs, cache, calls, ops,
                           f"llama_mixed_{cached}")
        out["at"][cached] = row
        dev = row["device_ms"]
        for name in jits:
            if {name, "prefill_chunk", "decode_step"} <= set(dev):
                row.setdefault("saved_ms", {})[name] = round(
                    dev["prefill_chunk"] + dev["decode_step"] - dev[name], 3)
        print(json.dumps({cached: {k: v for k, v in row.items()
                                   if k != "top_ops_ms"}}), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "llama_mixed.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return {k: v for k, v in out.items() if k != "at"}


def step(calls: int = 10, ops: int = 12) -> dict:
    """A step of ``decode_burst(8)`` with every line live at half its
    length, and ``prefill_chunk(512)`` against 1,024 cached rows of slot 0,
    at each use (``LLAMA_USE`` names one; all three without it), the tree as
    the engine places it: ``timed``'s row a use, printed and written to
    ``chiprun_out/llama_step.json``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import llama_serving as serving

    uses = ([os.environ["LLAMA_USE"]] if "LLAMA_USE" in os.environ
            else ["serve_chat", "serve_reason", "serve_docqa"])
    i32 = jnp.int32
    out = {"mode": "step", "device": jax.devices()[0].device_kind,
           "rows": ROWS, "burst": BURST, "calls": calls, "use": {}}
    for use in uses:
        cfg, slots, max_seq = config(use)
        params, cache, chunk, tokens = on_device(cfg, slots, max_seq)
        positions = jnp.full((slots,), max_seq // 2, i32)
        kv_len = i32(min(1024, max_seq - ROWS))
        burst = (jnp.ones((slots,), bool), jnp.zeros((slots,)),
                 jnp.ones((slots,)), jax.random.PRNGKey(0))
        programs = {
            "decode_burst": lambda c: serving.decode_burst(
                cfg, params, c, tokens, positions, *burst, BURST, False)[0],
            "prefill_chunk": lambda c: serving.prefill_chunk(
                cfg, params, c, chunk, kv_len, kv_len + 2 * ROWS,
                i32(0))[0]}
        cache, row = timed(programs, cache, calls, ops, f"llama_step_{use}")
        held = sum(a.nbytes for a in jax.tree.leaves((params, cache)))
        stats = jax.devices()[0].memory_stats() or {}
        # what the allocator counts beside the tree and the cache: whether a
        # program's temporaries are in ``peak_bytes_in_use`` (the
        # benchmark's ``memory_peak_bytes``); a process's peak, so the
        # first use's alone says it
        row["memory"] = {"tree_and_cache_bytes": held, **{
            k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}}
        out["use"][use] = {"layers": cfg.num_layers, "slots": slots,
                           "max_seq": max_seq, **row}
        print(json.dumps({use: {k: v for k, v in row.items()
                                if k != "top_ops_ms"}}), flush=True)
        del params, cache, programs
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "llama_step.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return {k: v for k, v in out.items() if k != "use"}


MODES = {"aot": aot, "mixed": mixed, "step": step}

if __name__ == "__main__":
    for mode in sys.argv[1:] or ["aot"]:
        print(json.dumps(MODES[mode]()), flush=True)
