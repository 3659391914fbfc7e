"""The looped stack's serving programs at the shapes of ``ouro2.6b-serve-solve``
(Ouro-2.6B whole, 8 slots x 768): compiled for a described v5e with no chip,
and timed on one.

    python3 devbench/ouro_bench.py aot            # no chip, about 20 seconds
    chiprun -- python3 devbench/ouro_bench.py step

``aot``: ``llm/ouro_serving.py``'s ``prefill_chunk`` at every bucket the
cell's prompts reach and ``decode_burst(8)``, compiled for ``v5e:2x2``'s
first device (nothing runs: no time comes out of it): XLA's
``memory_analysis`` (arguments, temporaries, their sum against the chip's
15.75 GiB), the Mosaic calls, and every instruction whose result has the
shape of the whole cache or of a stacked weight, by opcode (a copy of one of
those is 9 GiB or 400 MB moved a program). ``step``: wall milliseconds of
one decode step inside a burst of 8 at 8 lines of 256, 448 and 640 live
positions, and of a prefill chunk of each bucket (the clock stops on a host
read of the result). One JSON object a mode.
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

SLOTS, MAX_SEQ = 8, 768
BUCKETS = (64, 128, 256, 512)
GIB = float(1 << 30)


def config():
    from ray_tpu.models.ouro import OuroConfig

    return OuroConfig(max_seq_len=MAX_SEQ)


def shapes(cfg, place):
    import jax

    from ray_tpu.llm import ouro_serving as serving
    from ray_tpu.models import ouro

    params = place(jax.eval_shape(partial(ouro.init_params, cfg),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(partial(serving.init_cache, cfg, SLOTS,
                                         MAX_SEQ)))
    return params, cache


def lowerings(cfg, params, cache, arg) -> dict:
    """{name: a function that lowers that program} at the cell's shapes."""
    import jax.numpy as jnp

    from ray_tpu.llm import ouro_serving as serving

    def chunk(b):
        return lambda: serving.prefill_chunk.lower(
            cfg, params, cache, arg((b,)), arg(()), arg(()), arg(()))

    out = {f"prefill_chunk({b})": chunk(b) for b in BUCKETS}
    out["decode_burst(8)"] = lambda: serving.decode_burst.lower(
        cfg, params, cache, arg((SLOTS,)), arg((SLOTS,)),
        arg((SLOTS,), jnp.bool_), arg((SLOTS,), jnp.float32),
        arg((SLOTS,), jnp.float32), arg((2,), jnp.uint32), 8, False)
    return out


def big_shapes(cfg) -> dict:
    """The shapes no instruction should produce: the cache, one pass's
    lines of it, and each stacked matrix."""
    L, h, i = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    line = f"{SLOTS},{cfg.num_kv_heads},{MAX_SEQ},{cfg.head_dim}]"
    return {"cache": f"[{cfg.cache_lines},{line}",
            "pass_lines": f"[{L},{line}", "line": f"[{line}",
            "w_attn": f"bf16[{L},{h},{cfg.num_heads * cfg.head_dim}]",
            "w_up": f"bf16[{L},{h},{i}]", "w_down": f"bf16[{L},{i},{h}]"}


def opcodes_with_shape(text: str, shape: str) -> dict:
    ops: collections.Counter = collections.Counter()
    for line in text.splitlines():
        head = line.split(" = ", 1)
        if len(head) == 2 and shape in head[1].split("(", 1)[0]:
            m = re.search(r"\s([a-z][a-z-]*)\(", " " + head[1])
            if m:
                ops[m.group(1)] += 1
    return dict(ops)


def aot() -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.kernels import force_kernel_backend
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    devices = topologies.get_topology_desc("v5e:2x2", "tpu").devices
    cfg = config()
    out = {"mode": "aot", "slots": SLOTS, "max_seq": MAX_SEQ, "programs": {}}
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


def step() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import ouro_serving as serving
    from ray_tpu.models import ouro

    cfg = config()
    params = jax.jit(ouro.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    cache = serving.init_cache(cfg, SLOTS, MAX_SEQ)
    i32 = jnp.int32
    out = {"mode": "step", "device": jax.devices()[0].device_kind,
           "decode_ms_per_step": {}, "prefill_chunk_ms": {}}
    for bucket in BUCKETS:
        times = []
        for _ in range(4):
            t0 = time.monotonic()
            cache, logits, _ = serving.prefill_chunk(
                cfg, params, cache, jnp.arange(bucket, dtype=i32) + 300,
                i32(0), i32(bucket), i32(0))
            np.asarray(logits[:1])
            times.append((time.monotonic() - t0) * 1e3)
        out["prefill_chunk_ms"][bucket] = round(min(times[1:]), 2)
    temps = jnp.zeros((SLOTS,), jnp.float32)
    for live in (256, 448, 640):
        times = []
        for _ in range(4):
            t0 = time.monotonic()
            cache, toks, counts = serving.decode_burst(
                cfg, params, cache, jnp.full((SLOTS,), 300, i32),
                jnp.full((SLOTS,), live, i32), jnp.ones((SLOTS,), bool),
                temps, temps + 1.0, jax.random.PRNGKey(1), 8, False)
            np.asarray(toks)
            times.append((time.monotonic() - t0) * 1e3 / 8)
        out["decode_ms_per_step"][live] = round(min(times[1:]), 2)
        out["counts"] = [int(n) for n in counts]
    return out


MODES = {"aot": aot, "step": step}

if __name__ == "__main__":
    for mode in sys.argv[1:] or ["aot"]:
        print(json.dumps(MODES[mode]()), flush=True)
