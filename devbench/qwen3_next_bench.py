"""Qwen3-Next's serving programs at the shapes of
``qwen3-next-serve-longctx-32k`` (16 layers at the published widths: 12 Gated
DeltaNet and 4 gated attentions, 64 of 512 experts, 16 slots x 32,768):
compiled for a described v5e with no chip, and timed on one.

    python3 devbench/qwen3_next_bench.py aot        # no chip, about a minute
    QWEN3_NEXT_LAYERS=12 python3 devbench/qwen3_next_bench.py aot
    chiprun -- python3 devbench/qwen3_next_bench.py rule step
    chiprun -- python3 devbench/qwen3_next_bench.py margins

``aot``: ``llm/qwen3_next_serving.py``'s ``prefill_chunk(512)`` and
``decode_burst(8)``, compiled for ``v5e:2x2``'s first device (nothing runs:
no time comes out of it): XLA's ``memory_analysis`` (arguments,
temporaries, their sum against the chip's 15.75 GiB), the Mosaic calls, and
every instruction whose result has the shape of a cache leaf or of a
stacked leaf of the experts, by opcode. ``rule``: the gated delta rule
alone (``ops/gated_delta.py``) at the cell's shapes: the chunked form on 512
rows x 32 heads (16 key heads) from a carried state, three ways: the kernel
(what ``gated_delta_chunk`` is on a TPU), the jnp body it is held to
(``gated_delta_chunk_reference``) and the recurrence both stand in for, each
one's device time a call (32 calls inside one program, each from the state
the last left), its share of the yardstick and its largest difference from
the recurrence on outputs and on states, and the kernel again at 2, 4 and 16
value heads a grid step beside its own 8; then the step on 16 slots x
32 heads, a line of the stacked state leaf in place, the kernel beside the
jnp body it is held to; the yardsticks are ``adapters/qwen3_next.delta_rule_token_work`` and
``linear_step_bytes`` over the chip's peaks. ``step``: wall milliseconds
of one decode step inside a burst of 8 at 16 lines of 4,096, 12,288 and
30,720 live positions and of a prefill chunk of 512 against 0 to 30,720 cached rows (the clock
stops on a host read of the result). ``margins``: the serving programs in
bfloat16, teacher-forced, against ``benchmark/reference/qwen3_next.py`` on
the same weights, with the routed experts' output at zero and at the seeded
scale, and once with the rule's state rounded to bfloat16 after every chunk
and step and once with the router's weights in bfloat16 (what the
comparison must not pass). One JSON object a mode. The configuration is the
benchmark's file through its adapter. Run as a script, ``QWEN3_NEXT_LAYERS``
overrides the depth and ``QWEN3_NEXT_CASES`` names the rows of ``margins`` to
run; imported (tests/test_tpu_aot.py), the environment changes nothing.
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

from devbench.lfm2_bench import GIB, opcodes_with_shape  # noqa: E402
from devbench.longcat_bench import timed  # noqa: E402

SLOTS, MAX_SEQ, CHUNK = 16, 32768, 512
# ``rule``: calls of a form inside one timed program, and the value heads a
# grid step that the chunk kernel is timed at beside its own
# (``ops/gated_delta._heads_a_step``).
CALLS = 32
FIT = (2, 4, 16)
# The script's overrides (``__main__`` reads them from the environment).
LAYERS: int | None = None
CASES: list[str] | None = None


def config_json() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        c = json.load(f)
    if LAYERS is not None:
        c["num_hidden_layers"] = LAYERS
    return c


def config(max_seq: int = MAX_SEQ):
    from rtbench.adapters import qwen3_next as adapter

    return adapter.model_config(config_json(), "serve_longctx", max_seq)


def lowerings(cfg, params, cache, arg) -> dict:
    """{name: a function that lowers that program} at the cell's shapes."""
    import jax.numpy as jnp

    from ray_tpu.llm import qwen3_next_serving as serving

    return {
        "prefill_chunk(512)": lambda: serving.prefill_chunk.lower(
            cfg, params, cache, arg((CHUNK,)), arg(()), arg(()), arg(())),
        "decode_burst(8)": lambda: serving.decode_burst.lower(
            cfg, params, cache, arg((SLOTS,)), arg((SLOTS,)),
            arg((SLOTS,), jnp.bool_), arg((SLOTS,), jnp.float32),
            arg((SLOTS,), jnp.float32), arg((2,), jnp.uint32), 8, False)}


def big_shapes(cfg, slots: int = SLOTS, max_seq: int = MAX_SEQ) -> dict:
    """The shapes no instruction should produce but a parameter, a loop's
    tuple, a kernel's in-place operand or an update in place: the cache's
    leaves (the state's above all: 2 MiB a slot and layer) and the stacked
    experts."""
    L, h = cfg.num_layers, cfg.hidden_size
    E, fe = cfg.experts_held, cfg.moe_intermediate_size
    nv, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                  cfg.linear_value_head_dim)
    line = f"{slots},{cfg.num_kv_heads},{max_seq},{cfg.head_dim}]"
    return {"lines": f"bf16[{cfg.attention_lines},{line}",
            "state": f"f32[{cfg.linear_lines},{slots},{nv},{dk},{dv}]",
            "we_in": f"bf16[{L},{E},{h},{fe}]",
            "we_down": f"bf16[{L},{E},{fe},{h}]",
            "in_qkvz": f"bf16[{cfg.linear_lines},{h},"
                       f"{cfg.conv_dim + cfg.value_dim}]",
            "wq": f"bf16[{cfg.attention_lines},{h},"
                  f"{2 * cfg.num_heads * cfg.head_dim}]"}


def compile_programs(cfg, slots: int = SLOTS, max_seq: int = MAX_SEQ,
                     only: str | None = None) -> dict:
    """The programs (or the one named) compiled for a described v5e: {name:
    (memory analysis, HLO text, seconds)}. tests/test_tpu_aot.py reads the
    same."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.llm import qwen3_next_serving as serving
    from ray_tpu.models import qwen3_next
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

        params = place(jax.eval_shape(partial(qwen3_next.init_params, cfg),
                                      jax.random.PRNGKey(0)))
        cache = place(jax.eval_shape(partial(serving.init_cache, cfg, slots,
                                             max_seq)))
        for name, lower in lowerings(cfg, params, cache, arg).items():
            if only not in (None, name):
                continue
            t0 = time.monotonic()
            compiled = lower().compile()
            out[name] = (compiled.memory_analysis(), compiled.as_text(),
                         time.monotonic() - t0)
    return out


def aot() -> dict:
    cfg = config()
    out = {"mode": "aot", "layers": cfg.num_layers, "slots": SLOTS,
           "max_seq": MAX_SEQ, "params": cfg.num_params(), "programs": {}}
    for name, (mem, text, seconds) in compile_programs(cfg).items():
        out["programs"][name] = {
            "compile_s": round(seconds, 1),
            "arguments_gib": round(mem.argument_size_in_bytes / GIB, 3),
            "temporaries_gib": round(mem.temp_size_in_bytes / GIB, 3),
            "sum_gib": round((mem.argument_size_in_bytes
                              + mem.temp_size_in_bytes) / GIB, 3),
            "mosaic_calls": text.count(
                'custom_call_target="tpu_custom_call"'),
            "big": {k: opcodes_with_shape(text, s)
                    for k, s in big_shapes(cfg).items()}}
    return out


def _peaks():
    import jax

    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        return json.load(f)[jax.devices()[0].device_kind]


def step_seconds_a_line(a, lines: int, backend: str, key, reps: int):
    """Seconds a line of ``gated_delta_step`` on every line in turn of a
    stacked leaf of ``lines`` lines, as a decode program has it (the leaf
    donated, a line updated in place), under ``backend`` (``"mosaic"``: the
    kernel; ``"reference"``: the jnp body). ``a``: q, k, v, g, beta of
    every slot. devbench/ling_bench.py times its step with it too."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ray_tpu.ops import gated_delta as gd
    from ray_tpu.ops.kernels import force_kernel_backend

    def all_lines(state, *a):
        def body(line, carry):
            return gd.gated_delta_step(*a, carry[1], line)
        return lax.fori_loop(0, lines, body, (jnp.zeros(a[2].shape), state))

    state = jax.random.normal(key, (lines, *a[1].shape, a[2].shape[-1]))
    with force_kernel_backend(backend):
        fn = jax.jit(all_lines, donate_argnums=0)
        _, state = fn(state, *a)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(reps):
        _, state = fn(state, *a)
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / reps / lines


def rule() -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from rtbench.adapters import qwen3_next as adapter

    from ray_tpu.ops import gated_delta as gd

    cfg, cj, peaks = config(), config_json(), _peaks()
    nv, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                  cfg.linear_value_head_dim)
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    unit = lambda x: x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))  # noqa: E731

    def inputs(rows, key_heads):
        q = unit(jax.random.normal(ks[0], (rows, key_heads, dk))) * dk ** -0.5
        k = unit(jax.random.normal(ks[1], (rows, key_heads, dk)))
        v = jax.random.normal(ks[2], (rows, nv, dv))
        g = -jnp.exp(jax.random.uniform(ks[3], (rows, nv), minval=-7.0,
                                        maxval=-0.4))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, nv)))
        return q, k, v, g, beta

    work = adapter.delta_rule_token_work(cj)
    least_chunk = CHUNK * max(work["flops"] / peaks["bf16_flops_per_s"],
                              work["bytes"] / peaks["hbm_bytes_per_s"])
    out = {"mode": "rule", "device": jax.devices()[0].device_kind,
           "rows": CHUNK, "heads": nv, "slots": SLOTS,
           "chunk_least_us": round(least_chunk * 1e6, 2), "chunk": [],
           "step": []}
    # the chunk takes a key head once for its value heads, as the prefill
    # program hands them over; the step a key head a value head
    a = inputs(CHUNK, cfg.linear_num_key_heads)
    s0 = jax.random.normal(ks[5], (nv, dk, dv))
    want_o, want_s = jax.jit(gd.gated_delta_recurrence)(*a, s0)

    def chunk_row(name, form, **more):
        # Device time: ``CALLS`` calls in one program, each from the state
        # the last left, so the host dispatches once (a call a dispatch
        # read 0.40 ms for every form under 0.4: the host's time); the
        # carry reaches ``g`` and ``k`` (plus a number that flushes to
        # zero), so nothing of a call is the loop's invariant.
        def calls(q, k, v, g, beta, s):
            def body(_, carry):
                nought = carry[1][0, 0, 0] * 1e-38
                o, s1 = form(q, k + nought, v, g + nought, beta, carry[1])
                return carry[0] + o, s1
            return lax.fori_loop(0, CALLS, body, (jnp.zeros_like(v), s))

        fn = jax.jit(calls)
        sec = timed(lambda: fn(*a, s0), 5) / CALLS
        o, s1 = jax.jit(form)(*a, s0)
        out["chunk"].append({
            "form": name, **more, "ms": round(sec * 1e3, 4),
            "roofline_pct": round(100 * least_chunk / sec, 2),
            "max_err_o": float(jnp.abs(o - want_o).max()),
            "max_err_state": float(jnp.abs(s1 - want_s).max())})

    # ``gated_delta_chunk`` is the kernel on a TPU; the reference body is
    # the jnp form it is held to, the recurrence what both stand in for.
    chunk_row("kernel", gd.gated_delta_chunk,
              heads_a_step=gd._heads_a_step(nv))
    chunk_row("reference_body", gd.gated_delta_chunk_reference)
    chunk_row("recurrence", gd.gated_delta_recurrence)
    # The kernel's fit: value heads a grid step, each in the rule's place.
    rule_of_heads = gd._heads_a_step
    for heads in FIT:
        gd._heads_a_step = lambda h, n=heads: n
        jax.clear_caches()
        chunk_row("kernel", gd.gated_delta_chunk, heads_a_step=heads)
    gd._heads_a_step = rule_of_heads
    jax.clear_caches()
    # The step on every line of the stacked leaf in turn, as the decode
    # program has it (the leaf donated, a line updated in place), the
    # kernel beside the jnp body it is held to: both from this one call.
    lines = cfg.linear_lines
    b = inputs(SLOTS, nv)
    least_step = adapter.linear_step_bytes(cj, SLOTS) \
        / peaks["hbm_bytes_per_s"]
    for form, backend in (("kernel", "mosaic"), ("jnp", "reference")):
        sec = step_seconds_a_line(b, lines, backend, ks[6], 20)
        out["step"].append({
            "form": form, "states_a_step": gd.states_a_step(nv, dk, dv),
            "ms_per_line": round(sec * 1e3, 4),
            "least_us": round(least_step * 1e6, 2),
            "roofline_pct": round(100 * least_step / sec, 2)})
    return out


def _prefilled(cfg, params, cache, live: int, slots):
    """Every slot of ``slots`` prefilled with ``live`` tokens of its own."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import qwen3_next_serving as serving

    i32 = jnp.int32
    for slot in slots:
        ids = jax.random.randint(jax.random.PRNGKey(100 + slot), (live,),
                                 259, cfg.vocab_size, i32)
        for start in range(0, live, CHUNK):
            cache, logits, _ = serving.prefill_chunk(
                cfg, params, cache, ids[start:start + CHUNK], i32(start),
                i32(live), i32(slot))
    return cache, logits


def step() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import qwen3_next_serving as serving
    from ray_tpu.models import qwen3_next

    cfg = config()
    params = jax.jit(qwen3_next.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    cache = serving.init_cache(cfg, SLOTS, MAX_SEQ)
    i32 = jnp.int32
    out = {"mode": "step", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "decode_ms_per_step": {},
           "prefill_chunk_ms": {}}
    ids = jax.random.randint(jax.random.PRNGKey(7), (CHUNK,), 259,
                             cfg.vocab_size, i32)
    for cached in (0, 4096, 12288, 30720):
        times = []
        for _ in range(4):
            t0 = time.monotonic()
            cache, logits, counts = serving.prefill_chunk(
                cfg, params, cache, ids, i32(cached), i32(cached + CHUNK),
                i32(0))
            np.asarray(logits[:1])
            times.append((time.monotonic() - t0) * 1e3)
        out["prefill_chunk_ms"][cached] = round(min(times[1:]), 2)
        out["prefill_counts"] = [int(n) for n in counts]
    temps = jnp.zeros((SLOTS,), jnp.float32)
    t0 = time.monotonic()
    cache, logits = _prefilled(cfg, params, cache, 2048, range(SLOTS))
    np.asarray(logits[:1])
    out["prefill_16_x_2048_s"] = round(time.monotonic() - t0, 2)
    tok = jax.random.randint(jax.random.PRNGKey(8), (SLOTS,), 259,
                             cfg.vocab_size, i32)
    # Past 2,048 the rows are what earlier calls left or zeros: the
    # kernel's time does not depend on their values, the states and the
    # router's counts are served ones.
    for live in (4096, 12288, 30720):
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


def margins() -> dict:
    """What a sound run's margin is made of, and what must not pass: the
    serving programs in bfloat16, a prompt of 2,048 in chunks of 512 and
    then 512 positions teacher-forced a token a step, against the float32
    reference on the same weights. Rows: the routed experts'
    down-projections at 0 and 1 times their seeded scale (rounding alone,
    then rounding and the tenth place's swaps); the seeded scale with the
    rule's state rounded to bfloat16 after every chunk and step; the seeded
    scale with the router's weights rounded to bfloat16. The number is a
    run's: the reference's top logit minus its logit of the program's top
    token, over the decoded positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from reference import qwen3_next as reference
    from rtbench.adapters import qwen3_next as adapter

    from ray_tpu.llm import qwen3_next_serving as serving
    from ray_tpu.models import qwen3_next

    cfg, cj = config(4096), config_json()
    i32 = jnp.int32
    prompt, steps = 2048, 512
    out = {"mode": "margins", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "prompt": prompt, "steps": steps,
           "rows": []}
    init = jax.jit(qwen3_next.init_params, static_argnums=0)
    scale = jax.jit(lambda a, s: (a.astype(jnp.float32) * s).astype(a.dtype),
                    donate_argnums=0)
    # bfloat16's 8 exponent and 7 mantissa bits by ``reduce_precision``,
    # which the compiler keeps: a cast down and back it removes on the TPU
    # (benchmark/control.to_fp8's finding; the first run of this mode read
    # the very same margins with and without it).
    low = jax.jit(lambda a: jax.lax.reduce_precision(
        a, exponent_bits=8, mantissa_bits=7))
    cases = (("experts_zero", 0.0, False, False),
             ("seeded", 1.0, False, False),
             ("state_bf16", 1.0, True, False),
             ("router_bf16", 1.0, False, True))
    if CASES is not None:
        cases = tuple(c for c in cases if c[0] in CASES)
    for seed in (11, 12):
        for name, factor, state_low, router_low in cases:
            params = init(cfg, jax.random.PRNGKey(seed))
            params["layers"]["we_down"] = scale(
                params["layers"]["we_down"], factor)
            # the reference keeps the float32 router whatever the program
            # is given
            weights = adapter.reference_weights(params)
            if router_low:
                params["layers"]["router"] = low(params["layers"]["router"])
            ids = jax.random.randint(jax.random.PRNGKey(100 + seed),
                                     (prompt + steps,), 259, cfg.vocab_size,
                                     i32)
            cache = serving.init_cache(cfg, 2, 4096)
            for start in range(0, prompt, CHUNK):
                cache, logits, _ = serving.prefill_chunk(
                    cfg, params, cache, ids[start:start + CHUNK], i32(start),
                    i32(prompt), i32(1))
                if state_low:
                    cache["state"] = low(cache["state"])
            picks = [int(np.asarray(logits).argmax())]
            write = jnp.array([False, True])
            host_ids = np.asarray(ids)
            for p in range(prompt, prompt + steps - 1):
                cache, logits, _ = serving.decode_step(
                    cfg, params, cache, jnp.array([0, host_ids[p]], i32),
                    jnp.array([0, p], i32), write)
                if state_low:
                    cache["state"] = low(cache["state"])
                picks.append(int(np.asarray(logits[1]).argmax()))
            del cache
            want = np.asarray(reference.logits(cj, weights, ids))[
                prompt - 1:-1]
            gaps = want.max(axis=1) - want[np.arange(len(picks)),
                                           np.asarray(picks)]
            out["rows"].append({
                "seed": seed, "case": name, "worst": float(gaps.max()),
                "p99": float(np.percentile(gaps, 99)),
                "mean": float(gaps.mean()),
                "over_0.2": int((gaps > 0.2).sum()),
                "swapped": int((gaps > 0).sum())})
            print(json.dumps(out["rows"][-1]), flush=True)
            del params, weights, want
    return out


MODES = {"aot": aot, "rule": rule, "step": step, "margins": margins}

if __name__ == "__main__":
    if "QWEN3_NEXT_LAYERS" in os.environ:
        LAYERS = int(os.environ["QWEN3_NEXT_LAYERS"])
    if "QWEN3_NEXT_CASES" in os.environ:
        CASES = os.environ["QWEN3_NEXT_CASES"].split(",")
    for mode in sys.argv[1:] or ["aot"]:
        print(json.dumps(MODES[mode]()), flush=True)
