"""Phi-4-mini-flash's serving programs at the shapes of
``phi4-mini-flash-serve-reason-12k`` (all 32 layers at the published widths,
the whole vocabulary, 64 slots x 12,288): compiled for a described v5e with
no chip, and timed on one.

    python3 devbench/phi4flash_bench.py aot         # no chip, about a minute
    chiprun -- python3 devbench/phi4flash_bench.py rule step
    chiprun -- python3 devbench/phi4flash_bench.py margins

``aot``: ``llm/phi4flash_serving.py``'s ``prefill_chunk(512)`` and
``decode_burst(8)``, compiled for ``v5e:2x2``'s first device (nothing runs:
no time comes out of it): XLA's ``memory_analysis`` (arguments,
temporaries, their sum against the chip's 15.75 GiB), the Mosaic calls, and
every instruction whose result has the shape of a cache leaf, by opcode.
``rule``: the selective scan alone (``ops/selective_scan.py``) at the cell's
shapes: the chunk form on 512 rows x 5,120 channels x 16 states from a
carried state, every implementation tried (the kernel at several unrolls,
an associative scan, the token-by-token recurrence in XLA), each one's
device time a call (several calls inside one program, each from the state
the last left), its share of the yardstick and its largest difference from
the recurrence; then the step on 64 slots reading one line of the stacked
state leaf in place; the yardsticks are ``adapters/phi4flash.ssm_token_work``
and ``ssm_step_bytes`` over the chip's peaks. ``step``: wall milliseconds of
one decode step inside a burst of 8 at 64 lines of 2,048 to 11,776 live
positions, and of a prefill chunk of 512 against 0 to 11,264 cached rows,
a chunk that skips the cross-decoder and one that is a prompt's last.
``margins``: the serving programs in bfloat16, teacher-forced, against
``benchmark/reference/phi4flash.py`` on the same weights, and once with the
scan's state rounded to bfloat16 after every chunk and step (what the
comparison should not pass). ``reference``: seconds of the plain reference
at the lengths a run's check meets, the first call of a length with its
compilations. One JSON object a mode. The configuration is
the benchmark's file through its adapter. Run as a script,
``PHI4FLASH_SLOTS`` overrides the slots and ``PHI4FLASH_CASES`` names the
rows of ``margins`` to run; imported (tests/test_tpu_aot.py), the
environment changes nothing.
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
from devbench.qwen3_next_bench import _peaks  # noqa: E402

SLOTS, MAX_SEQ, CHUNK = 64, 12288, 512
# ``rule``: calls of a form inside one timed program, and the tokens a trip
# of the kernel's loop it is timed at beside its own.
CALLS = 16
UNROLLS = (1, 4, 8)
CASES: list[str] | None = None


def config_json() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        return json.load(f)


def config(max_seq: int = MAX_SEQ):
    from rtbench.adapters import phi4flash as adapter

    return adapter.model_config(config_json(), "serve_reason", max_seq)


def lowerings(cfg, params, cache, arg, slots: int = SLOTS) -> dict:
    """{name: a function that lowers that program} at the cell's shapes."""
    import jax.numpy as jnp

    from ray_tpu.llm import phi4flash_serving as serving

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
    leaves."""
    pair = f"{slots},{cfg.kv_pairs},"
    return {"line": f"bf16[1,{pair}{max_seq},{cfg.pair_dim}]",
            "ring": f"bf16[{cfg.window_lines},{pair}{cfg.sliding_window},"
                    f"{cfg.pair_dim}]",
            "state": f"f32[{cfg.ssm_lines},{slots},{cfg.mamba_d_state},"
                     f"{cfg.d_inner}]",
            "embed": f"bf16[{cfg.vocab_size},{cfg.hidden_size}]",
            "w_gate": f"bf16[{cfg.num_layers},{cfg.hidden_size},"
                      f"{cfg.intermediate_size}]"}


def compile_programs(cfg, slots: int = SLOTS, max_seq: int = MAX_SEQ,
                     only: str | None = None) -> dict:
    """The programs (or the one named) compiled for a described v5e: {name:
    (memory analysis, HLO text, seconds)}. tests/test_tpu_aot.py reads the
    same."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.llm import phi4flash_serving as serving
    from ray_tpu.models import phi4flash
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

        params = place(jax.eval_shape(partial(phi4flash.init_params, cfg),
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


def scan_associative(x, dt, a, b, c, d, h0):
    """The chunk form as ``lax.associative_scan`` over the pairs (decay,
    input) of every (state, channel): one of the implementations ``rule``
    tries. [T, N, C] float32 twice in HBM."""
    import jax.numpy as jnp
    from jax import lax

    decay = jnp.exp(dt[:, None, :] * a[None])              # [T, N, C]
    inp = (dt * x)[:, None, :] * b[:, :, None]

    def combine(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    cum, acc = lax.associative_scan(combine, (decay, inp))
    h = cum * h0[None] + acc
    return (h * c[:, :, None]).sum(1) + d * x, h[-1]


def rule() -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from rtbench.adapters import phi4flash as adapter

    from ray_tpu.models.routed import layer_of
    from ray_tpu.ops import selective_scan as ss

    cfg, cj, peaks = config(), config_json(), _peaks()
    n, ch = cfg.mamba_d_state, cfg.d_inner
    ks = jax.random.split(jax.random.PRNGKey(0), 8)

    def inputs(rows):
        x = jax.random.normal(ks[0], (rows, ch))
        dt = jnp.exp(jax.random.uniform(ks[1], (rows, ch), minval=-6.9,
                                        maxval=-2.3))
        b = jax.random.normal(ks[2], (rows, n))
        c = jax.random.normal(ks[3], (rows, n))
        return x, dt, b, c

    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1)[:, None], (n, ch))
    d = jax.random.normal(ks[4], (ch,))
    work = adapter.ssm_token_work(cj)
    least_chunk = CHUNK * max(work["flops"] / peaks["bf16_flops_per_s"],
                              work["bytes"] / peaks["hbm_bytes_per_s"])
    out = {"mode": "rule", "device": jax.devices()[0].device_kind,
           "rows": CHUNK, "channels": ch, "states": n, "slots": SLOTS,
           "chunk_least_us": round(least_chunk * 1e6, 2), "chunk": [],
           "step": []}
    x, dt, b, c = inputs(CHUNK)
    h0 = jax.random.normal(ks[5], (n, ch))
    want_y, want_h = jax.jit(ss.selective_scan_recurrence)(
        x, dt, a, b, c, d, h0)

    def chunk_row(name, form, calls=CALLS, **more):
        # Device time: ``calls`` calls in one program, each from the state
        # the last left, so the host dispatches once; the carry reaches
        # ``x`` (plus a number that flushes to zero), so nothing of a call
        # is the loop's invariant.
        def run(x, dt, b, c, h):
            def body(_, carry):
                nought = carry[1][0, 0] * 1e-38
                y, h1 = form(x + nought, dt, a, b, c, d, carry[1])
                return carry[0] + y, h1
            return lax.fori_loop(0, calls, body, (jnp.zeros_like(x), h))

        fn = jax.jit(run)
        sec = timed(lambda: fn(x, dt, b, c, h0), 3) / calls
        y, h1 = jax.jit(form)(x, dt, a, b, c, d, h0)
        out["chunk"].append({
            "form": name, **more, "ms": round(sec * 1e3, 4),
            "roofline_pct": round(100 * least_chunk / sec, 2),
            "max_err_y": float(jnp.abs(y - want_y).max()),
            "max_err_state": float(jnp.abs(h1 - want_h).max())})
        print(json.dumps(out["chunk"][-1]), flush=True)

    chunk_row("kernel", ss.selective_scan_chunk, unroll=ss._UNROLL)
    for unroll in UNROLLS:
        chunk_row("kernel", partial(ss._selective_scan_pallas,
                                    unroll=unroll), unroll=unroll)
    chunk_row("associative_scan", scan_associative, calls=2)
    chunk_row("recurrence_xla", ss.selective_scan_recurrence, calls=2)
    # The step on one line of the stacked leaf, as the decode program has
    # it: the leaf is donated and updated in place.
    lines = cfg.ssm_lines
    state = jax.random.normal(ks[6], (lines, SLOTS, n, ch))
    xs, dts, bs, cs = inputs(SLOTS)
    least_step = adapter.ssm_step_bytes(cj, SLOTS) / peaks["hbm_bytes_per_s"]

    def all_lines(state, x, dt, b, c):
        def body(line, carry):
            y, h1 = ss.selective_scan_step(x, dt, a, b, c, d,
                                           layer_of(carry[1], line))
            return carry[0] + y, lax.dynamic_update_index_in_dim(
                carry[1], h1, line, 0)
        return lax.fori_loop(0, lines, body, (jnp.zeros_like(x), state))

    fn = jax.jit(all_lines, donate_argnums=0)
    y, state = fn(state, xs, dts, bs, cs)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(20):
        y, state = fn(state, xs, dts, bs, cs)
    jax.block_until_ready(state)
    sec = (time.perf_counter() - t0) / 20 / lines
    out["step"].append({"form": "xla_in_place", "ms_per_line": round(
        sec * 1e3, 4), "least_us": round(least_step * 1e6, 2),
        "roofline_pct": round(100 * least_step / sec, 2)})
    return out


def _prefilled(cfg, params, cache, live: int, slots):
    """Every slot of ``slots`` prefilled with ``live`` tokens of its own."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import phi4flash_serving as serving

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

    from ray_tpu.llm import phi4flash_serving as serving
    from ray_tpu.models import phi4flash

    cfg, slots = config(), SLOTS
    params = jax.jit(phi4flash.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    cache = serving.init_cache(cfg, slots, MAX_SEQ)
    i32 = jnp.int32
    out = {"mode": "step", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "slots": slots,
           "decode_ms_per_step": {}, "prefill_chunk_ms": {},
           "prefill_last_chunk_ms": {}}
    ids = jax.random.randint(jax.random.PRNGKey(7), (CHUNK,), 259,
                             cfg.vocab_size, i32)
    for cached in (0, 4096, 11264):
        # a chunk that skips the cross-decoder, then one that is the last
        for key, length in (("prefill_chunk_ms", cached + 2 * CHUNK),
                            ("prefill_last_chunk_ms", cached + CHUNK)):
            times = []
            for _ in range(4):
                t0 = time.monotonic()
                cache, logits, counts = serving.prefill_chunk(
                    cfg, params, cache, ids, i32(cached), i32(length), i32(0))
                np.asarray(logits[:1])
                times.append((time.monotonic() - t0) * 1e3)
            out[key][cached] = round(min(times[1:]), 2)
            out[key + "_counts"] = [int(n) for n in counts]
    temps = jnp.zeros((slots,), jnp.float32)
    t0 = time.monotonic()
    cache, logits = _prefilled(cfg, params, cache, 1024, range(slots))
    np.asarray(logits[:1])
    out["prefill_slots_x_1024_s"] = round(time.monotonic() - t0, 2)
    tok = jax.random.randint(jax.random.PRNGKey(8), (slots,), 259,
                             cfg.vocab_size, i32)
    # Past 1,024 the rows are what earlier calls left or zeros: the
    # kernel's time does not depend on their values, the states are served
    # ones.
    for live in (2048, 5120, 11776):
        times = []
        for _ in range(4):
            t0 = time.monotonic()
            cache, toks, counts = serving.decode_burst(
                cfg, params, cache, tok, jnp.full((slots,), live, i32),
                jnp.ones((slots,), bool), temps, temps + 1.0,
                jax.random.PRNGKey(1), 8, False)
            np.asarray(toks)
            times.append((time.monotonic() - t0) * 1e3 / 8)
        out["decode_ms_per_step"][live] = round(min(times[1:]), 2)
        out[f"decode_counts_{live}"] = [int(n) for n in counts]
    return out


def margins() -> dict:
    """What a sound run's margin is made of, and what should not pass: the
    serving programs in bfloat16, a prompt of 2,048 in chunks of 512 and
    then 512 positions teacher-forced a token a step, against the float32
    reference on the same weights. Rows: the seeded weights; the same with
    the scan's state rounded to bfloat16 after every chunk and step. The
    number is a run's: the reference's top logit minus its logit of the
    program's top token, over the decoded positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from reference import phi4flash as reference
    from rtbench.adapters import phi4flash as adapter

    from ray_tpu.llm import phi4flash_serving as serving
    from ray_tpu.models import phi4flash

    cfg, cj = config(4096), config_json()
    i32 = jnp.int32
    prompt, steps = 2048, 512
    out = {"mode": "margins", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "prompt": prompt, "steps": steps,
           "rows": []}
    init = jax.jit(phi4flash.init_params, static_argnums=0)
    # bfloat16's 8 exponent and 7 mantissa bits by ``reduce_precision``,
    # which the compiler keeps: a cast down and back it removes on the TPU
    # (benchmark/control.to_fp8's finding).
    low = jax.jit(lambda a: jax.lax.reduce_precision(
        a, exponent_bits=8, mantissa_bits=7))
    cases = (("seeded", False), ("state_bf16", True))
    if CASES is not None:
        cases = tuple(c for c in cases if c[0] in CASES)
    for seed in (11, 12):
        for name, state_low in cases:
            params = init(cfg, jax.random.PRNGKey(seed))
            weights = adapter.reference_weights(params)
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
            want = reference.logits(cj, weights, ids)[prompt - 1:-1]
            gaps = want.max(axis=1) - want[np.arange(len(picks)),
                                           np.asarray(picks)]
            out["rows"].append({
                "seed": seed, "case": name, "worst": float(gaps.max()),
                "p99": float(np.percentile(gaps, 99)),
                "mean": float(gaps.mean()),
                "over_0.2": int((gaps > 0.2).sum()),
                "swapped": int((gaps > 0).sum()),
                "logit_std": float(want.std())})
            print(json.dumps(out["rows"][-1]), flush=True)
            del params, weights, want
    return out


def reference_time() -> dict:
    """Seconds of ``benchmark/reference/phi4flash.logits`` at the lengths a
    run's check meets (the first call of a length compiles)."""
    import jax
    import jax.numpy as jnp
    from reference import phi4flash as reference
    from rtbench.adapters import phi4flash as adapter

    from ray_tpu.models import phi4flash

    cfg, cj = config(4096), config_json()
    t0 = time.monotonic()
    params = jax.jit(phi4flash.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(3))
    jax.block_until_ready(params)
    out = {"mode": "reference", "device": jax.devices()[0].device_kind,
           "init_params_s": round(time.monotonic() - t0, 1), "calls": []}
    weights = adapter.reference_weights(params)
    for length in (2560, 3072, 3072, 2560):
        ids = jax.random.randint(jax.random.PRNGKey(length), (length,), 259,
                                 cfg.vocab_size, jnp.int32)
        t0 = time.monotonic()
        got = reference.logits(cj, weights, ids)
        out["calls"].append({"length": length, "rows": len(got),
                             "s": round(time.monotonic() - t0, 1)})
        print(json.dumps(out["calls"][-1]), flush=True)
    return out


MODES = {"aot": aot, "rule": rule, "step": step, "margins": margins,
         "reference": reference_time}

if __name__ == "__main__":
    if "PHI4FLASH_SLOTS" in os.environ:
        SLOTS = int(os.environ["PHI4FLASH_SLOTS"])
    if "PHI4FLASH_CASES" in os.environ:
        CASES = os.environ["PHI4FLASH_CASES"].split(",")
    for mode in sys.argv[1:] or ["aot"]:
        print(json.dumps(MODES[mode]()), flush=True)
