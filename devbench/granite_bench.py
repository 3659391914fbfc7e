"""granite-4.0-h-small's serving programs at the shapes of
``granite4-h-small-serve-support-2k`` (10 layers at the published widths:
nine Mamba-2 and one attention, 36 of 72 experts, 96 slots x 2,048):
compiled for a described v5e with no chip, and timed on one.

    python3 devbench/granite_bench.py aot        # no chip, about two minutes
    chiprun -- python3 devbench/granite_bench.py rule step
    chiprun -- python3 devbench/granite_bench.py margins

``aot``: ``llm/granite_serving.py``'s ``prefill_chunk(512)`` and
``decode_burst(8)``, compiled for ``v5e:2x2``'s first device (nothing runs:
no time comes out of it): XLA's ``memory_analysis`` (arguments,
temporaries, their sum against the chip's 15.75 GiB), the Mosaic calls, and
every instruction whose result has the shape of a cache leaf or of a
stacked leaf, by opcode. ``rule``: Mamba-2's rule alone (``ops/ssd.py``) at
the cell's shapes beside the delta rule's (``ops/gated_delta.py``, Ling's
shapes) and the selective scan's (``ops/selective_scan.py``, Phi-4's) at
theirs: the chunked form on 512 rows from a carried state, device time a
call, its share of the adapter's yardstick and its largest difference from
the recurrence; then the step on 96 slots, every line of a stacked state
leaf in place, the kernel beside the jnp body it is held to. ``step``: wall
milliseconds of one decode step inside a burst of 8 at 96 lines of 256 and
1,024 live positions and of a prefill chunk of 512 against 0 and 512 cached
rows. ``margins``: the serving programs in bfloat16, teacher-forced, against
``benchmark/reference/granite.py`` on the same weights, with the routed
experts' output at zero and at the seeded scale, and once with the rule's
state rounded to bfloat16 after every chunk and step and once with the
router's weights in bfloat16 (what the comparison should not pass). One
JSON object a mode. The configuration is the benchmark's file through its
adapter. Run as a script, ``GRANITE_LAYERS`` keeps the first layers alone
and ``GRANITE_CASES`` names the rows of ``margins`` to run; imported
(tests/test_tpu_aot.py), the environment changes nothing.
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

SLOTS, MAX_SEQ, CHUNK = 96, 2048, 512
# ``rule``: calls of a form inside one timed program.
CALLS = 16
# The script's overrides (``__main__`` reads them from the environment).
LAYERS: int | None = None
CASES: list[str] | None = None


def config_json() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-small.json")) as f:
        c = json.load(f)
    if LAYERS is not None:
        c["num_hidden_layers"] = LAYERS
        c["layer_types"] = c["layer_types"][:LAYERS]
    return c


def config(max_seq: int = MAX_SEQ):
    from rtbench.adapters import granite as adapter

    return adapter.model_config(config_json(), "serve_support", max_seq)


def lowerings(cfg, params, cache, arg, slots: int = SLOTS) -> dict:
    """{name: a function that lowers that program} at the cell's shapes."""
    import jax.numpy as jnp

    from ray_tpu.llm import granite_serving as serving

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
    leaves (the state's above all: 4 MiB a slot and layer) and the stacked
    leaves of the experts, the mixers and the tied embedding."""
    h, L = cfg.hidden_size, cfg.num_layers
    E, fe = cfg.experts_held, cfg.intermediate_size
    g, n, w = cfg.state_shape
    return {"state": f"f32[{cfg.linear_lines},{slots},{g},{n},{w}]",
            "k": f"bf16[{cfg.attention_lines},{slots},{cfg.num_kv_heads},"
                 f"{max_seq},{cfg.head_dim}]",
            "we_in": f"bf16[{L},{E},{h},{fe}]",
            "we_down": f"bf16[{L},{E},{fe},{h}]",
            "in_xbcz": f"bf16[{cfg.linear_lines},{h},"
                       f"{cfg.conv_dim + cfg.d_inner}]",
            "out_proj": f"bf16[{cfg.linear_lines},{cfg.d_inner},{h}]",
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

    from ray_tpu.llm import granite_serving as serving
    from ray_tpu.models import granite
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

        params = place(jax.eval_shape(partial(granite.init_params, cfg),
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
            "remat": sorted({w for w in text.split() if ".remat" in w
                             and "update" in w})[:8],
            "big": {k: opcodes_with_shape(text, s)
                    for k, s in big_shapes(cfg).items()}}
        if os.environ.get("DUMP"):
            with open(os.path.join(os.environ["DUMP"],
                                   name.split("(")[0] + ".hlo.txt"),
                      "w") as f:
                f.write(text)
    return out


def _peaks():
    import jax

    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        return json.load(f)[jax.devices()[0].device_kind]


def ssd_step_seconds_a_line(a, state, backend: str, reps: int):
    """Seconds a line of ``ssd_step`` on every line in turn of a stacked
    leaf, as a decode program has it (the leaf donated, a line updated in
    place), under ``backend`` (``"mosaic"``: the kernel; ``"reference"``: the
    jnp body). ``a``: x, dt, a, b, c of every slot."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ray_tpu.ops import ssd
    from ray_tpu.ops.kernels import force_kernel_backend

    lines = state.shape[0]

    def all_lines(state, *a):
        def body(line, carry):
            return ssd.ssd_step(*a, carry[1], line)
        return lax.fori_loop(0, lines, body, (jnp.zeros(a[0].shape), state))

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
    from rtbench.adapters import granite as adapter

    from ray_tpu.ops import gated_delta as gd, selective_scan as scan, ssd

    cfg, cj, peaks = config(), config_json(), _peaks()
    nh, p, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    ks = jax.random.split(jax.random.PRNGKey(0), 10)

    def inputs(rows):
        x = jax.random.normal(ks[0], (rows, nh, p))
        # a step log-uniform over 0.001 to 0.1 and a rate of 1 to 128 over
        # the heads: exp(dt A) from 0.999 to under 1e-5
        dt = jnp.exp(jax.random.uniform(ks[1], (rows, nh),
                                        minval=jnp.log(1e-3),
                                        maxval=jnp.log(1e-1)))
        a = -jnp.linspace(1.0, 128.0, nh)
        b = jax.random.normal(ks[2], (rows, n)) * n ** -0.5
        c = jax.random.normal(ks[3], (rows, n))
        return x, dt, a, b, c

    work = adapter.delta_rule_token_work(cj)
    least_chunk = CHUNK * max(work["flops"] / peaks["bf16_flops_per_s"],
                              work["bytes"] / peaks["hbm_bytes_per_s"])
    out = {"mode": "rule", "device": jax.devices()[0].device_kind,
           "rows": CHUNK, "heads": nh, "slots": SLOTS, "sub": ssd.SUB,
           "chunk_least_us": round(least_chunk * 1e6, 2), "chunk": [],
           "step": [], "others": []}
    s0 = jax.random.normal(ks[4], cfg.state_shape)

    def loop_ms(form, a, s, acc_of):
        """Device time a call: ``CALLS`` calls in one program, each from the
        state the last left (devbench/qwen3_next_bench.rule's way)."""
        def calls(s, *a):
            def body(_, carry):
                nought = carry[1].reshape(-1)[0] * 1e-38
                o, s1 = form(a[0] + nought, *a[1:], carry[1])
                return carry[0] + o, s1
            return lax.fori_loop(0, CALLS, body, (acc_of(a), s))

        fn = jax.jit(calls)
        return timed(lambda: fn(s, *a), 5) / CALLS * 1e3

    a = inputs(CHUNK)
    want_y, want_s = jax.jit(ssd.ssd_recurrence)(*a, s0)
    for name, form in (("chunk", ssd.ssd_chunk),
                       ("recurrence", ssd.ssd_recurrence)):
        ms = loop_ms(form, a, s0, lambda a: jnp.zeros(a[0].shape))
        y, s1 = jax.jit(form)(*a, s0)
        out["chunk"].append({
            "form": name, "ms": round(ms, 4),
            "roofline_pct": round(100 * least_chunk * 1e3 / ms, 2),
            "max_err_y": float(jnp.abs(y - want_y).max()),
            "max_err_state": float(jnp.abs(s1 - want_s).max())})
    # The step on every line of a stacked leaf in turn, the kernel beside
    # the jnp body it is held to.
    least_step = adapter.linear_step_bytes(cj, SLOTS) \
        / peaks["hbm_bytes_per_s"]
    b = inputs(SLOTS)
    for form, backend in (("kernel", "mosaic"), ("jnp", "reference")):
        leaf = jax.random.normal(ks[5], (cfg.linear_lines, SLOTS,
                                         *cfg.state_shape))
        sec = ssd_step_seconds_a_line(b, leaf, backend, 10)
        del leaf
        out["step"].append({
            "form": form,
            "states_a_step": gd.states_a_step(*cfg.state_shape),
            "ms_per_line": round(sec * 1e3, 4),
            "least_us": round(least_step * 1e6, 2),
            "roofline_pct": round(100 * least_step / sec, 2)})
    # The kernel against the jnp body on one leaf: outputs, the line's
    # states, and the other lines as they were.
    from ray_tpu.ops.kernels import force_kernel_backend

    leaf = jax.random.normal(ks[5], (2, SLOTS, *cfg.state_shape))
    quiet = (b[0], b[1].at[1].set(0.0), *b[2:])        # slot 1 has no step
    with force_kernel_backend("reference"):
        y0, l0 = jax.jit(ssd.ssd_step)(*quiet, leaf, 1)
    y1, l1 = jax.jit(ssd.ssd_step)(*quiet, leaf, jnp.int32(1))
    out["step_parity"] = {
        "max_err_y": float(jnp.abs(y1 - y0).max()),
        "max_err_state": float(jnp.abs(l1 - l0).max()),
        "other_line_kept": bool((l1[0] == leaf[0]).all()),
        "quiet_slot_kept": bool((l1[1, 1] == leaf[1, 1]).all())}
    del leaf, l0, l1
    # The two other rules at their cells' shapes, the same loop: the delta
    # rule with a decay a key channel (Ling: 32 heads of 128 x 128) and the
    # selective scan (Phi-4: 5,120 channels x 16).
    unit = lambda x: x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))  # noqa: E731
    q = unit(jax.random.normal(ks[6], (CHUNK, 32, 128)))
    g = -jnp.exp(jax.random.uniform(ks[7], (CHUNK, 32, 128),
                                    minval=jnp.log(1e-3), maxval=jnp.log(5.)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[8], (CHUNK, 32)))
    ms = loop_ms(
        lambda q, k, v, g, beta, s: gd.gated_delta_chunk(
            q, k, v, g, beta, s, g_floor=-5.0),
        (q * 128 ** -0.5, q, jax.random.normal(ks[9], (CHUNK, 32, 128)), g,
         beta), jnp.zeros((32, 128, 128)),
        lambda a: jnp.zeros(a[2].shape))
    out["others"].append({"form": "gated_delta_chunk (Ling's shapes)",
                          "ms": round(ms, 4)})
    d, ns = 5120, 16
    xs = jax.random.normal(ks[6], (CHUNK, d))
    dts = jax.nn.softplus(jax.random.normal(ks[7], (CHUNK, d)) - 3.0)
    ms = loop_ms(
        lambda x, dt, a, b, c, d_skip, s: scan.selective_scan_chunk(
            x, dt, a, b, c, d_skip, s),
        (xs, dts, -jnp.exp(jax.random.normal(ks[8], (ns, d))),
         jax.random.normal(ks[9], (CHUNK, ns)),
         jax.random.normal(ks[5], (CHUNK, ns)), jnp.ones((d,))),
        jnp.zeros((ns, d)),
        lambda a: jnp.zeros(a[0].shape))
    out["others"].append({"form": "selective_scan_chunk (Phi-4's shapes)",
                          "ms": round(ms, 4)})
    return out


def step() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import granite_serving as serving
    from ray_tpu.models import granite

    cfg = config()
    params = jax.jit(granite.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    cache = serving.init_cache(cfg, SLOTS, MAX_SEQ)
    i32 = jnp.int32
    out = {"mode": "step", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "decode_ms_per_step": {},
           "prefill_chunk_ms": {}}
    ids = jax.random.randint(jax.random.PRNGKey(7), (CHUNK,), 259,
                             cfg.vocab_size, i32)
    for cached in (0, 512):
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
    tok = jax.random.randint(jax.random.PRNGKey(8), (SLOTS,), 259,
                             cfg.vocab_size, i32)
    # The rows are what earlier calls left or zeros: the kernels' time does
    # not depend on their values.
    for live in (256, 1024):
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
    """What a sound run's margin is made of, and what should not pass: the
    serving programs in bfloat16, a prompt of 512 in one chunk and then 512
    positions teacher-forced a token a step, against the float32 reference
    on the same weights. Rows: the routed experts' down-projections at 0
    and 1 times their seeded scale (rounding alone, then rounding and the
    tenth place's swaps); the seeded scale with the rule's state rounded to
    bfloat16 after every chunk and step; the seeded scale with the router's
    weights rounded to bfloat16. The number is a run's: the reference's top
    logit minus its logit of the program's top token, over the decoded
    positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from reference import granite as reference
    from rtbench.adapters import granite as adapter

    from ray_tpu.llm import granite_serving as serving
    from ray_tpu.models import granite

    cfg, cj = config(1024), config_json()
    i32 = jnp.int32
    prompt, steps = 512, 512
    out = {"mode": "margins", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "prompt": prompt, "steps": steps,
           "rows": []}
    init = jax.jit(granite.init_params, static_argnums=0)
    scale = jax.jit(lambda a, s: (a.astype(jnp.float32) * s).astype(a.dtype),
                    donate_argnums=0)
    # bfloat16's 8 exponent and 7 mantissa bits by ``reduce_precision``,
    # which the compiler keeps (devbench/qwen3_next_bench.margins).
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
            cache = serving.init_cache(cfg, 2, 1024)
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
                "over_0.1": int((gaps > 0.1).sum()),
                "swapped": int((gaps > 0).sum())})
            print(json.dumps(out["rows"][-1]), flush=True)
            del params, weights, want
    return out


MODES = {"aot": aot, "rule": rule, "step": step, "margins": margins}

if __name__ == "__main__":
    if "GRANITE_LAYERS" in os.environ:
        LAYERS = int(os.environ["GRANITE_LAYERS"])
    if "GRANITE_CASES" in os.environ:
        CASES = os.environ["GRANITE_CASES"].split(",")
    for mode in sys.argv[1:] or ["aot"]:
        print(json.dumps(MODES[mode]()), flush=True)
