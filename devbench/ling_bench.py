"""Ling-3.0-flash-VL's serving programs at the shapes of
``ling3-flash-serve-rollout-8k`` (12 layers at the published widths: 10 Kimi
Delta Attention and 2 gated latent attentions, 64 of 512 experts, 96 slots x
8,192): compiled for a described v5e with no chip, and timed on one.

    python3 devbench/ling_bench.py aot        # no chip, about two minutes
    chiprun -- python3 devbench/ling_bench.py rule step
    chiprun -- python3 devbench/ling_bench.py margins

``aot``: ``llm/ling_serving.py``'s ``prefill_chunk(512)`` and
``decode_burst(8)``, compiled for ``v5e:2x2``'s first device (nothing runs:
no time comes out of it): XLA's ``memory_analysis`` (arguments,
temporaries, their sum against the chip's 15.75 GiB), the Mosaic calls, and
every instruction whose result has the shape of a cache leaf or of a
stacked leaf, by opcode. ``rule``: the delta rule alone
(``ops/gated_delta.py``) at the cell's shapes, a decay a key channel
against a decay a head at the same shapes: the chunked form on 512 rows x
32 heads from a carried state (the per-channel kernel and the jnp body it
is held to; the scalar kernel and its jnp body), each one's device time a
call, its share of the yardstick and its largest difference from the
recurrence on outputs and on states (the per-channel inputs have channels
at the gate's floor of -5 every token); then the step on 96 slots x 32
heads, a line of a stacked state leaf in place, both decays, the kernel
beside the jnp body it is held to; the yardsticks are
``adapters/ling.delta_rule_token_work`` and ``linear_step_bytes`` over the
chip's peaks. ``step``: wall milliseconds of one decode step inside a burst
of 8 at 96 lines of 1,024 and 4,096 live positions and of a prefill chunk
of 512 against 0 to 4,096 cached rows. ``margins``: the serving programs in
bfloat16, teacher-forced, against ``benchmark/reference/ling.py`` on the
same weights, with the routed experts' output at zero and at the seeded
scale, and once with the rule's state rounded to bfloat16 after every chunk
and step and once with the router's weights in bfloat16 (what the
comparison should not pass). One JSON object a mode. The configuration is
the benchmark's file through its adapter. Run as a script, ``LING_LAYERS``
overrides the depth and ``LING_CASES`` names the rows of ``margins`` to run;
imported (tests/test_tpu_aot.py), the environment changes nothing.
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
from devbench.qwen3_next_bench import step_seconds_a_line  # noqa: E402

SLOTS, MAX_SEQ, CHUNK = 96, 8192, 512
# ``rule``: calls of a form inside one timed program.
CALLS = 16
# The script's overrides (``__main__`` reads them from the environment).
LAYERS: int | None = None
CASES: list[str] | None = None


def config_json() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling-3.0-flash-vl.json")) as f:
        c = json.load(f)
    if LAYERS is not None:
        c["num_hidden_layers"] = LAYERS
    return c


def config(max_seq: int = MAX_SEQ):
    from rtbench.adapters import ling as adapter

    return adapter.model_config(config_json(), "serve_rollout", max_seq)


def lowerings(cfg, params, cache, arg) -> dict:
    """{name: a function that lowers that program} at the cell's shapes."""
    import jax.numpy as jnp

    from ray_tpu.llm import ling_serving as serving

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
    leaves of the experts and the mixers."""
    h, nm = cfg.hidden_size, cfg.num_routed_layers
    E, fe = cfg.experts_held, cfg.moe_intermediate_size
    lh, d = cfg.linear_num_heads, cfg.linear_head_dim
    return {"latent": f"bf16[{cfg.latent_lines},{slots},{max_seq},"
                      f"{cfg.latent_row}]",
            # a leaf a KDA layer at the cell's depth (llm/ling_serving.py)
            "state": f"f32[1,{slots},{lh},{d},{d}]",
            "we_in": f"bf16[{nm},{E},{h},{fe}]",
            "we_down": f"bf16[{nm},{E},{fe},{h}]",
            "in_qkvz": f"bf16[{cfg.linear_lines},{h},"
                       f"{cfg.conv_dim + cfg.linear_dim}]",
            "in_f": f"bf16[{cfg.linear_lines},{h},{cfg.linear_dim}]",
            "wq": f"bf16[{cfg.latent_lines},{h},"
                  f"{cfg.num_heads * cfg.qk_head_dim}]",
            "wkv_b": f"bf16[{cfg.latent_lines},{cfg.num_heads},"
                     f"{cfg.kv_lora_rank},"
                     f"{cfg.qk_nope_head_dim + cfg.v_head_dim}]"}


def compile_programs(cfg, slots: int = SLOTS, max_seq: int = MAX_SEQ,
                     only: str | None = None) -> dict:
    """The programs (or the one named) compiled for a described v5e: {name:
    (memory analysis, HLO text, seconds)}. tests/test_tpu_aot.py reads the
    same."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.llm import ling_serving as serving
    from ray_tpu.models import ling
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

        params = place(jax.eval_shape(partial(ling.init_params, cfg),
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


def rule() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from rtbench.adapters import ling as adapter

    from ray_tpu.ops import gated_delta as gd

    cfg, cj, peaks = config(), config_json(), _peaks()
    nh, d, floor = cfg.linear_num_heads, cfg.linear_head_dim, \
        cfg.kda_lower_bound
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    unit = lambda x: x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))  # noqa: E731

    def inputs(rows, channel: bool):
        q = unit(jax.random.normal(ks[0], (rows, nh, d))) * d ** -0.5
        k = unit(jax.random.normal(ks[1], (rows, nh, d)))
        v = jax.random.normal(ks[2], (rows, nh, d))
        # -g log-uniform over 0.001 to 5; a decay a channel has channel 0 of
        # every head at the floor every token and channel 1 at -0.001
        g = -jnp.exp(jax.random.uniform(
            ks[3], (rows, nh, d) if channel else (rows, nh),
            minval=jnp.log(1e-3), maxval=jnp.log(-floor)))
        if channel:
            g = g.at[..., 0].set(floor).at[..., 1].set(-1e-3)
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, nh)))
        return q, k, v, g, beta

    work = adapter.delta_rule_token_work(cj)
    least_chunk = CHUNK * max(work["flops"] / peaks["bf16_flops_per_s"],
                              work["bytes"] / peaks["hbm_bytes_per_s"])
    out = {"mode": "rule", "device": jax.devices()[0].device_kind,
           "rows": CHUNK, "heads": nh, "slots": SLOTS,
           "chunk_least_us": round(least_chunk * 1e6, 2), "chunk": [],
           "step": []}
    s0 = jax.random.normal(ks[5], (nh, d, d))

    def truth(a, heads=2):
        """The recurrence of the first ``heads`` heads in float64 on the
        host: what tells the device's float32 recurrence from its chunked
        forms where the two disagree."""
        import numpy as np

        q, k, v, g, beta = (np.asarray(x, np.float64)[:, :heads] for x in a)
        s = np.asarray(s0, np.float64)[:heads]
        g = g if g.ndim == 3 else g[..., None]
        out = []
        for t in range(q.shape[0]):
            s = np.exp(g[t])[:, :, None] * s
            d = beta[t][:, None] * (v[t] - np.einsum("hk,hkv->hv", k[t], s))
            s = s + k[t][:, :, None] * d[:, None, :]
            out.append(np.einsum("hk,hkv->hv", q[t], s))
        return np.stack(out), s

    def chunk_row(name, form, a):
        # Device time: ``CALLS`` calls in one program, each from the state
        # the last left (devbench/qwen3_next_bench.rule's way).
        want_o, want_s = jax.jit(gd.gated_delta_recurrence)(*a, s0)
        true_o, true_s = truth(a)

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
            "form": name, "ms": round(sec * 1e3, 4),
            "roofline_pct": round(100 * least_chunk / sec, 2),
            "max_err_o": float(jnp.abs(o - want_o).max()),
            "max_err_state": float(jnp.abs(s1 - want_s).max()),
            # against the host's float64, two heads: this form's, then the
            # device's float32 recurrence's
            "f64_err_state": float(abs(np.asarray(s1)[:2] - true_s).max()),
            "f64_err_state_recurrence": float(
                abs(np.asarray(want_s)[:2] - true_s).max())})

    # ``gated_delta_chunk`` is the kernel on a TPU; the reference body is
    # the jnp form it is held to, the recurrence what both stand in for.
    chunk_row("channel_kernel", partial(gd.gated_delta_chunk, g_floor=floor),
              inputs(CHUNK, True))
    chunk_row("channel_jnp",
              partial(gd.gated_delta_chunk_reference, g_floor=floor),
              inputs(CHUNK, True))
    chunk_row("scalar_kernel", gd.gated_delta_chunk, inputs(CHUNK, False))
    chunk_row("scalar_jnp", gd.gated_delta_chunk_reference,
              inputs(CHUNK, False))
    # The step on every line of a stacked leaf in turn, as the decode
    # program has it (the leaf donated, a line updated in place), the
    # kernel beside the jnp body it is held to, both decays: all from this
    # one call.
    lines = cfg.linear_lines
    least_step = adapter.linear_step_bytes(cj, SLOTS) \
        / peaks["hbm_bytes_per_s"]
    for channel in (True, False):
        b = inputs(SLOTS, channel)
        for form, backend in (("kernel", "mosaic"), ("jnp", "reference")):
            sec = step_seconds_a_line(b, lines, backend, ks[6], 10)
            out["step"].append({
                "form": ("channel_" if channel else "scalar_") + form,
                "states_a_step": gd.states_a_step(nh, d, d),
                "ms_per_line": round(sec * 1e3, 4),
                "least_us": round(least_step * 1e6, 2),
                "roofline_pct": round(100 * least_step / sec, 2)})
    return out


def step() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import ling_serving as serving
    from ray_tpu.models import ling

    cfg = config()
    params = jax.jit(ling.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    cache = serving.init_cache(cfg, SLOTS, MAX_SEQ)
    i32 = jnp.int32
    out = {"mode": "step", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "decode_ms_per_step": {},
           "prefill_chunk_ms": {}}
    ids = jax.random.randint(jax.random.PRNGKey(7), (CHUNK,), 259,
                             cfg.vocab_size, i32)
    for cached in (0, 1024, 4096):
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
    for live in (1024, 4096):
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


def _low_states(cache: dict, low) -> dict:
    return {k: low(v) if k.startswith("state") else v
            for k, v in cache.items()}


def margins() -> dict:
    """What a sound run's margin is made of, and what should not pass: the
    serving programs in bfloat16, a prompt of 1,024 in chunks of 512 and
    then 512 positions teacher-forced a token a step, against the float32
    reference on the same weights. Rows: the routed experts'
    down-projections at 0 and 1 times their seeded scale (rounding alone,
    then rounding and the eighth place's swaps); the seeded scale with the
    rule's state rounded to bfloat16 after every chunk and step; the seeded
    scale with the router's weights rounded to bfloat16. The number is a
    run's: the reference's top logit minus its logit of the program's top
    token, over the decoded positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from reference import ling as reference
    from rtbench.adapters import ling as adapter

    from ray_tpu.llm import ling_serving as serving
    from ray_tpu.models import ling

    cfg, cj = config(2048), config_json()
    i32 = jnp.int32
    prompt, steps = 1024, 512
    out = {"mode": "margins", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "prompt": prompt, "steps": steps,
           "rows": []}
    init = jax.jit(ling.init_params, static_argnums=0)
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
            cache = serving.init_cache(cfg, 2, 2048)
            for start in range(0, prompt, CHUNK):
                cache, logits, _ = serving.prefill_chunk(
                    cfg, params, cache, ids[start:start + CHUNK], i32(start),
                    i32(prompt), i32(1))
                if state_low:
                    cache = _low_states(cache, low)
            picks = [int(np.asarray(logits).argmax())]
            write = jnp.array([False, True])
            host_ids = np.asarray(ids)
            for p in range(prompt, prompt + steps - 1):
                cache, logits, _ = serving.decode_step(
                    cfg, params, cache, jnp.array([0, host_ids[p]], i32),
                    jnp.array([0, p], i32), write)
                if state_low:
                    cache = _low_states(cache, low)
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
    if "LING_LAYERS" in os.environ:
        LAYERS = int(os.environ["LING_LAYERS"])
    if "LING_CASES" in os.environ:
        CASES = os.environ["LING_CASES"].split(",")
    for mode in sys.argv[1:] or ["aot"]:
        print(json.dumps(MODES[mode]()), flush=True)
