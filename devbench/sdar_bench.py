"""The SDAR-MoE serving programs at the shapes of ``sdar-30b-serve-generate-512``
(the first 6 layers of SDAR-30B-A3B-Chat with all 128 experts, 128 slots x
1,536): compiled for a described v5e with no chip, and timed on one.

    python3 devbench/sdar_bench.py aot            # no chip, about a minute
    chiprun -- python3 devbench/sdar_bench.py step glue head parity

``aot``: ``llm/sdar_serving.py``'s ``prefill_chunk`` at the buckets 16 and
512 and ``decode_burst`` of 1 and 2 blocks, compiled for ``v5e:2x2``'s first
device (nothing runs: no time comes out of it): XLA's ``memory_analysis``
(arguments, temporaries, their sum against the chip's 15.75 GiB), the bytes
a cached position takes, the Mosaic calls, and every instruction whose
result has the shape of a cache leaf, of one layer or line of it, or of a
stacked weight, by opcode (a copy of one of those is up to 6.75 GiB moved a
program). ``step``: wall milliseconds of a burst of 1 and of 2 blocks at 128
lines of 256, 768 and 1,280 live positions (4 forwards a block since PR 63,
every line with a block pending; 4 n + 1 a burst from PR 61, 5 n before),
of a forward of 4 and of 8 rows a line alone, and of a prefill chunk of 512
at 0 and 512 cached rows
(the clock stops on a host read of the result); every line is prefilled
with tokens of its own first, so the router reaches the 113 to 128 experts
a layer that a served forward does (``experts_touched_per_layer``; lines
that hold the same tokens reach 32, and the forward reads a quarter of the
experts' bytes). ``glue``: the routed layer's XLA code around the grouped
matmul (``models/routed.py``) alone, at the five shapes the routed cells'
programs have (SDAR's forward, LFM2's chunk and decode step, LongCat's
decode step and chunk): microseconds a call of ``route``, of
``dispatch_plan``, of the gather into tiles and of the combine, and of the
whole ``moe_block`` with one layer of seeded experts, each inside one
``fori_loop`` of 200 calls whose carry is the call's own result (written
whole every iteration; the inputs change with the counter so nothing is
hoisted). The same file runs against an older tree laid in ``.parent/``
(copy it into ``.parent/devbench/``). ``head``: what follows the stack
in a denoising forward, alone, at all 512 rows (128 lines x 4) and at the
128 a forward of the ``sequential`` rule can read: microseconds a call of
``sdar.lm_head``, of each piece of the choice on float32 logits that are
there (the arg-max; ``served.sample_tokens``, which computes the arg-max
and a categorical draw for every row and picks afterwards; the chosen
token's probability; a ``lax.cond`` that makes the draw only where a row
has a temperature, with none and with one), and of the head with a choice
on its own product, as the program chains them. ``parity``: a block-causal
prefill of 768 positions and 64 blocks decided by the program, against the
float32 reference over the finished sequence, as the harness compares
them: the reference's top logit minus its logit of the program's token,
worst over the generated positions. ``handover`` (PR 63): at 768 live
positions a forward of 4 rows a line, the wide one of 8 with every line's
clean half live, with a quarter of them dead and with all dead (a tree that
has no dead half times the first two), and a chain of four bursts of 2
queued behind one another with nothing read between them, each handed the
last block of the one before where the tree hands one over: milliseconds a
burst, old (9 forwards, in ``.parent/``) against new (8).
``engine`` and ``engine_stream``: the
engine's schedule alone under the cell's traffic, no HTTP and no router: 128
closed-loop clients on ``LLMEngine.submit`` for 20 s (the second with
``stream=True`` and a thread a client that drains the token queue, as the
server's generator does): tokens a second as the cell counts them, wall
milliseconds a forward (the device's forward plus whatever gap the host
leaves: compare ``decode_ms_per_step.tok_s`` of a traced run); one of the
two a process (an engine's weights stay on the chip after ``shutdown``).
One JSON object a mode.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from devbench.lfm2_bench import GIB, opcodes_with_shape  # noqa: E402

SLOTS, MAX_SEQ = 128, 1536
BUCKETS = (16, 512)
BURSTS = (1, 2)


def config_json() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        return json.load(f)


def config():
    from rtbench.adapters import sdar as adapter

    return adapter.model_config(config_json(), "serve_generate", MAX_SEQ)


def shapes(cfg, place, slots: int = SLOTS):
    import jax

    from ray_tpu.llm import sdar_serving as serving
    from ray_tpu.models import sdar

    params = place(jax.eval_shape(partial(sdar.init_params, cfg),
                                  jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(partial(serving.init_kv_cache, cfg, slots,
                                         MAX_SEQ)))
    return params, cache


def lowerings(cfg, params, cache, arg, slots: int = SLOTS) -> dict:
    """{name: a function that lowers that program} at the cell's shapes."""
    import jax.numpy as jnp

    from ray_tpu.llm import sdar_serving as serving

    def chunk(b):
        return lambda: serving.prefill_chunk.lower(
            cfg, params, cache, arg((b,)), arg(()), arg(()), arg(()))

    def burst(n):
        token0 = arg((slots, cfg.block_length))
        if _hands_over(serving):
            token0 = (token0, token0, arg((slots,), jnp.bool_))
        return lambda: serving.decode_burst.lower(
            cfg, params, cache, token0,
            arg((slots,)), arg((slots,), jnp.bool_),
            arg((slots,), jnp.float32), arg((slots,), jnp.float32),
            arg((2,), jnp.uint32), n, False)

    out = {f"prefill_chunk({b})": chunk(b) for b in BUCKETS}
    out.update({f"decode_burst({n})": burst(n) for n in BURSTS})
    return out


def _hands_over(serving) -> bool:
    """Whether the tree's bursts take the block before in (since PR 63)."""
    return getattr(serving.SERVED, "pending_step", False)


def _inputs(serving, token0, pending=None, has=None):
    """A burst's first argument after the cache: ``token0`` alone on a tree
    from before PR 63, with the block before and who has one since (every
    line, of zeros, where nothing is said)."""
    import jax.numpy as jnp

    if not _hands_over(serving):
        return token0
    if pending is None:
        pending = jnp.zeros_like(token0)
    if has is None:
        has = jnp.ones(token0.shape[:1], bool)
    return token0, pending, has


def big_shapes(cfg, slots: int = SLOTS) -> dict:
    """The shapes no instruction should produce: a cache leaf, one layer of
    it, one line, and each stacked matrix."""
    h, fe, L, e = (cfg.hidden_size, cfg.moe_intermediate_size,
                   cfg.num_layers, cfg.num_experts)
    line = f"{cfg.num_kv_heads},{MAX_SEQ},{cfg.head_dim}]"
    return {"kv": f"bf16[{L},{slots},{line}",
            "kv_layer": f"bf16[{slots},{line}",
            "kv_line": f"bf16[1,{line}",
            "experts_up": f"bf16[{L},{e},{h},{fe}]",
            "experts_down": f"bf16[{L},{e},{fe},{h}]",
            "experts_layer_up": f"bf16[{e},{h},{fe}]",
            "experts_layer_down": f"bf16[{e},{fe},{h}]",
            "wq": f"bf16[{L},{h},{cfg.num_heads * cfg.head_dim}]",
            "embed": f"bf16[{cfg.vocab_size},{h}]",
            "head": f"bf16[{h},{cfg.vocab_size}]",
            "head_f32": f"f32[{h},{cfg.vocab_size}]"}


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
        k = cache["k"]
        out["cached_position_bytes"] = (
            2 * k.size * k.dtype.itemsize // (slots * MAX_SEQ))
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
            if os.environ.get("SDAR_BENCH_HLO"):
                with open(os.path.join(os.environ["SDAR_BENCH_HLO"],
                                       name + ".hlo.txt"), "w") as f:
                    f.write(text)
    return out


def _programs():
    import jax

    from ray_tpu.llm import sdar_serving as serving
    from ray_tpu.models import sdar

    cfg = config()
    params = jax.jit(sdar.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    return cfg, params, serving, serving.init_kv_cache(cfg, SLOTS, MAX_SEQ)


def _time_chunk(serving, cfg, params, cache, kv_len: int = 0):
    """(cache, ms, counts) of ``prefill_chunk(512)`` against ``kv_len``
    cached rows: the best of three calls after a first that may compile."""
    import jax.numpy as jnp
    import numpy as np

    i32, times = jnp.int32, []
    for _ in range(4):
        t0 = time.monotonic()
        cache, _, counts = serving.prefill_chunk(
            cfg, params, cache, jnp.arange(512, dtype=i32) + 300,
            i32(kv_len), i32(kv_len + 512), i32(0))
        np.asarray(counts)
        times.append((time.monotonic() - t0) * 1e3)
    return cache, round(min(times[1:]), 2), counts


def _time_burst(serving, cfg, params, cache, live: int, blocks: int):
    """(cache, ms, counts) of ``decode_burst(blocks)`` at every line
    ``live`` long, timed as ``_time_chunk`` does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    i32, times = jnp.int32, []
    temps = jnp.zeros((SLOTS,), jnp.float32)
    for _ in range(4):
        t0 = time.monotonic()
        cache, toks, counts = serving.decode_burst(
            cfg, params, cache,
            _inputs(serving, jnp.full((SLOTS, cfg.block_length), -1, i32)),
            jnp.full((SLOTS,), live, i32), jnp.ones((SLOTS,), bool),
            temps, temps + 1.0, jax.random.PRNGKey(1), blocks, False)
        np.asarray(toks)
        times.append((time.monotonic() - t0) * 1e3)
    return cache, round(min(times[1:]), 2), counts


FORWARDS = 8


def _time_forwards(serving, cfg, params, cache, live: int, blocks: int,
                   clean=None):
    """(cache, ms a forward) of ``_forward`` alone over ``blocks`` blocks a
    line side by side (1: a denoising forward without its head; 2: the
    forward a commit rides), every line ``live`` long before the rows:
    FORWARDS calls in one program, timed as ``_time_chunk``. ``clean``
    [SLOTS] bool: the wide forward as a burst's first (PR 63), each half
    written under its own mask, the clean half dead where it says false."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from ray_tpu.ops.decode_attention import decode_plan_of

    k = cfg.block_length
    write = jnp.ones((SLOTS,), bool)
    # the last of the side-by-side blocks starts at ``live``
    start = jnp.full((SLOTS,), live - (blocks - 1) * k, jnp.int32)
    tokens = jnp.full((SLOTS, blocks * k), cfg.mask_token_id, jnp.int32)
    # (a tree from before PR 63 has no such argument)
    clean = {} if clean is None else {"clean": clean}

    @partial(jax.jit, donate_argnums=(1,))
    def run(params, cache):
        plan = decode_plan_of(jnp.where(write, live + k, 0), cache["k"])

        def one(i, carry):
            cache, seen = carry
            cache, x, _ = serving._forward(cfg, params, cache, tokens, start,
                                           write, plan, **clean)
            return cache, seen + x[0, -1, 0].astype(jnp.float32)

        return lax.fori_loop(0, FORWARDS, one, (cache, jnp.float32(0)))

    times = []
    for _ in range(4):
        t0 = time.monotonic()
        cache, seen = run(params, cache)
        np.asarray(seen)
        times.append((time.monotonic() - t0) * 1e3 / FORWARDS)
    return cache, round(min(times[1:]), 2)


def _own_tokens(serving, cfg, params, cache, positions: int = MAX_SEQ):
    """Every line prefilled with ``positions`` tokens of its own."""
    import jax
    import jax.numpy as jnp

    i32 = jnp.int32
    ids = jax.random.randint(jax.random.PRNGKey(42), (SLOTS, positions), 0,
                             cfg.vocab_size, i32)
    for slot in range(SLOTS):
        for a in range(0, positions, 512):
            cache, _, _ = serving.prefill_chunk(
                cfg, params, cache, ids[slot, a:a + 512], i32(a),
                i32(positions), i32(slot))
    return cache


def step() -> dict:
    import jax

    cfg, params, serving, cache = _programs()
    out = {"mode": "step", "device": jax.devices()[0].device_kind,
           "burst_ms": {}, "forwards_a_burst": {}, "forward_ms": {},
           "prefill_chunk_ms": {}, "experts_touched_per_layer": {}}
    # a tree from before PR 61 commits every block by a forward of its own
    # and has no forward of two blocks to time
    wide = hasattr(serving, "burst_forwards")
    for kv_len in (0, 512):
        cache, out["prefill_chunk_ms"][kv_len], counts = _time_chunk(
            serving, cfg, params, cache, kv_len)
        out["prefill_counts"] = [int(n) for n in counts]
    cache = _own_tokens(serving, cfg, params, cache)
    for live in (256, 768, 1280):
        out["burst_ms"][live], out["forward_ms"][live] = {}, {}
        for blocks in BURSTS:
            cache, out["burst_ms"][live][blocks], counts = _time_burst(
                serving, cfg, params, cache, live, blocks)
            # the router's layer-steps over the layers: forwards that ran
            out["forwards_a_burst"][blocks] = int(counts[4]) // cfg.num_layers
        out["decode_counts"] = [int(n) for n in counts]
        out["experts_touched_per_layer"][live] = round(
            int(counts[3]) / max(int(counts[4]), 1), 1)
        for name, blocks in (("4_rows", 1), ("8_rows", 2))[:1 + wide]:
            cache, out["forward_ms"][live][name] = _time_forwards(
                serving, cfg, params, cache, live, blocks)
    return out


# The routed layer's shapes in the three routed cells' programs: tokens a
# call, hidden and expert widths, and the rule's integers (the cells'
# configuration files; LongCat's chip holds experts 0 to 15 of 512).
GLUE_SHAPES = {
    "sdar forward (P 4096, held 128)": dict(
        tokens=512, hidden=2048, ffn=768,
        rule=dict(experts=128, topk=8, use_bias=False, renormalize=True,
                  renorm_eps=0.0)),
    "lfm2 chunk (P 2048, held 64)": dict(
        tokens=512, hidden=2048, ffn=1536,
        rule=dict(experts=64, topk=4, score="sigmoid", renormalize=True)),
    "lfm2 step (P 256, held 64)": dict(
        tokens=64, hidden=2048, ffn=1536,
        rule=dict(experts=64, topk=4, score="sigmoid", renormalize=True)),
    "longcat step (P 384, held 16)": dict(
        tokens=32, hidden=6144, ffn=2048,
        rule=dict(experts=512, topk=12, scaling_factor=6.0,
                  zero_experts=256, expert_shards=32)),
    "longcat chunk (P 6144, held 16)": dict(
        tokens=512, hidden=6144, ffn=2048,
        rule=dict(experts=512, topk=12, scaling_factor=6.0,
                  zero_experts=256, expert_shards=32)),
}
GLUE_CALLS = 200


def _us_a_call(fn, *args) -> float:
    """Microseconds a call of ``fn(i, *args)`` inside one ``fori_loop`` of
    GLUE_CALLS whose carry is the call's result; the best of five."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    shapes = jax.eval_shape(lambda *a: fn(jnp.int32(0), *a), *args)

    @jax.jit
    def loop(*a):
        init = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        return lax.fori_loop(0, GLUE_CALLS, lambda i, c: fn(i, *a), init)

    jax.block_until_ready(loop(*args))
    times = []
    for _ in range(5):
        t0 = time.monotonic()
        jax.block_until_ready(loop(*args))
        times.append(time.monotonic() - t0)
    return round(min(times) / GLUE_CALLS * 1e6, 1)


def glue() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import routed

    out = {"mode": "glue", "device": jax.devices()[0].device_kind,
           "calls": GLUE_CALLS, "us_a_call": {}}
    for name, shape in GLUE_SHAPES.items():
        rule = routed.RouterRule(**shape["rule"])
        t, h, f = shape["tokens"], shape["hidden"], shape["ffn"]
        held, topk = rule.held, rule.topk
        tm = routed.row_tile(t, topk, rule.outputs)
        keys = jax.random.split(jax.random.PRNGKey(len(name)), 6)
        dt = jnp.bfloat16
        # One stacked layer; the router's columns differ in scale so that
        # the experts' fills do, as a trained router's.
        layers = {
            "router": (jax.random.normal(keys[0], (1, h, rule.outputs))
                       * (1 + 0.5 * jax.random.normal(
                           keys[1], (1, 1, rule.outputs)))
                       / np.sqrt(h)).astype(dt),
            "router_bias": jnp.zeros((1, rule.outputs), jnp.float32),
            "we_gate": (jax.random.normal(keys[2], (1, held, h, f), dt)
                        / np.sqrt(h)).astype(dt),
            "we_up": (jax.random.normal(keys[3], (1, held, h, f), dt)
                      / np.sqrt(h)).astype(dt),
            "we_down": (jax.random.normal(keys[4], (1, held, f, h), dt)
                        / np.sqrt(f)).astype(dt)}
        u = jax.random.normal(keys[5], (t, h), dt)
        valid = jnp.ones((t,), bool)
        bias = layers["router_bias"][0] if rule.use_bias else None

        def vary(i, u):
            return u + (i & 1).astype(u.dtype) * jnp.asarray(0.001, u.dtype)

        def route(i, u, router):
            return routed.route(rule, router, bias, vary(i, u))

        idx, w = jax.jit(lambda u, r: routed.route(rule, r, bias, u))(
            u, layers["router"][0])
        lo = rule.expert_shard * held
        local = (idx >= lo) & (idx < lo + held)
        pick_keys = jnp.where(local, idx - lo, held).reshape(-1).astype(
            jnp.int32)

        def plan(i, k):
            # the same fills on other experts: a pick here stays here
            return routed.dispatch_plan(
                jnp.where(k < held, (k + i) % held, held), held, tm)

        pick_of_row, row_of_pick, tile_expert, n_live, sizes = jax.jit(
            lambda k: routed.dispatch_plan(k, held, tm))(pick_keys)

        def rows_until_pr_42(u, pick_of_row, topk):
            return jnp.where((pick_of_row >= 0)[:, None],
                             u[jnp.maximum(pick_of_row, 0) // topk], 0)

        tile_rows = getattr(routed, "tile_rows", rows_until_pr_42)

        def gather_in(i, u, pick_of_row):
            return tile_rows(vary(i, u), pick_of_row, topk)

        out_rows = jax.random.normal(keys[5], (pick_of_row.shape[0], h), dt)

        def combine(i, out_rows, row_of_pick, local, w):
            # other rows each call, or the gather is hoisted out of the loop
            rows = (row_of_pick + i) % out_rows.shape[0]
            picked = out_rows[jnp.where(local, rows.reshape(t, topk), 0)]
            return jnp.sum(jnp.where(
                local[..., None], w[..., None] * picked.astype(jnp.float32),
                0.0), axis=1).astype(out_rows.dtype)

        def block(i, layers, u):
            return routed.moe_block(rule, layers, 0, vary(i, u), valid)

        out["us_a_call"][name] = {
            "tile": tm, "rows": int(pick_of_row.shape[0]),
            "picks_here": int(local.sum()),
            "experts_touched": int((sizes > 0).sum()),
            "live_tiles": int(n_live),
            "route": _us_a_call(route, u, layers["router"][0]),
            "dispatch_plan": _us_a_call(plan, pick_keys),
            "gather_in": _us_a_call(gather_in, u, pick_of_row),
            "combine": _us_a_call(combine, out_rows, row_of_pick, local, w),
            "moe_block": _us_a_call(block, layers, u)}
    return out


HEAD_ROWS = (512, 128)


def head() -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ray_tpu.llm.served import sample_tokens
    from ray_tpu.models import sdar

    cfg = config()
    h, v, k = cfg.hidden_size, cfg.vocab_size, cfg.block_length
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    dt = cfg.jnp_dtype
    params = {
        "final_norm": (1 + 0.1 * jax.random.normal(keys[0], (h,))).astype(dt),
        "lm_head": (jax.random.normal(keys[1], (h, v), jnp.float32)
                    / h ** 0.5).astype(dt)}
    x = (3.0 * jax.random.normal(keys[2], (SLOTS, k, h))).astype(dt)
    key = jax.random.PRNGKey(9)
    out = {"mode": "head", "device": jax.devices()[0].device_kind,
           "calls": GLUE_CALLS, "us_a_call": {}}

    def vary(i, a):
        return a + (i & 1).astype(a.dtype) * jnp.asarray(0.001, a.dtype)

    def logits_of(i, x):
        return sdar.lm_head(cfg, params, vary(i, x))

    def greedy(flat):
        return jnp.argmax(flat, axis=-1).astype(jnp.int32)

    def both(i, flat, temps):
        return sample_tokens(flat, temps, temps + 1.0, 0,
                             jax.random.fold_in(key, i),
                             False).astype(jnp.int32)

    def cond(i, flat, temps):
        return lax.cond((temps > 0).any(), lambda: both(i, flat, temps),
                        lambda: greedy(flat))

    def confidence(flat, x0):
        chosen = jnp.take_along_axis(flat, x0[:, None], axis=-1)[:, 0]
        return jnp.exp(chosen - jax.nn.logsumexp(flat, axis=-1))

    for rows in HEAD_ROWS:
        xr = x[:, :rows // SLOTS]
        flat = jax.jit(lambda x: logits_of(jnp.int32(0), x))(xr).reshape(
            rows, v)
        cold = jnp.zeros((rows,), jnp.float32)
        one_hot = cold.at[0].set(0.8)
        x0 = jax.jit(greedy)(flat)

        def tail(choose, conf):
            def fn(i, x, temps):
                flat = logits_of(i, x).reshape(rows, v)
                x0 = choose(i, flat, temps)
                return (x0, confidence(flat, x0)) if conf else x0
            return fn

        out["us_a_call"][rows] = {
            "head": _us_a_call(logits_of, xr),
            "argmax": _us_a_call(lambda i, f: greedy(vary(i, f)), flat),
            "argmax_and_draw": _us_a_call(
                lambda i, f, t: both(i, vary(i, f), t), flat, cold),
            "confidence": _us_a_call(
                lambda i, f, x0: confidence(vary(i, f), x0), flat, x0),
            "cond_none_drawn": _us_a_call(
                lambda i, f, t: cond(i, vary(i, f), t), flat, cold),
            "cond_one_drawn": _us_a_call(
                lambda i, f, t: cond(i, vary(i, f), t), flat, one_hot),
            "head+argmax_and_draw+confidence": _us_a_call(
                tail(both, True), xr, cold),
            "head+argmax_and_draw": _us_a_call(tail(both, False), xr, cold),
            "head+cond": _us_a_call(tail(cond, False), xr, cold),
            "head+argmax": _us_a_call(
                tail(lambda i, f, t: greedy(f), False), xr, cold)}
    rows_of = jnp.zeros((SLOTS, 1), jnp.int32)
    out["us_a_call"]["gather_128_of_512"] = _us_a_call(
        lambda i, x, r: jnp.take_along_axis(
            vary(i, x), ((r + i) % k)[:, :, None], axis=1), x, rows_of)
    return out


def parity(prompt: int = 770, blocks: int = 64, seed: int = 7) -> dict:
    """The programs' tokens against the float32 reference, as
    ``kinds/serve_common.worst_margin`` compares a run's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from reference import sdar as reference
    from rtbench import gen
    from rtbench.adapters import sdar as adapter
    from rtbench.kinds.serve_common import worst_margin

    cfg, params, serving, cache = _programs()
    cj = config_json()
    k = cfg.block_length
    ids = gen.prompt_ids(seed, 1, prompt, cj["vocab_size"])
    whole = prompt - prompt % k
    slot = 3
    for a in range(0, whole, 512):
        toks = np.zeros((512,), np.int32)
        take = min(512, whole - a)
        toks[:take] = ids[a:a + take]
        cache, _, _ = serving.prefill_chunk(
            cfg, params, cache, jnp.asarray(toks), jnp.int32(a),
            jnp.int32(whole), jnp.int32(slot))
    write = np.zeros(SLOTS, bool)
    write[slot] = True
    temps = jnp.zeros((SLOTS,), jnp.float32)
    out_ids: list[int] = []
    toks = jnp.zeros((1, SLOTS, k), jnp.int32)
    for j in range(0, blocks, 2):
        tok = np.full((SLOTS, k), -1, np.int32)
        pos = np.zeros(SLOTS, np.int32)
        pos[slot] = whole + j * k
        if j == 0:
            tok[slot, :prompt - whole] = ids[whole:]
        # each burst is handed the last block of the one before (PR 63)
        cache, toks, _ = serving.decode_burst(
            cfg, params, cache,
            _inputs(serving, jnp.asarray(tok), toks[-1],
                    jnp.asarray(write & (j > 0))), jnp.asarray(pos),
            jnp.asarray(write), temps, temps + 1.0, jax.random.PRNGKey(j), 2,
            False)
        out_ids += np.asarray(toks)[:, slot].reshape(-1).tolist()
    out_ids = out_ids[prompt - whole:]
    weights = adapter.reference_weights(params)

    def logits_of(seq):
        padded = seq + [0] * (-len(seq) % 512)
        return np.asarray(reference.logits(cj, weights,
                                           jnp.asarray(padded, jnp.int32)))

    return {"mode": "parity", "prompt": prompt, "generated": len(out_ids),
            "device": jax.devices()[0].device_kind,
            "worst_margin": worst_margin(ids, out_ids, logits_of)}


def handover(live: int = 768, bursts: int = 4) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, params, serving, cache = _programs()
    cache = _own_tokens(serving, cfg, params, cache)
    out = {"mode": "handover", "device": jax.devices()[0].device_kind,
           "live": live, "hands_over": _hands_over(serving),
           "forward_ms": {}}
    forwards = [("4_rows", 1, None), ("8_rows", 2, None)]
    if _hands_over(serving):
        line = jnp.arange(SLOTS)
        forwards += [("8_rows_two_writes", 2, line >= 0),
                     ("8_rows_quarter_dead", 2, line % 4 != 0),
                     ("8_rows_all_dead", 2, line < 0)]
    for name, blocks, clean in forwards:
        cache, out["forward_ms"][name] = _time_forwards(
            serving, cfg, params, cache, live, blocks, clean)
    i32, k, times = jnp.int32, cfg.block_length, []
    temps = jnp.zeros((SLOTS,), jnp.float32)
    open_block = jnp.full((SLOTS, k), -1, i32)
    write = jnp.ones((SLOTS,), bool)
    for _ in range(4):
        toks, t0 = jnp.zeros((1, SLOTS, k), i32), time.monotonic()
        for j in range(bursts):
            cache, toks, counts = serving.decode_burst(
                cfg, params, cache, _inputs(serving, open_block, toks[-1]),
                jnp.full((SLOTS,), live + 2 * j * k, i32), write, temps,
                temps + 1.0, jax.random.PRNGKey(j), 2, False)
        np.asarray(toks)
        times.append((time.monotonic() - t0) * 1e3 / bursts)
    out["chain_ms_a_burst"] = round(min(times[1:]), 2)
    out["forwards_a_burst"] = int(counts[4]) // cfg.num_layers
    out["burst_counts"] = [int(n) for n in counts]
    return out


def engine(stream: bool = False, window_s: float = 20.0) -> dict:
    import threading

    import jax
    from rtbench import gen

    from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams
    from ray_tpu.llm import engine as engine_mod

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "serve-generate-512.json")) as f:
        traffic = json.load(f)
    jitted = jax.jit(engine_mod.init_params, static_argnums=0)
    engine_mod.init_params = lambda cfg, key: jitted(cfg, key)
    eng_kw = {k: v for k, v in traffic["engine"].items()
              if k != "max_ongoing_requests"}
    eng = LLMEngine(LLMConfig(model=config(), **eng_kw))
    vocab = config_json()["vocab_size"]
    for w in traffic["warmup"]:
        eng.generate(gen.prompt_ids(0, 0, w["prompt_tokens"], vocab),
                     SamplingParams(max_tokens=w["max_tokens"]))
    plan = gen.closed_loop_plan(traffic, 7, 60)["requests"]
    lock, served, stop = threading.Lock(), [], threading.Event()

    def client():
        while not stop.is_set():
            with lock:
                r = plan.pop(0)
            ids = gen.prompt_ids(7, r["index"], r["prompt_tokens"], vocab)
            t0 = time.monotonic()
            req = eng.submit(ids, SamplingParams(max_tokens=r["max_tokens"]),
                             stream=stream)
            n = 0
            if stream:
                while req.stream_queue.get() is not None:
                    n += 1
            req.done.wait(300)
            served.append((time.monotonic() - t0, len(req.out_tokens), n))

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(traffic["clients"])]
    for i, t in enumerate(threads):
        t.start()
        time.sleep(traffic["stagger_s"] / len(threads))
    time.sleep(15.0)                     # every line at its own depth
    s0, t_open = eng.stats(), time.monotonic()
    time.sleep(window_s)
    s1, t_close = eng.stats(), time.monotonic()
    stop.set()
    forwards = s1["decode_steps"] - s0["decode_steps"]
    out = {"mode": "engine_stream" if stream else "engine",
           "device": jax.devices()[0].device_kind,
           "serve_tok_s_by_counters": round(
               (s1["decode_tokens"] - s0["decode_tokens"]
                + s1["prompt_tokens_prefilled"]
                - s0["prompt_tokens_prefilled"]) / (t_close - t_open), 1),
           "requests_finished": s1["finished"] - s0["finished"],
           "decode_tok_s": round((s1["decode_tokens"] - s0["decode_tokens"])
                                 / (t_close - t_open), 1),
           "wall_ms_per_forward": round((t_close - t_open) * 1e3
                                        / max(forwards, 1), 2),
           "lines_per_forward": round(
               (s1["diffusion_forwards"] - s0["diffusion_forwards"])
               / max(forwards, 1), 1),
           "prefill_chunks": s1["prefill_chunks"] - s0["prefill_chunks"],
           "ahead_share": round(
               (s1["decode_dispatches_ahead"] - s0["decode_dispatches_ahead"])
               / max(s1["decode_dispatches"] - s0["decode_dispatches"], 1),
               3)}
    eng.shutdown()
    return out


MODES = {"aot": aot, "step": step, "glue": glue, "head": head,
         "parity": parity, "handover": handover,
         "engine": engine, "engine_stream": partial(engine, stream=True)}

if __name__ == "__main__":
    for mode in sys.argv[1:] or ["aot"]:
        print(json.dumps(MODES[mode]()), flush=True)
