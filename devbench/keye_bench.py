"""Keye-VL-2.0's serving programs at the shapes of
``keye-vl2-serve-longctx-48k`` (12 layers at the published widths, 16 of 128
experts, 8 slots x 49,152): compiled for a described v5e with no chip, and
timed on one.

    python3 devbench/keye_bench.py aot          # no chip, about a minute
    chiprun -- python3 devbench/keye_bench.py kernel step
    chiprun -- python3 devbench/keye_bench.py pair   # .parent/ beside this
    chiprun -- python3 devbench/keye_bench.py margins

``aot``: ``llm/keye_serving.py``'s ``prefill_chunk(512)`` and
``decode_burst(8)``, compiled for ``v5e:2x2``'s first device (nothing runs:
no time comes out of it): XLA's ``memory_analysis`` (arguments,
temporaries, their sum against the chip's 15.75 GiB), the Mosaic calls, and
every instruction whose result has the shape of a cache leaf or of a
stacked leaf of the experts, by opcode. ``kernel``: the three ops of
``ops/sparse_attention.py`` alone on a cache of two layers, a prefill
chunk's 512 rows against 4,096 to 44,544 cached rows and a decode step's 8
rows at lines of 8,192 to 45,056: device milliseconds a call (12 calls
inside one program), each beside its yardstick (``adapters/keye.py``'s
work over the chip's peaks) and the attention beside what its pass does
besides: a chunk's call as a share of the dense pass's FLOPs at the peak, a
step's as a share of its whole lines' bytes at the peak; the kernels
against their jnp references at one shape, and what the selection's and the
decode attention's other forms cost there: ``lax.top_k`` in the
threshold's place, and a gather of the 2,048 chosen rows in the masked
pass's place; every check reads the scores below the bound they are written
to (``written``) and a digest of ``thr`` and ``pcut`` says whether two trees
select the same sets. ``pair``: ``kernel`` on the tree laid in ``.parent/``
(this file copied over its own, so both sides are timed one way) and on this
one, a process each, and the two beside each other, before | after.
``step``: wall milliseconds of
one decode step inside a burst of 8 and of a prefill chunk of 512 (the clock
stops on a host read of the result). ``margins``: the serving programs in
bfloat16, a prompt of 8,192 in chunks of 512 and then 256 positions
teacher-forced a token a step, against ``benchmark/reference/keye.py`` on
the same weights: as they are (a sound run), with the selection left out
(every seen position attended) and with the most recent 2,048 positions in
the learned set's place; the number is a run's, the reference's top logit
minus its logit of the program's top token, worst over the decoded
positions. And how often a set differs between bfloat16 and the float32
reference: the model's own whole-sequence pass in bfloat16 against the
reference's sets over 4,096 positions. One JSON object a mode. The
configuration is the benchmark's file through its adapter; ``KEYE_CASES``
names the rows of ``margins`` to run, ``KEYE_SEEDS`` its seeds.
"""

from __future__ import annotations

import hashlib
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
from devbench.qwen3_next_bench import _peaks  # noqa: E402

SLOTS, MAX_SEQ, CHUNK = 8, 49152, 512
CALLS = 12
# ``kernel``'s rows: the cached rows under a chunk, and a step's 8 lines.
CHUNK_CACHED = (4096, 16384, 32768, 44544)
STEP_LINES = (8192, 24576, 45056)
CASES: list[str] | None = None
SEEDS = (11, 12)


def config_json() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        return json.load(f)


def config(max_seq: int = MAX_SEQ):
    from rtbench.adapters import keye as adapter

    return adapter.model_config(config_json(), "serve_longctx", max_seq)


def lowerings(cfg, params, cache, arg, slots: int = SLOTS) -> dict:
    """{name: a function that lowers that program} at the cell's shapes."""
    import jax.numpy as jnp

    from ray_tpu.llm import keye_serving as serving

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
    leaves and the stacked experts."""
    L, h = cfg.num_layers, cfg.hidden_size
    E, fe = cfg.experts_held, cfg.moe_intermediate_size
    return {"lines": f"bf16[{L},{slots},{cfg.num_kv_heads},{max_seq},"
                     f"{cfg.head_dim}]",
            "index_k": f"bf16[{L},{slots},1,{cfg.index_head_dim},{max_seq}]",
            "we_in": f"bf16[{L},{E},{h},{fe}]",
            "we_down": f"bf16[{L},{E},{fe},{h}]",
            "wq": f"bf16[{L},{h},{cfg.num_heads * cfg.head_dim}]"}


def compile_programs(cfg, slots: int = SLOTS, max_seq: int = MAX_SEQ,
                     only: str | None = None) -> dict:
    """The programs (or the one named) compiled for a described v5e: {name:
    (memory analysis, HLO text, seconds)}. tests/test_tpu_aot.py reads the
    same."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.llm import keye_serving as serving
    from ray_tpu.models import keye
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

        params = place(jax.eval_shape(partial(keye.init_params, cfg),
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
            "big": {k: opcodes_with_shape(text, s)
                    for k, s in big_shapes(cfg).items()}}
    return out


def _device_ms(fn, *args, reps: int = 3) -> float:
    """Milliseconds a call of ``fn`` (a jitted program that runs CALLS calls
    of an op inside itself), after a warm call."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        last = fn(*args)
    jax.block_until_ready(last)
    return (time.perf_counter() - t0) / reps / CALLS * 1e3


def kernel() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from rtbench.adapters import keye as adapter

    from ray_tpu.ops import sparse_attention as sa
    from ray_tpu.ops.kernels import force_kernel_backend

    cfg, cj, peaks = config(), config_json(), _peaks()
    L = 2
    dt = jnp.bfloat16
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 64))
    kc = jax.random.normal(next(ks), (L, SLOTS, cfg.num_kv_heads, MAX_SEQ,
                                      cfg.head_dim), dt)
    vc = jax.random.normal(next(ks), kc.shape, dt)
    ic = jax.random.normal(next(ks), (L, SLOTS, 1, cfg.index_head_dim,
                                      MAX_SEQ), dt)
    topk = cfg.index_topk

    def least_ms(work):
        return 1e3 * max(work["flops"] / peaks["bf16_flops_per_s"],
                         work["bytes"] / peaks["hbm_bytes_per_s"])

    def inputs(n, c):
        q = jax.random.normal(next(ks), (n, cfg.num_heads, c, cfg.head_dim),
                              dt)
        qi = jax.random.normal(next(ks), (n, cfg.index_heads, c,
                                          cfg.index_head_dim), dt)
        w = jax.random.normal(next(ks), (n, cfg.index_heads, c), jnp.float32)
        return q, qi, w

    def loop(op):
        """``op(layer)`` CALLS times in one program, a corner of each result
        summed so that none is dropped (the first 128 of its last axis: a
        sum over a chunk's whole ``[512, 49152]`` scores is a pass of XLA's
        over 100 MB, timed with the kernel until PR 67, and reads what no
        kernel wrote)."""
        def run(*args):
            def body(i, acc):
                return acc + op(i % L, *args)[..., :128].astype(
                    jnp.float32).sum()
            return lax.fori_loop(0, CALLS, body, jnp.float32(0))
        return jax.jit(run)

    out = {"mode": "kernel", "device": jax.devices()[0].device_kind,
           "calls": CALLS, "rows": []}
    shapes = [("chunk", 1, CHUNK, cached) for cached in CHUNK_CACHED] + \
             [("step", SLOTS, 1, live) for live in STEP_LINES]
    for form, n, c, cached in shapes:
        q, qi, w = inputs(n, c)
        slots = jnp.arange(n, dtype=jnp.int32)
        q0 = jnp.full((n,), cached, jnp.int32)
        lim = q0 + c
        seen = float(np.sum(cached + 1 + np.arange(c))) * n
        row = {"form": form, "rows": n * c, "cached": cached}
        scores = sa.index_scores(qi, w, ic, 0, slots, q0, lim)
        live = (q0[:, None] + jnp.arange(1, c + 1)[None, :]).reshape(-1)
        thr, pcut = sa.topk_threshold(scores.reshape(n * c, -1), topk, live)
        thr, pcut = thr.reshape(n, c), pcut.reshape(n, c)
        # ``index_scores`` writes whole chunks of the selection's 2,048
        # columns up to the last seen position and nothing above: what is
        # checked, counted or handed to ``lax.top_k`` is what lies below.
        written = -(-(cached + c) // 2048) * 2048
        below = scores[..., :written]
        row["kept_a_row"] = float(sa.kept(below, thr, pcut).sum() / (n * c))
        row["scores_digest"], row["thr_pcut_digest"] = (
            hashlib.sha1(b"".join(np.asarray(a).tobytes() for a in group)
                         ).hexdigest()[:16]
            for group in ((below,), (thr, pcut)))
        ms = _device_ms(loop(lambda l, qi, w, ic: sa.index_scores(
            qi, w, ic, l, slots, q0, lim)), qi, w, ic)
        work = (adapter.index_chunk_work(cj, seen, c) if form == "chunk"
                else adapter.index_scores_work(cj, seen))
        row["index_scores_ms"] = ms
        row["index_scores_roofline_pct"] = 100 * least_ms(work) / ms
        flat = scores.reshape(n * c, -1)
        # The selection alone: what varies a call is ``live`` (by one
        # position, as the layer varies the other rows' calls), an input the
        # kernel takes; ``flat + l`` was a pass of XLA's over the whole
        # ``[rows, 49152]`` outside it, timed with it until PR 67.
        row["index_select_ms"] = _device_ms(loop(
            lambda l, flat: sa.topk_threshold(flat, topk, live - l)[0]), flat)
        flat_below = below.reshape(n * c, -1)
        row["lax_top_k_ms"] = _device_ms(loop(
            lambda l, flat: lax.top_k(flat + l, topk)[0][:, -1]), flat_below)
        ms = _device_ms(loop(lambda l, q, kc, vc, scores: sa.sparse_attention(
            q, kc, vc, scores, thr, pcut, l, slots, q0, lim)),
            q, kc, vc, scores)
        work = adapter.sparse_attention_work(
            cj, float(n * c * min(topk, cached + 1)))
        if form == "chunk":
            # A chunk's rows share their line: the line's bytes once.
            work["bytes"] = adapter.selected_bytes_per_position(cj) * float(
                cached + c)
        row["sparse_attention_ms"] = ms
        row["sparse_attention_roofline_pct"] = 100 * least_ms(work) / ms
        # What the pass under the mask does, where the yardstick above
        # counts the chosen positions alone: a chunk multiplies every
        # position a row sees, a step fetches its lines whole.
        if form == "chunk":
            dense = 4.0 * cfg.head_dim * cfg.num_heads * seen
            row["sparse_attention_dense_flops_pct"] = (
                100 * 1e3 * dense / peaks["bf16_flops_per_s"] / ms)
        else:
            whole = adapter.selected_bytes_per_position(cj) * seen
            row["sparse_attention_line_bytes_pct"] = (
                100 * 1e3 * whole / peaks["hbm_bytes_per_s"] / ms)

            def gathered(l, q, kc, vc, scores):
                """The other form: the chosen rows gathered, then a dense
                attention over 2,048."""
                _, idx = lax.top_k(scores[:, 0, :written], topk)  # [N, topk]
                def rows(stack):
                    line = lax.dynamic_index_in_dim(stack, l, 0, False)
                    return jnp.take_along_axis(
                        line, idx[:, None, :, None], axis=2)
                kk, vv = rows(kc), rows(vc)                   # [N,Hkv,k,D]
                qg = q.reshape(n, cfg.num_kv_heads, -1, cfg.head_dim)
                s = jnp.einsum("nhgd,nhkd->nhgk", qg, kk,
                               preferred_element_type=jnp.float32)
                p = jax.nn.softmax(s / cfg.head_dim ** 0.5, axis=-1)
                return jnp.einsum("nhgk,nhkd->nhgd", p.astype(dt), vv)
            row["gather_attention_ms"] = _device_ms(
                loop(gathered), q, kc, vc, scores)
        if (form, cached) in (("chunk", CHUNK_CACHED[0]),
                              ("step", STEP_LINES[0])):
            # The kernels against their jnp references, here where the
            # reference's [rows, heads, positions] products still fit.
            with force_kernel_backend("reference"):
                want = sa.index_scores(qi, w, ic, 0, slots, q0, lim)
                t_ref, p_ref = sa.topk_threshold(
                    want.reshape(n * c, -1), topk)
                same = sa.kept(want, t_ref.reshape(n, c), p_ref.reshape(n, c))
                # (the jnp form reads the whole array: -inf where the
                # kernel wrote nothing)
                o_ref = sa.sparse_attention(
                    q, kc, vc, scores.at[..., written:].set(-jnp.inf), thr,
                    pcut, 0, slots, q0, lim)
            want, same = want[..., :written], same[..., :written]
            fin = jnp.isfinite(want)
            row["index_scores_max_diff"] = float(jnp.max(jnp.where(
                fin, jnp.abs(below - want), 0.0)))
            row["index_scores_inf_agree"] = bool(
                jnp.all(fin == jnp.isfinite(below)))
            # The selection on the kernel's own scores, against top_k's.
            t2, p2 = sa.topk_threshold_reference(flat_below, topk)
            row["select_sets_equal"] = bool(jnp.all(
                sa.kept(flat_below, t2, p2) == sa.kept(
                    flat_below, thr.reshape(-1), pcut.reshape(-1))))
            row["sets_differ_rows_vs_reference_scores"] = int(jnp.sum(jnp.any(
                same != sa.kept(below, thr, pcut), axis=-1)))
            o = sa.sparse_attention(q, kc, vc, scores, thr, pcut, 0, slots,
                                    q0, lim)
            row["sparse_attention_max_diff"] = float(jnp.max(jnp.abs(
                o.astype(jnp.float32) - o_ref.astype(jnp.float32))))
        out["rows"].append(row)
        print(json.dumps(row), flush=True)
    return out


PAIRED = ("index_scores_ms", "index_select_ms", "sparse_attention_ms")


def pair() -> dict:
    """``kernel`` before | after: the tree in ``.parent/`` (``git archive
    <commit> | tar -x -C .parent``) and this one, a process each (neither
    may find the chip held: this process stays off JAX)."""
    import shutil
    import subprocess

    parent = os.path.join(ROOT, ".parent")
    shutil.copy(os.path.abspath(__file__),
                os.path.join(parent, "devbench", "keye_bench.py"))
    sides = {}
    for side, tree in (("before", parent), ("after", ROOT)):
        done = subprocess.run(
            [sys.executable, os.path.join(tree, "devbench", "keye_bench.py"),
             "kernel"], capture_output=True, text=True)
        if done.returncode:
            raise RuntimeError(f"{side}: {done.stderr[-2000:]}")
        sides[side] = json.loads(done.stdout.splitlines()[-1])["rows"]
    out = {"mode": "pair", "rows": []}
    for before, after in zip(sides["before"], sides["after"]):
        row = {"form": before["form"], "cached": before["cached"],
               "same_scores": before["scores_digest"]
               == after["scores_digest"],
               "same_thr_pcut": before["thr_pcut_digest"]
               == after["thr_pcut_digest"]}
        for key in PAIRED + ("kept_a_row",):
            row[key] = [before[key], after[key]]
        out["rows"].append(row)
        print(f"{row['form']:>5} {row['cached']:>6}  " + "  ".join(
            f"{key[:-3]} {before[key]:.3f} | {after[key]:.3f}"
            for key in PAIRED) + f"  same scores {row['same_scores']}"
            f"  same thr, pcut {row['same_thr_pcut']}", flush=True)
    out["sides"] = sides
    return out


def step() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm import keye_serving as serving
    from ray_tpu.models import keye

    cfg = config()
    params = jax.jit(keye.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))
    cache = serving.init_cache(cfg, SLOTS, MAX_SEQ)
    i32 = jnp.int32
    out = {"mode": "step", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "decode_ms_per_step": {},
           "prefill_chunk_ms": {}}
    ids = jax.random.randint(jax.random.PRNGKey(7), (CHUNK,), 259,
                             cfg.vocab_size, i32)
    # Past what was written the rows are zeros: the kernels' time does not
    # depend on the values.
    for cached in (0, 4096, 16384, 32768, 44544):
        times = []
        for _ in range(4):
            t0 = time.monotonic()
            cache, logits, counts = serving.prefill_chunk(
                cfg, params, cache, ids, i32(cached), i32(cached + CHUNK),
                i32(0))
            np.asarray(logits[:1])
            times.append((time.monotonic() - t0) * 1e3)
        out["prefill_chunk_ms"][cached] = round(min(times[1:]), 2)
        out[f"prefill_counts_{cached}"] = [int(n) for n in counts]
    temps = jnp.zeros((SLOTS,), jnp.float32)
    tok = jax.random.randint(jax.random.PRNGKey(8), (SLOTS,), 259,
                             cfg.vocab_size, i32)
    for live in (2048, 8192, 24576, 45056):
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
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    return out


def _controls():
    """{case: a context manager that puts that form in the program's
    place}. The serving programs are traced under it (``jax.clear_caches``
    around each)."""
    import contextlib

    import jax.numpy as jnp

    from ray_tpu.ops import sparse_attention as sa

    @contextlib.contextmanager
    def swapped(**names):
        old = {k: getattr(sa, k) for k in names}
        for k, v in names.items():
            setattr(sa, k, v)
        try:
            yield
        finally:
            for k, v in old.items():
                setattr(sa, k, v)

    scores_of, select = sa.index_scores, sa.topk_threshold

    def keep_all(scores, k, live=None):
        """The selection left out: every seen position is attended."""
        r = scores.shape[0]
        return (jnp.full((r,), -jnp.inf, jnp.float32),
                jnp.full((r,), -1, jnp.int32))

    def recency(*args):
        """The most recent positions in the learned set's place: a seen
        position's score is its position."""
        real = scores_of(*args)
        at = jnp.arange(real.shape[-1], dtype=jnp.float32)
        return jnp.where(real > -jnp.inf, at, -jnp.inf)

    return {"sound": lambda: swapped(),
            "no_selection": lambda: swapped(topk_threshold=keep_all),
            "recent_window": lambda: swapped(index_scores=recency,
                                             topk_threshold=select)}


def margins() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from reference import keye as reference
    from rtbench.adapters import keye as adapter

    from ray_tpu.llm import keye_serving as serving
    from ray_tpu.models import keye

    prompt, steps, max_seq = 8192, 256, 8704
    cfg, cj = config(max_seq), config_json()
    i32 = jnp.int32
    out = {"mode": "margins", "device": jax.devices()[0].device_kind,
           "layers": cfg.num_layers, "prompt": prompt, "steps": steps,
           "embed_size": keye.EMBED_SIZE, "rows": []}
    init = jax.jit(keye.init_params, static_argnums=0)
    controls = _controls()
    names = [c for c in controls if CASES is None or c in CASES]
    for seed in SEEDS:
        params = init(cfg, jax.random.PRNGKey(seed))
        weights = adapter.reference_weights(params)
        ids = jax.random.randint(jax.random.PRNGKey(100 + seed),
                                 (prompt + steps,), 259, cfg.vocab_size, i32)
        host_ids = np.asarray(ids)
        want = np.asarray(reference.logits(cj, weights, jnp.pad(
            ids, (0, -len(host_ids) % CHUNK))))[prompt - 1:prompt + steps - 1]
        for name in names:
            jax.clear_caches()
            with controls[name]():
                cache = serving.init_cache(cfg, 2, max_seq)
                for start in range(0, prompt, CHUNK):
                    cache, logits, _ = serving.prefill_chunk(
                        cfg, params, cache, ids[start:start + CHUNK],
                        i32(start), i32(prompt), i32(1))
                picks = [int(np.asarray(logits).argmax())]
                write = jnp.array([False, True])
                for p in range(prompt, prompt + steps - 1):
                    cache, logits, counts = serving.decode_step(
                        cfg, params, cache, jnp.array([0, host_ids[p]], i32),
                        jnp.array([0, p], i32), write)
                    picks.append(int(np.asarray(logits[1]).argmax()))
                del cache
            gaps = want.max(axis=1) - want[np.arange(len(picks)),
                                           np.asarray(picks)]
            out["rows"].append({
                "seed": seed, "case": name, "worst": float(gaps.max()),
                "p99": float(np.percentile(gaps, 99)),
                "mean": float(gaps.mean()),
                "over_0.2": int((gaps > 0.2).sum()),
                "differ": int((gaps > 0).sum()),
                "last_step_counts": [int(n) for n in counts]})
            print(json.dumps(out["rows"][-1]), flush=True)
        jax.clear_caches()
        if CASES is None or "sets" in CASES:
            out["rows"].append(_sets_row(cfg, cj, params, weights, ids, seed))
            print(json.dumps(out["rows"][-1]), flush=True)
        del params, weights, want
    return out


def _sets_row(cfg, cj, params, weights, ids, seed: int, length: int = 4096
              ) -> dict:
    """How often a row's set differs between bfloat16 and the float32
    reference: the model's own whole-sequence pass in bfloat16
    (``keye.forward``, the jnp forms of the ops) against the reference's
    sets, over the rows that see more than ``topk`` positions."""
    import jax.numpy as jnp
    import numpy as np
    from reference import keye as reference

    from ray_tpu.models import keye
    from ray_tpu.ops.kernels import force_kernel_backend

    topk = cfg.index_topk
    ref_sets: list = []
    reference.logits(cj, weights, ids[:length], ref_sets)
    picks: list = []
    with force_kernel_backend("reference"):
        keye.forward(cfg, params, ids[None, :length], picks=picks)
    differ, swaps, rows = 0, 0, 0
    for layer in range(cfg.num_layers):
        ref = np.concatenate([np.asarray(b) for b in ref_sets[layer]])
        got = np.asarray(picks[layer][0])
        wrong = (ref != got)[topk:].sum(axis=1) // 2
        differ += int((wrong > 0).sum())
        swaps += int(wrong.sum())
        rows += length - topk
    return {"seed": seed, "case": "sets", "positions": length,
            "pairs": rows, "pairs_that_differ": differ,
            "share_pct": 100.0 * differ / rows,
            "swapped_positions_a_differing_pair": swaps / max(differ, 1),
            "swapped_share_of_a_set_pct": 100.0 * swaps / max(rows, 1) / topk}


MODES = {"aot": aot, "kernel": kernel, "pair": pair, "step": step,
         "margins": margins}

if __name__ == "__main__":
    if "KEYE_CASES" in os.environ:
        CASES = os.environ["KEYE_CASES"].split(",")
    if "KEYE_SEEDS" in os.environ:
        SEEDS = tuple(int(s) for s in os.environ["KEYE_SEEDS"].split(","))
    for mode in sys.argv[1:] or ["aot"]:
        print(json.dumps(MODES[mode]()), flush=True)
