"""Three probes of the ``tracing.part`` mechanism that need the chip.

    chiprun -- python3 devbench/trace_parts_probe.py stale record clock

``stale``: the persistent compile cache against the scopes. The cache's
key leaves location metadata out unless
``jax_compilation_cache_include_metadata_in_key`` is set, so a program
compiled before a scope existed may be served to the tree that has it, and
its trace then shows the old name stacks. For a plain XLA function and for
one that holds a Mosaic kernel: compile without a scope into an empty
cache (process 1), then run with the scope against that cache under the
profiler (process 2) and print whether the cache hit and whether ``tf_op``
carries the part; then the same pair with the option on.

``record``: three steps of a two-layer Llama train step (flash kernels,
full remat, ``adamw_lowmem``) under the profiler, the trace's first
device plane, host plane and ``Task Environment`` written to
``chiprun_out/parts.xplane.pb`` (``benchmark/testdata/parts.xplane.pb`` is
one such run; the host plane is left out if the file would pass 1.4 MB).

``clock``: whether ``util/tracing`` spans (``time.time()``) line up with
the profiler's clock. A tiny engine decodes under one profiler session
with ``enable_tracing()`` on; for 20 ``engine.tick`` phases the recorded
span's start is compared with the same phase's ``TraceAnnotation`` start
(the trace's ``profile_start_time`` plus the event's offset).

Each mode runs in processes of its own (a chip belongs to one process at
a time; this parent never imports JAX). One JSON object a mode, last
lines.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
WORK = os.path.join(ROOT, ".chipwork", "trace_parts_probe")
OUT = os.path.join(ROOT, "chiprun_out")


def _child(mode: str, *args: str, env: dict | None = None) -> dict:
    """Run ``_<mode>`` in a process of its own; its last line is JSON."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "_" + mode, *args],
        env={**os.environ, **(env or {})}, capture_output=True, text=True)
    if proc.returncode:
        sys.stderr.write(proc.stdout[-4000:] + proc.stderr[-8000:])
        raise SystemExit(f"{mode} {args}: exit code {proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _trace_ops(trace_dir: str):
    from rtbench import trace_reduce, xplane_meta

    return xplane_meta.load(trace_reduce.find_xplane(trace_dir))


# ---- stale ----------------------------------------------------------------

def _stale_child(kind: str, scoped: str, in_key: str) -> None:
    import contextlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.util import tracing

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      in_key == "1")
    counts = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event.endswith("/cache_hits"):
            counts["hits"] += 1
        elif event.endswith("/cache_misses"):
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    def probe_fn(x, w):
        scope = tracing.part("mlp") if scoped == "1" \
            else contextlib.nullcontext()
        with scope:
            if kind == "mosaic":
                from ray_tpu.ops.norms import rms_norm

                x = rms_norm(x, w[0], 1e-5, None)
            return jnp.tanh(x @ w)

    x = jnp.ones((256, 512), jnp.bfloat16)
    w = jnp.ones((512, 512), jnp.bfloat16)
    fn = jax.jit(probe_fn)
    fn(x, w).block_until_ready()
    trace_dir = os.path.join(WORK, f"trace_{kind}_{scoped}_{in_key}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for _ in range(3):
        fn(x, w).block_until_ready()
    jax.profiler.stop_trace()
    dev = _trace_ops(trace_dir)
    names = dev.program_names()
    mine = [op for op in dev.ops
            if names.get(op.program_id) == "jit_probe_fn"]
    print(json.dumps({
        "kind": kind, "scoped": scoped == "1",
        "metadata_in_key": in_key == "1", **counts, "ops": len(mine),
        "ops_in_mlp": sum(op.part == "mlp" for op in mine),
        "tf_ops": sorted({op.tf_op for op in mine if op.tf_op})[:6]}))


def stale() -> dict:
    out = []
    for in_key in ("0", "1"):
        for kind in ("xla", "mosaic"):
            cache = os.path.join(WORK, f"cache_{kind}_{in_key}")
            shutil.rmtree(cache, ignore_errors=True)
            env = {"JAX_COMPILATION_CACHE_DIR": cache}
            cold = _child("stale", kind, "0", in_key, env=env)
            warm = _child("stale", kind, "1", in_key, env=env)
            out.append({"kind": kind, "metadata_in_key": in_key == "1",
                        "unscoped_cold": cold, "scoped_on_its_cache": warm,
                        "stale": warm["hits"] > 0
                        and warm["ops_in_mlp"] == 0})
    return {"stale": out}


# ---- record ---------------------------------------------------------------

def _write_planes(src: str, dst: str, names: list[str]) -> int:
    """Copy the planes called ``names`` of one ``.xplane.pb`` to another
    (an XSpace is its planes one after the other); returns the bytes."""
    from rtbench import xplane_meta as xm

    with open(src, "rb") as f:
        buf = f.read()
    out = bytearray()
    i = 0
    while i < len(buf):
        start = i
        key, i = xm._varint(buf, i)
        if key & 7 != 2:
            raise ValueError(f"XSpace field {key >> 3}: not a message")
        n, i = xm._varint(buf, i)
        end = i + n
        name = next((xm._text(buf, v) for num, _w, v
                     in xm._fields(buf, i, end) if num == 2), "")
        if key >> 3 == 1 and name in names:
            out += buf[start:end]
        i = end
    with open(dst, "wb") as f:
        f.write(out)
    return len(out)


def _record_child() -> None:
    import jax
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train.optim import adamw_lowmem
    from ray_tpu.train.spmd import make_llama_train_step

    cfg = LlamaConfig(vocab_size=2048, hidden_size=512,
                      intermediate_size=1024, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=128, max_seq_len=1024,
                      dtype="bfloat16", tie_embeddings=False)
    mesh = build_mesh(MeshSpec(), jax.devices()[:1])
    step_fn, init_state, shard = make_llama_train_step(
        cfg, mesh, optimizer=adamw_lowmem(3e-4, weight_decay=0.1),
        attn_impl="flash", remat=True)
    state = init_state()
    rng = np.random.default_rng(0)
    tokens = shard(rng.integers(0, cfg.vocab_size, (2, 1024), dtype=np.int32))
    targets = shard(np.roll(np.asarray(tokens), -1, axis=1))
    for _ in range(2):
        state, m = step_fn(state, tokens, targets)
    float(m["loss"])
    trace_dir = os.path.join(WORK, "trace_record")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for _ in range(3):
        state, m = step_fn(state, tokens, targets)
        float(m["loss"])
    jax.profiler.stop_trace()
    from rtbench import trace_reduce

    path = trace_reduce.find_xplane(trace_dir)
    os.makedirs(OUT, exist_ok=True)
    dst = os.path.join(OUT, "parts.xplane.pb")
    keep = ["/device:TPU:0", "Task Environment", "/host:CPU"]
    if _write_planes(path, dst, keep) > 1_400_000:
        _write_planes(path, dst, keep[:2])
    dev = _trace_ops(trace_dir)
    shares: dict = {}
    for op in dev.ops:
        shares[op.part] = shares.get(op.part, 0.0) + op.self_s
    print(json.dumps({"file": dst, "bytes": os.path.getsize(dst),
                      "ops": len(dev.ops), "busy_s": dev.busy_s(),
                      "seconds_by_part": shares}))


# ---- clock ----------------------------------------------------------------

def _profile_start_ns(path: str) -> int | None:
    """``profile_start_time`` (unix nanoseconds) of the trace's ``Task
    Environment`` plane."""
    from rtbench import xplane_meta as xm

    with open(path, "rb") as f:
        buf = f.read()
    for num, _w, plane in xm._fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, stats, stat_names = "", [], {}
        for pnum, _pw, v in xm._fields(buf, *plane):
            if pnum == 2:
                name = xm._text(buf, v)
            elif pnum == 5:
                key, value = xm._map_entry(buf, v)
                for snum, _sw, sv in xm._fields(buf, *value):
                    if snum == 2:
                        stat_names[key] = xm._text(buf, sv)
            elif pnum == 6:
                stats.append(v)
        if name != "Task Environment":
            continue
        for span in stats:
            stat_id, value = 0, None
            for snum, _sw, sv in xm._fields(buf, *span):
                if snum == 1:
                    stat_id = sv
                elif snum in (3, 4):
                    value = sv
            if stat_names.get(stat_id) == "profile_start_time":
                return value
    return None


def _clock_child() -> None:
    import time

    import jax

    from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.util import tracing
    from rtbench import trace_reduce
    from rtbench.readers import phases

    cfg = LlamaConfig(vocab_size=2048, hidden_size=512,
                      intermediate_size=1024, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=128, max_seq_len=512,
                      dtype="bfloat16", tie_embeddings=False)
    eng = LLMEngine(LLMConfig(model=cfg, max_num_seqs=4, max_seq_len=512))
    sampling = SamplingParams(max_tokens=64, temperature=0.0)
    eng.generate(list(range(5, 40)), sampling)          # warm every program
    tracing.enable_tracing()
    trace_dir = os.path.join(WORK, "trace_clock")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    t0 = time.time()
    for i in range(3):
        eng.generate(list(range(7 + i, 60 + i)), sampling)
    jax.profiler.stop_trace()
    eng.shutdown()
    path = trace_reduce.find_xplane(trace_dir)
    start_ns = _profile_start_ns(path)
    ticks = [p for p in phases.load(path) if p.name == "engine.tick"]
    spans = sorted((s for s in tracing.spans() if s.name == "engine.tick"
                    and s.start_ts >= t0), key=lambda s: s.start_ts)
    out = {"profile_start_time_ns": start_ns, "ticks_in_trace": len(ticks),
           "tick_spans": len(spans)}
    if start_ns is not None and ticks and len(ticks) == len(spans):
        # phase.start is seconds on the trace's clock; the annotation is
        # entered before the span's time.time() is taken.
        diffs_us = [(s.start_ts - (start_ns * 1e-9 + p.start)) * 1e6
                    for p, s in zip(ticks, spans)][:20]
        durs_us = [((s.end_ts - s.start_ts) - (p.end - p.start)) * 1e6
                   for p, s in zip(ticks, spans)][:20]
        diffs = sorted(diffs_us)
        out.update({
            "compared": len(diffs_us),
            "span_minus_annotation_start_us": {
                "min": diffs[0], "median": diffs[len(diffs) // 2],
                "max": diffs[-1]},
            "span_minus_annotation_duration_us": {
                "min": min(durs_us), "max": max(durs_us)},
            "first_20_us": [round(d, 1) for d in diffs_us]})
    print(json.dumps(out))


MODES = {"stale": stale,
         "record": lambda: {"record": _child("record")},
         "clock": lambda: {"clock": _child("clock")}}
CHILDREN = {"_stale": _stale_child, "_record": _record_child,
            "_clock": _clock_child}


def main(argv: list[str]) -> int:
    if argv and argv[0] in CHILDREN:
        CHILDREN[argv[0]](*argv[1:])
        return 0
    os.makedirs(WORK, exist_ok=True)
    for mode in argv or list(MODES):
        print(json.dumps(MODES[mode]()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
