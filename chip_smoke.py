"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process drives both main paths once, through the entry points a user
calls, at the published Llama-3.2-1B widths with seeded random weights:

- train: ``ray_tpu.init`` → ``JaxTrainer(train_fn).fit()``, where
  ``train_fn`` builds ``make_llama_train_step`` over every local chip with the
  flash kernels, takes a few steps on one fixed batch and calls
  ``session.report`` after each;
- serve: ``serve.run(build_openai_app(LLMConfig(...)), http=True)`` with the
  engine tensor-parallel over every local chip, then streamed
  ``/v1/chat/completions`` requests over HTTP.

Before them it compares the Pallas kernels with their jnp references on a
small input.

Nothing is cut from the model: all 16 layers, vocabulary 128,256, sequences
of 2,048. The global batch is 4 sequences on any number of chips, so the
loss of a step is comparable between a one-chip and a four-chip run; to
leave room for that batch on one 16 GB chip the step recomputes each layer
in the backward pass (``remat=True``, 12.2 GiB by XLA's own accounting
against 15.2 GiB with ``remat="attn"``).

It fails, with an exit code other than 0 and no result line, when JAX finds
no TPU, when ``ray_tpu`` cannot be imported, or when either half raises or
fails a check. It takes no flag and reads no environment switch. The two
halves are importable (``train_phase``, ``serve_phase``) and take a
:class:`SmokeConfig`, so tests/test_chip_smoke.py drives the same control
flow on the CPU at ``LlamaConfig.tiny()`` widths.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

Run it through the chip tool from the root of a checkout:
``chiprun -- python3 chip_smoke.py`` (``--chips 4`` for the four-chip host).
"""

from __future__ import annotations

import dataclasses
import json
import re
import sys
import threading
import time
import urllib.request
from typing import Any

# Kernels the compiled train step must contain (the names the pallas_calls in
# ray_tpu/ops carry): without them a silent reference path would pass.
REQUIRED_KERNELS = ("flash_fwd", "flash_bwd", "rms_norm")


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    model: Any                    # LlamaConfig: the widths both halves use
    kernel_seq: int = 512         # sequence length of the kernel comparison
    train_batch: int = 4          # global batch, whatever the chip count
    train_seq: int = 2048
    train_steps: int = 4
    remat: bool | str = True
    # How the trainer lays the local chips out. The smoke itself runs data
    # parallel; devbench/chip_probes.py runs the other layouts by hand.
    train_mesh_axis: str = "dp"   # "dp" | "fsdp" | "tp"
    zero1: bool = False
    serve_slots: int = 8
    serve_max_seq: int = 1024
    serve_dtype: str | None = "bfloat16"
    prefill_chunk: int = 512
    max_tokens: int = 16          # 2 x decode_burst: walks every burst length
    long_prompt_chars: int = 700  # byte tokenizer: more than prefill_chunk
    request_timeout_s: float = 600.0


def chip_config() -> SmokeConfig:
    from ray_tpu.models.llama import LlamaConfig

    return SmokeConfig(model=dataclasses.replace(
        LlamaConfig.llama3_1b(), max_seq_len=2048))


class CacheCounter:
    """Persistent-compile-cache traffic, from jax.monitoring's events."""

    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
              "/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        import jax

        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        key = self.EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1


def kernel_ops(hlo_text: str, mosaic_only: bool) -> dict[str, int]:
    """How many instructions of a compiled module come from each named
    kernel in :data:`REQUIRED_KERNELS` (``op_name=".../<kernel>/..."``).
    ``mosaic_only`` counts Mosaic custom calls alone, which is what a TPU
    module must show; the Pallas interpreter leaves plain HLO under the
    same names."""
    counts = dict.fromkeys(REQUIRED_KERNELS, 0)
    for line in hlo_text.splitlines():
        if mosaic_only and 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        if m is None:
            continue
        scopes = m.group(1).split("/")
        for name in REQUIRED_KERNELS:
            if name in scopes:
                counts[name] += 1
    return counts


def device_memory(devices, holding: str | None = None) -> list[dict | None]:
    """bytes_in_use / peak_bytes_in_use per device (None where the backend
    keeps no statistics, as on the CPU). With ``holding`` it also checks
    that every device holds some of that state right now."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        if stats is None and d.platform == "tpu":
            raise RuntimeError(f"{d} reports no memory statistics")
        out.append(None if stats is None else {
            "bytes_in_use": int(stats["bytes_in_use"]),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0))})
    idle = [i for i, m in enumerate(out)
            if m is not None and m["bytes_in_use"] <= 0]
    if holding and idle:
        raise AssertionError(f"devices {idle} hold no {holding}")
    return out


def relative_error(a, b) -> float:
    """max |a - b| over max |b|, in float32."""
    import numpy as np

    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-9))


# -------------------------------------------------------------------- kernels

def kernel_phase(cfg: SmokeConfig, n_devices: int) -> dict:
    """The Pallas kernels against their jnp references on a small input:
    rms_norm and causal GQA flash attention, forward and gradients, per
    shard over the local chips when there are several; then rms_norm on a
    row count that no block divides, and the serving decode and prefill
    kernels.
    Returns the largest relative errors."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.attention import attention_reference, flash_attention
    from ray_tpu.ops.norms import rms_norm, rms_norm_reference
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import kernel_mesh

    kmesh = kernel_mesh(build_mesh(MeshSpec(dp=n_devices),
                                   jax.local_devices()[:n_devices]))
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    b, h, hkv, s, d = 2 * n_devices, 8, 4, cfg.kernel_seq, 64
    q = jax.random.normal(keys[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, hkv, s, d), jnp.bfloat16)
    w = 1.0 + 0.1 * jax.random.normal(keys[3], (d,), jnp.bfloat16)
    mix = jax.random.normal(keys[4], (b, h, s, d), jnp.float32)

    def objective(attn, norm):
        def f(q, k, v, w):
            out = attn(norm(q, w), k, v)
            return (out.astype(jnp.float32) * mix).sum(), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3),
                                          has_aux=True))

    (_, out), grads = objective(
        lambda q, k, v: flash_attention(q, k, v, True, None, True, kmesh),
        lambda x, w: rms_norm(x, w, 1e-5, kmesh))(q, k, v, w)
    (_, ref), ref_grads = objective(
        lambda q, k, v: attention_reference(q, k, v, causal=True),
        lambda x, w: rms_norm_reference(x, w, 1e-5))(q, k, v, w)

    errors = {"out": relative_error(out, ref)}
    errors.update({name: relative_error(g, r) for name, g, r in
                   zip(("dq", "dk", "dv", "dw"), grads, ref_grads)})
    # 424 rows: a prefill chunk clamped to the cache tail (one full block
    # of 256 and a partial one).
    x = jax.random.normal(keys[0], (1, 424, 2048), jnp.bfloat16)
    wx = 1.0 + 0.1 * jax.random.normal(keys[3], (2048,), jnp.bfloat16)
    errors["rms_norm_424_rows"] = relative_error(
        jax.jit(lambda x, w: rms_norm(x, w, 1e-5))(x, wx),
        rms_norm_reference(x, wx, 1e-5))
    errors.update(_serving_kernel_errors(cfg, kmesh, keys, b, hkv, d))
    bad = {name: e for name, e in errors.items()
           if not np.isfinite(e) or e > 2e-2}
    if bad:
        raise AssertionError(f"kernels disagree with their references "
                             f"(relative error > 2e-2): {bad}")
    return errors


def _serving_kernel_errors(cfg: SmokeConfig, kmesh, keys, b: int, hkv: int,
                          d: int) -> dict:
    """The serving decode kernels on layer 1 of a stack of two: one new row
    a slot written in place, then grouped attention over the live blocks,
    per shard over the slots; lines empty, of one row, across a block edge
    and full. Then the prefill kernel on the same stack."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import decode_attention as da

    s = 2 * cfg.kernel_seq
    lengths = jnp.asarray([(0, 129, 1, s)[i % 4] for i in range(b)],
                          jnp.int32)
    write, pos = lengths > 0, jnp.maximum(lengths - 1, 0)
    q = jax.random.normal(keys[0], (b, 2 * hkv, 1, d), jnp.bfloat16)
    kc = jax.random.normal(keys[1], (2, b, hkv, s, d), jnp.bfloat16)
    vc = jax.random.normal(keys[2], (2, b, hkv, s, d), jnp.bfloat16)
    nk = jax.random.normal(keys[3], (b, hkv, 1, d), jnp.bfloat16)
    nv = jax.random.normal(keys[4], (b, hkv, 1, d), jnp.bfloat16)

    @jax.jit
    def kernels(kc, vc):
        kc, vc = da.kv_row_write(kc, vc, nk, nv, 1, pos, write, kmesh=kmesh)
        return kc, da.decode_attention(q, kc, vc, 1, lengths, pos,
                                       kmesh=kmesh, block=128)

    @jax.jit
    def references(kc, vc):
        kc, vc = da.kv_row_write_reference(kc, vc, nk, nv, 1, pos, write)
        return kc, da.decode_attention_reference(q, kc, vc, 1, lengths, pos)

    (got_k, got), (want_k, want) = kernels(kc, vc), references(kc, vc)
    return {"kv_row_write": float(not np.array_equal(got_k, want_k)),
            "decode_attention": relative_error(got, want),
            "prefill_attention": _prefill_kernel_error(kmesh, keys, kc, vc)}


def _prefill_kernel_error(kmesh, keys, kc, vc) -> float:
    """The serving prefill kernel on layer 1 of the same stack: a padded
    final chunk clamped to the tail of the last slot's line (neither its
    size nor its first row aligned to anything), written in place, then
    attended to through ops/prefill_attention.py."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import prefill_attention as pa

    _, b, hkv, s, d = kc.shape
    c = s // 2 - 24
    slot, kv_len, length = b - 1, s - c, s - 3
    q = jax.random.normal(keys[0], (2 * hkv, c, d), jnp.bfloat16)
    nk = jax.random.normal(keys[3], (hkv, c, d), jnp.bfloat16)
    nv = jax.random.normal(keys[4], (hkv, c, d), jnp.bfloat16)

    def run(attend):
        def f(kc, vc):
            kc, vc = pa.prefill_kv_write(kc, vc, nk, nv, 1, slot, kv_len)
            return attend(q, kc, vc, 1, slot, kv_len, length)
        return jax.jit(f)(kc, vc)[:, :length - kv_len]

    return relative_error(
        run(lambda *a: pa.prefill_attention(*a, kmesh=kmesh, block_k=128)),
        run(pa.prefill_attention_reference))


# ---------------------------------------------------------------------- train

def _train_loop(config: dict) -> None:
    """Runs on the trainer's worker: build the step over every local chip,
    step, report. Checks raise here and reach the driver as Result.error."""
    import jax
    import numpy as np

    from ray_tpu.ops.kernels import kernel_backend
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train import session
    from ray_tpu.train.optim import adamw_lowmem
    from ray_tpu.train.spmd import make_llama_train_step

    cfg: SmokeConfig = config["smoke"]
    devices = jax.local_devices()[:config["n_devices"]]
    mesh = build_mesh(MeshSpec(**{cfg.train_mesh_axis: len(devices)}),
                      devices)
    step_fn, init_state, shard = make_llama_train_step(
        cfg.model, mesh, optimizer=adamw_lowmem(3e-4, weight_decay=0.1),
        attn_impl="flash", remat=cfg.remat, zero1=cfg.zero1)
    state = init_state()
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.model.vocab_size,
                          (cfg.train_batch, cfg.train_seq), dtype=np.int32)
    targets = shard(np.roll(tokens, -1, axis=1))
    tokens = shard(tokens)

    t0 = time.perf_counter()
    compiled = step_fn.lower(state, tokens, targets).compile()
    compile_s = time.perf_counter() - t0
    kernels = kernel_ops(compiled.as_text(),
                         mosaic_only=kernel_backend() == "mosaic")
    missing = [k for k, n in kernels.items() if n == 0]
    if missing:
        raise AssertionError(
            f"compiled train step has no {missing} kernel "
            f"(kernel backend {kernel_backend()!r}): {kernels}")

    for step in range(cfg.train_steps):
        t0 = time.perf_counter()
        state, metrics = compiled(state, tokens, targets)
        loss = float(metrics["loss"])  # waits for the step
        step_s = time.perf_counter() - t0
        if not np.isfinite(loss):
            raise AssertionError(f"step {step}: loss {loss}")
        report = {"step": step, "loss": loss, "step_s": step_s,
                  "tokens": cfg.train_batch * cfg.train_seq}
        if step == 0:
            # The state is alive here: every chip of the mesh must hold
            # some of it.
            report.update(compile_s=compile_s, kernels=kernels,
                          memory=device_memory(devices, "train state"))
        session.report(report)
    del state, compiled


def train_phase(cfg: SmokeConfig, n_devices: int) -> dict:
    """Train half, on an initialised runtime. Returns what it observed."""
    from ray_tpu.train.config import ScalingConfig
    from ray_tpu.train.trainer import JaxTrainer

    result = JaxTrainer(
        _train_loop,
        train_loop_config={"smoke": cfg, "n_devices": n_devices},
        scaling_config=ScalingConfig(
            num_workers=1, resources_per_worker={"TPU": float(n_devices)}),
    ).fit()
    if result.error:
        raise RuntimeError(f"train phase failed:\n{result.error}")
    history = result.metrics_history
    losses = [m["loss"] for m in history]
    if len(losses) != cfg.train_steps:
        raise AssertionError(
            f"{len(losses)} reports for {cfg.train_steps} steps")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    first = history[0]
    if len(first["memory"]) != n_devices:
        raise AssertionError(
            f"trainer ran on {len(first['memory'])} devices, not {n_devices}")
    return {"losses": losses, "step_s": [m["step_s"] for m in history],
            "compile_s": first["compile_s"], "kernels": first["kernels"],
            "memory": first["memory"]}


# ---------------------------------------------------------------------- serve

def _stream_chat(url: str, content: str, max_tokens: int,
                 timeout: float) -> dict:
    """One streamed greedy chat completion; returns its text, the number of
    content frames and the finish reason."""
    body = json.dumps({
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens, "temperature": 0.0, "stream": True,
    }).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    text, frames, finish, done = [], 0, None, False
    with urllib.request.urlopen(req, timeout=timeout) as r:
        if r.status != 200:
            raise RuntimeError(f"HTTP {r.status}")
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            data = line[len("data:"):].strip()
            if data == "[DONE]":
                done = True
                break
            choice = json.loads(data)["choices"][0]
            if "content" in choice["delta"]:
                frames += 1
                text.append(choice["delta"]["content"])
            if choice["finish_reason"] is not None:
                finish = choice["finish_reason"]
    if not done:
        raise RuntimeError("stream ended without [DONE]")
    return {"text": "".join(text), "frames": frames, "finish_reason": finish}


def serve_phase(cfg: SmokeConfig, n_devices: int) -> dict:
    """Serve half, on an initialised runtime. Returns what it observed."""
    import jax

    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.serving import build_openai_app

    llm = LLMConfig(model=cfg.model, max_num_seqs=cfg.serve_slots,
                    max_seq_len=cfg.serve_max_seq, dtype=cfg.serve_dtype,
                    prefill_chunk=cfg.prefill_chunk,
                    tensor_parallel_size=n_devices)
    # The replica builds 1B parameters before it reports healthy.
    handle = serve.run(build_openai_app(llm), route_prefix="/", http=True,
                       _blocking_timeout=cfg.request_timeout_s)
    try:
        url = (f"http://127.0.0.1:{serve.http_port()}"
               "/v1/chat/completions")

        def ask(content: str) -> dict:
            return _stream_chat(url, content, cfg.max_tokens,
                                cfg.request_timeout_s)

        # Two identical greedy requests, one after the other: the second
        # adopts the first one's cached prefix and must say the same.
        # (The byte tokenizer writes every id beyond its own range as
        # <|id|>, so equal text means equal tokens.)
        same = "Name the planets of the solar system in order."
        results = [ask(same), ask(same)]
        if not results[0]["text"] or \
                results[0]["text"] != results[1]["text"]:
            raise AssertionError(
                "same greedy prompt, different text: "
                f"{results[0]['text']!r} vs {results[1]['text']!r}")

        # Four at once, one of them long enough to prefill in chunks.
        long_prompt = ("Summarise the following notes. " +
                       "The quick brown fox jumps over the lazy dog. " * 40
                       )[:cfg.long_prompt_chars]
        prompts = [long_prompt] + [
            f"Question {i}: what is {i} times {i + 1}?" for i in range(3)]
        outs: list = [None] * len(prompts)

        def worker(i: int) -> None:
            try:
                outs[i] = ask(prompts[i])
            except Exception as e:  # noqa: BLE001 - re-raised on the caller
                outs[i] = e

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(cfg.request_timeout_s)
        for i, out in enumerate(outs):
            if out is None:
                raise TimeoutError(f"concurrent request {i} did not return")
            if isinstance(out, Exception):
                raise out
        results += outs

        for i, out in enumerate(results):
            if out["frames"] != cfg.max_tokens or \
                    out["finish_reason"] in (None, "error"):
                raise AssertionError(
                    f"request {i}: {out['frames']} content frames of "
                    f"{cfg.max_tokens}, finish_reason "
                    f"{out['finish_reason']!r}")

        stats = handle.stats.remote().result(timeout=60)
        if stats["device_failures"] or stats["requests_failed"]:
            raise AssertionError(f"engine reported failures: {stats}")
        memory = device_memory(jax.local_devices()[:n_devices],
                               "engine state")
    finally:
        serve.shutdown()
    return {"requests": len(results), "stats": stats, "memory": memory,
            "finish_reasons": sorted({o["finish_reason"] for o in results})}


# ----------------------------------------------------------------------- main

def run_smoke(cfg: SmokeConfig, n_devices: int) -> dict:
    """Both halves on one in-process runtime (one OS process holds the
    chips; the runtime does not detect them, so it is told)."""
    import ray_tpu

    kernels = kernel_phase(cfg, n_devices)
    # A runtime that is already up (a test's, earlier in this process) has
    # no TPU among its resources, and ``init`` would return it as it is.
    ray_tpu.shutdown()
    ray_tpu.init(resources={"TPU": float(n_devices)})
    try:
        t0 = time.perf_counter()
        train = train_phase(cfg, n_devices)
        t1 = time.perf_counter()
        serve = serve_phase(cfg, n_devices)
        t2 = time.perf_counter()
    finally:
        ray_tpu.shutdown()
    train["wall_s"], serve["wall_s"] = t1 - t0, t2 - t1
    return {"kernels": kernels, "train": train, "serve": serve}


def main() -> int:
    t_start = time.perf_counter()
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform="
              f"{device.platform!r} ({device.device_kind})", file=sys.stderr)
        return 2

    import importlib.metadata

    import jaxlib

    from ray_tpu.utils.compile_cache import ensure_compile_cache

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "unknown"
    cache_dir = ensure_compile_cache()
    cache = CacheCounter()
    n = jax.local_device_count()
    print(f"chip_smoke: platform={device.platform} "
          f"device_kind={device.device_kind!r} local_devices={n} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu} compile_cache={cache_dir}", flush=True)

    out = run_smoke(chip_config(), n)

    train, serve = out["train"], out["serve"]
    print("chip_smoke: kernels agree with their references, relative error "
          f"{ {k: round(v, 4) for k, v in out['kernels'].items()} }")
    print(f"chip_smoke: train ok in {train['wall_s']:.1f}s "
          f"(compile {train['compile_s']:.1f}s): losses "
          f"{[round(x, 4) for x in train['losses']]} step_s "
          f"{[round(x, 3) for x in train['step_s']]} "
          f"mosaic_calls {train['kernels']}")
    print(f"chip_smoke: train memory per chip {train['memory']}")
    print(f"chip_smoke: serve ok in {serve['wall_s']:.1f}s: "
          f"{serve['requests']} streamed requests, finish "
          f"{serve['finish_reasons']}, stats {serve['stats']}")
    print(f"chip_smoke: serve memory per chip {serve['memory']}")
    print(f"chip_smoke: compile cache {cache.counts} dir {cache_dir} "
          f"total {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
