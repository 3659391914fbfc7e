"""What a multi-chip host shows that chip_smoke.py does not ask.

Run each probe as its own command through the chip tool, on the four-chip
host (a chip belongs to one process at a time; every probe but ``cluster``
is one process, and ``cluster``'s parent never touches JAX):

    chiprun --chips 4 -- python3 devbench/chip_probes.py train
    chiprun --chips 4 -- python3 devbench/chip_probes.py ring
    chiprun --chips 4 -- python3 devbench/chip_probes.py replicas
    chiprun --chips 4 -- python3 devbench/chip_probes.py cluster 4
    chiprun --chips 4 -- python3 devbench/chip_probes.py detect

- ``train``: chip_smoke's train half over the local chips laid out as
  ``fsdp=n`` and as ``dp=n`` with ``zero1=True`` (the smoke itself runs
  ``dp=n``). Same model, batch and seed, so the losses compare across
  layouts and with a one-chip run; prints them and per-chip memory.
- ``ring``: ring attention over ``sp=n`` with the Pallas chunk kernel
  (already inside ``shard_map``), forward and gradients against the jnp
  reference. Nothing else on the chip path reaches that kernel.
- ``replicas``: ``build_llm_deployment(num_replicas=n)`` with one-chip
  engines in one process. Prints which device each live array sits on: the
  engine places everything on the default device unless
  ``tensor_parallel_size > 1``.
- ``cluster``: ``ray_tpu.init(address="local-cluster")`` with the node told
  it has ``TPU: n``, then two actors that each hold ``TPU: 1`` and each touch
  JAX at the same time. Nothing pins a worker to a chip, so this prints what
  each worker's JAX actually gets.
- ``detect``: what resource detection finds on this host without JAX
  (``scripts/start.py`` counts chips from ``TPU_ACCELERATOR_TYPE`` or
  metadata that the machine may not set).

Findings go to PERF.md; these print, they do not judge. Each probe prints one
JSON object as its last line.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def probe_train(cfg, n: int) -> dict:
    import ray_tpu
    from chip_smoke import train_phase

    out = {}
    ray_tpu.init(resources={"TPU": float(n)})
    try:
        for label, layout in (("fsdp", {"train_mesh_axis": "fsdp"}),
                              ("dp_zero1", {"zero1": True})):
            t0 = time.perf_counter()
            res = train_phase(dataclasses.replace(cfg, **layout), n)
            res["wall_s"] = time.perf_counter() - t0
            out[label] = res
            print(f"chip_probes: train {label}: losses {res['losses']} "
                  f"memory {res['memory']}", flush=True)
    finally:
        ray_tpu.shutdown()
    return out


def probe_ring(cfg, n: int) -> dict:
    import jax
    import jax.numpy as jnp

    from chip_smoke import relative_error
    from ray_tpu.ops.attention import attention_reference
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(sp=n), jax.local_devices()[:n])
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    b, h, hkv, s, d = 2, 8, 4, 1024 * n, 64
    q = jax.random.normal(keys[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, hkv, s, d), jnp.bfloat16)
    mix = jax.random.normal(keys[3], (b, h, s, d), jnp.float32)

    def objective(attn):
        def f(q, k, v):
            out = attn(q, k, v)
            return (out.astype(jnp.float32) * mix).sum(), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, out), grads = objective(lambda q, k, v: ring_attention_sharded(
        q, k, v, mesh, axis="sp", causal=True, impl="flash"))(q, k, v)
    (_, ref), ref_grads = jax.jit(objective(
        lambda q, k, v: attention_reference(q, k, v, causal=True)))(q, k, v)

    errors = {"out": relative_error(out, ref)}
    errors.update({name: relative_error(g, r) for name, g, r in
                   zip(("dq", "dk", "dv"), grads, ref_grads)})
    return {"sp": n, "seq": s, "relative_error": errors,
            "agree_within_2e-2": all(e <= 2e-2 for e in errors.values())}


def probe_replicas(cfg, n: int) -> dict:
    import collections
    import threading

    import jax

    import ray_tpu
    from chip_smoke import _stream_chat, device_memory
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.serving import build_openai_app

    ray_tpu.init(resources={"TPU": float(n)})
    try:
        llm = LLMConfig(model=cfg.model, max_num_seqs=4, max_seq_len=512,
                        dtype=cfg.serve_dtype, prefill_chunk=cfg.prefill_chunk)
        serve.run(build_openai_app(llm, num_replicas=n), route_prefix="/",
                  http=True, _blocking_timeout=cfg.request_timeout_s)
        url = f"http://127.0.0.1:{serve.http_port()}/v1/chat/completions"
        outs: list = [None] * (2 * n)

        def ask(i: int) -> None:
            try:
                outs[i] = _stream_chat(url, f"Replica probe request {i}.",
                                       8, cfg.request_timeout_s)
            except Exception as e:  # noqa: BLE001 - reported below
                outs[i] = repr(e)

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(outs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(cfg.request_timeout_s)
        by_devices: dict = collections.Counter()
        for a in jax.live_arrays():
            by_devices[",".join(str(d.id) for d in a.devices())] += a.nbytes
        memory = device_memory(jax.local_devices())
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return {"replicas": n, "requests": outs,
            "live_array_bytes_by_device_ids": dict(by_devices),
            "memory": memory}


_WORKER_JAX = """
import json, os, time
t0 = time.time()
import jax
devs = jax.devices()
print(json.dumps({"pid": os.getpid(), "platform": devs[0].platform,
                  "device_ids": [d.id for d in devs],
                  "init_s": round(time.time() - t0, 1)}), flush=True)
time.sleep(float(os.environ.get("PROBE_HOLD_S", "0")))
"""


class _ChipHolder:
    """An actor that holds TPU: 1, so two of them are two worker processes
    alive at the same time."""

    def see(self, hold_s: float) -> dict:
        return _what_a_worker_sees(hold_s)


def _what_a_worker_sees(hold_s: float) -> dict:
    """Runs in a cluster worker. JAX is touched in a child with a timeout,
    so a wait for a busy chip cannot outlive the probe."""
    env = {k: v for k, v in os.environ.items()
           if k.startswith(("TPU_", "JAX_", "XLA_", "LIBTPU"))}
    try:
        r = subprocess.run(
            [sys.executable, "-c", _WORKER_JAX],
            env=dict(os.environ, PROBE_HOLD_S=str(hold_s)),
            capture_output=True, text=True, timeout=hold_s + 120)
        lines = r.stdout.strip().splitlines()
        jax_view = (json.loads(lines[-1]) if r.returncode == 0 and lines
                    else {"returncode": r.returncode,
                          "stderr": r.stderr[-800:]})
    except subprocess.TimeoutExpired as e:
        jax_view = {"timeout_s": e.timeout}
    return {"worker_pid": os.getpid(), "env": env, "jax": jax_view}


def probe_cluster(n: int) -> dict:
    import ray_tpu  # the parent never imports jax: the workers need the chips

    ray_tpu.init(address="local-cluster", num_cpus=4,
                 resources={"TPU": float(n)})
    try:
        holder = ray_tpu.remote(resources={"TPU": 1.0})(_ChipHolder)
        actors = [holder.remote(), holder.remote()]
        views = ray_tpu.get([a.see.remote(20.0) for a in actors],
                            timeout=400)
    finally:
        ray_tpu.shutdown()
    assert "jax" not in sys.modules
    return {"node_tpu_resource": n, "workers": views}


def probe_detect() -> dict:
    from ray_tpu.accelerators.tpu import TpuAcceleratorManager
    from ray_tpu.scripts.start import _node_resources

    mgr = TpuAcceleratorManager()
    return {
        "env": {k: v for k, v in os.environ.items()
                if k.startswith(("TPU_", "JAX_", "XLA_", "LIBTPU"))},
        "dev_accel": sorted(p for p in os.listdir("/dev")
                            if p.startswith(("accel", "vfio"))),
        "manager_num_accelerators": mgr.get_current_node_num_accelerators(),
        "manager_resources": mgr.get_current_node_resources(),
        "start_node_resources": _node_resources(None, None),
    }


def main(which: str, *args: str) -> int:
    if which == "detect":
        out = probe_detect()
    elif which == "cluster":
        out = probe_cluster(int(args[0]))  # chips the node is told it has
    else:
        import jax

        from chip_smoke import chip_config
        from ray_tpu.accelerators.tpu import require_tpu
        from ray_tpu.utils.compile_cache import ensure_compile_cache

        require_tpu("chip_probes")
        n = jax.local_device_count()
        ensure_compile_cache()
        probe = {"train": probe_train, "ring": probe_ring,
                 "replicas": probe_replicas}[which]
        out = probe(chip_config(), n)
    print(json.dumps({which: out}, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
