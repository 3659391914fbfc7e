"""The LongCat-Flash serving programs and their kernels, timed on the chip.

    chiprun -- python3 devbench/longcat_bench.py [check] [parity] [burst] [prefill] [trace]

At the benchmark's configuration (benchmark/configs/longcat-flash-chat.json:
published widths, 4 double layers, 16 of 512 experts, 32 slots x 8,192),
random weights from the program's own ``init_params``.

- ``check``: each new kernel (latent decode attention, latent row write,
  grouped matmul) against its jnp reference on the same inputs at the real
  widths.
- ``parity``: one sequence through the serving programs (three chunks, then
  single decode steps through the latent cache), through
  ``models/longcat.forward`` and through the float32 reference, on one set
  of weights: largest logit difference and margin of each pair.
- ``burst``: ``decode_burst(steps=8)`` as the engine calls it at a few live
  lengths, ms a step, with the floor of its bytes beside it.
- ``prefill``: ``prefill_chunk(512)`` at a few cached lengths, and the
  forms of the chunk's attention side by side: the kernel of
  ops/latent_attention.py (up-projected in VMEM), its XLA reference
  (up-projected, a block's keys, values and scores arrays in HBM) and
  absorbed (kept here, for the comparison alone).
- ``trace``: a profiler trace of a few bursts and chunks, its top device
  ops printed (benchmark/rtbench/trace_reduce.py).

Prints one JSON object per measurement. Times are host clock around
``block_until_ready`` over repeated calls of one jitted program.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

HBM_BYTES_PER_S = 819e9  # TPU v5e, Google Cloud documentation
SLOTS, MAX_SEQ, USE = 32, 8192, "serve_agent"


def out(**row) -> None:
    print(json.dumps(row), flush=True)


def timed(fn, reps: int):
    """Seconds a call, after one warm call; ``fn()`` returns something to
    wait for."""
    import jax

    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        last = fn()
    jax.block_until_ready(last)
    return (time.perf_counter() - t0) / reps


def absorbed_prefill_attention(q_n, q_r, cache, w_kb, w_vb, layer, slot,
                               kv_len, length, *, rope_dim, sm_scale,
                               block=None):
    """The other form of ops/latent_attention.latent_prefill_attention: the
    key up-projection folded into the queries, scores and sums over latent
    rows at their own width (rank + Dr and rank), the value up-projection
    applied once at the end. Same loop over live blocks."""
    import jax.numpy as jnp
    from jax import lax

    from ray_tpu.ops.latent_attention import NEG_INF, latent_kv_block

    c, h, _ = q_n.shape
    s, d = cache.shape[2], cache.shape[3]
    rank = w_kb.shape[0]
    block = block or latent_kv_block(s, 512)
    qpos = kv_len + jnp.arange(c)
    n_blocks = (jnp.minimum(kv_len + c, length) + block - 1) // block
    q_abs = jnp.einsum("chd,rhd->hcr", q_n, w_kb)
    q_rot = q_r.transpose(1, 0, 2)

    def body(j, carry):
        m, l, acc = carry
        rows = lax.dynamic_slice(cache, (layer, slot, j * block, 0),
                                 (1, 1, block, d))[0, 0].astype(q_n.dtype)
        ckv, kr = rows[:, :rank], rows[:, rank:rank + rope_dim]
        sc = jnp.einsum("hcr,sr->hcs", q_abs, ckv,
                        preferred_element_type=jnp.float32)
        sc += jnp.einsum("hcd,sd->hcs", q_rot, kr,
                         preferred_element_type=jnp.float32)
        kpos = j * block + jnp.arange(block)
        visible = ((kpos[None, :] <= qpos[:, None])
                   & (kpos[None, :] < length))[None]
        sc = jnp.where(visible, sc * sm_scale, NEG_INF)
        m_new = jnp.maximum(m, sc.max(-1, keepdims=True))
        p = jnp.where(visible, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(-1, keepdims=True)
        acc = alpha * acc + jnp.einsum(
            "hcs,sr->hcr", p.astype(q_n.dtype), ckv,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((h, c, 1), NEG_INF, jnp.float32),
            jnp.zeros((h, c, 1), jnp.float32),
            jnp.zeros((h, c, rank), jnp.float32))
    _, l, acc = lax.fori_loop(0, n_blocks, body, init)
    lat = (acc / jnp.maximum(l, 1e-30)).astype(q_n.dtype)
    return jnp.einsum("hcr,rhd->chd", lat, w_vb)


def main(argv: list[str]) -> int:
    want = set(argv) or {"check", "burst", "prefill", "trace"}
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        print("longcat_bench: needs a TPU", file=sys.stderr)
        return 1
    from rtbench.adapters import longcat as adapter

    from ray_tpu.llm import latent, longcat_serving as serving, served
    from ray_tpu.models.longcat import forward as longcat_forward
    from ray_tpu.ops import grouped_matmul as gmm
    from ray_tpu.ops import latent_attention as la
    from ray_tpu.ops.kernels import force_kernel_backend

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "longcat-flash-chat.json")) as f:
        config = json.load(f)
    layers = adapter.depth(config, USE)
    cfg = adapter.model_config(config, USE, MAX_SEQ)
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)

    if "check" in want:
        la_cache = (jax.random.normal(key, (2, 4, 2048, cfg.latent_row),
                                      jnp.float32) * 0.5).astype(jnp.bfloat16)
        q = jax.random.normal(jax.random.fold_in(key, 1),
                              (4, 1, cfg.num_heads, cfg.latent_dim),
                              jnp.float32).astype(jnp.bfloat16)
        lengths = jnp.asarray([0, 1, 1024, 2048], jnp.int32)
        pos0 = jnp.maximum(lengths - 1, 0)
        args = (q, la_cache, 1, lengths, pos0)
        kw = dict(rank=cfg.kv_lora_rank, sm_scale=cfg.sm_scale)
        got = la.latent_decode_attention(*args, **kw)
        with force_kernel_backend("reference"):
            ref = la.latent_decode_attention(*args, **kw)
        out(check="latent_decode_attention", max_abs_err=float(
            jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)).max()),
            scale=float(jnp.abs(ref.astype(jnp.float32)).max()))
        new = jax.random.normal(jax.random.fold_in(key, 2),
                                (4, 1, cfg.latent_row),
                                jnp.float32).astype(jnp.bfloat16)
        mask = jnp.asarray([True, False, True, True])
        wpos = jnp.asarray([0, 5, 1023, 2047], jnp.int32)
        got = la.latent_row_write(la_cache, new, 1, wpos, mask)
        with force_kernel_backend("reference"):
            ref = la.latent_row_write(la_cache, new, 1, wpos, mask)
        out(check="latent_row_write",
            equal=bool(jnp.array_equal(got, ref)))
        w = (jax.random.normal(jax.random.fold_in(key, 3),
                               (2, 4, cfg.hidden_size,
                                cfg.expert_ffn_hidden_size), jnp.float32)
             * 0.02).astype(jnp.bfloat16)
        w2 = jnp.flip(w, axis=1)
        x = jax.random.normal(jax.random.fold_in(key, 4),
                              (96, cfg.hidden_size),
                              jnp.float32).astype(jnp.bfloat16)
        te = jnp.asarray([0, 2, 2, 3, 3, 3], jnp.int32)
        got = gmm.grouped_matmul(x, w, 1, te, 4, tm=16, w2=w2)[:64]
        with force_kernel_backend("reference"):
            ref = gmm.grouped_matmul(x, w, 1, te, 4, tm=16, w2=w2)[:64]
        out(check="moe_grouped_matmul", max_abs_err=float(
            jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)).max()),
            scale=float(jnp.abs(ref.astype(jnp.float32)).max()))

    if "parity" in want:
        # The serving programs through the cache, the model's own forward
        # pass and the float32 reference on one sequence and one set of
        # weights: where the three part ways.
        from reference import longcat as reference

        params = jax.jit(served.init_params, static_argnums=0)(cfg, key)
        n, prompt = 1300, 1284
        ids = jnp.asarray(rng.integers(300, cfg.vocab_size, n), jnp.int32)
        # (the rms_norm kernel's block of 256 rows of 6,144 does not fit
        # its VMEM at 1,300 rows; the forward pass is only the comparison)
        with force_kernel_backend("reference"):
            fwd = np.asarray(jax.jit(longcat_forward, static_argnums=0)(
                cfg, params, ids[None])[0][0])
        ref = np.asarray(reference.logits(
            config, adapter.reference_weights(params), ids))
        cache = serving.init_cache(cfg, 4, MAX_SEQ)
        done = 0
        for bucket in (512, 512, 512):
            take = min(bucket, prompt - done)
            chunk = np.zeros(bucket, np.int32)
            chunk[:take] = np.asarray(ids[done:done + take])
            cache, last, _ = serving.prefill_chunk(
                cfg, params, cache, jnp.asarray(chunk), jnp.int32(done),
                jnp.int32(prompt), jnp.int32(2))
            done += take
        rows = [np.asarray(last)]
        write = np.asarray([False, False, True, False])
        for pos in range(prompt, n - 1):
            tok = np.zeros(4, np.int32)
            at = np.zeros(4, np.int32)
            tok[2], at[2] = int(ids[pos]), pos
            cache, logits, _ = serving.decode_step(
                cfg, params, cache, jnp.asarray(tok), jnp.asarray(at),
                jnp.asarray(write))
            rows.append(np.asarray(logits[2]))
        served = np.stack(rows)                 # positions prompt-1 .. n-2
        span = slice(prompt - 1, n - 1)

        def margin(want_rows, got_rows):
            pick = got_rows.argmax(axis=1)
            return float((want_rows.max(axis=1)
                          - want_rows[np.arange(len(pick)), pick]).max())

        out(parity="served vs forward", max_abs=float(
            np.abs(served - fwd[span]).max()),
            margin=margin(fwd[span], served))
        out(parity="served vs reference", max_abs=float(
            np.abs(served - ref[span]).max()),
            margin=margin(ref[span], served))
        out(parity="forward vs reference, all positions", max_abs=float(
            np.abs(fwd - ref).max()), margin=margin(ref[256:], fwd[256:]),
            by_quarter=[float(np.abs(fwd[i:i + n // 4] - ref[i:i + n // 4]
                                     ).max()) for i in range(0, n, n // 4)],
            logit_scale=float(np.abs(ref).max()))
        del params, cache

    if not want & {"burst", "prefill", "trace"}:
        return 0
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(served.init_params, static_argnums=0)(cfg, key))
    cache = jax.block_until_ready(serving.init_cache(cfg, SLOTS, MAX_SEQ))
    stats = jax.local_devices()[0].memory_stats() or {}
    out(setup_s=round(time.perf_counter() - t0, 1),
        bytes_in_use=stats.get("bytes_in_use"))

    def burst_at(cache, live: int, busy: int, steps: int = 8):
        write = np.zeros(SLOTS, bool)
        write[:busy] = True
        pos = np.where(write, live, 0).astype(np.int32)
        tok = rng.integers(300, cfg.vocab_size, SLOTS).astype(np.int32)
        args = (jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(write),
                jnp.zeros(SLOTS, jnp.float32), jnp.ones(SLOTS, jnp.float32),
                key, steps, False)
        return serving.decode_burst(cfg, params, cache, *args)

    def chunk_at(cache, kv_len: int, c: int = 512):
        toks = jnp.asarray(rng.integers(300, cfg.vocab_size, c), jnp.int32)
        return serving.prefill_chunk(
            cfg, params, cache, toks, jnp.int32(kv_len),
            jnp.int32(kv_len + c), jnp.int32(3))

    if "burst" in want:
        for live, busy in ((6000, 32), (1000, 32), (6000, 1), (100, 32)):
            def call():
                nonlocal cache
                cache, toks, counts = burst_at(cache, live, busy)
                return toks
            sec = timed(call, 5) / 8
            floor = adapter.decode_step_bytes(
                config, layers, live * busy, slots=busy) / HBM_BYTES_PER_S
            out(program="decode_burst(8)", live=live, busy=busy,
                ms_per_step=sec * 1e3, floor_ms=floor * 1e3)

    if "prefill" in want:
        def up_projected(*args, **kw):
            with force_kernel_backend("reference"):
                return la.latent_prefill_attention(*args, **kw)

        forms = {"kernel": la.latent_prefill_attention,
                 "up_projected": up_projected,
                 "absorbed": absorbed_prefill_attention}
        for name, fn in forms.items():
            latent.latent_prefill_attention = fn
            serving.prefill_chunk.clear_cache()
            for kv_len in (0, 2048, 5632):
                def call():
                    nonlocal cache
                    cache, logits, counts = chunk_at(cache, kv_len)
                    return logits
                out(program="prefill_chunk(512)", attention=name,
                    cached=kv_len, ms=timed(call, 5) * 1e3)
        latent.latent_prefill_attention = la.latent_prefill_attention
        serving.prefill_chunk.clear_cache()

    if "trace" in want:
        from rtbench import trace_reduce

        trace_dir = os.path.join(ROOT, ".bench_trace", "longcat_bench")
        cache, toks, _ = burst_at(cache, 6000, 32)
        cache, logits, _ = chunk_at(cache, 5632)
        jax.block_until_ready((toks, logits))
        jax.profiler.start_trace(trace_dir)
        for _ in range(2):
            cache, toks, _ = burst_at(cache, 6000, 32)
            cache, logits, _ = chunk_at(cache, 5632)
        jax.block_until_ready((toks, logits))
        jax.profiler.stop_trace()
        trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        out(modules=trace.module_seconds(), counts=trace.module_counts())
        out(top_ops=trace.top_device_ops(40))
        for name in ("latent_decode_attention", "latent_row_write",
                     "moe_grouped_matmul"):
            ev = trace.kernel_events(name)
            out(kernel=name, events=len(ev),
                mean_us=sum(e.end - e.start for e in ev) / max(len(ev), 1)
                * 1e6)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
