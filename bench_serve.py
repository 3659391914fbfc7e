"""Serve-LLM latency benchmark: time-to-first-token through the full stack.

Measures TTFT (request start → first SSE token frame) and per-token latency
through proxy → router → replica → engine with streaming enabled, under
concurrent load — the serving health metric BASELINE.md targets ("Serve LLM
inference p50 TTFT", reference: release serve_tests latency suites).

The 1B model serves on a TPU or not at all: when JAX finds no TPU the run
exits non-zero and prints nothing, and a failed warm-up, request or phase
fails the run. Writes PERF_SERVE.json.

Run: python bench_serve.py
"""

from __future__ import annotations

import json
import sys
import threading
import time
import urllib.request

import numpy as np


def main() -> int:
    import jax

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.accelerators.tpu import require_tpu
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.serving import build_openai_app
    from ray_tpu.utils.compile_cache import ensure_compile_cache

    device = require_tpu("bench_serve")
    ensure_compile_cache()
    # decode_burst=16 and max_num_seqs=8 are the values the last sweep on
    # record chose for concurrency 8: deep bursts amortise per-tick host
    # work, and the static slot batch reads every slot's KV each step, so
    # slots beyond the load cost throughput. They await a benchmark cell.
    cfg = LLMConfig(model="llama3_1b", max_num_seqs=8, max_seq_len=1024,
                    dtype="bfloat16", decode_burst=16)
    n_requests, concurrency, max_tokens = 100, 8, 32
    sweep_concurrency = [1, 4, 16]

    ray_tpu.init()
    # The replica builds 1B parameters before it reports healthy; on a
    # machine with a cold compile cache that outlasts the default minute.
    t0 = time.perf_counter()
    serve.run(build_openai_app(cfg), route_prefix="/", http=True,
              _blocking_timeout=600.0)
    print(f"bench_serve: replica healthy after "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
    port = serve.http_port()
    url = f"http://127.0.0.1:{port}/v1/chat/completions"

    # Warm EVERY steady-state shape before timing — a first compile is
    # tens of seconds and must not land inside the measurement:
    #   - prefill bucket for the short prompts,
    #   - every burst-decode shape plus the single-step decode path:
    #     prefill emits token 1, so max_tokens = decode_burst*2 leaves
    #     2D-1 = D + D/2 + ... + 1 — aligned requests walk exactly the
    #     full power-of-two ladder,
    #   - sampling + admission under concurrency.
    _run_phase(url, concurrency, concurrency, 2 * cfg.decode_burst,
               seed0=900)
    # Prefix-phase shapes: same token LENGTH as phase B's shared prefix
    # (same chunk buckets) but zero common prefix (first char differs), so
    # phase B's cold request stays genuinely cold. The second call warms
    # the rehit path (donor adoption + tail-chunk bucket).
    warm_prefix = "Xou are a careful assistant. " * 40
    _one_request(url, max_tokens=8, prefix=warm_prefix, seed=980)
    _one_request(url, max_tokens=8, prefix=warm_prefix, seed=981)

    ttfts, totals, tokens_out, wall = _run_phase(
        url, n_requests, concurrency, max_tokens)

    # Throughput-vs-TTFT frontier: the same workload at other concurrency
    # levels, so admission-policy regressions (e.g. decode bursts starving
    # prefills) are visible instead of hiding behind the single headline
    # point.
    sweep = []
    for c in sweep_concurrency:
        n = max(3 * c, 12)
        s_ttfts, _s_totals, s_tok, s_wall = _run_phase(
            url, n, c, max_tokens, seed0=3000 + 100 * c)
        sm = np.array(s_ttfts) * 1e3
        sweep.append({
            "concurrency": c,
            "requests": len(s_ttfts),
            "ttft_ms_p50": round(float(np.percentile(sm, 50)), 1),
            "ttft_ms_p90": round(float(np.percentile(sm, 90)), 1),
            "tokens_per_sec_total": round(sum(s_tok) / s_wall, 1),
        })

    # ---- phase B: shared-prefix TTFT (prefix KV-cache reuse) ------------
    # One long shared prefix (a system-prompt shape): the first request
    # prefills it cold; repeats adopt the cached KV and should see TTFT
    # collapse to ~one prefill chunk + routing (reference: vLLM APC +
    # prefix-aware routing; engine: LLMEngine prefix cache + proxy
    # _prefix_route_hint affinity).
    shared = "You are a careful assistant. " * 40
    cold_ttft, _, _ = _one_request(url, max_tokens=8, prefix=shared,
                                   seed=990)
    warm = [_one_request(url, max_tokens=8, prefix=shared, seed=991 + i)[0]
            for i in range(6)]

    serve.shutdown()
    ray_tpu.shutdown()

    ttfts_ms = np.array(ttfts) * 1e3
    warm_ms = np.array(warm) * 1e3
    out = {
        "model": "llama_1b",
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
        "requests": len(ttfts),
        "concurrency": concurrency,
        "ttft_ms": {"p50": round(float(np.percentile(ttfts_ms, 50)), 1),
                    "p90": round(float(np.percentile(ttfts_ms, 90)), 1),
                    "p99": round(float(np.percentile(ttfts_ms, 99)), 1)},
        "tokens_per_sec_total": round(sum(tokens_out) / wall, 1),
        "mean_request_s": round(float(np.mean(totals)), 3),
        "concurrency_sweep": sweep,
        "prefix_cache": {
            "cold_ttft_ms": round(cold_ttft * 1e3, 1),
            "hit_ttft_ms_p50": round(float(np.percentile(warm_ms, 50)), 1),
            "hit_ttft_ms_min": round(float(warm_ms.min()), 1),
        },
    }
    with open("PERF_SERVE.json", "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))
    return 0


def _run_phase(url: str, n_requests: int, concurrency: int,
               max_tokens: int, seed0: int = 0):
    """``n_requests`` requests, ``concurrency`` at a time. Any failed
    request fails the phase."""
    ttfts, totals, tokens_out, errors = [], [], [], []
    lock = threading.Lock()
    sem = threading.Semaphore(concurrency)

    def worker(i):
        with sem:
            try:
                ttft, total, ntok = _one_request(url, max_tokens=max_tokens,
                                                 seed=i)
            except Exception as e:  # noqa: BLE001 - raised after the join
                with lock:
                    errors.append(f"request {i}: {e!r}")
                return
            with lock:
                ttfts.append(ttft)
                totals.append(total)
                tokens_out.append(ntok)

    threads = [threading.Thread(target=worker, args=(seed0 + i,))
               for i in range(n_requests)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise RuntimeError(f"{len(errors)} of {n_requests} requests failed: "
                           + "; ".join(errors[:5]))
    return ttfts, totals, tokens_out, wall


def _one_request(url: str, max_tokens: int, seed: int = 0,
                 prefix: str | None = None):
    content = (f"{prefix}question {seed}" if prefix
               else f"benchmark prompt {seed} " * 4)
    body = json.dumps({
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens,
        "temperature": 0.0,
        "stream": True,
    }).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    ttft = None
    ntok = 0
    buf = b""
    with urllib.request.urlopen(req, timeout=300) as r:
        while True:
            chunk = r.read1(8192)
            if not chunk:
                break
            buf += chunk
            frames = buf.split(b"\n\n")
            buf = frames.pop()  # partial frame stays buffered
            for f in frames:
                # A token frame carries delta content; skip [DONE] and the
                # finish-reason-only frame.
                if f.startswith(b"data:") and b'"content"' in f:
                    if ttft is None:
                        ttft = time.perf_counter() - t0
                    ntok += 1
                # The engine fails a request inside an HTTP 200 stream.
                if b'"finish_reason": "error"' in f:
                    raise RuntimeError(f"engine failed the request: {f!r}")
    if ttft is None:
        raise RuntimeError("stream carried no token")
    return ttft, time.perf_counter() - t0, ntok


if __name__ == "__main__":
    sys.exit(main())
