"""What the two serving kinds share: the served path, the load generator's
process, the window, the metrics and the correctness check.

The system under test is ``serve.run(build_openai_app(cfg), http=True)``:
proxy -> router -> replica -> ``LLMServer`` -> engine, in this process (one
process holds the chip). Load comes over HTTP from ``loadgen.py`` in a
process of its own.

``correct``: no request failed, the engine counted no ``device_failures``
or ``requests_failed``, nothing compiled inside the window, every request
yielded the tokens it asked for, and for a sample of finished requests the
plain reference, run once over prompt + generated tokens, gives the
engine's token at every generated position a logit within ``margin`` of
its own maximum.
"""

from __future__ import annotations

import importlib
import json
import os
import re
import subprocess
import sys
import threading
import time

from rtbench import common, gen, manifest, readers, stats

_ID = re.compile(r"^<\|(\d+)\|>$")


def token_id(text: str) -> int | None:
    """The id behind one streamed frame's text, where the byte tokenizer's
    decoding is unambiguous: ``<|id|>`` for an id beyond its range, one
    ASCII character for a byte under 128. Otherwise None (a byte of 128 to
    255 decodes alone to U+FFFD, and the three specials to nothing)."""
    m = _ID.match(text)
    if m:
        return int(m.group(1))
    if len(text) == 1 and ord(text) < 128:
        return ord(text)
    return None


def _jitted_init_params(engine_mod, jax, sink: dict):
    """Hand the engine a jitted copy of the program's own ``init_params``
    (the same function on the same key), and time it. Called as the engine
    calls it, op by op, the 3.8B parameters of the docqa cell take 72 s
    (my chip run, PR 23); nothing but the program can change how
    ``LLMEngine`` calls it, so the benchmark swaps the module's name for
    the length of ``serve.run``. Returns the original, to put back."""
    inner = engine_mod.init_params
    jitted = jax.jit(inner, static_argnums=0)

    def init_params(cfg, key):
        t0 = time.monotonic()
        params = jax.block_until_ready(jitted(cfg, key))
        sink["init_params_s"] = time.monotonic() - t0
        return params

    engine_mod.init_params = init_params
    return inner


def _take_engine(engine_mod):
    """The replica's engine, found among the process's objects (the served
    path hands out no reference to it). Used after the window only: the
    reference check runs on the very weights that served, and the engine's
    cache is dropped to make room for it."""
    import gc

    found = [o for o in gc.get_objects()
             if isinstance(o, engine_mod.LLMEngine)]
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} engines in this process")
    return found[0]


class Poller(threading.Thread):
    """``stats()`` of the engine every 100 ms through the handle."""

    def __init__(self, handle, period_s: float = 0.1):
        super().__init__(daemon=True)
        self.handle, self.period_s = handle, period_s
        self.samples: list[tuple[float, dict]] = []
        self._stop_evt = threading.Event()

    def run(self) -> None:
        while not self._stop_evt.wait(self.period_s):
            try:
                s = self.handle.stats.remote().result(timeout=10)
            except Exception:  # noqa: BLE001 - a missed sample, not a fault
                continue
            self.samples.append((time.monotonic(), s))

    def stop(self) -> None:
        self._stop_evt.set()
        self.join(15)


def _events(proc, on_event) -> None:
    for line in proc.stdout:
        line = line.strip()
        if line.startswith("{"):
            on_event(json.loads(line))
        elif line:
            common.log(f"loadgen: {line}")


def run(ctx: dict, plan_for, loop: str) -> None:
    """One run of a serving cell. ``plan_for(traffic, seed, seconds)``
    makes the requests, ``loop`` (``open_loop`` or ``closed_loop``) says
    how the load generator sends them and which end-to-end metrics the
    window gives: a traffic kind of its own passes its plan and one of the
    two."""
    cell, clock, seed = ctx["cell"], ctx["clock"], ctx["seed"]
    traffic, model_json = cell["traffic"], cell["config"]
    ctx["loop"] = loop
    jax, devices, counter = common.start_jax(cell["workload"]["chips"])
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm import engine as engine_mod
    from ray_tpu.llm.serving import build_openai_app

    adapter = importlib.import_module(
        "rtbench.adapters." + model_json["adapter"])
    clock.mark("imports_and_backend")

    eng = dict(traffic["engine"])
    max_ongoing = eng.pop("max_ongoing_requests")
    model_cfg = adapter.model_config(model_json, traffic["use"],
                                     eng["max_seq_len"])
    llm = LLMConfig(model=model_cfg, seed=common.jax_seed(seed), **eng)
    ray_tpu.init(resources={"TPU": float(cell["workload"]["chips"])})
    clock.mark("runtime_init")

    tmp = os.path.join(manifest.repo_root(), ".bench_tmp")
    os.makedirs(tmp, exist_ok=True)
    proc = None
    timing: dict = {}
    inner_init = _jitted_init_params(engine_mod, jax, timing)
    try:
        handle = serve.run(
            build_openai_app(llm, max_ongoing_requests=max_ongoing),
            route_prefix="/", http=True, _blocking_timeout=900.0)
        engine_mod.init_params = inner_init
        clock.mark("serve_run_to_healthy")
        if "init_params_s" in timing:
            clock.note("init_params", timing["init_params_s"])
        url = f"http://127.0.0.1:{serve.http_port()}/v1/completions"

        plan = {"url": url, "seed": seed, "vocab": model_json["vocab_size"],
                "loop": loop, "seconds": ctx["seconds"],
                "timeout_s": traffic["timeout_s"],
                "drain_s": traffic.get("drain_s", 30),
                "stagger_s": traffic.get("stagger_s", 0.0),
                "warmup": gen.warmup_requests(traffic),
                "out": os.path.join(tmp, "records.json"),
                **plan_for(traffic, seed, ctx["seconds"])}
        plan_path = os.path.join(tmp, "plan.json")
        with open(plan_path, "w") as f:
            json.dump(plan, f)

        state: dict = {"poller": None, "tracing": False}
        snap0 = counter.snapshot()

        def on_event(ev: dict) -> None:
            if ev["event"] == "warm_done":
                hits = counter.counts["hits"] - snap0["hits"]
                reqs = counter.counts["requests"] - snap0["requests"]
                clock.mark(f"warm_shapes(programs={reqs},cache_hits={hits})",
                           ev["t"])
                if ev["failed"]:
                    raise RuntimeError("a warm-up request failed")
            elif ev["event"] == "window_open":
                state["t_open"] = ev["t_open"]
                state["snap"] = counter.snapshot()
                clock.mark("ramp_to_steady_state", ev["t_open"])
                clock.summary(ev["t_open"])
                state["poller"] = Poller(handle)
                state["poller"].start()
                if ctx["trace"]:
                    threading.Thread(target=_trace_span, daemon=True, args=(
                        jax, state, ev["t_open"], traffic["trace"])).start()
            elif ev["event"] == "window_close":
                state["t_close"] = ev["t_close"]
                state["compiles"] = counter.compiled_since(state["snap"])
                common.log("compilations inside the window: "
                           f"{state['compiles']}")
                state["poller"].stop()

        proc = subprocess.Popen(
            [sys.executable, os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "loadgen.py"), plan_path],
            stdout=subprocess.PIPE, text=True, bufsize=1)
        _events(proc, on_event)
        if proc.wait(60) != 0:
            raise RuntimeError(f"load generator exited {proc.returncode}")
        while state.get("tracing"):
            time.sleep(0.05)
        final_stats = handle.stats.remote().result(timeout=60)
        device = common.device_record(devices)
        engine = _take_engine(engine_mod)
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(10)
        serve.shutdown()
        ray_tpu.shutdown()

    # The server is down; its scheduler thread and cache go too, the
    # weights stay for the reference.
    engine.shutdown()
    params, engine.params, engine.cache = engine.params, None, None
    del engine
    with open(plan["out"]) as f:
        records = json.load(f)
    _finish(ctx, cell, adapter, jax, devices, device, records, state,
            final_stats, params)


def _trace_span(jax, state: dict, t_open: float, spec: dict) -> None:
    """A few seconds of profiler trace inside the window."""
    state["tracing"] = True
    try:
        time.sleep(max(0.0, t_open + spec["after_s"] - time.monotonic()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(common.trace_dir(fresh=True),
                                 profiler_options=opts)
        t0 = time.monotonic()
        time.sleep(spec["for_s"])
        t1 = time.monotonic()
        jax.profiler.stop_trace()
        state["trace_span"] = (t0, t1)
    finally:
        state["tracing"] = False


def request_failed(r: dict) -> bool:
    """A request fails when its stream errs, or when it yields fewer tokens
    than it asked for without the engine saying it stopped by itself."""
    if r["abandoned"]:
        return False
    if r["error"] is not None:
        return True
    return r["frames"] < r["max_tokens"] and r["finish"] != "stop"


def ttft_ms(due: list[dict], t_close: float, timeout_s: float) -> list[float]:
    """Milliseconds from the instant each request was due to its first SSE
    token frame; a failed or refused request counts as the worst (its
    time-out)."""
    worst = t_close + timeout_s
    return [((r["first_t"] if r["first_t"] is not None
              and not request_failed(r) else worst) - r["due_t"]) * 1e3
            for r in due]


def finished_inside(records: list[dict], t_open: float,
                    t_close: float) -> list[dict]:
    """The sound requests whose last token came inside the window."""
    return [r for r in records if not request_failed(r)
            and not r["abandoned"] and r["last_t"] is not None
            and t_open <= r["last_t"] <= t_close]


def tpot_ms(records: list[dict], t_open: float, t_close: float) -> list[float]:
    """Milliseconds per output token, (last token - first) / (tokens - 1)
    at the client, of each request that finished inside the window."""
    return [x for x in (stats.tpot_ms(r["first_t"], r["last_t"], r["frames"])
                        for r in finished_inside(records, t_open, t_close))
            if x is not None]


def tpot_mean_ms(records: list[dict], t_open: float,
                 t_close: float) -> float:
    """Mean milliseconds between two output tokens at the client, over
    every token of the requests that finished inside the window: the sum of
    (last token - first) over the sum of (tokens - 1). All the decoding
    time of the window over all its tokens, so a request weighs as its
    answer's length and no single request decides the number. Raises where
    no request gave two tokens, as ``stats.percentile`` does on no values."""
    done = [r for r in finished_inside(records, t_open, t_close)
            if r["frames"] >= 2]
    gaps = sum(r["frames"] - 1 for r in done)
    if not gaps:
        raise ValueError("no finished request with two tokens")
    return sum(r["last_t"] - r["first_t"] for r in done) / gaps * 1e3


def _finish(ctx, cell, adapter, jax, devices, device, records, state,
            final_stats, params) -> None:
    traffic, model_json = cell["traffic"], cell["config"]
    t_open, t_close = state["t_open"], state["t_close"]
    counted = [r for r in records if r["phase"] != "warm"]
    failed = [r for r in counted if request_failed(r)]
    for r in failed[:5]:
        common.log(f"failed request {r['index']}: {r['error']} frames "
                   f"{r['frames']}/{r['max_tokens']} finish {r['finish']}")

    values: dict = {"setup_s": t_open - ctx["clock"].t_start}
    if ctx["loop"] == "open_loop":
        due = [r for r in counted if r["phase"] == "window"]
        late = [r["send_t"] - r["due_t"] for r in counted if r["send_t"]]
        common.log(f"generator lateness ms: median "
                   f"{stats.percentile(late, 50) * 1e3:.3f} worst "
                   f"{max(late) * 1e3:.3f} over {len(late)} requests")
        ttft = ttft_ms(due, t_close, traffic["timeout_s"])
        tpot = tpot_ms(counted, t_open, t_close)
        values["tpot_mean_ms"] = tpot_mean_ms(counted, t_open, t_close)
        common.log(f"window: {len(due)} requests due, {len(tpot)} finished "
                   f"inside, ttft p50 {stats.percentile(ttft, 50):.1f} p90 "
                   f"{stats.percentile(ttft, 90):.1f} ms, tpot p50 "
                   f"{stats.percentile(tpot, 50):.2f} p90 "
                   f"{stats.percentile(tpot, 90):.2f} mean of requests "
                   f"{sum(tpot) / len(tpot):.2f} of tokens "
                   f"{values['tpot_mean_ms']:.2f} ms")
        attempted = len(due)
        n_failed = sum(1 for r in due if request_failed(r))
    else:
        ok = [r for r in counted if not request_failed(r)]
        intervals = [(r["send_t"], r["end_t"],
                      r["prompt_tokens"] + r["frames"]) for r in ok]
        window_s = t_close - t_open
        values["serve_tok_s"] = stats.pro_rata_tokens(
            intervals, t_open, t_close) / window_s
        whole = stats.whole_request_tokens(intervals, t_open, t_close)
        inside = [r for r in counted
                  if r["end_t"] > t_open and r["send_t"] < t_close]
        common.log(f"window: {len(inside)} requests touched it, pro rata "
                   f"{values['serve_tok_s']:.1f} tokens/s, by requests that "
                   f"ended inside {whole / window_s:.1f} tokens/s")
        attempted = len(inside)
        n_failed = sum(1 for r in inside if request_failed(r))

    engine_ok = not (final_stats["device_failures"]
                     or final_stats["requests_failed"])
    common.log(f"engine stats {final_stats}")

    ref_ok = _reference_check(cell, adapter, jax, counted, params,
                              ctx["seed"], t_open, t_close)
    correct = (not failed and engine_ok and state["compiles"] == 0
               and ref_ok)

    breakdown = None
    if ctx["trace"]:
        from rtbench import trace_reduce

        path = trace_reduce.find_xplane(common.trace_dir())
        trace = trace_reduce.load(path)
        device["busy_s"], device["window_s"] = trace.busy_s(), trace.window_s()
        breakdown = trace.breakdown()
        obs = {"kind": "serve", "cell": cell, "trace": trace,
               "trace_span": state["trace_span"], "records": counted,
               "t_open": t_open, "t_close": t_close,
               "polls": state["poller"].samples,
               "peaks": common.peaks_for(devices[0].device_kind)}
        common.log(f"traced programs: {trace.module_seconds()} "
                   f"counts {trace.module_counts()}")
        values = readers.read_all(cell["per_layer"], obs)
    ctx["emit"](correct, attempted, n_failed, values, device, breakdown)


def readable_ids(texts: list[str]) -> list[int]:
    """The ids of an answer's leading tokens, up to the first one whose
    text hides which id it was."""
    ids = []
    for t in texts:
        x = token_id(t)
        if x is None:
            break
        ids.append(x)
    return ids


def pick_sample(records, spec: dict, t_open: float, t_close: float):
    """The requests the reference is run on, each with the generated ids
    that are compared: finished requests of the window whose every token
    can be read back from the stream, the shortest first to keep the check
    cheap. Where answers are so long that a window may hold too few of
    those, ``min_readable`` also admits a request whose first that many
    tokens can be read; it is compared up to its first hidden token, and
    nothing after it (the reference would need the hidden id as input)."""
    found = []
    for r in records:
        if r["error"] or r["abandoned"] or r["frames"] != r["max_tokens"] \
                or not (t_open <= (r["last_t"] or 0) <= t_close):
            continue
        ids = readable_ids(r["texts"])
        cut = len(ids) < r["frames"]
        if cut and len(ids) < spec.get("min_readable", r["frames"]):
            continue
        found.append((cut, r["prompt_tokens"] + len(ids), r, ids))
    found.sort(key=lambda f: f[:2])
    return [(r, ids) for _c, _n, r, ids in found[:spec["requests"]]]


def worst_margin(prompt: list[int], out_ids: list[int], logits_of) -> float:
    """How far, at worst, a generated token's logit lies under the top
    logit of its position, by ``logits_of(sequence) -> [positions, vocab]``
    (the logits at position p choose token p + 1)."""
    import numpy as np

    n = len(prompt) + len(out_ids)
    rows = logits_of(prompt + out_ids)[len(prompt) - 1:n - 1]
    chosen = rows[np.arange(len(out_ids)), np.asarray(out_ids)]
    return float((rows.max(axis=1) - chosen).max())


def _reference_check(cell, adapter, jax, records, params, seed, t_open,
                     t_close) -> bool:
    """After the server has given the cache's memory back: the reference's
    logits over prompt + generated tokens of a few finished requests, on
    the weights that served them."""
    import gc

    import numpy as np

    traffic, model_json = cell["traffic"], cell["config"]
    spec = traffic["check"]
    t0 = time.monotonic()
    sample = pick_sample(records, spec, t_open, t_close)
    if len(sample) < spec["requests"]:
        common.log(f"reference: only {len(sample)} requests to compare")
        return False

    gc.collect()
    in_use = (jax.local_devices()[0].memory_stats() or {}).get("bytes_in_use")
    common.log(f"reference: device bytes in use after shutdown {in_use}")
    reference = importlib.import_module(adapter.REFERENCE)
    weights = adapter.reference_weights(params)

    def logits_of(seq):
        padded = seq + [0] * (-len(seq) % 512)   # causal: the tail is inert
        return np.asarray(reference.logits(
            model_json, weights, jax.numpy.asarray(padded, jax.numpy.int32)))

    worst = 0.0
    for r, out_ids in sample:
        prompt = gen.prompt_ids(seed, r["index"], r["prompt_tokens"],
                                model_json["vocab_size"])
        margin = worst_margin(prompt, out_ids, logits_of)
        worst = max(worst, margin)
        common.log(f"reference: request {r['index']} ({len(prompt)} + "
                   f"{len(out_ids)} of {r['frames']} tokens) worst margin "
                   f"{margin:.4f}")
    common.log(f"reference: worst margin {worst:.4f} (allowed "
               f"{spec['margin']}) in {time.monotonic() - t0:.1f}s")
    return worst <= spec["margin"]
