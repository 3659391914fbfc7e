"""The serving path measures itself: scheduler phases on the profiler's
clock (``tracing.phase``), request phases that survive streaming, and the
work and wait counters of ``LLMEngine.stats()`` / ``LLMServer.stats()``.
All on the CPU: what is counted and named, never how long it took."""

import glob
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from ray_tpu.util import tracing


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.clear()
    tracing.disable_tracing()
    yield
    tracing.clear()
    tracing.disable_tracing()


def _host_events(logdir: str) -> list[tuple[str, dict]]:
    """(name, stats) of every event on the host plane of the newest trace
    under ``logdir``, as ``jax.profiler.ProfileData`` gives them."""
    import jax

    path = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if "." in ev.name:   # ours are dotted; skip the runtime's
                    out.append((ev.name, dict(ev.stats)))
    return out


# ------------------------------------------------------------ tracing.phase
def test_phase_is_inert_with_everything_off():
    with tracing.phase("engine.tick", steps=8) as ph:
        ph.set(tokens=3)
    assert tracing.spans() == []


def test_phase_records_a_span_on_the_threads_lane_when_tracing_is_on():
    tracing.enable_tracing()
    with tracing.phase("engine.decode_dispatch", steps=8, slots=3):
        pass
    with tracing.phase("engine.emit") as ph:
        ph.set(tokens=24)
    other = []
    t = threading.Thread(target=lambda: (
        tracing.phase("engine.wait").__enter__().__exit__(None, None, None),
        other.extend(tracing.spans()[-1:])))
    t.start()
    t.join()
    a, b = [s for s in tracing.spans() if s.name != "engine.wait"]
    assert (a.name, a.attributes) == ("engine.decode_dispatch",
                                      {"steps": 8, "slots": 3})
    assert (b.name, b.attributes) == ("engine.emit", {"tokens": 24})
    assert a.trace_id == b.trace_id and a.parent_id is None
    assert a.end_ts >= a.start_ts > 0
    # one lane a thread: another thread's phases are another trace
    assert other[0].trace_id != a.trace_id


def test_phase_joins_the_threads_current_trace():
    tracing.enable_tracing()
    with tracing.span("train.step") as outer:
        with tracing.phase("train.report"):
            pass
    inner = next(s for s in tracing.spans() if s.name == "train.report")
    assert inner.trace_id == outer.trace_id
    assert inner.parent_id == outer.span_id


def test_phase_reaches_the_profilers_host_plane_with_its_counts(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    with tracing.phase("engine.decode_dispatch", steps=8, slots=3):
        jax.numpy.ones(4).block_until_ready()
    with tracing.phase("engine.emit") as ph:
        ph.set(tokens=24)
    jax.profiler.stop_trace()
    events = dict(_host_events(str(tmp_path)))
    assert events["engine.decode_dispatch"] == {"steps": 8, "slots": 3}
    assert events["engine.emit"] == {"tokens": 24}
    assert tracing.spans() == []   # the profiler alone: no span recorded


def test_the_two_helpers_nothing_called_are_gone():
    assert not hasattr(tracing, "profile")
    assert not hasattr(tracing, "save_otlp")
    assert callable(tracing.export_otlp)


# ------------------------------------------------------------------- engine
@pytest.fixture
def engine():
    from ray_tpu.llm import LLMConfig, LLMEngine

    eng = LLMEngine(LLMConfig(model="tiny", max_num_seqs=4, max_seq_len=96,
                              seed=5, decode_burst=4,
                              prefix_block_tokens=0))
    yield eng
    eng.shutdown()


def _run(engine, prompts, max_tokens):
    from ray_tpu.llm import SamplingParams

    reqs = [engine.submit(p, SamplingParams(max_tokens=n, temperature=0.0))
            for p, n in zip(prompts, max_tokens)]
    for r in reqs:
        assert r.done.wait(120) and not r.error
    return reqs


def _prompts(n, rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    return [[int(t) for t in rng.integers(1, 200, int(k))]
            for k in rng.integers(5, 40, n)]


def test_scheduler_thread_has_its_name_at_the_os_too(engine):
    """The profiler labels a thread's line with the OS name."""
    assert engine._thread.name == "llm-engine"
    _run(engine, _prompts(1), [1])   # the loop has started by now
    with open(f"/proc/self/task/{engine._thread.native_id}/comm") as f:
        assert f.read().strip() == "llm-engine"


def test_counters_conserve_requests_tokens_and_steps(engine):
    prompts = _prompts(7)
    wants = [3, 9, 1, 6, 12, 2, 5]
    reqs = _run(engine, prompts, wants)
    s = engine.stats()
    n = len(reqs)
    assert s["admitted"] == s["finished"] == s["first_tokens"] == n
    adopted = s["prefix_tokens_saved"]
    assert s["prompt_tokens_prefilled"] == \
        sum(len(p) for p in prompts) - adopted
    assert s["prefill_chunks"] >= n - s["prefix_hits"]
    out = sum(len(r.out_tokens) for r in reqs)
    assert s["decode_tokens"] + s["first_tokens"] == out
    assert s["decode_steps"] * s["slots"] >= s["decode_tokens"]
    assert s["decode_steps"] >= s["decode_dispatches"] >= 1
    assert s["ticks"] >= 1
    # waits are sums over the requests counted beside them
    assert s["queue_wait_s"] == pytest.approx(
        sum(r.admit_ts - r.submit_ts for r in reqs))
    assert s["first_token_wait_s"] == pytest.approx(
        sum(r.first_token_ts - r.admit_ts for r in reqs))
    assert all(r.first_token_ts >= r.admit_ts >= r.submit_ts > 0
               for r in reqs)   # stamped though no request was traced


COUNTERS = ("ticks", "admitted", "finished", "prompt_tokens_prefilled",
            "prefill_chunks", "decode_dispatches", "decode_steps",
            "decode_tokens", "first_tokens", "queue_wait_s",
            "first_token_wait_s")


def test_counters_are_monotone_across_a_device_failure(engine):
    _run(engine, _prompts(3, 1), [4, 4, 4])
    before = engine.stats()
    assert all(before[k] > 0 for k in COUNTERS if k != "queue_wait_s")
    # the scheduler thread is idle: run the recovery as it would
    engine._recover_device_failure("injected")
    after = engine.stats()
    assert after["device_failures"] == before["device_failures"] + 1
    assert all(after[k] >= before[k] for k in COUNTERS)
    _run(engine, _prompts(2, 2), [3, 3])
    later = engine.stats()
    assert all(later[k] >= after[k] for k in COUNTERS)
    assert later["finished"] == before["finished"] + 2


def test_scheduler_phases_reach_a_profiler_session(engine, tmp_path):
    import jax

    _run(engine, _prompts(2, 3), [6, 6])   # compile outside the session
    before = engine.stats()
    jax.profiler.start_trace(str(tmp_path))
    _run(engine, _prompts(2, 4), [9, 9])
    time.sleep(0.05)   # the wait between the two lies inside the session
    _run(engine, _prompts(1, 5), [9])
    jax.profiler.stop_trace()
    after = engine.stats()
    events = _host_events(str(tmp_path))
    names = {n for n, _ in events}
    assert {"engine.tick", "engine.admit", "engine.prefill_dispatch",
            "engine.decode_dispatch", "engine.fetch", "engine.emit",
            "engine.wait"} <= names

    def total(name, key):
        return sum(st[key] for n, st in events if n == name and key in st)

    # the phases carry the very counts the counters took
    assert total("engine.admit", "requests") == \
        after["admitted"] - before["admitted"] == 3
    assert total("engine.prefill_dispatch", "tokens") == \
        after["prompt_tokens_prefilled"] - before["prompt_tokens_prefilled"]
    assert total("engine.decode_dispatch", "steps") == \
        after["decode_steps"] - before["decode_steps"]
    assert total("engine.emit", "tokens") == \
        (after["decode_tokens"] - before["decode_tokens"]) + 3
    assert all(st["bucket"] >= st["tokens"] for n, st in events
               if n == "engine.prefill_dispatch")
    assert all(1 <= st["slots"] <= 4 for n, st in events
               if n == "engine.decode_dispatch")
    assert {st["which"] for n, st in events if n == "engine.fetch"} <= \
        {"burst", "pending", "prefill", "step"}
    assert {st["why"] for n, st in events if n == "engine.fetch"} <= WHYS


# Why the scheduler read (``engine.fetch``'s ``why``, beside ``which``).
WHYS = {"oldest", "tail", "single_step", "host_only", "speculative",
        "serial", "stop"}


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["look-ahead", "serial"])
def test_a_fetch_says_why_the_scheduler_read(pipeline):
    """Every blocking read carries the reason of the call that made it.
    Two lines of 14 tokens at bursts of 4: a first token, three bursts and
    a single step each. Under the look-ahead a burst is read as the oldest
    in flight once another is queued behind it, and what is left when
    nothing more can be queued as the tail (or because the single step
    that follows takes its tokens from the host); serial, a tick reads the
    burst it dispatched."""
    from ray_tpu.llm import LLMConfig, LLMEngine

    eng = LLMEngine(LLMConfig(model="tiny", max_num_seqs=4, max_seq_len=128,
                              seed=5, decode_burst=4, prefix_block_tokens=0,
                              decode_pipeline=pipeline))
    try:
        _run(eng, _prompts(2, 6), [14, 14])   # compile outside the record
        tracing.clear()
        tracing.enable_tracing()
        _run(eng, _prompts(2, 7), [14, 14])
        tracing.disable_tracing()
    finally:
        eng.shutdown()
    reads = [s.attributes for s in tracing.spans()
             if s.name == "engine.fetch"]
    tracing.clear()
    assert reads and all(r["why"] in WHYS for r in reads)
    bursts = {r["why"] for r in reads if r["which"] == "burst"}
    assert {r["why"] for r in reads if r["which"] == "step"} == \
        {"single_step"}
    if pipeline:
        assert "oldest" in bursts
        assert bursts <= {"oldest", "tail", "single_step"}
    else:
        assert bursts == {"serial"}


def test_an_idle_engine_waits_in_one_phase(engine):
    tracing.enable_tracing()
    _run(engine, _prompts(1, 5), [2])
    tracing.clear()
    time.sleep(0.3)           # fifteen polls of 20 ms, were each a phase
    _run(engine, _prompts(1, 6), [2])
    waits = [s for s in tracing.spans() if s.name == "engine.wait"]
    assert 1 <= len(waits) <= 2
    assert max(s.end_ts - s.start_ts for s in waits) >= 0.25


# ---------------------------------------------------------- streamed request
def _stream_completion(port: int, prompt, max_tokens: int) -> list[dict]:
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "temperature": 0.0, "stream": True}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        text = r.read().decode()
    assert text.rstrip().endswith("data: [DONE]")
    return [json.loads(ln[6:]) for ln in text.splitlines()
            if ln.startswith("data: ") and ln != "data: [DONE]"]


def test_streamed_request_keeps_its_trace_through_the_engine(monkeypatch):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.serving import build_openai_app
    from ray_tpu.utils.config import get_config

    monkeypatch.setattr(get_config(), "trace_sample_rate", 1.0)
    tracing.enable_tracing()
    ray_tpu.init()
    try:
        cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=128)
        handle = serve.run(build_openai_app(cfg), route_prefix="/",
                           http=True)
        frames = _stream_completion(serve.http_port(), [5, 6, 7, 8], 5)
        assert len(frames) >= 2
        stats = handle.stats.remote().result(timeout=30)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    roots = [s for s in tracing.spans()
             if s.name.startswith("serve.request.")
             and s.attributes.get("method") != "stats"]
    assert len(roots) == 1
    mine = {s.name for s in tracing.spans()
            if s.trace_id == roots[0].trace_id}
    assert {"engine.queue", "engine.prefill", "engine.decode"} <= mine
    # the scheduler's own phases are not the request's: another trace
    assert not any(n in mine for n in ("engine.tick", "engine.emit"))
    assert any(s.name == "engine.tick" for s in tracing.spans())
    # the LLM server counted its first frame, beside the engine's counters
    assert stats["first_frames"] == 1 and stats["first_tokens"] == 1
    assert 0.0 <= stats["first_frame_lag_s"] < 30.0


def test_stream_steps_run_inside_the_captured_context_only():
    from ray_tpu.serve.replica import _steps_in_context

    seen = []

    def gen():
        for i in range(3):
            seen.append(tracing.current_trace_id())
            yield i

    ctx = {"trace_id": "t" * 32, "parent_span_id": "p" * 16,
           "sampled": True}
    between = []
    for _ in _steps_in_context(gen(), ctx):
        between.append(tracing.current_trace_id())
    assert seen == ["t" * 32] * 3
    assert between == [None] * 3     # the pool thread's own context is back


def test_a_burst_is_dispatched_before_the_last_is_read():
    """The order itself, on the phase record of 32 lines decoding
    steadily: the scheduler dispatches burst n+1 between the reads of
    burst n-1 and burst n, every burst and not every second one, so the
    device has burst n to run while the host works; and a prompt's first
    token is read before the burst dispatched after its last chunk."""
    from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams

    eng = LLMEngine(LLMConfig(model="tiny", max_num_seqs=32, max_seq_len=128,
                              seed=5, decode_burst=4, prefix_block_tokens=0))
    try:
        _run(eng, _prompts(2, 6), [6, 6])   # compile outside the record
        before = eng.stats()
        tracing.enable_tracing()
        _run(eng, _prompts(32, 7), [65] * 32)
        tracing.disable_tracing()
        after = eng.stats()
    finally:
        eng.shutdown()
    record = sorted((s for s in tracing.spans()
                     if s.name in ("engine.fetch", "engine.decode_dispatch",
                                   "engine.prefill_dispatch")),
                    key=lambda s: s.start_ts)
    kinds = [s.attributes.get("which", s.name) for s in record]
    # between two consecutive reads of bursts there is a decode dispatch
    reads = [i for i, k in enumerate(kinds) if k == "burst"]
    assert len(reads) > 12
    # (but for the last, which nothing is left to follow: 65 tokens are a
    # first and 16 bursts of 4, so no line ends on a single step, the
    # serial path that reads out everything in flight before it)
    for a, b in zip(reads[:-1], reads[1:-1]):
        assert "engine.decode_dispatch" in kinds[a:b], (a, b, kinds[a:b])
    assert "step" not in kinds
    # ... which goes behind a burst not yet read: dispatches run one ahead
    # of reads from the second burst on, to the last
    assert kinds.index("burst") > [i for i, k in enumerate(kinds)
                                   if k == "engine.decode_dispatch"][1]
    # first tokens: read after their own chunk, before the read of the
    # burst dispatched after it (the k-th read of a first token follows
    # the k-th last chunk; here every prompt is one chunk)
    chunks = [i for i, k in enumerate(kinds)
              if k == "engine.prefill_dispatch"]
    firsts = [i for i, k in enumerate(kinds) if k == "prefill"]
    assert len(chunks) == len(firsts) == 32
    for chunk, first in zip(chunks, firsts):
        assert chunk < first
        joined = kinds.index("engine.decode_dispatch", chunk)
        # reads are in dispatch order: count the bursts dispatched and
        # read before each point
        dispatched = kinds[:joined].count("engine.decode_dispatch")
        assert kinds[:first].count("burst") <= dispatched
    ahead = after["decode_dispatches_ahead"] - before["decode_dispatches_ahead"]
    total = after["decode_dispatches"] - before["decode_dispatches"]
    assert ahead / total > 0.9, (ahead, total)
