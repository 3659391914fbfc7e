"""A request's way in and a chunk's way out, measured where they happen
(PR 50): the proxy's arrival stamp and ``serve.chunk_out`` / ``serve.close``
phases, the replica's stamp on a chunk, the LLM server's ``ingress_s`` and
``last_frame_lag_s``, the engine's ``slot_vacant_s``. All on the CPU: what
is stamped, counted and framed, never how long it took."""

import http.client
import json
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.util import tracing


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.clear()
    tracing.disable_tracing()
    yield
    tracing.clear()
    tracing.disable_tracing()


@pytest.fixture
def runtime():
    ray_tpu.init()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture
def sample_rate(monkeypatch):
    """Request tracing on, at the head-sampling rate the test sets."""
    from ray_tpu.utils.config import get_config

    def at(rate: float) -> None:
        monkeypatch.setattr(get_config(), "trace_sample_rate", rate)
        tracing.enable_tracing()
    return at


def _request_spans() -> list:
    """What the requests left in the main buffer (the control plane's own
    calls, each a trace of one span, are not a request's: a long poll's
    ``listen`` returns when it will, an earlier test's among them)."""
    return [s for s in tracing.spans() if s.name.startswith(
        ("proxy.", "serve.", "handle_request", "engine."))]


def _read_all(port: int, path: str, body: bytes | None = None) -> bytes:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST" if body is not None else "GET", path, body=body,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    try:
        return resp.read()
    finally:
        conn.close()


# What a user's generator may yield: text, bytes, and anything JSON holds.
YIELDED = ["data: one\n\n", b"\x00raw\xff", {"k": [1, 2]}, ("a", 1), 7, ""]


@serve.deployment
class Chunks:
    def __call__(self, request: serve.Request):
        assert request.received_ts > 0   # the proxy stamped its arrival
        return self.chunks()

    def chunks(self):
        yield from YIELDED

    def whole(self):
        return len(YIELDED)


# ----------------------------------------------------------- a chunk's way out
def test_the_wire_carries_what_the_generator_yielded_byte_for_byte(
        runtime, sample_rate):
    serve.run(Chunks.bind(), route_prefix="/", http=True)
    got = _read_all(serve.http_port(), "/x")
    wire = (b"data: one\n\n" + b"\x00raw\xff" + b'{"k": [1, 2]}'
            + b'["a", 1]' + b"7" + b"")
    assert got == wire
    assert _request_spans() == []
    # and traced: the same bytes, counted on the request's root
    sample_rate(1.0)
    assert _read_all(serve.http_port(), "/x") == wire
    tracing.disable_tracing()
    root, = [s for s in tracing.spans() if s.name == "proxy.request"]
    assert root.attributes == {"path": "/x", "status": 200,
                               "chunks": len(YIELDED), "bytes": len(wire)}
    outs = [s for s in tracing.spans() if s.name == "serve.chunk_out"]
    assert [s.attributes["bytes"] for s in outs] == [11, 5, 13, 8, 1, 0]
    assert all(s.attributes["lag_us"] >= 0 for s in outs)


def test_concurrent_streams_keep_their_traces_apart(runtime, sample_rate):
    """More request threads than cores, a short switch interval: each
    request's chunks are counted on its own root and lie under it."""
    import sys
    import threading

    serve.run(Chunks.bind(), route_prefix="/", http=True)
    port = serve.http_port()
    one = len(_read_all(port, "/x"))
    sample_rate(1.0)
    got = []

    def client():
        for _ in range(3):
            got.append(len(_read_all(port, "/x")))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    tracing.disable_tracing()
    assert got == [one] * 48
    roots = {s.span_id: s for s in tracing.spans()
             if s.name == "proxy.request"}
    assert len(roots) == 48 == len({s.trace_id for s in roots.values()})
    assert all(r.attributes["chunks"] == len(YIELDED)
               and r.attributes["bytes"] == one for r in roots.values())
    outs = [s for s in tracing.spans() if s.name == "serve.chunk_out"]
    assert len(outs) == 48 * len(YIELDED)
    assert all(roots[s.parent_id].trace_id == s.trace_id for s in outs)


def test_a_handle_caller_gets_the_chunks_and_never_the_stamp(runtime):
    handle = serve.run(Chunks.bind())
    t0 = time.time()
    gen = handle.options(method_name="chunks", stream=True).remote()
    assert gen.last_chunk_ts == 0.0 and gen.streaming
    got = []
    for chunk in gen:
        got.append(chunk)
        assert t0 <= gen.last_chunk_ts <= time.time()
    assert got == YIELDED and [type(c) for c in got] == \
        [type(c) for c in YIELDED]


def test_the_replica_frames_a_generators_chunks_and_nothing_else():
    from ray_tpu.serve.replica import ServeReplica, StampedChunk
    from ray_tpu.utils import serialization

    def gen(n):
        for i in range(n):
            yield i

    def whole(n):
        return n

    blob = serialization.serialize(((), {}))
    rep = ServeReplica("framed", "r1", serialization.serialize(gen), blob)
    t0 = time.time()
    meta, *items = rep.handle_request_streaming("__call__", (3,), {})
    assert meta == {"streaming": True}
    assert all(isinstance(c, StampedChunk) for c in items)
    assert [c.chunk for c in items] == [0, 1, 2]
    stamps = [c.ts for c in items]
    assert stamps == sorted(stamps) and t0 <= stamps[0] <= time.time()
    # a whole result is no chunk of a stream: it goes as it is
    rep = ServeReplica("plain", "r2", serialization.serialize(whole), blob)
    assert list(rep.handle_request_streaming("__call__", (3,), {})) == \
        [{"streaming": False}, 3]


@serve.deployment
class Boom:
    def __call__(self, request):
        raise ValueError("no")


def _status(port: int, path: str) -> int:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", path)
    try:
        resp = conn.getresponse()
        resp.read()   # to the connection's close: the handler has returned
        return resp.status
    finally:
        conn.close()


# ------------------------------------------------------------ head sampling
def test_a_request_passed_over_leaves_the_main_buffer_as_it_was(
        runtime, sample_rate):
    """The verdict is drawn where the trace starts, at the proxy, and all
    of the request inherits it: at rate 0 the whole trace waits in the
    tail ring, a span a chunk is not made, and a keep brings the trace
    back whole."""
    serve.run(Chunks.bind(), route_prefix="/", http=True)
    port = serve.http_port()
    _read_all(port, "/x")            # the router is built outside the record
    sample_rate(0.0)
    _read_all(port, "/x")
    tracing.disable_tracing()
    assert _request_spans() == []
    tail = tracing.tail_stats()
    assert tail["traces"] == 1 and tail["kept"] == 0
    trace_id, = tracing._tail
    tracing.mark_keep(trace_id, "test")
    kept = _request_spans()
    assert {s.trace_id for s in kept} == {trace_id}
    assert len(kept) == tail["spans"] and tracing.tail_stats()["spans"] == 0
    root, = [s for s in kept if s.parent_id is None]
    assert root.name == "proxy.request"
    assert root.attributes["chunks"] == len(YIELDED)
    handle, = [s for s in kept if s.name.startswith("serve.request.")]
    assert handle.parent_id == root.span_id
    assert any(s.name == "handle_request_streaming" for s in kept)
    assert not any(s.name.startswith(("serve.chunk_out", "serve.close"))
                   for s in kept)


def test_a_unary_handle_call_inherits_the_verdict_it_is_made_under(
        runtime, sample_rate):
    handle = serve.run(Chunks.bind())
    assert handle.whole.remote().result(timeout=60) == len(YIELDED)
    sample_rate(1.0)                 # the handle's own draw would say yes
    with tracing.span("outer", ctx={"sampled": False}) as outer:
        resp = handle.whole.remote()
        assert resp._sampled is False
        assert resp.result(timeout=60) == len(YIELDED)
    assert _request_spans() == []
    tracing.mark_keep(outer.trace_id, "test")
    mine = {s.name: s for s in _request_spans()}
    assert mine["serve.request.Chunks"].parent_id == outer.span_id
    assert {s.trace_id for s in mine.values()} == {outer.trace_id}
    tracing.clear()
    sample_rate(0.0)                 # ... and here it would say no
    with tracing.span("outer", ctx={"sampled": True}):
        resp = handle.whole.remote()
        assert resp._sampled is True
        resp.result(timeout=60)
    assert "serve.request.Chunks" in {s.name for s in _request_spans()}
    # with no trace around the call the handle draws, as it did
    assert handle.whole.remote()._sampled is False


def test_a_request_that_fails_is_kept_whatever_the_draw(runtime, sample_rate):
    serve.run(Boom.bind(), route_prefix="/", http=True)
    port = serve.http_port()
    assert _status(port, "/x") == 500    # untraced: served the same
    assert _request_spans() == []
    sample_rate(0.0)
    assert _status(port, "/x") == 500
    tracing.disable_tracing()
    root, = [s for s in tracing.spans() if s.name == "proxy.request"]
    assert root.status == "ERROR: HTTP 500"
    assert root.attributes == {"path": "/x", "status": 500, "chunks": 0,
                               "bytes": 0}
    assert tracing.tail_stats()["kept"] == 1
    assert tracing.tail_stats()["spans"] == 0


def test_a_chunk_is_a_phase_on_the_profilers_host_plane(runtime, tmp_path):
    import glob
    import os

    import jax

    serve.run(Chunks.bind(), route_prefix="/", http=True)
    jax.profiler.start_trace(str(tmp_path))
    _read_all(serve.http_port(), "/x")
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    events = [(e.name, dict(e.stats))
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith("serve.")]
    out = [st for name, st in events if name == "serve.chunk_out"]
    assert [st["bytes"] for st in out] == [11, 5, 13, 8, 1, 0]
    assert all(set(st) == {"lag_us", "bytes"} and st["lag_us"] >= 0
               for st in out)
    close = [st for name, st in events if name == "serve.close"]
    assert len(close) == 1 and close[0]["lag_us"] >= 0
    assert _request_spans() == []   # the profiler alone: no span recorded


# ------------------------------------------------- the LLM server's two ends
def _completion(port: int, prompt, max_tokens: int, stream: bool) -> bytes:
    return _read_all(port, "/v1/completions", json.dumps({
        "prompt": prompt, "max_tokens": max_tokens, "temperature": 0.0,
        "stream": stream, "received_ts": 12.5}).encode())


@pytest.fixture
def llm_app(runtime):
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.serving import build_openai_app

    cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=128,
                    prefix_block_tokens=0)
    return serve.run(build_openai_app(cfg), route_prefix="/", http=True)


def test_a_streamed_completion_is_counted_at_both_ends(llm_app):
    before = llm_app.stats.remote().result(timeout=60)
    text = _completion(serve.http_port(), [5, 6, 7, 8], 9, True).decode()
    assert text.rstrip().endswith("data: [DONE]")
    assert "received_ts" not in text and "StampedChunk" not in text
    frames = [json.loads(ln[6:]) for ln in text.splitlines()
              if ln.startswith("data: ") and ln != "data: [DONE]"]
    assert set(frames[0]) == {"id", "object", "model", "choices"}
    after = llm_app.stats.remote().result(timeout=60)
    for k in ("ingress_requests", "last_frames", "first_frames"):
        assert after[k] - before[k] == 1, k
    # what a client put under the stamp's name was not taken for one: the
    # way in is this machine's milliseconds, not 1.7e9 s back to 12.5
    for k in ("ingress_s", "last_frame_lag_s"):
        assert 0.0 <= after[k] - before[k] < 30.0, k


def test_a_unary_completion_counts_its_way_in_and_no_frames(llm_app):
    before = llm_app.stats.remote().result(timeout=60)
    out = json.loads(_completion(serve.http_port(), [5, 6, 7], 3, False))
    assert out["usage"]["completion_tokens"] >= 1
    after = llm_app.stats.remote().result(timeout=60)
    assert after["ingress_requests"] - before["ingress_requests"] == 1
    assert after["last_frames"] == before["last_frames"]
    assert after["finished"] - before["finished"] == 1


def test_a_request_without_a_stamp_is_served_and_counted_nowhere(llm_app):
    before = llm_app.stats.remote().result(timeout=60)
    bare = serve.Request(method="POST", path="/v1/completions", body=json.dumps(
        {"prompt": [5, 6, 7], "max_tokens": 2}).encode())
    assert bare.received_ts == 0.0
    out = llm_app.remote(bare).result(timeout=120)
    assert out["choices"][0]["finish_reason"] in ("length", "stop")
    direct = llm_app.completions.remote([5, 6, 7], max_tokens=2).result(
        timeout=120)
    assert direct["usage"]["prompt_tokens"] == 3
    after = llm_app.stats.remote().result(timeout=60)
    assert after["finished"] - before["finished"] == 2
    assert after["ingress_requests"] == before["ingress_requests"]
    assert after["ingress_s"] == before["ingress_s"]


# ------------------------------------------------------------------ the spans
def test_the_request_s_trace_starts_at_the_proxy(runtime, sample_rate):
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.serving import build_openai_app

    sample_rate(1.0)
    cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=128)
    serve.run(build_openai_app(cfg), route_prefix="/", http=True)
    text = _completion(serve.http_port(), [5, 6, 7, 8], 5, True)
    tracing.disable_tracing()
    spans = tracing.spans()
    root, = [s for s in spans if s.name == "proxy.request"]
    assert root.parent_id is None and root.kind == "server"
    assert root.status == "OK"
    assert root.attributes["path"] == "/v1/completions"
    assert root.attributes["status"] == 200
    assert root.attributes["bytes"] == len(text)
    mine = [s for s in spans if s.trace_id == root.trace_id]
    handle, = [s for s in mine if s.name.startswith("serve.request.")]
    assert handle.parent_id == root.span_id
    queue, = [s for s in mine if s.name == "engine.queue"]
    assert root.start_ts <= queue.start_ts <= queue.end_ts <= root.end_ts
    assert {"engine.prefill", "engine.decode"} <= {s.name for s in mine}
    outs = [s for s in mine if s.name == "serve.chunk_out"]
    # a burst's frames are one chunk; then the finish frame and [DONE]
    assert len(outs) == root.attributes["chunks"] >= 3
    assert all(s.parent_id == root.span_id for s in outs)
    close, = [s for s in mine if s.name == "serve.close"]
    assert outs[-1].end_ts <= close.start_ts <= root.end_ts
    # the scheduler's own phases stay another trace
    assert not any(s.name == "engine.tick" for s in mine)


# --------------------------------------------------------------- a vacant slot
def _engine(slots: int):
    from ray_tpu.llm import LLMConfig, LLMEngine

    return LLMEngine(LLMConfig(model="tiny", max_num_seqs=slots,
                               max_seq_len=96, seed=5, decode_burst=4,
                               prefix_block_tokens=0))


def _one(engine, prompt, n):
    from ray_tpu.llm import SamplingParams

    req = engine.submit(prompt, SamplingParams(max_tokens=n, temperature=0.0))
    assert req.done.wait(120) and not req.error
    return req


def test_a_slot_s_turn_round_is_booked_where_it_is_taken_again():
    eng = _engine(2)
    try:
        a = _one(eng, [3, 4, 5], 3)
        s = eng.stats()
        # a first use of a slot counts nowhere
        assert (s["slot_refills"], s["slot_vacant_s"]) == (0, 0.0)
        time.sleep(0.05)
        b = _one(eng, [6, 7, 8, 9], 3)
        c = _one(eng, [9, 8, 7], 3)
        s = eng.stats()
        slots = [r.last_slot for r in (a, b, c)]
        assert len(set(slots)) == 2   # two slots, three requests: one refill
        assert s["slot_refills"] == 1
        first = a if slots.count(a.last_slot) == 2 else b
        again = c if c.last_slot == first.last_slot else b
        assert s["slot_vacant_s"] == pytest.approx(
            again.admit_ts - first.finish_ts)
        assert all(r.finish_ts >= r.first_token_ts >= r.admit_ts
                   for r in (a, b, c))
        d = _one(eng, [1, 2, 3], 2)
        assert eng.stats()["slot_refills"] == 2 and d.finish_ts > 0
    finally:
        eng.shutdown()


def test_a_held_slot_s_vacancy_starts_at_its_release():
    eng = _engine(1)
    try:
        payload = eng.prefill_only([3, 4, 5, 6])
        assert payload["first_token"] is not None
        assert eng.stats()["slot_refills"] == 0
        t_released = time.time()   # prefill_only released it before it came back
        nxt = _one(eng, [7, 8, 9], 2)
        s = eng.stats()
        assert s["slot_refills"] == 1
        assert 0.0 <= s["slot_vacant_s"] <= nxt.admit_ts - t_released + 1.0
    finally:
        eng.shutdown()


def test_a_burst_s_dispatch_says_how_many_of_its_steps_took_a_chunk():
    eng = _engine(2)
    try:
        _one(eng, [3, 4, 5], 2)   # compile outside the record
        tracing.enable_tracing()
        _one(eng, [6, 7, 8, 9], 9)
        tracing.disable_tracing()
        s = eng.stats()
    finally:
        eng.shutdown()
    dispatches = [sp for sp in tracing.spans()
                  if sp.name == "engine.decode_dispatch"]
    assert dispatches and all(
        set(sp.attributes) >= {"steps", "slots", "riders"}
        for sp in dispatches)
    # a model without a mixed burst takes no chunk along, ever
    assert s["prefill_chunks_riding"] == 0
    assert all(sp.attributes["riders"] == 0 for sp in dispatches)
