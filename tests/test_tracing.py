"""End-to-end request tracing: head/tail sampling, keep gossip, SLO
exemplars, and causal-context propagation across the serve and DAG planes
(ray_tpu/util/tracing.py + the handle/router/batcher/channel hops that
carry the context). Propagation edge drills: actor restart mid-call,
never-sent retry, cross-host DAG channel hop, head outage during an
in-flight traced request."""

import time

import pytest

import ray_tpu
from ray_tpu.core.exceptions import ActorDiedError
from ray_tpu.serve.config import ReplicaInfo
from ray_tpu.serve.handle import DeploymentResponse
from ray_tpu.serve.resilience import ResilienceSettings, RetryPolicy
from ray_tpu.serve.router import Router
from ray_tpu.util import metrics, tracing


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.clear()
    tracing.disable_tracing()
    tracing.configure_tail(max_traces=512, max_spans_per_trace=64,
                           ttl_s=30.0)
    yield
    tracing.clear()
    tracing.disable_tracing()
    tracing.configure_tail(max_traces=512, max_spans_per_trace=64,
                           ttl_s=30.0)


def _replicas(n, cap=8, settings=None):
    s = settings.to_dict() if settings is not None else None
    return [ReplicaInfo(replica_id=f"r{i}", deployment_name="d",
                        actor_name=f"a{i}", max_ongoing_requests=cap,
                        settings=s)
            for i in range(n)]


class _FakeRef:
    pass


class _FakeMethod:
    def remote(self, *a, **k):
        return _FakeRef()


class _FakeHandle:
    handle_request = _FakeMethod()


def _patch_submission(monkeypatch, result="ok"):
    monkeypatch.setattr(ray_tpu, "get_actor",
                        lambda *a, **k: _FakeHandle())
    monkeypatch.setattr(ray_tpu, "wait",
                        lambda refs, **k: (list(refs), []))
    monkeypatch.setattr(ray_tpu, "get", lambda ref, **k: result)


def _first_attempt_dies(resp, why, never_sent):
    """A ``ray_tpu.get`` that loses ``resp``'s first attempt with its
    replica, for every caller: the response asks for that ref, and so does
    the router's reaper, which observes a completed ref's outcome on a
    thread of its own; threads that earlier tests left behind ask for refs
    of theirs. (Failing the first call, whoever made it, let any of them
    take the error in the response's place.)"""
    dying = resp._attempts[0][0]

    def get(ref, **k):
        if ref is dying:
            raise ActorDiedError(next(iter(resp._tried)), why,
                                 never_sent=never_sent)
        return "ok"

    return get


def _traced_router(settings, n=1, cap=8):
    reps = _replicas(n, cap=cap, settings=settings)
    router = Router("d", lambda: reps)
    router.notify_replicas_changed(reps)
    return router


def _trace_spans(tid):
    return [s for s in tracing.spans() if s.trace_id == tid]


# ------------------------------------------------------------ sampling unit
class TestSampling:
    def test_head_sampling_boundaries(self):
        assert tracing.sample_request(1.0) is True
        assert tracing.sample_request(0.0) is False

    def test_unsampled_spans_land_in_tail_ring_not_buffer(self):
        tracing.enable_tracing()
        s = tracing.start_span("req", sampled=False)
        tracing.finish_span(s, sampled=False)
        assert tracing.spans() == []
        assert tracing.tail_stats()["traces"] == 1

    def test_mark_keep_promotes_and_queues_for_gossip(self):
        tracing.enable_tracing()
        s = tracing.start_span("req")
        tracing.finish_span(s, sampled=False)
        tracing.mark_keep(s.trace_id, "slow")
        assert [x.span_id for x in tracing.spans()] == [s.span_id]
        assert tracing.tail_stats()["traces"] == 0
        keeps = tracing.drain_keeps()
        assert keeps == [{"trace_id": s.trace_id, "reason": "slow"}]
        assert tracing.drain_keeps() == []  # drained

    def test_late_spans_of_kept_trace_go_straight_to_buffer(self):
        tracing.enable_tracing()
        s = tracing.start_span("early")
        tracing.finish_span(s, sampled=False)
        tracing.mark_keep(s.trace_id, "error")
        late = tracing.start_span(
            "late", ctx={"trace_id": s.trace_id, "parent_span_id": s.span_id})
        tracing.finish_span(late, sampled=False)
        assert {x.name for x in _trace_spans(s.trace_id)} == {"early", "late"}

    def test_apply_keeps_promotes_without_requeueing(self):
        """Head-gossiped keeps must not echo back to the head forever."""
        tracing.enable_tracing()
        s = tracing.start_span("req")
        tracing.finish_span(s, sampled=False)
        tracing.apply_keeps([s.trace_id])
        assert [x.span_id for x in tracing.spans()] == [s.span_id]
        assert tracing.drain_keeps() == []

    def test_tail_ring_bounds_and_ttl(self):
        tracing.enable_tracing()
        tracing.configure_tail(max_traces=2, max_spans_per_trace=2,
                               ttl_s=0.05)
        for i in range(3):
            s = tracing.start_span(f"t{i}")
            tracing.finish_span(s, sampled=False)
        st = tracing.tail_stats()
        assert st["traces"] == 2 and st["dropped"] >= 1  # oldest evicted
        time.sleep(0.06)
        s = tracing.start_span("fresh")
        tracing.finish_span(s, sampled=False)  # triggers lazy TTL sweep
        assert tracing.tail_stats()["traces"] == 1

    def test_latency_window_slow_verdict_needs_history(self):
        fresh = tracing.LatencyWindow(size=64, min_samples=8, refresh=1)
        assert fresh.observe(100.0) is False  # no history: never "slow"
        w = tracing.LatencyWindow(size=64, min_samples=8, refresh=100)
        for _ in range(8):
            w.observe(0.01)
        assert w.observe(5.0) is True
        assert w.observe(0.01) is False

    def test_sampled_context_round_trips_the_wire(self):
        assert tracing._coerce_sampled("False") is False
        assert tracing._coerce_sampled("0") is False
        assert tracing._coerce_sampled("true") is True
        tracing.adopt({"trace_id": "t", "parent_span_id": "p",
                       "sampled": "False"})
        assert tracing.current_sampled() is False
        tracing.adopt(None)
        assert tracing.current_context() is None


# ------------------------------------------------------------ exemplars
class TestExemplars:
    def test_histogram_observe_attaches_exemplar(self):
        h = metrics.Histogram("ex_test_latency", "t", boundaries=(0.1, 1.0),
                              tag_keys=("deployment",))
        h.observe(0.5, tags={"deployment": "d"}, exemplar="tid1")
        h.observe(0.6, tags={"deployment": "d"})  # no exemplar: no row
        snap = metrics.registry().snapshot()
        entry = next(m for m in snap["metrics"]
                     if m["name"] == "ex_test_latency")
        [(series_key, rows)] = entry["exemplars"]
        assert series_key == ["d"]
        assert len(rows) == 1 and rows[0][0] == "tid1"

    def test_merge_snapshots_keeps_newest_exemplars(self):
        entry = {"name": "m", "type": "histogram", "desc": "", "tag_keys": [],
                 "boundaries": [1.0], "buckets": [[[], [1, 0]]],
                 "sums": [[[], 0.5]], "counts": [[[], 1]]}
        a = dict(entry, exemplars=[[[], [["old", 0.5, 1.0]]]])
        b = dict(entry, exemplars=[[[], [[f"t{i}", 0.1, 10.0 + i]
                                         for i in range(6)]]])
        merged = metrics.merge_snapshots([{"metrics": [a]}, {"metrics": [b]}])
        rows = merged["metrics"][0]["exemplars"][0][1]
        assert "old" not in [r[0] for r in rows]  # newest-N wins
        assert rows[-1][0] == "t5"


# --------------------------------------------------- serve-plane propagation
class TestServePropagation:
    def test_request_root_spans_attempt_and_replica_share_trace(
            self, monkeypatch):
        router = _traced_router(
            ResilienceSettings(trace_sample_rate=1.0))
        _patch_submission(monkeypatch)
        tracing.enable_tracing()
        resp = DeploymentResponse(router, "m", (), {})
        assert resp.result(timeout=5) == "ok"
        root = next(s for s in tracing.spans()
                    if s.name == "serve.request.d")
        attempt = next(s for s in tracing.spans()
                       if s.name == "serve.attempt.d")
        assert attempt.trace_id == root.trace_id
        assert attempt.parent_id == root.span_id
        assert attempt.attributes.get("attempt") == 1
        assert root.attributes.get("latency_s") is not None

    def test_actor_restart_mid_call_retries_as_numbered_attempts(
            self, monkeypatch):
        """A replica dying mid-call (restart) surfaces as ActorDiedError;
        the policy retry must appear as attempt #2 under the SAME request
        trace, with the retry decision visible as a root-span event."""
        router = _traced_router(ResilienceSettings(
            trace_sample_rate=1.0,
            retry=RetryPolicy(max_retries=2, backoff_s=0.0)), n=2)
        _patch_submission(monkeypatch)
        tracing.enable_tracing()
        resp = DeploymentResponse(router, "m", (), {})
        # the first attempt is in flight on the dying incarnation
        monkeypatch.setattr(ray_tpu, "get", _first_attempt_dies(
            resp, "restarted", never_sent=False))
        assert resp.result(timeout=5) == "ok"
        root = next(s for s in tracing.spans()
                    if s.name == "serve.request.d")
        attempts = sorted(s.attributes.get("attempt")
                          for s in tracing.spans()
                          if s.name == "serve.attempt.d")
        assert attempts == [1, 2]
        assert root.attributes.get("retries") == 1
        assert any(ev["name"] == "retry" and ev.get("attempt") == 2
                   for ev in root.events)

    def test_never_sent_retry_is_attempt_two_same_trace(self, monkeypatch):
        """The transparent never-sent retry (policy budget untouched) still
        shows up as a numbered attempt span in the request trace."""
        router = _traced_router(ResilienceSettings(
            trace_sample_rate=1.0, retry=RetryPolicy(max_retries=0)), n=2)
        _patch_submission(monkeypatch)
        tracing.enable_tracing()
        resp = DeploymentResponse(router, "m", (), {})
        monkeypatch.setattr(ray_tpu, "get", _first_attempt_dies(
            resp, "mailbox drained", never_sent=True))
        assert resp.result(timeout=5) == "ok"
        root = next(s for s in tracing.spans()
                    if s.name == "serve.request.d")
        assert any(ev["name"] == "retry" and ev.get("kind") == "never_sent"
                   for ev in root.events)
        attempts = sorted(s.attributes.get("attempt")
                          for s in tracing.spans()
                          if s.name == "serve.attempt.d")
        assert attempts == [1, 2]

    def test_unsampled_errored_request_is_tail_kept(self, monkeypatch):
        """Head sampling said no, but the request errored: the trace is
        retroactively promoted and its keep queued for head gossip."""
        router = _traced_router(ResilienceSettings(
            trace_sample_rate=0.0, retry=RetryPolicy(max_retries=0)))
        _patch_submission(monkeypatch)

        def boom(ref, **k):
            raise RuntimeError("app error")

        monkeypatch.setattr(ray_tpu, "get", boom)
        tracing.enable_tracing()
        resp = DeploymentResponse(router, "m", (), {})
        with pytest.raises(RuntimeError):
            resp.result(timeout=5)
        root = next(s for s in tracing.spans()
                    if s.name == "serve.request.d")
        assert root.status.startswith("ERROR")
        assert any(ev["name"] == "tail_keep" and ev.get("reason") == "error"
                   for ev in root.events)
        keeps = tracing.drain_keeps()
        assert [k["trace_id"] for k in keeps] == [root.trace_id]

    def test_head_outage_during_traced_request_degrades_to_partial(
            self, monkeypatch):
        """With the head unreachable the keep verdict cannot flush — the
        caller still gets its result, the spans stay locally promoted, and
        the drained keep is requeued for the head's return (partial trace,
        never a wedged caller, never a lost verdict)."""
        router = _traced_router(ResilienceSettings(
            trace_sample_rate=0.0, retry=RetryPolicy(max_retries=0)))
        _patch_submission(monkeypatch)

        def boom(ref, **k):
            raise RuntimeError("app error")

        monkeypatch.setattr(ray_tpu, "get", boom)
        tracing.enable_tracing()
        resp = DeploymentResponse(router, "m", (), {})
        with pytest.raises(RuntimeError):
            resp.result(timeout=5)
        # The flusher drains the keep, the head RPC fails, the flusher
        # requeues — exactly what runtime/node_daemon do on call failure.
        keeps = tracing.drain_keeps()
        assert keeps
        tracing.requeue_keeps(keeps)
        assert tracing.drain_keeps() == keeps  # verdict survived the outage
        # And the spans were promoted locally regardless of the head.
        assert any(s.name == "serve.request.d" for s in tracing.spans())

    def test_batched_items_parent_to_their_own_traces(self):
        """@serve.batch fans many requests into one execution: each item's
        batch span must land on ITS request's trace."""
        from ray_tpu.serve.batching import batch

        @batch(max_batch_size=4, batch_wait_timeout_s=0.02)
        def doubled(items):
            return [x * 2 for x in items]

        tracing.enable_tracing()
        import threading

        tids, results = [], []

        def caller(i):
            with tracing.span(f"req{i}") as s:
                tids.append(s.trace_id)
                results.append(doubled(i))

        threads = [threading.Thread(target=caller, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sorted(results) == [0, 2, 4]
        # Item futures resolve BEFORE the loop stamps the batch spans:
        # wait for the stamps rather than racing them.
        deadline = time.time() + 5
        while time.time() < deadline:
            batch_spans = [s for s in tracing.spans()
                           if s.name == "serve.batch_item"]
            if len(batch_spans) == 3:
                break
            time.sleep(0.01)
        assert len(batch_spans) == 3
        assert sorted(s.trace_id for s in batch_spans) == sorted(tids)
        assert all(s.attributes["status"] == "OK" for s in batch_spans)


# ------------------------------------------------------- DAG-plane hop
@pytest.mark.dag
class TestDagPropagation:
    def test_channel_hop_carries_context_and_chains(self):
        """The push frame carries the trace; the reader's recv span parents
        to the push span, and the adopted context makes the reader's NEXT
        write chain hop 2 onto the same trace (cross-host shape: the reader
        is a different 'process' as far as the context is concerned)."""
        from ray_tpu.core.worker import global_worker
        from ray_tpu.dag.direct import DirectChannel

        ray_tpu.shutdown()
        ray_tpu.init(address="local-cluster", num_cpus=2)
        try:
            rt = global_worker.runtime
            ch1 = DirectChannel("trc1").connect(rt)
            ch1.ensure_reader(0)
            ch2 = DirectChannel("trc2").connect(rt)
            ch2.ensure_reader(0)
            tracing.enable_tracing()
            with tracing.span("driver") as root:
                tid = root.trace_id
                ch1.write({"x": 1})
            out = ch1.read(0, timeout=10)
            assert out == {"x": 1}
            # read() adopted the hop context: this write chains hop 2.
            ch2.write(out)
            assert ch2.read(0, timeout=10) == {"x": 1}
            spans = {s.name: s for s in tracing.spans()
                     if s.trace_id == tid}
            assert "dag.push.trc1" in spans and "dag.recv.trc1" in spans
            assert "dag.push.trc2" in spans and "dag.recv.trc2" in spans
            assert spans["dag.recv.trc1"].parent_id == \
                spans["dag.push.trc1"].span_id
            # Hop 2's push descends from hop 1's recv — the chain holds.
            assert spans["dag.push.trc2"].parent_id == \
                spans["dag.recv.trc1"].span_id
            # An untraced frame must clear the adopted context.
            tracing.adopt(None)
            ch1.write({"y": 2})
            ch1.read(0, timeout=10)
            assert tracing.current_context() is None
            ch1.destroy()
            ch2.destroy()
        finally:
            tracing.disable_tracing()
            ray_tpu.shutdown()
