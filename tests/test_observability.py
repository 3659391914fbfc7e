"""Metrics / tracing / task events / state API / dashboard.

Mirrors the reference's observability test surface (reference:
python/ray/tests/test_metrics_agent.py, test_state_api.py, tracing tests):
everything runs against the in-process runtime.
"""

import json
import urllib.error
import urllib.request

import pytest

from ray_tpu.core import events
from ray_tpu.util import metrics, tracing


@pytest.fixture(autouse=True)
def _clean_buffers():
    events.global_event_buffer().clear()
    tracing.clear()
    tracing.disable_tracing()
    yield
    tracing.disable_tracing()


class TestMetrics:
    def test_counter_gauge(self):
        c = metrics.Counter("test_requests_total", "reqs", tag_keys=("route",))
        c.inc(tags={"route": "/a"})
        c.inc(2, tags={"route": "/a"})
        c.inc(tags={"route": "/b"})
        g = metrics.Gauge("test_queue_depth", "depth")
        g.set(7)
        text = metrics.registry().export_prometheus()
        assert 'test_requests_total{route="/a"} 3.0' in text
        assert 'test_requests_total{route="/b"} 1.0' in text
        assert "test_queue_depth 7.0" in text
        assert "# TYPE test_requests_total counter" in text

    def test_histogram_buckets(self):
        h = metrics.Histogram("test_latency_s", "lat", boundaries=[0.1, 1.0])
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        text = metrics.registry().export_prometheus()
        assert 'test_latency_s_bucket{le="0.1"} 1' in text
        assert 'test_latency_s_bucket{le="1.0"} 2' in text
        assert 'test_latency_s_bucket{le="+Inf"} 3' in text
        assert "test_latency_s_count 3" in text

    def test_counter_rejects_negative_and_unknown_tags(self):
        c = metrics.Counter("test_neg", "", tag_keys=("a",))
        with pytest.raises(ValueError):
            c.inc(-1)
        with pytest.raises(ValueError):
            c.inc(tags={"bogus": "x"})

    def test_le_canonical_float_format(self):
        """Integer boundaries must render like their float equivalents
        (le="5.0", not le="5") so scrapers see one canonical format."""
        h = metrics.Histogram("test_int_bounds", "", boundaries=[1, 5])
        h.observe(0.5)
        h.observe(3)
        text = metrics.registry().export_prometheus()
        assert 'test_int_bounds_bucket{le="1.0"} 1' in text
        assert 'test_int_bounds_bucket{le="5.0"} 2' in text
        assert 'le="1"' not in text and 'le="5"' not in text

    def test_label_escaping_shared_helper(self):
        c = metrics.Counter("test_escape", "", tag_keys=("path",))
        c.inc(tags={"path": 'a"b\\c\nd'})
        text = metrics.registry().export_prometheus()
        assert 'path="a\\"b\\\\c\\nd"' in text

    def test_export_prometheus_concurrent_writers(self):
        """N writer threads inc/observe while the main thread exports: no
        exceptions, and the final export carries every increment."""
        import threading

        c = metrics.Counter("test_conc_total", "", tag_keys=("t",))
        h = metrics.Histogram("test_conc_lat", "", boundaries=[0.5, 1.0])
        n_threads, n_iters = 8, 300
        start = threading.Barrier(n_threads + 1)
        errors: list = []

        def writer(idx: int):
            try:
                start.wait(timeout=10)
                for _ in range(n_iters):
                    c.inc(tags={"t": str(idx)})
                    h.observe(0.25)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        start.wait(timeout=10)
        exports = []
        while any(t.is_alive() for t in threads):
            exports.append(metrics.registry().export_prometheus())
        for t in threads:
            t.join(timeout=10)
        assert not errors
        final = metrics.registry().export_prometheus()
        for i in range(n_threads):
            assert f'test_conc_total{{t="{i}"}} {float(n_iters)}' in final
        assert f"test_conc_lat_count {n_threads * n_iters}" in final
        assert exports  # exporting concurrently never raised

    def test_snapshot_merge_and_federated_export(self):
        """Round-trip: registry -> snapshot -> (merge) -> federated text
        with node_id labels on every series."""
        c = metrics.Counter("test_fed_total", "reqs", tag_keys=("route",))
        c.inc(2, tags={"route": "/x"})
        g = metrics.Gauge("test_fed_depth", "")
        g.set(3)
        h = metrics.Histogram("test_fed_lat", "", boundaries=[1.0])
        h.observe(0.5)
        snap_a = metrics.registry().snapshot()
        c.inc(3, tags={"route": "/x"})  # node B reports a later state
        snap_b = metrics.registry().snapshot()
        # Two processes on one node merge: counters sum, gauges last-write.
        merged = metrics.merge_snapshots([snap_a, snap_b])
        entry = next(e for e in merged["metrics"]
                     if e["name"] == "test_fed_total")
        assert dict((tuple(k), v) for k, v in entry["points"])[("/x",)] == 7.0
        text = metrics.export_prometheus_federated(
            {"nodeA": snap_a, "nodeB": snap_b})
        assert 'test_fed_total{route="/x",node_id="nodeA"} 2.0' in text
        assert 'test_fed_total{route="/x",node_id="nodeB"} 5.0' in text
        assert 'test_fed_depth{node_id="nodeA"} 3.0' in text
        assert 'test_fed_lat_bucket{node_id="nodeA",le="1.0"} 1' in text
        # HELP/TYPE once per metric name, not once per node
        assert text.count("# TYPE test_fed_total counter") == 1

    def test_dropped_events_counter_exported(self):
        buf = events.TaskEventBuffer(max_events=2)
        for i in range(5):
            buf.record(f"t{i}", "noisy", "SUBMITTED")
        assert buf.dropped == 3
        text = metrics.registry().export_prometheus()
        assert "task_events_dropped_total" in text
        value = next(
            float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("task_events_dropped_total "))
        assert value >= 3


class TestTaskEventsAndTimeline:
    def test_events_recorded(self, rt_start):
        rt = rt_start

        @rt.remote
        def f():
            return 1

        assert rt.get(f.remote()) == 1
        states = {e.state for e in events.global_event_buffer().events()}
        assert {"SUBMITTED", "RUNNING", "FINISHED"} <= states

    def test_failed_task_event(self, rt_start):
        rt = rt_start

        @rt.remote(max_retries=0)
        def boom():
            raise ValueError("x")

        with pytest.raises(Exception):
            rt.get(boom.remote())
        states = [e.state for e in events.global_event_buffer().events()]
        assert "FAILED" in states

    def test_timeline_chrome_trace(self, rt_start, tmp_path):
        rt = rt_start

        @rt.remote
        def g():
            return 2

        rt.get([g.remote() for _ in range(3)])
        trace = rt.timeline()
        assert len(trace) >= 3
        assert all(ev["ph"] == "X" and ev["dur"] >= 0 for ev in trace)
        path = rt.timeline(str(tmp_path / "trace.json"))
        with open(path) as f:
            assert json.load(f)


class TestTracing:
    def test_span_propagation_into_task(self, rt_start):
        rt = rt_start
        tracing.enable_tracing()

        @rt.remote
        def traced():
            return 42

        with tracing.span("driver-op") as root:
            ref = traced.remote()
            assert rt.get(ref) == 42
        spans = tracing.spans()
        names = [s.name for s in spans]
        assert "driver-op" in names
        assert "traced" in names
        worker_span = next(s for s in spans if s.name == "traced")
        assert worker_span.trace_id == root.trace_id
        assert worker_span.parent_id == root.span_id

    def test_disabled_is_noop(self, rt_start):
        rt = rt_start

        @rt.remote
        def f():
            return 1

        rt.get(f.remote())
        assert tracing.spans() == []

    def test_span_error_status(self):
        tracing.enable_tracing()
        with pytest.raises(RuntimeError):
            with tracing.span("bad"):
                raise RuntimeError("no")
        s = tracing.spans()[-1]
        assert s.status.startswith("ERROR")
        assert s.attributes["exception.type"] == "RuntimeError"
        assert s.attributes["exception.message"] == "no"

    def test_span_context_restored_in_pool_threads(self):
        """A span opened on an executor pool thread must not leak its ids
        into the next task that reuses the same thread."""
        from concurrent.futures import ThreadPoolExecutor

        tracing.enable_tracing()
        pool = ThreadPoolExecutor(max_workers=1)

        def traced_work():
            with tracing.span("pooled-op"):
                pass
            return tracing.current_context()

        def probe():
            return tracing.current_context()

        assert pool.submit(traced_work).result() is None
        # Same thread, next task: no inherited context.
        assert pool.submit(probe).result() is None
        pool.shutdown()

    def test_flush_new_keeps_local_spans(self):
        tracing.enable_tracing()
        with tracing.span("a"):
            pass
        with tracing.span("b"):
            pass
        batch, cursor = tracing.flush_new(0)
        assert [s["name"] for s in batch] == ["a", "b"]
        assert len(tracing.spans()) == 2  # flush is a copy, not a drain
        batch2, cursor2 = tracing.flush_new(cursor)
        assert batch2 == [] and cursor2 == cursor
        with tracing.span("c"):
            pass
        batch3, _ = tracing.flush_new(cursor)
        assert [s["name"] for s in batch3] == ["c"]


class TestStateApi:
    def test_list_entities(self, rt_start):
        rt = rt_start
        from ray_tpu.util import state

        @rt.remote
        class A:
            def ping(self):
                return "pong"

        a = A.remote()
        assert rt.get(a.ping.remote()) == "pong"
        nodes = state.list_nodes()
        assert len(nodes) == 1 and nodes[0]["alive"]
        actors = state.list_actors()
        assert len(actors) == 1 and actors[0]["state"] == "ALIVE"
        tasks = state.list_tasks(filters=[("state", "=", "FINISHED")])
        assert any(t["name"] == "ping" for t in tasks)
        summary = state.summarize_tasks()
        assert summary["ping"]["FINISHED"] == 1
        objs = state.list_objects()
        assert objs[0]["num_objects"] >= 0

    def test_filters(self, rt_start):
        rt = rt_start

        @rt.remote
        def ok():
            return 1

        rt.get(ok.remote())
        from ray_tpu.util import state

        assert state.list_tasks(filters=[("state", "=", "NOPE")]) == []
        with pytest.raises(ValueError):
            state.list_tasks(filters=[("state", ">", "x")])


class TestClusterEvents:
    def test_worker_events_reach_driver(self, wait_for):
        """Worker-side RUNNING/FINISHED events flush to the head and appear in
        the driver's list_tasks and timeline (reference: TaskEventBuffer →
        GcsTaskManager → state API)."""
        import ray_tpu
        from ray_tpu.util import state

        ray_tpu.shutdown()
        ray_tpu.init(address="local-cluster", num_cpus=2)
        try:
            @ray_tpu.remote
            def traced_task():
                return 7

            assert ray_tpu.get(traced_task.remote()) == 7

            def finished():
                rows = state.list_tasks(filters=[("name", "=", "traced_task")])
                return rows and rows[0]["state"] == "FINISHED"

            wait_for(finished, timeout=15, desc="worker events at the head")
            trace = ray_tpu.timeline()
            assert any(ev["name"] == "traced_task" for ev in trace)
        finally:
            ray_tpu.shutdown()


class TestDashboard:
    def test_http_endpoints(self, rt_start):
        rt = rt_start
        from ray_tpu.dashboard.http_server import DashboardServer

        @rt.remote
        def h():
            return 1

        rt.get(h.remote())
        srv = DashboardServer()
        host, port = srv.start()
        try:
            def get(path):
                with urllib.request.urlopen(f"http://{host}:{port}{path}", timeout=5) as r:
                    body = r.read()
                    return r.headers.get_content_type(), body

            ctype, body = get("/api/version")
            assert ctype == "application/json"
            assert json.loads(body)["version"]
            _, body = get("/api/nodes")
            assert json.loads(body)[0]["alive"]
            _, body = get("/api/tasks")
            assert any(t["name"] == "h" for t in json.loads(body))
            _, body = get("/api/cluster_status")
            assert "cluster_resources" in json.loads(body)
            ctype, body = get("/metrics")
            assert ctype == "text/plain"
            _, body = get("/api/timeline")
            assert isinstance(json.loads(body), list)
            # watchdog surfaces degrade gracefully off-cluster
            _, body = get("/api/incidents")
            assert json.loads(body) == []
            _, body = get("/api/timeseries")
            assert json.loads(body) == []
            _, body = get("/api/watchdog")
            assert json.loads(body)["enabled"] is False
            # web UI at the root: an SPA shell that loads the app module
            ctype, body = get("/")
            assert ctype == "text/html"
            page = body.decode()
            assert "/app.js" in page and "</html>" in page
            ctype, body = get("/app.js")
            assert ctype == "text/javascript"
            app = body.decode()
            # the client drives the same JSON API surface
            for ep in ("/api/cluster_status", "/api/nodes", "/api/actors",
                       "/api/tasks", "/api/placement_groups",
                       "/api/jobs/list", "/api/logs"):
                assert ep in app, ep
            ctype, _ = get("/app.css")
            assert ctype == "text/css"
            # per-node log endpoints exist (cluster mode returns data; the
            # in-process runtime yields an empty listing)
            _, body = get("/api/logs")
            assert json.loads(body) == []
        finally:
            srv.stop()

    def test_unknown_route_404(self, rt_start):
        from ray_tpu.dashboard.http_server import DashboardServer

        srv = DashboardServer()
        host, port = srv.start()
        try:
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(f"http://{host}:{port}/nope", timeout=5)
        finally:
            srv.stop()


def test_otlp_export_shape(rt_start):
    from ray_tpu.util import tracing

    tracing.clear()
    tracing.enable_tracing()
    try:
        with tracing.span("outer", kind="client"):
            with tracing.span("inner"):
                pass
        otlp = tracing.export_otlp()
        spans = otlp["resourceSpans"][0]["scopeSpans"][0]["spans"]
        names = {s["name"] for s in spans}
        assert {"outer", "inner"} <= names
        inner = next(s for s in spans if s["name"] == "inner")
        outer = next(s for s in spans if s["name"] == "outer")
        assert inner["parentSpanId"] == outer["spanId"]
        assert inner["traceId"] == outer["traceId"]
        assert int(inner["endTimeUnixNano"]) >= int(inner["startTimeUnixNano"])
    finally:
        tracing.disable_tracing()


def test_cross_process_trace_propagation(rt_start):
    """A traced submission's context rides the TaskSpec into the executor
    (reference: _DictPropagator through task metadata)."""
    import ray_tpu
    from ray_tpu import remote
    from ray_tpu.util import tracing

    tracing.clear()
    tracing.enable_tracing()
    try:
        @remote
        def traced():
            return 1

        with tracing.span("driver", kind="client"):
            ref = traced.remote()
        assert ray_tpu.get(ref, timeout=30) == 1
        by_name = {s.name: s for s in tracing.spans()}
        assert "driver" in by_name and "traced" in by_name
        assert by_name["traced"].trace_id == by_name["driver"].trace_id
    finally:
        tracing.disable_tracing()


def test_cli_status_and_list(rt_start, capsys):
    from ray_tpu.scripts.cli import main

    assert main(["status"]) == 0
    out = capsys.readouterr().out
    assert "Cluster resources" in out and "CPU" in out
    assert main(["list", "nodes", "--json"]) == 0
    import json as _json

    rows = _json.loads(capsys.readouterr().out)
    assert isinstance(rows, list)


def test_cli_timeline(rt_start, tmp_path, capsys):
    import ray_tpu
    from ray_tpu import remote
    from ray_tpu.scripts.cli import main

    @remote
    def work():
        return 1

    ray_tpu.get([work.remote() for _ in range(3)])
    out = str(tmp_path / "tl.json")
    assert main(["timeline", "--out", out]) == 0
    import json as _json

    doc = _json.load(open(out))
    # Chrome-trace object format: task slices + span rows under traceEvents.
    assert isinstance(doc, dict) and doc["traceEvents"]
    names = {ev.get("name") for ev in doc["traceEvents"]}
    assert "work" in names


def test_usage_recording(rt_start, tmp_path, monkeypatch):
    from ray_tpu import usage

    usage.record_library_usage("train")
    usage.record_library_usage("train")  # dedup
    assert "library:train" in usage.recorded_features()
    monkeypatch.setenv("RTPU_USAGE_STATS_ENABLED", "0")
    usage.record_library_usage("secret")
    assert "library:secret" not in usage.recorded_features()


class TestFlightRecorder:
    def test_failing_task_dumps_bundle(self, rt_start, tmp_path, wait_for,
                                       monkeypatch):
        """A terminally failing task produces a debug bundle with the task's
        events, the client + worker spans, and a metrics snapshot —
        retrievable via ray_tpu.util.state (reference capability: a
        post-mortem slice of GcsTaskManager + the metrics agent)."""
        import os

        from ray_tpu.core import flight_recorder
        from ray_tpu.utils.config import get_config

        monkeypatch.setattr(get_config(), "temp_dir", str(tmp_path))
        rt = rt_start
        tracing.enable_tracing()
        gate = str(tmp_path / "gate")

        @rt.remote(max_retries=0)
        def kaboom(gate_path):
            import os as _os
            import time as _time

            deadline = _time.monotonic() + 5
            while not _os.path.exists(gate_path) and \
                    _time.monotonic() < deadline:
                _time.sleep(0.005)
            raise ValueError("flight-test")

        with tracing.span("driver-submit", kind="client"):
            ref = kaboom.remote(gate)
        # Open the gate only once the client span is closed, so the bundle
        # dumped at failure time deterministically contains it.
        with open(gate, "w") as f:
            f.write("go")
        with pytest.raises(Exception):
            rt.get(ref)

        def bundle():
            for rec in reversed(flight_recorder.list_records()):
                b = flight_recorder.get_record(rec["name"])
                if b["kind"] == "task_failure" and any(
                        e["state"] == "FAILED" and e["name"] == "kaboom"
                        for e in b["events"]):
                    return b
            return None

        b = wait_for(bundle, timeout=10, desc="task_failure flight record")
        assert "flight-test" in b["reason"]
        span_names = {s["name"] for s in b["spans"]}
        assert "driver-submit" in span_names  # client side
        assert "kaboom" in span_names  # worker side
        worker_span = next(s for s in b["spans"] if s["name"] == "kaboom")
        client_span = next(s for s in b["spans"]
                           if s["name"] == "driver-submit")
        assert worker_span["trace_id"] == client_span["trace_id"]
        assert b["metrics"]["metrics"]  # snapshot captured
        assert os.path.dirname(bundle_path := flight_recorder.list_records()
                               [-1]["path"]) == flight_recorder.records_dir()
        assert os.path.exists(bundle_path)
        # state API surface
        from ray_tpu.util.state import get_flight_record, list_flight_records

        rows = list_flight_records(kind="task_failure")
        assert rows
        assert get_flight_record(rows[-1]["name"])["kind"] == "task_failure"

    def test_bundle_pruning(self, tmp_path, monkeypatch):
        from ray_tpu.core import flight_recorder
        from ray_tpu.utils.config import get_config

        monkeypatch.setattr(get_config(), "temp_dir", str(tmp_path))
        monkeypatch.setattr(get_config(), "flight_recorder_max_bundles", 3)
        monkeypatch.setattr(flight_recorder, "MIN_INTERVAL_S", 0.0)
        for i in range(6):
            assert flight_recorder.record("task_failure", reason=f"r{i}")
        rows = flight_recorder.list_records()
        assert len(rows) == 3
        assert flight_recorder.get_record(rows[-1]["name"])["reason"] == "r5"

    def test_disabled(self, tmp_path, monkeypatch):
        from ray_tpu.core import flight_recorder
        from ray_tpu.utils.config import get_config

        monkeypatch.setattr(get_config(), "temp_dir", str(tmp_path))
        monkeypatch.setattr(get_config(), "flight_recorder_enabled", False)
        assert flight_recorder.record("task_failure") is None
        assert flight_recorder.list_records() == []


class TestHotPathMetrics:
    def test_train_report_gauges(self):
        from ray_tpu.train import session

        # Distinctive rank: other suites' Trainer runs report under ranks
        # 0..n in this same process-wide registry.
        ctx = session.TrainContext(world_rank=77)
        session.set_context(ctx)
        try:
            session.report({"loss": 1.0, "tokens": 512})
            session.report({"loss": 0.9, "tokens": 512,
                            "flops": 1e9, "peak_flops": 1e12})
        finally:
            session.set_context(None)
        text = metrics.registry().export_prometheus()
        assert 'train_step_time_s{rank="77"}' in text
        assert 'train_tokens_per_s{rank="77"}' in text
        assert 'train_mfu{rank="77"}' in text
        assert 'train_reports_total{rank="77"} 2.0' in text

    def test_serve_replica_ttft_tpot(self):
        from ray_tpu.serve.replica import ServeReplica
        from ray_tpu.utils import serialization

        def double(x):
            return x * 2

        rep = ServeReplica("obsdep", "r1", serialization.serialize(double),
                           serialization.serialize(((), {})))
        assert rep.handle_request("__call__", (21,), {}) == 42
        text = metrics.registry().export_prometheus()
        assert 'serve_ttft_s_count{deployment="obsdep"} 1' in text
        assert 'serve_request_latency_s_count{deployment="obsdep"} 1' in text
        assert 'serve_replica_requests_total{deployment="obsdep",' \
               'replica="r1"} 1.0' in text

        def gen(n):
            for i in range(n):
                yield i

        rep2 = ServeReplica("obsgen", "r2", serialization.serialize(gen),
                            serialization.serialize(((), {})))
        chunks = list(rep2.handle_request_streaming("__call__", (3,), {}))
        # a generator's chunks leave framed with the time they left at
        assert chunks[0] == {"streaming": True}
        assert [c.chunk for c in chunks[1:]] == [0, 1, 2]
        text = metrics.registry().export_prometheus()
        assert 'serve_ttft_s_count{deployment="obsgen"} 1' in text
        assert 'serve_tpot_s_count{deployment="obsgen"} 2' in text

    def test_collective_op_metrics(self, cpu_mesh_devices):
        import numpy as np

        import ray_tpu.collective as col

        col.init_collective_group(backend="xla", group_name="obs_coll",
                                  devices=cpu_mesh_devices, world_size=8)
        try:
            out = np.asarray(col.allreduce(np.ones(8, np.float32),
                                           group_name="obs_coll"))
            np.testing.assert_allclose(out, 8 * np.ones(8))
        finally:
            col.destroy_collective_group("obs_coll")
        text = metrics.registry().export_prometheus()
        assert 'collective_op_latency_s_count{op="allreduce",' \
               'group="obs_coll"} 1' in text
        assert 'collective_op_bytes_count{op="allreduce",' \
               'group="obs_coll"} 1' in text


class TestFederatedTelemetry:
    def test_two_node_metrics_at_head(self, wait_for):
        """Acceptance path: a 2-node cluster whose workers populate train +
        serve metrics; the head's telemetry table and the dashboard's
        /metrics show series from BOTH nodes under distinct node_id labels."""
        import urllib.request as _rq

        import ray_tpu
        from ray_tpu.cluster_utils import Cluster
        from ray_tpu.core.worker import global_worker
        from ray_tpu.utils.ids import JobID

        c = Cluster()
        c.add_node(num_cpus=1, node_id="obsnodea")
        c.add_node(num_cpus=1, node_id="obsnodeb")
        rt = c.connect()
        old = (global_worker.runtime, global_worker.worker_id,
               global_worker.node_id, global_worker.mode,
               global_worker.job_id)
        global_worker.runtime = rt
        global_worker.worker_id = rt.worker_id
        global_worker.node_id = rt.node_id
        global_worker.job_id = JobID.from_random()
        global_worker.mode = "cluster"
        try:
            @ray_tpu.remote(num_cpus=1)
            class Reporter:
                def bump(self):
                    from ray_tpu.serve.replica import ServeReplica
                    from ray_tpu.train import session
                    from ray_tpu.utils import serialization as ser

                    ctx = session.TrainContext(world_rank=0)
                    session.set_context(ctx)
                    session.report({"tokens": 128})
                    session.report({"tokens": 128})
                    session.set_context(None)
                    rep = ServeReplica(
                        "fed", "r0", ser.serialize(lambda x: x),
                        ser.serialize(((), {})))
                    rep.handle_request("__call__", (1,), {})
                    return True

            # One 1-CPU actor per 1-CPU node: placement must spread them.
            a, b = Reporter.remote(), Reporter.remote()
            assert ray_tpu.get([a.bump.remote(), b.bump.remote()],
                               timeout=120) == [True, True]

            def both_nodes():
                # Only WORKER-process sources count: this pytest process
                # (driver + in-process daemons, source "<node>:<ourpid>")
                # reports a registry other tests already filled with train
                # series, which must not satisfy the wait before both
                # Reporter workers actually flushed.
                import os as _os

                me = f":{_os.getpid()}"
                nodes = set()
                for src, row in rt.get_telemetry().get(
                        "sources", {}).items():
                    if src.endswith(me):
                        continue
                    for entry in (row.get("snapshot") or {}).get(
                            "metrics", []):
                        if entry["name"] == "train_step_time_s" and \
                                entry.get("points"):
                            nodes.add(row["node_id"])
                return nodes if len(nodes) >= 2 else None

            nodes = wait_for(both_nodes, timeout=30,
                             desc="train metrics from both nodes")
            assert nodes == {"obsnodea", "obsnodeb"}

            from ray_tpu.dashboard.http_server import DashboardServer

            srv = DashboardServer()
            host, port = srv.start()
            try:
                with _rq.urlopen(f"http://{host}:{port}/metrics",
                                 timeout=10) as r:
                    text = r.read().decode()
            finally:
                srv.stop()
            for nid in ("obsnodea", "obsnodeb"):
                assert f'train_step_time_s{{rank="0",node_id="{nid}"}}' \
                    in text, text[:2000]
                assert f'train_tokens_per_s{{rank="0",node_id="{nid}"}}' \
                    in text
            assert 'serve_ttft_s_bucket{deployment="fed"' in text
            assert 'serve_ttft_s_count{deployment="fed"' in text
        finally:
            rt.shutdown()
            c.shutdown()
            (global_worker.runtime, global_worker.worker_id,
             global_worker.node_id, global_worker.mode,
             global_worker.job_id) = old


class _FakeSeries:
    """Minimal stand-in for timeseries.Series in detector unit tests."""

    class _Key:
        def __init__(self, name, source="src", tags=()):
            self.name, self.source, self.tags = name, source, tuple(tags)

        def tag_dict(self):
            return dict(self.tags)

        def __hash__(self):
            return hash((self.name, self.source, self.tags))

        def __eq__(self, other):
            return (self.name, self.source, self.tags) == \
                (other.name, other.source, other.tags)

    def __init__(self, name="train_step_time_s", source="src", tags=()):
        self.key = self._Key(name, source, tags)
        self.node_id = "nodeX"


class TestWatchdogDetectors:
    """Streaming detector units: warmup, debounce, cooldown, and
    no-trip-on-clean-series — the firing discipline the zero-false-
    incident acceptance gate rests on."""

    def _spike_rule(self, **kw):
        from ray_tpu.observability.detectors import SpikeRule

        args = dict(z=6.0, ratio=2.0, warmup=5, debounce=2, cooldown_s=30.0)
        args.update(kw)
        return SpikeRule("r", ("train_step_time_s",), "train", **args)

    def test_warmup_suppresses_early_verdicts(self):
        rule = self._spike_rule(warmup=8)
        s = _FakeSeries()
        # Wild values inside the warmup window never trip.
        for i, v in enumerate([0.1, 5.0, 0.1, 9.0, 0.1, 7.0, 0.1, 8.0]):
            assert rule.update(s, float(i), v) is None

    def test_spike_trips_after_debounce(self):
        rule = self._spike_rule(debounce=2)
        s = _FakeSeries()
        t = 0.0
        for _ in range(10):
            t += 0.5
            assert rule.update(s, t, 0.1) is None
        t += 0.5
        assert rule.update(s, t, 2.0) is None  # first breach: debounced
        t += 0.5
        trip = rule.update(s, t, 2.0)  # second consecutive: trips
        assert trip is not None
        assert trip.rule == "r" and trip.kind == "train"
        assert "spiked" in trip.reason

    def test_single_blip_never_trips(self):
        rule = self._spike_rule(debounce=2)
        s = _FakeSeries()
        t = 0.0
        for _ in range(10):
            t += 0.5
            rule.update(s, t, 0.1)
        t += 0.5
        assert rule.update(s, t, 3.0) is None  # blip
        for _ in range(10):  # recovery resets the streak
            t += 0.5
            assert rule.update(s, t, 0.1) is None

    def test_cooldown_mutes_then_rearms(self):
        rule = self._spike_rule(debounce=1, cooldown_s=30.0)
        s = _FakeSeries()
        t = 0.0
        for _ in range(10):
            t += 0.5
            rule.update(s, t, 0.1)
        t += 0.5
        assert rule.update(s, t, 5.0) is not None
        # Sustained anomaly inside the cooldown: muted.
        for _ in range(5):
            t += 0.5
            assert rule.update(s, t, 5.0) is None
        # Past the cooldown, still anomalous vs the (slowly adapted)
        # baseline: a fresh incident fires.
        t += 31.0
        tripped = None
        for _ in range(6):
            t += 0.5
            tripped = tripped or rule.update(s, t, 8.0)
        assert tripped is not None

    def test_clean_noisy_series_never_trips(self):
        import random

        rule = self._spike_rule()
        rng = random.Random(7)
        s = _FakeSeries()
        t = 0.0
        for _ in range(300):
            t += 0.5
            assert rule.update(s, t, 0.1 * rng.uniform(0.8, 1.2)) is None

    def test_shed_threshold_rule(self):
        from ray_tpu.observability.detectors import ThresholdRule

        rule = ThresholdRule("shed", ("serve_shed_total:rate",), "serve",
                             threshold=0.5, warmup=0, debounce=2,
                             cooldown_s=30.0)
        s = _FakeSeries("serve_shed_total:rate")
        assert rule.update(s, 1.0, 0.1) is None  # under the floor
        assert rule.update(s, 1.5, 4.0) is None  # first breach
        trip = rule.update(s, 2.0, 6.0)
        assert trip is not None and "threshold" in trip.reason

    def test_queue_growth_derivative(self):
        from ray_tpu.observability.detectors import DerivativeRule

        rule = DerivativeRule("qg", ("serve_router_queue_depth",), "serve",
                              growth_per_s=2.0, warmup=3, debounce=2,
                              cooldown_s=30.0)
        s = _FakeSeries("serve_router_queue_depth")
        t, depth = 0.0, 0.0
        # Flat queue: no trip.
        for _ in range(10):
            t += 0.5
            assert rule.update(s, t, 5.0) is None
        # Queue growing 10/s: trips after debounce.
        tripped = None
        for _ in range(6):
            t += 0.5
            depth += 5.0
            tripped = tripped or rule.update(s, t, depth)
        assert tripped is not None

    def test_stalled_heartbeat_trips_while_silent(self, monkeypatch):
        """A FULLY stopped heartbeat must trip the jitter rule while the
        node is still silent (gap-so-far sampling) — not only after the
        next heartbeat finally lands and reports the gap in hindsight."""
        import time as _t

        from ray_tpu.observability import Watchdog

        class Info:
            def __init__(self, hb):
                self.last_heartbeat = hb
                self.alive = True

        hb0 = _t.monotonic()
        nodes = {"n1": Info(hb0)}
        wd = Watchdog(nodes_fn=lambda: nodes)
        for i in range(20):  # steady 0.25s heartbeats: baseline
            nodes["n1"].last_heartbeat = hb0 + (i + 1) * 0.25
            wd.observe_heartbeats()
        assert not wd._pending
        # then: total silence; ticks advance, no heartbeat ever arrives
        silent = nodes["n1"].last_heartbeat
        fake_now = iter(silent + 1.5 + 0.5 * i for i in range(40))
        monkeypatch.setattr(_t, "monotonic", lambda: next(fake_now))
        for _ in range(12):
            wd.observe_heartbeats()
        assert wd._pending, "stalled heartbeat never tripped"
        trip = wd._pending[0]
        assert trip.rule == "heartbeat_jitter"
        assert trip.series.key.tag_dict() == {"node": "n1"}

    def test_memory_leak_slope(self):
        from ray_tpu.observability.detectors import SlopeRule

        rule = SlopeRule("leak", ("proc_rss_bytes",), "memory",
                         slope_per_s=50e6, min_span_s=5.0, warmup=3,
                         debounce=2, cooldown_s=30.0)
        flat = _FakeSeries("proc_rss_bytes", source="flat")
        t = 0.0
        for _ in range(40):
            t += 0.5
            assert rule.update(flat, t, 1e9) is None
        leaky = _FakeSeries("proc_rss_bytes", source="leaky")
        t, rss = 0.0, 1e9
        tripped = None
        for _ in range(40):
            t += 0.5
            rss += 100e6  # 200 MB/s
            tripped = tripped or rule.update(leaky, t, rss)
        assert tripped is not None and "MB/s" in tripped.reason


class TestSeriesWire:
    """Delta-encoded sampler <-> store round trip (the report_telemetry
    piggyback format)."""

    def _snap(self, step=0.05, shed=0.0, bk=(0, 0, 0, 0), hsum=0.0,
              hcount=0.0):
        return {"metrics": [
            {"name": "train_step_time_s", "type": "gauge",
             "tag_keys": ["rank"], "points": [[["0"], step]]},
            {"name": "serve_shed_total", "type": "counter",
             "tag_keys": ["deployment", "where"],
             "points": [[["d", "router"], shed]]},
            {"name": "serve_ttft_s", "type": "histogram",
             "tag_keys": ["deployment"], "boundaries": [0.01, 0.1, 1.0],
             "buckets": [[["d"], list(bk)]],
             "sums": [[["d"], hsum]], "counts": [[["d"], hcount]]},
        ]}

    def test_defs_cross_wire_once(self):
        from ray_tpu.observability import SeriesSampler

        s = SeriesSampler()
        p1 = s.collect(self._snap(), now=100.0)
        assert any(name == "train_step_time_s"
                   for _sid, name, _t in p1["defs"])
        p2 = s.collect(self._snap(step=0.06), now=100.5)
        # Same series again: samples only, no re-declaration.
        assert not any(name == "train_step_time_s"
                       for _sid, name, _t in p2.get("defs", []))
        assert any(v == 0.06 for _sid, v in p2["s"])

    def test_unchanged_gauge_is_silent(self):
        from ray_tpu.observability import SeriesSampler

        s = SeriesSampler()
        p1 = s.collect(self._snap(step=0.05), now=100.0)
        step_sid = next(sid for sid, name, _t in p1["defs"]
                        if name == "train_step_time_s")
        p2 = s.collect(self._snap(step=0.05), now=100.5)
        # Identical snapshot: the train gauge must NOT resend (only RSS
        # wobble may show up).
        if p2 is not None:
            assert all(sid != step_sid for sid, _v in p2["s"])

    def test_counter_rate_and_trailing_zero(self):
        from ray_tpu.observability import SeriesSampler

        s = SeriesSampler()
        s.collect(self._snap(shed=0.0), now=100.0)
        p = s.collect(self._snap(shed=5.0), now=100.5)
        rates = [v for sid, v in p["s"]
                 if any(sid == d[0] and d[1] == "serve_shed_total:rate"
                        for d in p["defs"])]
        assert rates == [10.0]  # 5 sheds / 0.5 s
        p3 = s.collect(self._snap(shed=5.0), now=101.0)
        # Burst over: exactly one trailing zero-rate sample...
        assert any(v == 0.0 for _sid, v in (p3 or {}).get("s", []))
        # ...then silence (no zero-rate re-sends while the counter idles).
        p4 = s.collect(self._snap(shed=5.0), now=101.5)
        if p4 is not None:
            assert all(v != 0.0 for _sid, v in p4["s"])

    def test_hist_p99_estimate(self):
        from ray_tpu.observability.sampler import estimate_p99

        # 99 obs <= 0.01, 1 in (0.1, 1.0]: p99 lands inside bucket 1.
        assert estimate_p99([0.01, 0.1, 1.0], [99, 0, 1]) <= 0.1
        # All mass past the last boundary clamps to it.
        assert estimate_p99([0.01, 0.1, 1.0], [0, 0, 0]) is None
        p = estimate_p99([0.01, 0.1, 1.0], [5, 3, 1])
        assert 0.1 < p <= 1.0

    def test_store_roundtrip_and_resync(self):
        from ray_tpu.observability import SeriesSampler, SeriesStore

        s = SeriesSampler()
        store = SeriesStore()
        p1 = s.collect(self._snap(), now=None)
        assert store.ingest("w1", "nodeA", p1) is False
        p2 = s.collect(self._snap(step=0.07), now=None)
        # A fresh store (head restart) doesn't know p2's sids: resync.
        store2 = SeriesStore()
        assert store2.ingest("w1", "nodeA", p2) is True
        s.force_resync()
        p3 = s.collect(self._snap(step=0.09), now=None)
        assert any(name == "train_step_time_s"
                   for _sid, name, _t in p3["defs"])  # re-declared
        assert store2.ingest("w1", "nodeA", p3) is False
        rows = store2.query(name="train_step_time_s")
        assert rows and rows[0]["points"][-1][1] == 0.09
        assert rows[0]["node_id"] == "nodeA"

    def test_store_bounds_and_drop_source(self):
        from ray_tpu.observability import SeriesStore

        store = SeriesStore(max_points=4, max_series=2)
        for i in range(10):
            store.append("s1", "a", {}, float(i))
        assert len(store.query(name="a")[0]["points"]) == 4
        store.append("s1", "b", {}, 1.0)
        store.append("s1", "c", {}, 1.0)  # over the series cap: dropped
        assert store.dropped == 1
        assert not store.query(name="c")
        store.drop_source("s1")
        assert store.query() == []


class TestIncidentAssembly:
    """Watchdog evidence assembly with injectable host legs."""

    def _tripping_payloads(self):
        """defs+samples that walk a step-time series into a trip."""
        rows = []
        defs = [[0, "train_step_time_s", {"rank": "1"}]]
        import time as _t

        base = _t.time() - 20
        for i in range(20):
            v = 0.05 if i < 15 else 3.0
            rows.append({"t": base + i * 0.5,
                         "defs": defs if i == 0 else [],
                         "s": [[0, v]]})
        return rows

    def _drive(self, wd):
        for p in self._tripping_payloads():
            wd.ingest("wrk:1", "nodeZ", p)
        assert wd._pending, "detector never tripped"

    def test_complete_bundle_with_live_node(self, tmp_path, monkeypatch):
        import asyncio

        from ray_tpu.core import flight_recorder
        from ray_tpu.observability import Watchdog
        from ray_tpu.utils.config import get_config

        monkeypatch.setattr(get_config(), "temp_dir", str(tmp_path))
        monkeypatch.setattr(flight_recorder, "MIN_INTERVAL_S", 0.0)

        async def profile_ok(node_id, seconds):
            return {"captures": [{"samples": 42, "node_id": node_id}],
                    "errors": {}}

        stats = {"wrk:1": {"node_id": "nodeZ", "ts": __import__("time").time(),
                           "stats": {"1": {"steps": 20, "world_size": 2,
                                           "median_step_s": 3.0,
                                           "deciles": [3.0] * 11},
                                     "0": {"steps": 20, "world_size": 2,
                                           "median_step_s": 0.05,
                                           "deciles": [0.05] * 11}}}}
        wd = Watchdog(train_stats_fn=lambda: stats, nodes_fn=lambda: {},
                      profile_fn=profile_ok)
        self._drive(wd)
        inc = asyncio.run(wd._assemble(wd._pending.popleft()))
        assert inc["rule"] == "train_step_drift"
        # attribution found the slow rank via the straggler report
        assert inc["implicated"]["rank"] == 1
        assert inc["implicated"]["node_id"] == "nodeZ"
        assert len(inc["window"]) >= 3
        assert inc["flight_record"]
        assert inc["profile"]["status"] == "captured"
        assert inc["profile"]["samples"] == 42
        import os as _os

        assert _os.path.exists(inc["profile"]["path"])
        # retrievable through the deque API
        assert wd.list_incidents(incident_id=inc["id"])

    def test_dead_implicated_worker_partial_evidence(self, tmp_path,
                                                     monkeypatch):
        """A dead daemon (connect error) OR a wedged one (hang) must yield
        a partial bundle quickly — never stall the watchdog loop."""
        import asyncio
        import time as _t

        from ray_tpu.core import flight_recorder
        from ray_tpu.observability import Watchdog, watchdog
        from ray_tpu.utils.config import get_config

        monkeypatch.setattr(get_config(), "temp_dir", str(tmp_path))
        monkeypatch.setattr(get_config(), "watchdog_capture_seconds", 0.05)
        monkeypatch.setattr(watchdog, "CAPTURE_RPC_SLACK_S", 0.3)
        monkeypatch.setattr(flight_recorder, "MIN_INTERVAL_S", 0.0)

        async def profile_hang(node_id, seconds):
            await asyncio.sleep(3600)

        wd = Watchdog(profile_fn=profile_hang, nodes_fn=lambda: {})
        self._drive(wd)
        t0 = _t.monotonic()
        inc = asyncio.run(wd._assemble(wd._pending.popleft()))
        assert _t.monotonic() - t0 < 5.0  # bounded, not a hang
        assert inc["profile"]["status"].startswith("error:")
        # the REST of the evidence still landed
        assert inc["flight_record"] and len(inc["window"]) >= 3
        assert inc["implicated"]["node_id"] == "nodeZ"

    def test_capture_guardrails(self, tmp_path, monkeypatch):
        import asyncio

        from ray_tpu.observability import Watchdog
        from ray_tpu.utils.config import get_config

        monkeypatch.setattr(get_config(), "temp_dir", str(tmp_path))
        calls = []

        async def profile_ok(node_id, seconds):
            calls.append(node_id)
            return {"captures": [], "errors": {}}

        wd = Watchdog(profile_fn=profile_ok, nodes_fn=lambda: {})

        async def run():
            # budget
            wd.captures_done = get_config().watchdog_capture_budget
            out = await wd._auto_capture("i1", "nodeA")
            assert "budget" in out["status"]
            wd.captures_done = 0
            # per-node cooldown
            assert (await wd._auto_capture("i2", "nodeA"))["status"] \
                == "captured"
            out = await wd._auto_capture("i3", "nodeA")
            assert "cooldown" in out["status"]
            # a DIFFERENT node is not blocked by nodeA's cooldown
            assert (await wd._auto_capture("i4", "nodeB"))["status"] \
                == "captured"
            # concurrency cap
            wd._captures_inflight = get_config().watchdog_max_auto_captures
            out = await wd._auto_capture("i5", "nodeC")
            assert "concurrent" in out["status"]
            wd._captures_inflight = 0
            # disabled gate
            monkeypatch.setattr(get_config(), "watchdog_auto_capture",
                                False)
            wd.cfg = get_config()
            out = await wd._auto_capture("i6", "nodeD")
            assert "disabled" in out["status"]

        asyncio.run(run())
        assert calls == ["nodeA", "nodeB"]


class TestMetricsHygiene:
    """Satellite: the resilience/transfer/watchdog metric families keep
    consistent names and labels in the one federated namespace."""

    def test_label_and_name_conventions(self):
        from ray_tpu.core.transfer import _get_transfer_metrics
        from ray_tpu.observability.sampler import _get_sample_metrics
        from ray_tpu.observability.watchdog import _get_wd_metrics
        from ray_tpu.serve.replica import _get_replica_metrics
        from ray_tpu.serve.resilience import shed_metrics
        from ray_tpu.serve.router import _get_router_metrics

        serve_metrics = (list(shed_metrics().values())
                         + list(_get_router_metrics().values())
                         + list(_get_replica_metrics().values()))
        for m in serve_metrics:
            assert m.name.startswith("serve_"), m.name
            assert "deployment" in m.tag_keys, \
                f"{m.name} missing the deployment label"
        # PR-8 resilience counters present under their documented names
        names = {m.name for m in serve_metrics}
        assert {"serve_shed_total", "serve_expired_total",
                "serve_breaker_transitions_total",
                "serve_retries_total"} <= names
        shed = next(m for m in serve_metrics
                    if m.name == "serve_shed_total")
        assert tuple(shed.tag_keys) == ("deployment", "where")
        # PR-2 transfer metrics all carry the data-plane `path` label
        for m in _get_transfer_metrics():
            assert m.name.startswith("transfer_"), m.name
            assert "path" in m.tag_keys, m.name
        # watchdog self-metrics under their ISSUE-specified names
        wd = {m.name: m for m in _get_wd_metrics().values()}
        assert set(wd) == {"watchdog_incidents_total",
                           "watchdog_eval_seconds",
                           "watchdog_dropped_samples"}
        assert tuple(wd["watchdog_incidents_total"].tag_keys) == ("rule",)
        assert "watchdog_sample_seconds" in {
            m.name for m in _get_sample_metrics().values()}
        # node_id is the federation label — no metric may declare it
        for m in serve_metrics + list(_get_transfer_metrics()) \
                + list(wd.values()):
            assert "node_id" not in m.tag_keys, m.name

    def test_watchdog_metrics_render_federated(self):
        from ray_tpu.observability.watchdog import _get_wd_metrics

        wd = _get_wd_metrics()
        wd["incidents"].inc(tags={"rule": "hygiene_test"})
        wd["eval_seconds"].inc(0.01)
        snap = metrics.registry().snapshot()
        text = metrics.export_prometheus_federated({"hygnode": snap})
        assert 'watchdog_incidents_total{rule="hygiene_test",' \
               'node_id="hygnode"}' in text
        assert 'watchdog_eval_seconds{node_id="hygnode"}' in text


class TestWatchdogCluster:
    def test_rpc_delay_trips_collective_latency(self, wait_for):
        """Cluster round trip: a chaos `rpc delay` rule on the actor-call
        dispatch slows the host-backend collective, the collective-latency
        detector trips, and the incident carries a complete evidence
        bundle — the whole watchdog loop over real process boundaries."""
        import os as _os
        import time as _time

        import ray_tpu
        from ray_tpu.chaos import injector
        from ray_tpu.cluster_utils import Cluster
        from ray_tpu.core.worker import global_worker
        from ray_tpu.util.state import inject_chaos
        from ray_tpu.utils import config as config_mod
        from ray_tpu.utils.ids import JobID

        env = {
            "RTPU_TELEMETRY_FLUSH_INTERVAL_S": "0.25",
            "RTPU_WATCHDOG_EVAL_INTERVAL_S": "0.25",
            "RTPU_WATCHDOG_WARMUP_SAMPLES": "5",
            "RTPU_WATCHDOG_DEBOUNCE": "2",
            "RTPU_WATCHDOG_CAPTURE_SECONDS": "0.5",
        }
        for k, v in env.items():
            _os.environ[k] = v
        injector.reset_for_tests()
        config_mod.set_config(config_mod.Config.load())
        ray_tpu.shutdown()
        c = Cluster()
        c.add_node(num_cpus=4, node_id="wdcola")
        rt = c.connect()
        old = (global_worker.runtime, global_worker.worker_id,
               global_worker.node_id, global_worker.mode,
               global_worker.job_id)
        global_worker.runtime = rt
        global_worker.worker_id = rt.worker_id
        global_worker.node_id = rt.node_id
        global_worker.job_id = JobID.from_random()
        global_worker.mode = "cluster"
        try:
            # Warm the pool: cold worker forks cost seconds each on a
            # loaded 1-core box and would eat the baseline window.
            try:
                rt._daemon.call("prestart_workers", n=3, timeout=10)
            except Exception:
                pass

            @ray_tpu.remote(num_cpus=1)
            class Member:
                def setup(self, rank, world):
                    import ray_tpu.collective as col
                    from ray_tpu.train import session

                    # A train-session context pins the group registry key
                    # across actor calls (GroupManager keys per rank
                    # context; without it every call is a fresh task id
                    # and `col.allreduce` can't find the group again).
                    session.set_context(session.TrainContext(
                        world_rank=rank, world_size=world))
                    col.init_collective_group(
                        world_size=world, rank=rank, backend="host",
                        group_name="wdcol")
                    return True

                def round(self):
                    import numpy as np

                    import ray_tpu.collective as col

                    # The MODULE-level op: it wraps the group op in the
                    # collective_op_latency_s/_bytes histograms the
                    # watchdog samples (g.allreduce would bypass them).
                    return float(col.allreduce(
                        np.ones(4, np.float32), group_name="wdcol")[0])

            members = [Member.remote() for _ in range(2)]
            assert ray_tpu.get(
                [m.setup.remote(r, 2) for r, m in enumerate(members)],
                timeout=120) == [True, True]

            # Driver-paced rounds: both ranks' contributions are issued
            # together, so every allreduce completes (or fails loudly) —
            # no long-running in-call loop to wedge on a loaded box.
            stop = {"flag": False}

            def pump():
                while not stop["flag"]:
                    try:
                        ray_tpu.get([m.round.remote() for m in members],
                                    timeout=60)
                    except Exception:
                        return
                    _time.sleep(0.05)

            import threading as _threading

            pump_t = _threading.Thread(target=pump, daemon=True)
            pump_t.start()

            def baseline_ready():
                rows = rt.get_timeseries(
                    name="collective_op_latency_s:mean").get("series", [])
                return any(len(r["points"]) >= 6 for r in rows) or None

            wait_for(baseline_ready, timeout=60,
                     desc="collective latency baseline series")
            t_inject = _time.time()
            inject_chaos(rules=[{
                "point": "rpc.server", "action": "delay", "delay_s": 0.5,
                "match": {"method": "^push_actor_call"}, "count": 120}])

            def tripped():
                for inc in rt.incidents().get("incidents", []):
                    if inc["rule"] == "collective_latency" and \
                            inc["wall_ts"] >= t_inject:
                        return inc
                return None

            inc = wait_for(tripped, timeout=30,
                           desc="collective_latency incident")
            inject_chaos(clear=True)
            # evidence bundle complete
            assert inc["series"]["name"].startswith(
                "collective_op_latency_s")
            assert inc["series"]["tags"].get("group") == "wdcol"
            assert inc["implicated"]["node_id"] == "wdcola"
            assert len(inc["window"]) >= 3
            assert inc["flight_record"]
            assert inc["profile"]["status"] == "captured", inc["profile"]
            # detection latency within the acceptance budget
            assert inc["wall_ts"] - t_inject <= 10.0
            # state API + CLI surfaces show it
            from ray_tpu.util.state import incidents as state_incidents

            assert any(i["id"] == inc["id"] for i in state_incidents())
            from ray_tpu.scripts.cli import main as cli_main

            assert cli_main(["incidents"]) == 0
            assert cli_main(["watch", "--once"]) == 0
            stop["flag"] = True
            pump_t.join(timeout=90)
            assert not pump_t.is_alive()
        finally:
            try:
                inject_chaos(clear=True)
            except Exception:
                pass
            rt.shutdown()
            c.shutdown()
            (global_worker.runtime, global_worker.worker_id,
             global_worker.node_id, global_worker.mode,
             global_worker.job_id) = old
            for k in env:
                _os.environ.pop(k, None)
            config_mod.set_config(config_mod.Config.load())
            injector.reset_for_tests()


def test_cli_watchdog_verbs_registered(capsys):
    """`incidents` and `watch` appear in --help and degrade gracefully on
    an in-process runtime (no head, no watchdog)."""
    from ray_tpu.scripts.cli import main

    with pytest.raises(SystemExit) as ei:
        main(["--help"])
    assert ei.value.code == 0
    out = capsys.readouterr().out
    assert "incidents" in out and "watch" in out


def test_cli_watchdog_verbs_in_process(rt_start, capsys):
    from ray_tpu.scripts.cli import main

    assert main(["incidents"]) == 0
    assert "no incidents" in capsys.readouterr().out
    assert main(["watch", "--once"]) == 1  # watchdog lives on a head
    assert "disabled" in capsys.readouterr().out


class TestLogs:
    def test_list_and_tail_worker_logs(self, wait_for):
        """Per-node worker log listing + tail through the daemons
        (reference: `ray logs` via the dashboard agent)."""
        from ray_tpu.cluster_utils import Cluster
        from ray_tpu.core.remote_function import remote
        from ray_tpu.core.worker import global_worker
        from ray_tpu.util.state.api import get_log, list_logs
        from ray_tpu.utils.ids import JobID

        import ray_tpu

        c = Cluster()
        c.add_node(num_cpus=2)
        rt = c.connect()
        old = (global_worker.runtime, global_worker.worker_id,
               global_worker.node_id, global_worker.mode,
               global_worker.job_id)
        global_worker.runtime = rt
        global_worker.worker_id = rt.worker_id
        global_worker.node_id = rt.node_id
        global_worker.job_id = JobID.from_random()
        global_worker.mode = "cluster"
        try:
            @remote
            def noisy():
                print("log-marker-xyzzy")
                return 1

            assert ray_tpu.get(noisy.remote(), timeout=60) == 1

            def marker_logged():
                logs = list_logs()
                if not logs:
                    return None
                assert all("filename" in l and "node_id" in l for l in logs)
                if any("log-marker-xyzzy" in get_log(l["filename"],
                                                     l["node_id"])
                       for l in logs):
                    return logs
                return None

            logs = wait_for(marker_logged, timeout=10,
                            desc="worker print in a log file")
            with pytest.raises(FileNotFoundError):
                get_log("../etc/passwd", logs[0]["node_id"])
        finally:
            rt.shutdown()
            c.shutdown()
            (global_worker.runtime, global_worker.worker_id,
             global_worker.node_id, global_worker.mode,
             global_worker.job_id) = old
