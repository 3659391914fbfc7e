"""Serve layer tests (reference test model: python/ray/serve/tests/ —
test_deploy, test_autoscaling_policy, test_batching, test_proxy)."""

import json
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(autouse=True)
def _rt():
    ray_tpu.init()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_basic_deploy_and_call():
    @serve.deployment
    class Echo:
        def __call__(self, x):
            return f"echo:{x}"

        def shout(self, x):
            return f"ECHO:{x}"

    handle = serve.run(Echo.bind(), route_prefix=None)
    assert handle.remote("hi").result() == "echo:hi"
    assert handle.shout.remote("hi").result() == "ECHO:hi"


def test_function_deployment_and_init_args():
    @serve.deployment
    class Adder:
        def __init__(self, base):
            self.base = base

        def __call__(self, x):
            return self.base + x

    handle = serve.run(Adder.bind(10), route_prefix=None)
    assert handle.remote(5).result() == 15


def test_composition_handle_passing():
    @serve.deployment
    class Tokenizer:
        def __call__(self, text):
            return text.split()

    @serve.deployment
    class Pipeline:
        def __init__(self, tok):
            self.tok = tok

        def __call__(self, text):
            toks = self.tok.remote(text).result()
            return len(toks)

    handle = serve.run(Pipeline.bind(Tokenizer.bind()), route_prefix=None)
    assert handle.remote("a b c d").result() == 4


def test_multiple_replicas_spread_load():
    @serve.deployment(num_replicas=3)
    class WhoAmI:
        def __init__(self):
            import uuid
            self.id = uuid.uuid4().hex

        def __call__(self):
            return self.id

    handle = serve.run(WhoAmI.bind(), route_prefix=None)
    ids = {handle.remote().result() for _ in range(40)}
    assert len(ids) >= 2  # pow-2 routing reaches multiple replicas


def test_status_and_delete():
    @serve.deployment(num_replicas=2)
    class D:
        def __call__(self):
            return "ok"

    serve.run(D.bind(), route_prefix=None)
    st = serve.status()
    assert st["D"].status == "HEALTHY"
    assert st["D"].replica_states.get("RUNNING") == 2
    serve.delete()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and serve.status():
        time.sleep(0.05)
    assert serve.status() == {}


def test_rolling_update_version_change():
    def make(version_tag):
        @serve.deployment(name="V", version=version_tag)
        class V:
            def __call__(self):
                return version_tag

        return V

    h = serve.run(make("v1").bind(), route_prefix=None)
    assert h.remote().result() == "v1"
    h = serve.run(make("v2").bind(), route_prefix=None)
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if h.remote().result() == "v2":
            break
        time.sleep(0.05)
    assert h.remote().result() == "v2"


def test_batching():
    @serve.deployment(max_ongoing_requests=16)
    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.1)
        def __call__(self, xs):
            self.batch_sizes.append(len(xs))
            return [x * 2 for x in xs]

        def sizes(self):
            return self.batch_sizes

    handle = serve.run(Batched.bind(), route_prefix=None)
    results = [None] * 8
    threads = []

    def call(i):
        results[i] = handle.remote(i).result()

    for i in range(8):
        t = threading.Thread(target=call, args=(i,))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    assert results == [i * 2 for i in range(8)]
    sizes = handle.sizes.remote().result()
    assert max(sizes) > 1  # batching actually coalesced concurrent calls


def test_autoscaling_up_and_down():
    @serve.deployment(
        max_ongoing_requests=4,
        autoscaling_config=dict(min_replicas=1, max_replicas=3,
                                target_ongoing_requests=1.0,
                                upscale_delay_s=0.2, downscale_delay_s=0.5,
                                metrics_interval_s=0.1),
        health_check_period_s=10.0,
    )
    class Slow:
        def __call__(self):
            time.sleep(0.4)
            return "done"

    handle = serve.run(Slow.bind(), route_prefix=None)
    st = serve.status()
    assert st["Slow"].replica_states.get("RUNNING") == 1

    stop = time.monotonic() + 4.0
    threads = [threading.Thread(
        target=lambda: [handle.remote().result() for _ in
                        iter(lambda: time.monotonic() < stop, False)])
        for _ in range(6)]
    for t in threads:
        t.start()
    peak = 1
    while time.monotonic() < stop:
        st = serve.status()
        peak = max(peak, st["Slow"].replica_states.get("RUNNING", 0))
        time.sleep(0.1)
    for t in threads:
        t.join()
    assert peak >= 2  # scaled up under load

    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        st = serve.status()
        if st["Slow"].replica_states.get("RUNNING") == 1 and \
                st["Slow"].status == "HEALTHY":
            break
        time.sleep(0.1)
    assert serve.status()["Slow"].replica_states.get("RUNNING") == 1


def test_replica_failure_recovers():
    @serve.deployment(num_replicas=1, health_check_period_s=0.1,
                      max_ongoing_requests=4)
    class Flaky:
        def __init__(self):
            self.healthy = True

        def poison(self):
            self.healthy = False

        def check_health(self):
            if not self.healthy:
                raise RuntimeError("poisoned")

        def __call__(self):
            return "alive"

    handle = serve.run(Flaky.bind(), route_prefix=None)
    assert handle.remote().result() == "alive"
    handle.poison.remote().result()
    # Controller must detect the failing health check and replace the
    # replica; the new one answers again.
    deadline = time.monotonic() + 15
    ok = False
    while time.monotonic() < deadline:
        try:
            if handle.remote().result(timeout=5) == "alive":
                st = serve.status()
                if st["Flaky"].status == "HEALTHY":
                    ok = True
                    break
        except Exception:
            pass
        time.sleep(0.1)
    assert ok


def test_http_ingress():
    @serve.deployment
    class App:
        def __call__(self, request: serve.Request):
            if request.method == "POST":
                data = request.json()
                return {"sum": data["a"] + data["b"]}
            return {"path": request.path,
                    "q": request.query_params.get("q")}

    serve.run(App.bind(), route_prefix="/", http=True)
    port = serve.http_port()

    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/x/y?q=hello", timeout=30) as r:
        body = json.loads(r.read())
    assert body == {"path": "/x/y", "q": "hello"}

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/", method="POST",
        data=json.dumps({"a": 2, "b": 3}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        assert json.loads(r.read()) == {"sum": 5}


def test_handle_streaming():
    """handle.options(stream=True) yields chunks as the replica produces
    them (reference: DeploymentResponseGenerator)."""
    @serve.deployment
    class Streamer:
        def chunks(self, n):
            for i in range(n):
                yield f"c{i}"

        def whole(self):
            return "complete"

    h = serve.run(Streamer.bind())
    gen = h.options(method_name="chunks", stream=True).remote(3)
    assert gen.streaming
    assert list(gen) == ["c0", "c1", "c2"]
    gen2 = h.options(method_name="whole", stream=True).remote()
    assert not gen2.streaming
    assert next(gen2) == "complete"


def test_http_sse_streaming():
    """An ingress generator method streams chunks over HTTP as SSE
    (reference: proxy.py:481 streaming response path)."""
    @serve.deployment
    class SSE:
        def __call__(self, request: serve.Request):
            def gen():
                for i in range(4):
                    yield f"data: tick{i}\n\n"
                    time.sleep(0.05)
            return gen()

    serve.run(SSE.bind(), route_prefix="/", http=True)
    port = serve.http_port()
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/events", timeout=30) as r:
        assert r.headers.get("Content-Type", "").startswith("text/event-stream")
        first_at = None
        t0 = time.monotonic()
        body = b""
        while True:
            chunk = r.read1(256)  # read1: return as data arrives, no refill
            if not chunk:
                break
            if first_at is None:
                first_at = time.monotonic() - t0
            body += chunk
    text = body.decode()
    assert all(f"tick{i}" in text for i in range(4))
    # Incremental delivery: the first chunk must arrive well before the
    # ~0.2s it takes to produce all four.
    assert first_at is not None and first_at < 0.15


def test_model_multiplexing():
    """Many models share a replica pool: per-replica LRU + model-affinity
    routing (reference: serve.multiplexed / multiplexed_model_id)."""
    @serve.deployment(num_replicas=2, max_ongoing_requests=8)
    class MuxServer:
        def __init__(self):
            self.load_counts = {}

        @serve.multiplexed(max_num_models_per_replica=2)
        def get_model(self, model_id: str):
            self.load_counts[model_id] = \
                self.load_counts.get(model_id, 0) + 1
            return {"id": model_id, "weights": model_id.upper()}

        def predict(self, x):
            model = self.get_model()
            return f"{model['weights']}:{x}"

        def loads(self):
            return dict(self.load_counts)

    h = serve.run(MuxServer.bind())
    h1 = h.options(method_name="predict", multiplexed_model_id="m1")
    h2 = h.options(method_name="predict", multiplexed_model_id="m2")
    assert h1.remote("a").result() == "M1:a"
    assert h2.remote("b").result() == "M2:b"
    # repeat calls reuse the cached model (affinity => same replica)
    for _ in range(4):
        assert h1.remote("c").result() == "M1:c"
    counts = h.options(method_name="loads",
                       multiplexed_model_id="m1").remote().result()
    assert counts.get("m1") == 1  # loaded exactly once on its home replica


def test_multiplex_lru_eviction():
    @serve.deployment(num_replicas=1)
    class Evicting:
        @serve.multiplexed(max_num_models_per_replica=2)
        def get_model(self, model_id: str):
            return model_id

        def which(self):
            from ray_tpu.serve.multiplex import get_multiplexed_model_id

            self.get_model()
            return get_multiplexed_model_id()

    h = serve.run(Evicting.bind())
    for mid in ("a", "b", "c", "a"):  # c evicts a; reloading a evicts b
        got = h.options(method_name="which",
                        multiplexed_model_id=mid).remote().result()
        assert got == mid


def test_route_hint_affinity(wait_for):
    """The same route hint lands on the same replica while it has capacity
    (reference: prefix-aware routing policy shape)."""
    @serve.deployment(num_replicas=3, max_ongoing_requests=8)
    class Who:
        def __init__(self):
            import os

            self.pid_tag = f"{os.getpid()}-{id(self)}"

        def __call__(self, _req=None):
            return self.pid_tag

    h = serve.run(Who.bind())
    router = h._ensure_router()
    tags = set()
    for _ in range(6):
        tags.add(h.options(route_hint="prefix-xyz").remote().result())
        # "While it has capacity": a slot is released by the router's
        # reaper thread some time after the result is out, and a hinted
        # replica more than HINT_BALANCE_DELTA above its siblings yields
        # to balancing. Wait for the release, not for a time.
        wait_for(lambda: not any(router._inflight.values()), timeout=30,
                 interval=0.002, desc="the slot's release")
    assert len(tags) == 1  # all six routed to one replica


def test_grpc_ingress(rt_start):
    """gRPC data plane: proto-agnostic generic handler routes any method to
    the app ingress; unary and server-streaming both work (reference:
    _private/proxy.py gRPCProxy + grpc_servicer_functions)."""
    import grpc
    import json as _json

    from ray_tpu import serve

    @serve.deployment
    class Echo:
        def __call__(self, req):
            if req.metadata.get("streaming") == "1":
                def gen():
                    for i in range(3):
                        yield f"chunk{i}".encode()
                return gen()
            body = req.json() or {}
            return _json.dumps({"method": req.method,
                                "echo": body.get("x")}).encode()

    serve.run(Echo.bind(), route_prefix="/", grpc=True)
    try:
        port = serve.grpc_port()
        chan = grpc.insecure_channel(f"127.0.0.1:{port}")
        unary = chan.unary_unary(
            "/test.Echo/Predict",
            request_serializer=None, response_deserializer=None)
        out = unary(_json.dumps({"x": 42}).encode(), timeout=30)
        parsed = _json.loads(out)
        assert parsed == {"method": "/test.Echo/Predict", "echo": 42}

        streamer = chan.unary_stream(
            "/test.Echo/Stream",
            request_serializer=None, response_deserializer=None)
        chunks = list(streamer(b"", metadata=(("streaming", "1"),),
                               timeout=30))
        assert chunks == [b"chunk0", b"chunk1", b"chunk2"]
        chan.close()
    finally:
        serve.shutdown()


def test_replica_placement_group(rt_start):
    """placement_group_bundles gives each replica a gang PG; the replica
    actor runs in bundle 0 and the PG is removed when the replica stops
    (reference: serve placement_group_bundles / ray.llm replica PGs)."""
    from ray_tpu import serve

    @serve.deployment(placement_group_bundles=[{"CPU": 1.0}, {"CPU": 1.0}],
                      placement_group_strategy="PACK")
    class Gang:
        def __call__(self, req):
            return "ok"

    serve.run(Gang.bind(), route_prefix="/")
    try:
        h = serve.get_app_handle()
        assert h.remote(None).result(timeout=30) == "ok"
        # a PG exists for the replica
        from ray_tpu.util.state.api import list_placement_groups
        pgs = list_placement_groups()
        assert any(p["state"] == "CREATED" for p in pgs), pgs
    finally:
        serve.shutdown()
    # after shutdown the replica PG is released
    from ray_tpu.util.state.api import list_placement_groups
    pgs = [p for p in list_placement_groups() if p["state"] == "CREATED"]
    assert not pgs, pgs


def test_grpc_only_app_no_http_route(rt_start):
    """A gRPC-only application (route_prefix=None) stays routable via the
    controller's app-ingress map (grpc_proxy.py update_routes)."""
    import grpc

    from ray_tpu import serve

    @serve.deployment
    class G:
        def __call__(self, req):
            return b"grpc-only"

    serve.run(G.bind(), name="gonly", route_prefix=None, grpc=True)
    try:
        chan = grpc.insecure_channel(f"127.0.0.1:{serve.grpc_port()}")
        unary = chan.unary_unary("/x.Y/Z", request_serializer=None,
                                 response_deserializer=None)
        assert unary(b"", metadata=(("application", "gonly"),),
                     timeout=30) == b"grpc-only"
        # single-app default routing works without metadata too
        assert unary(b"", timeout=30) == b"grpc-only"
        chan.close()
    finally:
        serve.shutdown()


def test_pg_options_validated_at_declaration():
    from ray_tpu import serve

    with pytest.raises(ValueError, match="strategy"):
        serve.deployment(placement_group_bundles=[{"CPU": 1}],
                         placement_group_strategy="pack")(object)
    with pytest.raises(ValueError, match="bundles"):
        serve.deployment(placement_group_bundles=[{}])(object)


def test_infeasible_pg_does_not_wedge_controller(rt_start):
    """An unsatisfiable gang PG must not block reconciliation: a healthy
    app deployed afterwards still comes up while the infeasible one stays
    pending (controller.py non-blocking PG startup)."""
    from ray_tpu import serve

    @serve.deployment(placement_group_bundles=[{"CPU": 512.0}])
    class Huge:
        def __call__(self, req):
            return "huge"

    @serve.deployment
    class Small:
        def __call__(self, req):
            return "small"

    import pytest as _pytest

    with _pytest.raises(TimeoutError):
        serve.run(Huge.bind(), name="huge", route_prefix="/huge",
                  _blocking_timeout=3.0)
    # the controller is still responsive: a normal app deploys fine
    serve.run(Small.bind(), name="small", route_prefix="/small")
    try:
        h = serve.get_deployment_handle("Small", app_name="small")
        assert h.remote(None).result(timeout=30) == "small"
    finally:
        serve.shutdown()


class TestRouterUnit:
    """Router-level tests without a cluster: load-aware hint affinity and
    event-driven admission (reference: _private/router.py assign loop wakes
    on events; prefix-aware policy's balance threshold)."""

    @staticmethod
    def _replicas(n, cap=4):
        from ray_tpu.serve.config import ReplicaInfo

        return [ReplicaInfo(replica_id=f"r{i}", deployment_name="d",
                            actor_name=f"a{i}", max_ongoing_requests=cap)
                for i in range(n)]

    def test_hint_yields_to_balance_when_overloaded(self):
        """A shared hint must not pin all traffic to one replica while its
        siblings idle: once the hinted replica is HINT_BALANCE_DELTA above
        the least-loaded, the router balances instead (ADVICE r3 medium)."""
        from ray_tpu.serve.router import Router

        router = Router("d", lambda: [])
        reps = self._replicas(3, cap=100)
        # Find which replica the hint prefers, then overload it.
        hinted = router._choose_locked(reps, route_hint="shared-prefix")
        router._inflight[hinted.replica_id] = \
            Router.HINT_BALANCE_DELTA + 1  # siblings at 0
        got = router._choose_locked(reps, route_hint="shared-prefix")
        assert got.replica_id != hinted.replica_id
        # Within the balance window the hint keeps its locality.
        router._inflight[hinted.replica_id] = Router.HINT_BALANCE_DELTA
        got = router._choose_locked(reps, route_hint="shared-prefix")
        assert got.replica_id == hinted.replica_id

    def test_saturated_assign_wakes_on_release(self, monkeypatch):
        """Admission is event-driven: a request parked on saturation is
        admitted promptly (condition notify, not a sleep-poll) when a
        slot frees."""
        import ray_tpu as _rt
        from ray_tpu.serve.router import Router

        reps = self._replicas(1, cap=2)
        router = Router("d", lambda: reps)
        router._inflight["r0"] = 2  # saturated

        class _FakeRef:
            pass

        class _FakeMethod:
            def remote(self, *a, **k):
                return _FakeRef()

        class _FakeHandle:
            handle_request = _FakeMethod()

        monkeypatch.setattr(_rt, "get_actor", lambda *a, **k: _FakeHandle())
        monkeypatch.setattr(_rt, "wait",
                            lambda *a, **k: ([], []))

        admitted = threading.Event()

        def _assign():
            router.assign_request("m", (), {}, timeout=10.0)
            admitted.set()

        t = threading.Thread(target=_assign, daemon=True)
        t.start()
        time.sleep(0.2)
        assert not admitted.is_set()  # genuinely parked
        t0 = time.perf_counter()
        router._release("r0")  # a request completed
        admitted.wait(timeout=2.0)
        dt = time.perf_counter() - t0
        assert admitted.is_set()
        assert dt < 0.1, f"wake took {dt*1e3:.1f} ms (poll, not notify?)"
