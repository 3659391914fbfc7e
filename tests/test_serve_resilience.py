"""Serve request resilience: deadlines, shedding, retries, circuit breaking
(ray_tpu/serve/resilience.py + the router/replica/handle/batcher hops that
compose it). Router-level tests run without a cluster, like
test_serve.TestRouterUnit; the end-to-end drills (replica churn under
traffic, chaos-injected failures) carry the ``serveload`` marker and skip
where the serve runtime can't come up."""

import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.config import ReplicaInfo
from ray_tpu.serve.resilience import (
    CircuitBreaker,
    CircuitBreakerConfig,
    DeadlineExceeded,
    Overloaded,
    ResilienceSettings,
    RetryPolicy,
    classify,
    open_reason_kind,
)
from ray_tpu.serve.router import Router


def _replicas(n, cap=4, draining=(), settings=None):
    s = settings.to_dict() if settings is not None else None
    return [ReplicaInfo(replica_id=f"r{i}", deployment_name="d",
                        actor_name=f"a{i}", max_ongoing_requests=cap,
                        draining=(i in draining), settings=s)
            for i in range(n)]


class _FakeRef:
    pass


class _FakeMethod:
    def remote(self, *a, **k):
        return _FakeRef()


class _FakeHandle:
    handle_request = _FakeMethod()


def _patch_submission(monkeypatch):
    monkeypatch.setattr(ray_tpu, "get_actor", lambda *a, **k: _FakeHandle())
    # The completion reaper waits on the submitted refs: report them all
    # ready immediately so release/settlement runs (the pre-reaper stub
    # returned ([], []), which the per-request watcher treated as done).
    monkeypatch.setattr(ray_tpu, "wait",
                        lambda refs, **k: (list(refs), []))


# ------------------------------------------------------------ breaker unit
class TestCircuitBreaker:
    def test_consecutive_failures_open_then_half_open_recovery(self):
        cb = CircuitBreaker(CircuitBreakerConfig(
            failure_threshold=3, open_s=0.1, half_open_probes=1))
        for _ in range(2):
            cb.record_failure("r0")
        assert not cb.is_open("r0")  # below threshold
        cb.record_failure("r0")
        assert cb.is_open("r0") and cb.state("r0") == "open"
        assert not cb.allow("r0")  # cooling down
        time.sleep(0.12)
        assert not cb.is_open("r0")  # due for probing
        assert cb.allow("r0")        # consumes the probe slot
        assert cb.state("r0") == "half_open"
        assert not cb.allow("r0")    # probe budget (1) spent
        cb.record_success("r0", 0.01)
        assert cb.state("r0") == "closed"
        assert cb.allow("r0")

    def test_half_open_failure_reopens(self):
        cb = CircuitBreaker(CircuitBreakerConfig(
            failure_threshold=1, open_s=0.05, half_open_probes=1))
        cb.record_failure("r0")
        time.sleep(0.07)
        assert cb.allow("r0")  # half-open probe
        cb.record_failure("r0")
        assert cb.state("r0") == "open"
        assert not cb.allow("r0")

    def test_success_resets_consecutive_count(self):
        cb = CircuitBreaker(CircuitBreakerConfig(failure_threshold=3))
        cb.record_failure("r0")
        cb.record_failure("r0")
        cb.record_success("r0", 0.01)
        cb.record_failure("r0")
        cb.record_failure("r0")
        assert not cb.is_open("r0")  # the streak was broken

    def test_latency_outlier_trips(self):
        cb = CircuitBreaker(CircuitBreakerConfig(
            failure_threshold=100, latency_factor=5.0,
            latency_min_samples=8))
        opened = []
        cb.on_open = lambda rid, reason: opened.append((rid, reason))
        for _ in range(20):
            cb.record_success("fast", 0.01)
        for _ in range(8):
            cb.record_success("slow", 0.5)  # 50x the fleet median
        assert cb.is_open("slow")
        assert not cb.is_open("fast")
        assert opened and opened[0][0] == "slow" \
            and "latency" in opened[0][1]

    def test_forget_drops_stale_replicas(self):
        cb = CircuitBreaker(CircuitBreakerConfig(failure_threshold=1))
        cb.record_failure("gone")
        cb.record_failure("kept")
        cb.forget(["kept"])
        assert not cb.is_open("gone")  # state dropped with the replica
        assert cb.is_open("kept")


def _feed_docqa_mix(record, clump):
    """docqa's traffic on one replica as the breaker saw it: the poller's
    ``stats`` calls of 1 ms, then ``clump`` first chunks of 2.4 s between
    two polls (16 closed-loop clients at a window's start)."""
    for _ in range(100):
        record(0.001, "stats")
    for _ in range(clump):
        record(2.4, "generate")


class TestLatencyOutlierAmongPeers:
    """A replica is a latency outlier only among peers that serve the same
    call (PR 51): the yardstick is the OTHER replicas' recent samples of
    the same method, never the replica's own history or another method."""

    @pytest.mark.parametrize("clump", [8, 12, 16])
    @pytest.mark.parametrize("by_method", [True, False],
                             ids=["by_method", "one_method"])
    def test_a_fleet_of_one_never_opens_on_latency(self, clump, by_method):
        """The clump sizes that opened the old rule ("median 2400 ms vs
        fleet 1 ms"), with the samples named by their call and, as a
        caller that names none would feed them, all under one name."""
        cb = CircuitBreaker(CircuitBreakerConfig())
        opened = []
        cb.on_open = lambda rid, reason: opened.append((rid, reason))
        _feed_docqa_mix(
            lambda lat, m: cb.record_success("r0", lat,
                                             m if by_method else ""),
            clump)
        assert not opened and cb.state("r0") == "closed"
        assert cb.allow("r0")

    @pytest.mark.parametrize("clump", [8, 12, 16])
    def test_two_healthy_replicas_under_one_mix_never_open_each_other(
            self, clump):
        """One replica's last 16 hold a clump of first chunks while the
        other's hold polls: alike by method, they are no outliers."""
        cb = CircuitBreaker(CircuitBreakerConfig())
        opened = []
        cb.on_open = lambda rid, reason: opened.append((rid, reason))
        for _ in range(3):  # several rounds, the replicas out of step
            _feed_docqa_mix(
                lambda lat, m: cb.record_success("r0", lat, m), clump)
            for _ in range(40):
                cb.record_success("r1", 0.001, "stats")
            _feed_docqa_mix(
                lambda lat, m: cb.record_success("r1", lat, m), clump)
        assert not opened
        assert cb.state("r0") == cb.state("r1") == "closed"

    def test_a_replica_slow_on_one_method_opens_within_min_samples(self):
        cfg = CircuitBreakerConfig()
        cb = CircuitBreaker(cfg)
        opened = []
        cb.on_open = lambda rid, reason: opened.append((rid, reason))
        for rid in ("r0", "r1"):
            for _ in range(40):
                cb.record_success(rid, 0.001, "stats")
                cb.record_success(rid, 0.05, "generate")
        assert not opened
        # r1 turns 50 x slower on generate alone; its stats stay fast and
        # keep arriving between the slow calls.
        slow_calls = 0
        while not opened and slow_calls < 2 * cfg.latency_min_samples:
            cb.record_success("r1", 2.5, "generate")
            slow_calls += 1
            cb.record_success("r1", 0.001, "stats")
            cb.record_success("r0", 0.05, "generate")
        assert [rid for rid, _ in opened] == ["r1"]
        assert slow_calls <= cfg.latency_min_samples
        assert open_reason_kind(opened[0][1]) == "latency"
        assert cb.is_open("r1") and not cb.is_open("r0")

    def test_five_times_slower_is_the_threshold(self):
        """Just over ``latency_factor`` x the peers' median opens; just
        under it does not."""
        for slow, expect_open in ((0.051, True), (0.049, False)):
            cb = CircuitBreaker(CircuitBreakerConfig())
            for _ in range(16):
                cb.record_success("r0", 0.01, "m")
                cb.record_success("r1", 0.01, "m")
            for _ in range(16):
                cb.record_success("r1", slow, "m")
            assert cb.is_open("r1") is expect_open, slow
            assert not cb.is_open("r0")

    def test_a_peer_with_too_few_samples_is_no_yardstick(self):
        cfg = CircuitBreakerConfig()
        cb = CircuitBreaker(cfg)
        for _ in range(cfg.latency_min_samples - 1):
            cb.record_success("r0", 0.01, "generate")
        for _ in range(64):  # r0 has plenty of another call: no matter
            cb.record_success("r0", 0.001, "stats")
        for _ in range(2 * cfg.latency_min_samples):
            cb.record_success("r1", 2.5, "generate")
        assert not cb.is_open("r1")  # nobody to be an outlier among
        cb.record_success("r0", 0.01, "generate")  # r0's 16th sample
        cb.record_success("r1", 2.5, "generate")
        assert cb.is_open("r1")

    def test_the_judged_replica_is_no_part_of_its_own_yardstick(self):
        """A slow replica that takes three calls of four would fill a
        pooled yardstick with its own samples and hide behind them; held
        against its peer alone it opens once the peer has 16 samples."""
        cb = CircuitBreaker(CircuitBreakerConfig())
        for i in range(16):
            assert not cb.is_open("r1"), i
            for _ in range(3):
                cb.record_success("r1", 1.0, "m")
            cb.record_success("r0", 0.01, "m")
        cb.record_success("r1", 1.0, "m")
        assert cb.is_open("r1") and not cb.is_open("r0")

    def test_an_opened_replica_s_samples_are_dropped_method_by_method(self):
        cb = CircuitBreaker(CircuitBreakerConfig(open_s=0.05))
        for _ in range(16):
            cb.record_success("r0", 0.01, "m")
            cb.record_success("r1", 0.01, "m")
            cb.record_success("r1", 0.01, "other")
        for _ in range(16):
            cb.record_success("r1", 1.0, "m")
        assert cb.is_open("r1")
        time.sleep(0.07)
        assert cb.allow("r1")  # half-open probe
        cb.record_success("r1", 0.01, "m")
        assert cb.state("r1") == "closed"
        # Healed: one slow call must not re-trip it on the old history,
        # and it is no yardstick for r0 until it has 16 samples again.
        cb.record_success("r1", 1.0, "m")
        for _ in range(16):
            cb.record_success("r0", 1.0, "m")
        assert not cb.is_open("r1") and not cb.is_open("r0")

    def test_forget_drops_a_gone_replica_s_per_method_samples(self):
        cfg = CircuitBreakerConfig()
        cb = CircuitBreaker(cfg)
        for _ in range(cfg.latency_min_samples):
            cb.record_success("gone", 0.01, "generate")
            cb.record_success("kept", 0.01, "generate")
        cb.forget(["kept"])
        assert "gone" not in cb._replicas
        # With its only peer gone, "kept" has nobody to be slower than...
        for _ in range(2 * cfg.latency_min_samples):
            cb.record_success("kept", 2.5, "generate")
        assert not cb.is_open("kept")
        # ...and a replica of the old name starts from no samples.
        cb.record_success("gone", 0.01, "generate")
        cb.record_success("kept", 2.5, "generate")
        assert not cb.is_open("kept")

    def test_samples_hold_under_threads_that_record_and_forget(self):
        """More threads than cores record two methods on four replicas
        while one keeps forgetting a replica: no thread raises (a peer
        walk over a dict another thread resizes would), and what is left
        is whole: each replica's cached median is the median of its last
        16 samples of that method."""
        import sys

        cb = CircuitBreaker(CircuitBreakerConfig(failure_threshold=10**9))
        stop = time.monotonic() + 0.5
        errors = []

        def record(i):
            try:
                n = 0
                while time.monotonic() < stop:
                    n += 1
                    cb.record_success(f"r{(i + n) % 4}", 0.01 + 1e-6 * n,
                                      "generate" if n % 3 else "stats")
            except Exception as e:  # noqa: BLE001 - the test's finding
                errors.append(e)

        def churn():
            try:
                while time.monotonic() < stop:
                    cb.forget(["r0", "r1", "r2"])
                    cb.state("r3")
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=record, args=(i,))
                       for i in range(16)] + [threading.Thread(target=churn)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads) and not errors
        assert not any(cb.is_open(f"r{i}") for i in range(4))
        for rb in cb._replicas.values():
            for method, samples in rb.latencies.items():
                assert len(samples) <= 64
                if len(samples) >= 16:
                    last = sorted(list(samples)[-16:])
                    assert rb.recent[method] == last[8]
                else:
                    assert method not in rb.recent

    def test_every_opening_s_reason_has_its_kind(self):
        """The reasons the breaker really gives, not copies of them."""
        cb = CircuitBreaker(CircuitBreakerConfig(
            failure_threshold=2, open_s=0.05, latency_min_samples=4))
        opened = []
        cb.on_open = lambda rid, reason: opened.append(
            open_reason_kind(reason))
        cb.record_failure("r0")
        cb.record_failure("r0")
        time.sleep(0.07)
        assert cb.allow("r0")
        cb.record_failure("r0")
        for _ in range(4):
            cb.record_success("r1", 0.01, "m")
        for _ in range(4):
            cb.record_success("r2", 1.0, "m")
        assert opened == ["failures", "probe", "latency"]


# ------------------------------------------------------------- router unit
class TestRouterChurn:
    """Router behavior under replica churn: draining/blacklisted exclusion,
    balanced _release accounting across failed assignments, breaker
    half-open recovery through the choose loop."""

    def test_choose_never_picks_draining_replica(self):
        router = Router("d", lambda: [])
        reps = _replicas(3, cap=100, draining={1})
        for _ in range(200):
            got = router._choose_locked(reps)
            assert got is not None and got.replica_id != "r1"
        # a draining replica keeps its hint traffic off too
        for _ in range(50):
            got = router._choose_locked(reps, route_hint="shared")
            assert got is not None and got.replica_id != "r1"

    def test_choose_never_picks_blacklisted_replica(self):
        router = Router("d", lambda: [])
        reps = _replicas(3, cap=100)
        router.breaker.config = CircuitBreakerConfig(
            failure_threshold=1, open_s=60.0)
        router.breaker.record_failure("r2")
        for _ in range(200):
            got = router._choose_locked(reps)
            assert got is not None and got.replica_id != "r2"

    def test_all_drained_or_blacklisted_reports_saturation(self):
        router = Router("d", lambda: [])
        router.breaker.config = CircuitBreakerConfig(
            failure_threshold=1, open_s=60.0)
        router.breaker.record_failure("r0")
        reps = _replicas(2, cap=100, draining={1})
        assert router._choose_locked(reps) is None

    def test_release_balanced_across_failed_assignments(self, monkeypatch):
        """Every failed submission path must return its in-flight slot:
        a leaked increment reads as permanent saturation."""
        reps = _replicas(2, cap=4)
        router = Router("d", lambda: reps)

        def dead_get_actor(*a, **k):
            raise ValueError("no actor named")

        monkeypatch.setattr(ray_tpu, "get_actor", dead_get_actor)
        for _ in range(6):
            with pytest.raises(ray_tpu.ActorDiedError) as ei:
                router.assign_request("m", (), {}, timeout=1.0)
            assert ei.value.never_sent  # submit-time death is never-sent
        assert all(v == 0 for v in router.metrics().values()), \
            router.metrics()

    def test_successful_assign_releases_on_completion(self, monkeypatch):
        reps = _replicas(1, cap=4)
        router = Router("d", lambda: reps)
        _patch_submission(monkeypatch)
        ref, rid = router.assign_request("m", (), {}, timeout=5.0)
        assert rid == "r0"
        deadline = time.monotonic() + 2
        while time.monotonic() < deadline:
            if router.metrics().get("r0") == 0:
                break
            time.sleep(0.01)
        assert router.metrics().get("r0") == 0  # watcher released the slot

    def test_breaker_half_open_recovery_through_router(self, monkeypatch):
        """An open replica is skipped; once the cooldown passes the router
        routes a bounded probe to it, and a probe success restores it."""
        reps = _replicas(1, cap=100)  # single replica: no sibling to hide
        router = Router("d", lambda: reps)
        router.breaker.config = CircuitBreakerConfig(
            failure_threshold=1, open_s=0.1, half_open_probes=1)
        router.breaker.record_failure("r0")
        assert router._choose_locked(reps) is None  # open: no traffic
        time.sleep(0.12)
        got = router._choose_locked(reps)  # half-open probe admitted
        assert got is not None and got.replica_id == "r0"
        assert router._choose_locked(reps) is None  # probe budget spent
        router.breaker.record_success("r0", 0.01)
        assert router._choose_locked(reps) is not None  # closed again

    @pytest.mark.parametrize("clump", [8, 12, 16])
    def test_a_lone_replica_s_first_chunks_park_nobody(self, monkeypatch,
                                                       clump):
        """docqa's mix through a Router with one published replica: the
        poller's unary ``stats`` completions, then a clump of streams'
        first chunks. The next request is assigned at once, and the
        router's own histogram holds no wait over 0.1 s."""
        from ray_tpu.util.metrics import registry

        dep = f"docqa-{clump}"
        reps = [ReplicaInfo(replica_id="r0", deployment_name=dep,
                            actor_name="a0", max_ongoing_requests=100)]
        reported = []
        router = Router(dep, lambda: reps,
                        report_unhealthy=lambda *a: reported.append(a))
        _patch_submission(monkeypatch)
        monkeypatch.setattr(ray_tpu, "get", lambda ref, **k: None)
        _feed_docqa_mix(
            lambda lat, m: (
                router._settle(_FakeRef(), "r0", m, lat, False)
                if m == "stats" else
                router.record_stream_outcome("r0", True, lat, m)),
            clump)
        assert router.breaker.state("r0") == "closed" and not reported
        t0 = time.monotonic()
        for method in ("generate", "stats"):
            _, rid = router.assign_request(method, (), {}, timeout=5.0)
            assert rid == "r0"
        assert time.monotonic() - t0 < 0.1
        deadline = time.monotonic() + 2
        while time.monotonic() < deadline and router.metrics().get("r0"):
            time.sleep(0.01)
        router.close()
        # The reaper's completions are samples of the methods assigned.
        assert set(router.breaker._replicas["r0"].latencies) == \
            {"stats", "generate"}
        wait = next(e for e in registry().snapshot()["metrics"]
                    if e["name"] == "serve_router_queue_wait_s")
        over = wait["boundaries"].index(0.1) + 1
        mine = [b for key, b in wait["buckets"] if key == [dep]]
        assert mine and sum(mine[0]) == 2 and not any(mine[0][over:])

    def test_a_slow_replica_among_peers_is_counted_by_reason(
            self, monkeypatch):
        """Through the Router: the opening is reported to the controller as
        before and ``serve_breaker_transitions_total`` carries which rule
        opened it."""
        from ray_tpu.serve.router import _get_router_metrics

        dep = "two-replicas-one-slow"
        reps = [ReplicaInfo(replica_id=f"r{i}", deployment_name=dep,
                            actor_name=f"a{i}", max_ongoing_requests=100)
                for i in range(2)]
        reported = []
        router = Router(dep, lambda: reps,
                        report_unhealthy=lambda *a: reported.append(a))
        monkeypatch.setattr(ray_tpu, "get", lambda ref, **k: None)
        n = router.breaker.config.latency_min_samples
        for _ in range(n):
            router._settle(_FakeRef(), "r0", "stats", 0.001, False)
            router._settle(_FakeRef(), "r1", "stats", 0.001, False)
            router.record_stream_outcome("r0", True, 0.05, "generate")
        for _ in range(n):
            assert not reported
            router.record_stream_outcome("r1", True, 2.5, "generate")
        assert [r[0] for r in reported] == ["r1"]
        assert "latency outlier" in reported[0][1]
        for _ in range(50):  # r1 takes no traffic while it is open
            assert router._choose_locked(reps).replica_id == "r0"
        points = _get_router_metrics()["breaker_transitions"]._points()
        assert {k: v for k, v in points.items() if k[0] == dep} == \
            {(dep, "r1", "latency"): 1.0}
        router.breaker.config = CircuitBreakerConfig(failure_threshold=1)
        router.breaker.record_failure("r0")
        points = _get_router_metrics()["breaker_transitions"]._points()
        assert points[(dep, "r0", "failures")] == 1.0

    def test_router_queue_cap_sheds_with_overloaded(self):
        reps = _replicas(1, cap=1)
        router = Router("d", lambda: reps)
        router.settings = ResilienceSettings(max_queued_requests=1)
        router._settings_adopted = True
        with router._lock:
            router._inflight["r0"] = 1  # saturated

        results = []

        def parked():
            try:
                router.assign_request("m", (), {}, timeout=1.5)
                results.append("assigned")
            except Overloaded:
                results.append("shed")
            except DeadlineExceeded:
                results.append("expired")

        t1 = threading.Thread(target=parked)
        t1.start()
        time.sleep(0.15)  # t1 is parked (queue depth 1 = cap)
        with pytest.raises(Overloaded) as ei:
            router.assign_request("m", (), {}, timeout=1.5)
        assert ei.value.where == "router" and ei.value.retry_after_s > 0
        t1.join()
        assert results == ["expired"]  # the parked caller ran out its budget

    def test_settings_adopted_from_snapshot(self):
        s = ResilienceSettings(
            request_timeout_s=7.0, max_queued_requests=3,
            retry=RetryPolicy(max_retries=5, hedge_after_s=0.9),
            breaker=CircuitBreakerConfig(failure_threshold=9))
        reps = _replicas(2, settings=s)
        router = Router("d", lambda: reps)
        router.notify_replicas_changed(reps)
        assert router.settings.request_timeout_s == 7.0
        assert router.settings.max_queued_requests == 3
        assert router.settings.retry.max_retries == 5
        assert router.settings.retry.hedge_after_s == 0.9
        assert router.breaker.config.failure_threshold == 9


# ----------------------------------------------------------- replica unit
class TestReplicaAdmission:
    def _replica(self, fn=None, max_ongoing=2, slack=1):
        from ray_tpu.serve.replica import ServeReplica
        from ray_tpu.utils import serialization

        fn = fn or (lambda: "ok")
        return ServeReplica(
            "d", "rep0", serialization.serialize(fn),
            serialization.serialize(((), {})),
            max_ongoing_requests=max_ongoing, replica_queue_slack=slack)

    def test_replica_sheds_over_admission_cap(self):
        def slow():
            time.sleep(1.5)
            return "done"

        rep = self._replica(slow, max_ongoing=1, slack=1)
        threads = [threading.Thread(
            target=lambda: rep.handle_request("__call__", (), {}))
            for _ in range(2)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 2
        while rep.get_metrics()["ongoing"] < 2 and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        assert rep.get_metrics()["ongoing"] == 2
        # cap = max_ongoing(1) + slack(1) = 2 → the third concurrent
        # request is shed before any user code runs
        with pytest.raises(Overloaded) as ei:
            rep.handle_request("__call__", (), {})
        assert ei.value.where == "replica"
        for t in threads:
            t.join()
        assert rep.get_metrics()["shed"] == 1
        assert rep.get_metrics()["ongoing"] == 0

    def test_replica_drops_expired_request_before_execution(self):
        ran = []

        def work():
            ran.append(1)
            return "ok"

        rep = self._replica(work)
        with pytest.raises(DeadlineExceeded):
            rep.handle_request("__call__", (), {
                "__rtpu_deadline": time.time() - 0.1})
        assert not ran  # dropped BEFORE spending compute
        assert rep.get_metrics()["expired"] == 1
        # a live deadline passes through (and is popped from kwargs)
        assert rep.handle_request("__call__", (), {
            "__rtpu_deadline": time.time() + 30}) == "ok"

    def test_request_deadline_visible_to_user_code(self):
        def work():
            from ray_tpu import serve as _serve

            return _serve.request_deadline()

        rep = self._replica(work)
        d = time.time() + 12.0
        got = rep.handle_request("__call__", (), {"__rtpu_deadline": d})
        assert got is not None and abs(got - d) < 1e-6
        # and it is cleared once the request finishes
        assert rep.handle_request("__call__", (), {}) is None


# ----------------------------------------------------------- batcher unit
def test_batcher_sheds_expired_items():
    from ray_tpu.serve.batching import _BatchQueue
    from ray_tpu.serve.resilience import _set_current_deadline

    calls = []

    def fn(items):
        calls.append(list(items))
        return [i * 10 for i in items]

    bq = _BatchQueue(fn, max_batch_size=4, batch_wait_timeout_s=0.05)
    _set_current_deadline(time.time() - 0.1)  # already expired
    f_dead = bq.submit(None, 1)
    _set_current_deadline(time.time() + 30)
    f_live = bq.submit(None, 2)
    _set_current_deadline(None)
    assert f_live.result(timeout=5.0) == 20
    with pytest.raises(DeadlineExceeded):
        f_dead.result(timeout=5.0)
    assert calls == [[2]]  # the expired item never entered a batch


# ------------------------------------------------------ stream retry unit
def test_stream_retry_consumes_fresh_attempts_meta(monkeypatch):
    """A pre-first-chunk stream retry must consume the FRESH attempt's
    meta frame internally: leaking it as a data chunk would hand the
    consumer a {"streaming": ...} payload and swallow the real first
    chunk as meta."""
    from ray_tpu.core.exceptions import ActorDiedError
    from ray_tpu.serve.handle import DeploymentResponseGenerator

    class FakeGen:
        def __init__(self, frames):
            self.frames = list(frames)

        def _next(self, timeout):
            if not self.frames:
                raise StopIteration
            f = self.frames.pop(0)
            if isinstance(f, BaseException):
                raise f
            return f

    monkeypatch.setattr(ray_tpu, "get", lambda r, **k: r)
    dead = FakeGen([{"streaming": True},
                    ActorDiedError("r0", "killed", never_sent=True)])
    fresh = FakeGen([{"streaming": True}, "c1", "c2"])
    resubmits = []

    def resubmit(exclude):
        resubmits.append(set(exclude))
        return (fresh, None), "r1"

    g = DeploymentResponseGenerator(dead, resubmit=resubmit)
    assert g.streaming is True          # original attempt's meta
    assert list(g) == ["c1", "c2"], "meta frame leaked or chunk lost"
    assert resubmits == [set()]         # exactly one transparent retry


def test_a_stream_s_first_chunk_is_a_sample_of_its_method(monkeypatch):
    """The generator hands the breaker its time to first chunk under the
    method it streams, once, and later chunks are no samples."""
    from ray_tpu.serve.handle import DeploymentResponseGenerator

    class FakeGen:
        def __init__(self, frames):
            self.frames = list(frames)

        def _next(self, timeout):
            if not self.frames:
                raise StopIteration
            return self.frames.pop(0)

    monkeypatch.setattr(ray_tpu, "get", lambda r, **k: r)
    router = Router("d", lambda: _replicas(1))
    g = DeploymentResponseGenerator(
        FakeGen([{"streaming": True}, "c1", "c2", "c3"]), router=router,
        replica_id="r0", method="generate")
    assert list(g) == ["c1", "c2", "c3"]
    samples = router.breaker._replicas["r0"].latencies
    assert list(samples) == ["generate"] and len(samples["generate"]) == 1


# -------------------------------------------------------------- taxonomy
def test_error_classification():
    from ray_tpu.chaos.injector import ChaosKilled
    from ray_tpu.core.exceptions import ActorDiedError, TaskError

    assert classify(ActorDiedError("a", "x")) == "replica_died"
    assert classify(ActorDiedError("a", "x", never_sent=True)) == \
        "never_sent"
    assert classify(TaskError(Overloaded(where="replica"))) == \
        "overloaded_replica"
    assert classify(Overloaded(where="router")) == "overloaded_router"
    assert classify(TaskError(DeadlineExceeded())) == "expired"
    assert classify(TaskError(ValueError("user bug"))) == "app_error"
    assert classify(TaskError(ChaosKilled("boom"))) == "replica_died"
    # never_sent survives serialization (cross-process replies)
    import pickle

    err = pickle.loads(pickle.dumps(
        ActorDiedError("a", "x", never_sent=True)))
    assert err.never_sent


# ------------------------------------------------------------- e2e drills
@pytest.fixture
def serve_rt():
    try:
        ray_tpu.shutdown()
        ray_tpu.init()
    except Exception as e:  # noqa: BLE001 - environment without runtime
        pytest.skip(f"serve runtime unavailable: {e}")
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.mark.serveload
def test_replica_kill_mid_traffic_zero_failures(serve_rt):
    """A replica dying under concurrent traffic must not surface raw
    errors: never-sent calls re-resolve, policy retries re-route, and the
    controller replaces the replica."""
    @serve.deployment(num_replicas=2, max_ongoing_requests=8,
                      health_check_period_s=0.1,
                      retry_policy=serve.RetryPolicy(max_retries=2))
    class Echo:
        def __call__(self, x):
            time.sleep(0.005)
            return f"ok:{x}"

    handle = serve.run(Echo.bind(), route_prefix=None)
    errors, done = [], []

    def client(i):
        for j in range(10):
            try:
                assert handle.remote(i).result(timeout=30) == f"ok:{i}"
                done.append(1)
            except Exception as e:  # noqa: BLE001 - recorded for assert
                errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    # kill one replica mid-burst
    time.sleep(0.05)
    victims = [a for a in _serve_replica_actors("Echo")]
    assert victims
    ray_tpu.kill(victims[0])
    for t in threads:
        t.join()
    assert not errors, errors
    assert len(done) == 40


def _serve_replica_actors(deployment_name):
    controller = ray_tpu.get_actor("SERVE_CONTROLLER", namespace="serve")
    infos = ray_tpu.get(controller.get_replicas.remote(deployment_name))
    out = []
    for info in infos:
        try:
            out.append(ray_tpu.get_actor(info.actor_name, namespace="serve"))
        except Exception:  # noqa: BLE001 - replica racing away
            pass
    return out


@pytest.mark.serveload
def test_chaos_error_rule_trips_breaker_and_reroutes(serve_rt):
    """serve.replica chaos errors on one replica open its breaker; traffic
    flows to the sibling and the controller is nudged to probe."""
    from ray_tpu.chaos import injector

    injector.reset_for_tests()

    @serve.deployment(num_replicas=2, max_ongoing_requests=8,
                      retry_policy=serve.RetryPolicy(max_retries=0),
                      circuit_breaker=serve.CircuitBreakerConfig(
                          failure_threshold=3, open_s=60.0))
    class Echo:
        def __call__(self, x):
            return f"ok:{x}"

    handle = serve.run(Echo.bind(), route_prefix=None)
    infos = ray_tpu.get(ray_tpu.get_actor(
        "SERVE_CONTROLLER", namespace="serve").get_replicas.remote("Echo"))
    sick = infos[0].replica_id
    try:
        injector.install([{"point": "serve.replica", "action": "error",
                           "match": {"replica": sick}, "count": -1}])
        router = handle._ensure_router()
        failures = 0
        # Drive until the breaker opens (errors surface to callers as app
        # errors — chaos errors are indistinguishable from a sick model).
        deadline = time.monotonic() + 20
        while not router.breaker.is_open(sick) and \
                time.monotonic() < deadline:
            try:
                handle.remote("x").result(timeout=10)
            except Exception:  # noqa: BLE001 - expected until open
                failures += 1
        assert router.breaker.is_open(sick)
        assert 0 < failures <= 4  # threshold 3 (+1 for racing watcher)
        # Blacklisted: every subsequent call lands on the healthy sibling.
        for i in range(10):
            assert handle.remote(i).result(timeout=10) == f"ok:{i}"
    finally:
        injector.reset_for_tests()


@pytest.mark.serveload
def test_overload_sheds_and_is_bounded(serve_rt):
    """2x-capacity overload: the bounded router queue sheds with
    Overloaded instead of queueing unboundedly, and in-capacity traffic
    keeps completing."""
    @serve.deployment(num_replicas=1, max_ongoing_requests=2,
                      max_queued_requests=2, request_timeout_s=15.0,
                      retry_policy=serve.RetryPolicy(max_retries=0))
    class Slow:
        def __call__(self, x):
            time.sleep(1.0)
            return "done"

    handle = serve.run(Slow.bind(), route_prefix=None)
    outcomes = []
    lock = threading.Lock()

    def client():
        try:
            r = handle.remote("x").result(timeout=20)
            with lock:
                outcomes.append(r)
        except serve.Overloaded:
            with lock:
                outcomes.append("shed")
        except Exception as e:  # noqa: BLE001 - recorded for assert
            with lock:
                outcomes.append(repr(e))

    # capacity: 2 executing + 2 parked; 8 clients = 2x the total. The
    # 1 s service time keeps the first wave occupying its slots while the
    # over-capacity tail arrives (arrivals 0.03 s apart).
    threads = [threading.Thread(target=client) for _ in range(8)]
    for t in threads:
        t.start()
        time.sleep(0.03)  # deterministic arrival order
    for t in threads:
        t.join()
    assert outcomes.count("shed") == 4, outcomes
    assert outcomes.count("done") == 4, outcomes


@pytest.mark.serveload
def test_deadline_expires_queued_request(serve_rt):
    """A request whose budget is smaller than the queue wait is dropped
    (router- or replica-side) instead of executing late."""
    @serve.deployment(num_replicas=1, max_ongoing_requests=1,
                      retry_policy=serve.RetryPolicy(max_retries=0))
    class Slow:
        def __call__(self, x):
            time.sleep(2.5)
            return "done"

    handle = serve.run(Slow.bind(), route_prefix=None)
    blocker = handle.remote("a")
    time.sleep(0.1)  # the replica slot is now occupied
    t0 = time.monotonic()
    with pytest.raises((DeadlineExceeded, TimeoutError)):
        handle.options(timeout_s=0.4).remote("b").result(timeout=5)
    waited = time.monotonic() - t0
    assert waited < 2.0, f"expired request waited {waited:.1f}s"
    assert blocker.result(timeout=10) == "done"


@pytest.mark.serveload
def test_hedge_launches_on_slow_replica(serve_rt, tmp_path):
    """Tail hedging: a slow first attempt gets a duplicate on another
    replica after hedge_after_s, and the fast response wins."""
    @serve.deployment(num_replicas=2, max_ongoing_requests=4,
                      retry_policy=serve.RetryPolicy(
                          max_retries=1, hedge_after_s=0.3))
    class Bimodal:
        def __init__(self, claim_dir):
            # Exactly ONE replica is the pathological straggler: the first
            # instance to claim the marker directory (replica instances
            # can't share class state — the class blob deserializes per
            # replica).
            import os as _os

            try:
                _os.mkdir(_os.path.join(claim_dir, "slow-claimed"))
                self.slow = True
            except FileExistsError:
                self.slow = False

        def __call__(self, x):
            if self.slow:
                time.sleep(3.0)  # pathological tail
            return "ok"

    handle = serve.run(Bimodal.bind(str(tmp_path)), route_prefix=None)
    # Whichever replica the first attempt lands on, the call returns fast:
    # either it hit the healthy replica, or the 0.3 s hedge rescued it.
    for i in range(4):
        t0 = time.monotonic()
        assert handle.remote(i).result(timeout=10) == "ok"
        took = time.monotonic() - t0
        assert took < 2.5, f"hedge did not rescue the tail ({took:.1f}s)"
