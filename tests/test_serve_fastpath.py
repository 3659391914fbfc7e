"""Serve inference fast path: KV-block-aware prefix routing
(serve/prefix.py + the router/controller/replica publication loop) and
the router hot path. Router-level tests run without a cluster, like
test_serve_resilience.TestRouterChurn; end-to-end drills carry the
``serveload`` marker. The zero-copy P/D KV hand-off round-trips live in
tests/test_pd_kv_handoff.py."""

import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.config import ReplicaInfo
from ray_tpu.serve.prefix import (
    block_hashes,
    match_len,
    text_block_hashes,
    union_hashes,
)
from ray_tpu.serve.router import Router


def _replicas(n, cap=4, draining=(), prefix=None, block=8):
    """prefix: {index: token-id sequence} — published as chain hashes."""
    out = []
    for i in range(n):
        blocks = None
        if prefix and i in prefix:
            blocks = union_hashes([prefix[i]], block)
        out.append(ReplicaInfo(
            replica_id=f"r{i}", deployment_name="d", actor_name=f"a{i}",
            max_ongoing_requests=cap, draining=(i in draining),
            prefix_blocks=blocks, prefix_block=block if blocks else 0))
    return out


# ------------------------------------------------------------- hash units
class TestPrefixHashes:
    def test_chained_blocks_identify_whole_prefix(self):
        a = list(range(100))
        b = list(range(100))
        b[50] = 999  # diverges inside block 6 (block=8: tokens 48..55)
        ha, hb = block_hashes(a, 8), block_hashes(b, 8)
        assert len(ha) == 100 // 8
        assert ha[:6] == hb[:6]
        # chaining: every hash AFTER the divergence differs too
        assert all(x != y for x, y in zip(ha[6:], hb[6:]))

    def test_partial_tail_block_not_hashed(self):
        assert len(block_hashes(list(range(17)), 8)) == 2
        assert block_hashes([1, 2, 3], 8) == ()
        assert block_hashes([], 8) == ()
        assert block_hashes([1, 2], 0) == ()

    def test_match_len_stops_at_first_miss(self):
        h = block_hashes(list(range(64)), 8)
        held = set(h[:5])
        assert match_len(h, held) == 5
        held.add(h[7])  # a gap: chained publication can't produce this
        assert match_len(h, held) == 5

    def test_text_domain_stable(self):
        h1 = text_block_hashes("sys-prompt " * 50, 64)
        h2 = text_block_hashes("sys-prompt " * 50 + "tail", 64)
        assert h1 and h1 == h2[:len(h1)]

    def test_stable_across_input_container(self):
        ids = tuple(range(32))
        assert block_hashes(ids, 8) == block_hashes(list(ids), 8) == \
            block_hashes(np.asarray(ids), 8)


# --------------------------------------------------------- router scoring
class TestPrefixRouting:
    def test_longest_match_wins(self):
        shared = list(range(64))
        reps = _replicas(3, prefix={0: shared[:16], 1: shared[:48]})
        router = Router("d", lambda: reps)
        router.notify_replicas_changed(reps)
        req = block_hashes(shared, 8)
        for _ in range(50):
            got = router._choose_locked(reps, prefix_hashes=req)
            assert got is not None and got.replica_id == "r1"

    def test_tie_break_equal_match_goes_least_loaded(self):
        shared = list(range(32))
        reps = _replicas(3, cap=100, prefix={0: shared, 2: shared})
        router = Router("d", lambda: reps)
        router.notify_replicas_changed(reps)
        with router._lock:
            router._inflight["r0"] = 2
            router._inflight["r2"] = 0
        req = block_hashes(shared, 8)
        for _ in range(50):
            got = router._choose_locked(reps, prefix_hashes=req)
            assert got is not None and got.replica_id == "r2"

    def test_balance_delta_overrides_locality(self):
        shared = list(range(32))
        reps = _replicas(2, cap=100, prefix={0: shared})
        router = Router("d", lambda: reps)
        router.notify_replicas_changed(reps)
        with router._lock:
            # matched replica is far above the least-loaded sibling
            router._inflight["r0"] = router.HINT_BALANCE_DELTA + 3
            router._inflight["r1"] = 0
        got = router._choose_locked(reps,
                                    prefix_hashes=block_hashes(shared, 8))
        assert got is not None and got.replica_id == "r1"

    def test_no_match_falls_back_to_pow2(self):
        reps = _replicas(3, prefix={0: list(range(32))})
        router = Router("d", lambda: reps)
        router.notify_replicas_changed(reps)
        req = block_hashes(list(range(1000, 1064)), 8)
        seen = {router._choose_locked(reps, prefix_hashes=req).replica_id
                for _ in range(100)}
        assert len(seen) > 1  # not pinned anywhere

    def test_never_prefix_routes_to_draining_replica(self):
        """Satellite regression guard (extends the PR-8 draining pin): the
        replica with the BEST prefix match is draining — it must get no
        traffic, via hint, prefix, or pow-2."""
        shared = list(range(64))
        reps = _replicas(3, draining={1},
                         prefix={1: shared, 0: shared[:8]})
        router = Router("d", lambda: reps)
        router.notify_replicas_changed(reps)
        req = block_hashes(shared, 8)
        for _ in range(100):
            got = router._choose_locked(reps, route_hint="h",
                                        prefix_hashes=req)
            assert got is not None and got.replica_id != "r1"
        # and the drain also evicted it from the prefix map itself
        assert "r1" not in router._prefix_map

    def test_prefix_map_drops_dead_replicas_on_snapshot(self):
        shared = list(range(32))
        reps = _replicas(3, prefix={0: shared, 1: shared})
        router = Router("d", lambda: reps)
        router.notify_replicas_changed(reps)
        assert set(router._prefix_map) == {"r0", "r1"}
        # r0 dies: the next snapshot no longer lists it
        survivors = [r for r in reps if r.replica_id != "r0"]
        router.notify_replicas_changed(survivors)
        assert set(router._prefix_map) == {"r1"}
        got = router._choose_locked(survivors,
                                    prefix_hashes=block_hashes(shared, 8))
        assert got is not None and got.replica_id == "r1"

    def test_prefix_map_ttl_ages_out_stale_entries(self):
        shared = list(range(32))
        reps = _replicas(2, prefix={0: shared})
        router = Router("d", lambda: reps)
        router.notify_replicas_changed(reps)
        router._prefix_ttl = 0.05
        time.sleep(0.08)  # no snapshot refresh within the TTL
        req = block_hashes(shared, 8)
        seen = {router._choose_locked(reps, prefix_hashes=req).replica_id
                for _ in range(100)}
        assert len(seen) > 1  # aged out: degraded to pow-2, not pinned

    def test_long_poll_liveness_refreshes_ttl(self):
        """The controller republishes only on CHANGE: a healthy
        deployment with a stable warm cache sends no snapshots, so each
        completed long-poll round touches the map — the TTL must expire
        only when polling stops (wedged controller), never steady state."""
        shared = list(range(32))
        reps = _replicas(2, prefix={0: shared})
        router = Router("d", lambda: reps)
        router.notify_replicas_changed(reps)
        router._prefix_ttl = 0.05
        req = block_hashes(shared, 8)
        for _ in range(4):  # total sleep well past the TTL
            time.sleep(0.03)
            router.touch_prefix_map()  # = one completed listen round
        got = router._choose_locked(reps, prefix_hashes=req)
        assert got is not None and got.replica_id == "r0"  # still pinned

    def test_breaker_open_match_falls_through(self):
        from ray_tpu.serve.resilience import CircuitBreakerConfig

        shared = list(range(32))
        reps = _replicas(2, prefix={0: shared})
        router = Router("d", lambda: reps)
        router.notify_replicas_changed(reps)
        router.breaker.config = CircuitBreakerConfig(
            failure_threshold=1, open_s=60.0)
        router.breaker.record_failure("r0")
        got = router._choose_locked(reps,
                                    prefix_hashes=block_hashes(shared, 8))
        assert got is not None and got.replica_id == "r1"


# ------------------------------------------------- engine hash publication
def test_engine_publishes_cached_prefix_hashes():
    from ray_tpu.llm import LLMConfig, LLMEngine, SamplingParams

    cfg = LLMConfig(model="tiny", max_num_seqs=2, max_seq_len=96,
                    prefix_block_tokens=8)
    eng = LLMEngine(cfg)
    try:
        prompt = list(range(1, 34))  # 33 tokens -> 4 full blocks of 8
        eng.generate(prompt, SamplingParams(max_tokens=2, temperature=0.0),
                     timeout=120)
        held = set(eng.prefix_block_hashes())
        want = block_hashes(prompt, 8)
        assert want and set(want) <= held
        # request-side hashes of a shared-prefix prompt match fully
        req = block_hashes(prompt + [200, 201, 202], 8)
        assert match_len(req, held) == len(want)
        # an unrelated prompt matches nothing
        assert match_len(block_hashes(list(range(500, 533)), 8), held) == 0
    finally:
        eng.shutdown()


# --------------------------------------------------------- e2e publication
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
def test_controller_publishes_prefix_blocks_and_router_scores(
        serve_rt, tmp_path):
    """End to end: a deployment whose callable publishes
    router_prefix_blocks reaches the router's prefix map through the
    controller poll + long-poll snapshot, and matching requests land on
    the publishing replica."""
    marker = list(range(100, 132))
    hashes = list(block_hashes(marker, 8))

    @serve.deployment(num_replicas=2, max_ongoing_requests=8,
                      health_check_period_s=0.2)
    class Cachey:
        def __init__(self, claim_dir):
            # exactly ONE replica claims (and publishes) the prefix —
            # replica instances can't share class state, so claim through
            # the filesystem like the PR-8 hedge drill.
            import os

            try:
                os.mkdir(os.path.join(claim_dir, "prefix-claimed"))
                self.claimed = True
            except FileExistsError:
                self.claimed = False

        def router_prefix_blocks(self):
            return {"blocks": hashes, "block": 8} if self.claimed else \
                {"blocks": [], "block": 8}

        def __call__(self, x):
            return self.claimed

    handle = serve.run(Cachey.bind(str(tmp_path)), route_prefix=None)
    router = handle._ensure_router()
    # generous: controller poll (0.5 s cadence) + long-poll fan-out must
    # land under full-suite load on the 1-core box
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if any(held for held, _ in router._prefix_map.values()):
            break
        time.sleep(0.05)
    assert any(held for held, _ in router._prefix_map.values()), \
        "prefix publication never reached the router"
    # requests whose hashes extend the published prefix pin to the
    # claiming replica (12/12). The reaper releases in-flight counts
    # asynchronously — drain between sequential requests so stale counts
    # can't trip the HINT_BALANCE_DELTA diversion (by-design balancing,
    # but a flake in a determinism assertion).
    def drained():
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            with router._lock:
                if not any(router._inflight.values()):
                    return
            time.sleep(0.005)

    req_hashes = tuple(block_hashes(marker + [7, 8, 9], 8))
    got = []
    for _ in range(12):
        drained()
        got.append(handle.options(prefix_hashes=req_hashes).remote("x")
                   .result(timeout=30))
    assert all(got), f"prefix-matched requests scattered: {got}"
    # ...while unmatched requests still spread over both replicas
    spread = {handle.remote("x").result(timeout=30) for _ in range(30)}
    assert spread == {True, False}


@pytest.mark.serveload
def test_router_throughput_smoke(serve_rt):
    """Four closed-loop clients drive unary assignments through the full
    handle → router → replica → reaper path at once: every request is
    answered, with its own argument. What the path sustains a second is
    devbench/router_bench.py's to measure on an idle box; a rate asserted
    here measured the suite's other workers."""

    @serve.deployment(num_replicas=2, max_ongoing_requests=64,
                      max_queued_requests=-1)
    class Echo:
        def __call__(self, x):
            return x

    handle = serve.run(Echo.bind(), route_prefix=None)
    router = handle._ensure_router()
    for i in range(50):  # primes caches + reaper
        handle.remote(i).result(timeout=30)

    each = 300
    answers = [[] for _ in range(4)]
    errors = []

    def client(k):
        try:
            for i in range(each):
                ref, rid = router.assign_request("__call__", ((k, i),), {},
                                                 timeout=30.0)
                answers[k].append(tuple(ray_tpu.get(ref, timeout=30)))
        except Exception as e:  # noqa: BLE001 - reported by the assert below
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert answers == [[(k, i) for i in range(each)] for k in range(4)]
