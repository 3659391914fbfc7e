"""Goodput ledger: exhaustive wall-clock attribution (observability/goodput).

Property tests assert the tentpole invariant — every classified interval's
phases are non-overlapping and sum exactly to the interval, across fresh
starts, restarts, and explicit tails — plus fixture tests per badput
classifier, the event-leg transport (drain / requeue / head-side dedup),
the rollup's overlap resolution, the peak-FLOPs registry, the sampler's
monotonic rate denominator, and the tracing flush-cursor wraparound.
"""

import random
import time
from collections import deque

import pytest

from ray_tpu.observability import goodput
from ray_tpu.observability.goodput import (
    GOOD_PHASE,
    PHASES,
    GoodputStore,
    RankLedger,
    classify_interval,
)

pytestmark = pytest.mark.goodput


@pytest.fixture(autouse=True)
def _reset():
    goodput._reset_for_tests()
    yield
    goodput._reset_for_tests()


# --------------------------------------------------------------- classifier
class TestClassifyInterval:
    def test_property_exhaustive_nonoverlapping(self):
        """The invariant the whole ledger rests on: for ANY mix of
        measured parts (including overcommitted ones), the classified
        phases partition the interval — each second lands in exactly one
        phase and the parts sum to the wall duration."""
        rng = random.Random(1234)
        candidates = ("compile", "input_wait", "collective_wait",
                      "checkpoint", "replication_push", "step_compute")
        for trial in range(500):
            dur = rng.uniform(0.0, 20.0)
            parts = {}
            for phase in candidates:
                if rng.random() < 0.5:
                    # up to 2x the interval: clamping must still hold
                    parts[phase] = rng.uniform(0.0, 2.0 * dur)
            first = rng.random() < 0.3
            remainder = rng.choice([None, None, "idle", "restart_downtime"])
            out = classify_interval(
                dur, parts, first=first,
                first_phase=rng.choice(["init", "restart_downtime"]),
                remainder=remainder)
            assert all(k in PHASES for k in out), (trial, out)
            assert all(v >= 0.0 for v in out.values()), (trial, out)
            assert sum(out.values()) == pytest.approx(dur, abs=1e-9), \
                (trial, dur, parts, out)

    def test_measured_parts_pass_through(self):
        out = classify_interval(10.0, {"input_wait": 3.0, "compile": 2.0})
        assert out["input_wait"] == pytest.approx(3.0)
        assert out["compile"] == pytest.approx(2.0)
        assert out[GOOD_PHASE] == pytest.approx(5.0)

    def test_overcommit_clamps_in_priority_order(self):
        # compile is consumed before input_wait; nothing exceeds the wall
        out = classify_interval(4.0, {"compile": 3.0, "input_wait": 9.0})
        assert out == {"compile": pytest.approx(3.0),
                       "input_wait": pytest.approx(1.0)}

    def test_first_interval_is_init(self):
        out = classify_interval(5.0, {"compile": 2.0}, first=True)
        assert out["init"] == pytest.approx(3.0)

    def test_restarted_first_interval_is_restart_downtime(self):
        out = classify_interval(5.0, None, first=True,
                                first_phase="restart_downtime")
        assert out == {"restart_downtime": pytest.approx(5.0)}

    def test_measured_compute_pushes_excess_to_idle(self):
        """When compute_time_s is reported (PR-5 share stream), the gap
        between step wall and measured compute is straggler-induced
        idle, not goodput."""
        out = classify_interval(10.0, {"collective_wait": 2.0,
                                       "step_compute": 5.0})
        assert out["collective_wait"] == pytest.approx(2.0)
        assert out[GOOD_PHASE] == pytest.approx(5.0)
        assert out["idle"] == pytest.approx(3.0)

    def test_explicit_remainder_overrides(self):
        out = classify_interval(2.0, {"step_compute": 99.0},
                                remainder="idle")
        assert out == {"idle": pytest.approx(2.0)}

    def test_zero_and_negative_durations(self):
        assert classify_interval(0.0, {"compile": 1.0}) == {}
        assert classify_interval(-3.0, None) == {}


# -------------------------------------------------------------- rank ledger
class TestRankLedger:
    def test_close_and_finish_account_everything(self):
        led = RankLedger("exp", rank=2, chips=4.0)
        led.add_pending("input_wait", 0.002)
        time.sleep(0.01)
        led.close_interval(parts={"collective_wait": 0.001})
        time.sleep(0.01)
        led.close_interval()
        led.finish()
        snap = led.snapshot()
        assert snap["run"] == "exp" and snap["rank"] == 2
        assert snap["chips"] == 4.0
        assert snap["finished"] is True
        assert snap["open_s"] == 0.0
        # Exhaustive: classified phases cover the ledger's whole lifetime.
        assert snap["unattributed_s"] == pytest.approx(0.0, abs=1e-6)
        assert snap["phase_s"]["input_wait"] == pytest.approx(0.002)
        assert snap["phase_s"]["collective_wait"] == pytest.approx(0.001)

    def test_restart_boundary_first_interval(self):
        led = RankLedger("exp", rank=0, restarted=True)
        time.sleep(0.005)
        led.close_interval()
        snap = led.snapshot()
        assert "restart_downtime" in snap["phase_s"]
        assert "init" not in snap["phase_s"]
        assert snap["unattributed_s"] == pytest.approx(0.0, abs=1e-6)

    def test_unknown_pending_phase_dropped(self):
        led = RankLedger("exp", rank=0)
        led.add_pending("nonsense", 5.0)
        led.add_pending("input_wait", -1.0)
        led.finish()
        assert "nonsense" not in led.snapshot()["phase_s"]

    def test_open_snapshot_has_no_residual(self):
        led = RankLedger("exp", rank=0)
        led.close_interval()
        time.sleep(0.005)
        snap = led.snapshot()  # mid-interval: tail counts as open, not lost
        assert snap["open_s"] > 0.0
        assert snap["unattributed_s"] == pytest.approx(0.0, abs=1e-3)

    def test_closes_after_finish_noop(self):
        led = RankLedger("exp", rank=0)
        led.finish()
        total = sum(led.snapshot()["phase_s"].values())
        time.sleep(0.005)
        assert led.close_interval() is None
        assert sum(led.snapshot()["phase_s"].values()) == total

    def test_active_ledger_hooks(self):
        led = RankLedger("exp", rank=0)
        goodput.set_active(led)
        try:
            goodput.add_active_pending("checkpoint", 0.5)
            with goodput.input_wait():
                pass
            assert led._pending["checkpoint"] == pytest.approx(0.5)
            assert led._pending.get("input_wait", 0.0) >= 0.0
        finally:
            goodput.set_active(None)


# ------------------------------------------------------- event leg transport
class TestEventLeg:
    def test_drain_requeue_and_dedup(self):
        goodput.record_event("restart_downtime", "exp", 7.5, chips=8.0,
                             detail={"tier": "restore"})
        leg = goodput.collect_for_flush()
        assert leg is not None and len(leg["events"]) == 1
        assert goodput.collect_for_flush() is None  # drained
        # Push failed: requeue, next flush re-ships the SAME event ids.
        goodput.flush_failed(leg)
        leg2 = goodput.collect_for_flush()
        assert [e["id"] for e in leg2["events"]] == \
            [e["id"] for e in leg["events"]]
        # Head-side dedup: the same leg delivered twice lands once.
        store = GoodputStore()
        store.ingest("src", "node", leg2)
        store.ingest("src", "node", leg2)
        evs = store.events()
        assert len(evs) == 1
        assert evs[0]["seconds"] == pytest.approx(7.5)
        assert evs[0]["source"] == "src"

    def test_disabled_gate_buffers_nothing_out(self, monkeypatch):
        import ray_tpu.utils.config as config_mod

        goodput.record_event("restart_downtime", "exp", 1.0)
        monkeypatch.setenv("RTPU_GOODPUT_ENABLED", "0")
        config_mod.set_config(config_mod.Config.load())
        try:
            assert goodput.collect_for_flush() is None
        finally:
            monkeypatch.delenv("RTPU_GOODPUT_ENABLED")
            config_mod.set_config(config_mod.Config.load())

    def test_stamp_and_run_filter(self):
        store = GoodputStore()
        store.stamp("head_outage", None, 12.0, chips=2.0)
        store.ingest("c", "n", {"events": [
            {"id": "e1", "kind": "restart_downtime", "run": "exp",
             "seconds": 3.0, "chips": 1.0, "ts": 0.0, "detail": {}}]})
        assert len(store.events()) == 2
        # run filter keeps fleet-scoped (run=None) events visible
        assert {e["kind"] for e in store.events(run="exp")} == \
            {"head_outage", "restart_downtime"}
        assert [e["kind"] for e in store.events(run="other")] == \
            ["head_outage"]


# ------------------------------------------------------------------- rollup
def _train_stats(rows):
    """Head train_stats table from a list of rank-ledger snapshot dicts."""
    table = {}
    for i, gp in enumerate(rows):
        table[f"src{i}"] = {"node_id": f"n{i}", "ts": time.time(),
                            "stats": {gp["rank"]: {"goodput": gp}}}
    return table


def _snap(run="exp", rank=0, chips=1.0, phase_s=None, unattributed=0.0):
    return {"run": run, "rank": rank, "chips": chips, "t0": 0.0,
            "ts": time.time(), "phase_s": dict(phase_s or {}),
            "open_s": 0.0, "unattributed_s": unattributed,
            "spent_s": 0.001, "finished": False}


class TestRollup:
    def test_chip_second_weighting_and_goodput_pct(self):
        stats = _train_stats([
            _snap(rank=0, chips=4.0,
                  phase_s={GOOD_PHASE: 9.0, "input_wait": 1.0}),
            _snap(rank=1, chips=4.0,
                  phase_s={GOOD_PHASE: 8.0, "collective_wait": 2.0}),
        ])
        out = GoodputStore().rollup(stats)
        run = out["runs"]["exp"]
        assert run["ranks"] == 2 and run["chips"] == 8.0
        assert run["chip_seconds"] == pytest.approx(80.0)
        assert run["good_chip_s"] == pytest.approx(68.0)
        assert run["goodput_pct"] == pytest.approx(85.0)
        assert run["badput_chip_s"]["collective_wait"] == pytest.approx(8.0)
        assert out["fleet"]["goodput_pct"] == pytest.approx(85.0)

    def test_restart_event_overlap_takes_max(self):
        """The controller's restart event window CONTAINS the restarted
        context's first (rank-side) restart_downtime interval — the
        rollup must not sum the two."""
        store = GoodputStore()
        store.ingest("c", "n", {"events": [
            {"id": "r1", "kind": "restart_downtime", "run": "exp",
             "seconds": 8.0, "chips": 1.0, "ts": 0.0, "detail": {}}]})
        stats = _train_stats([
            _snap(phase_s={GOOD_PHASE: 10.0, "restart_downtime": 5.0})])
        run = store.rollup(stats)["runs"]["exp"]
        assert run["phase_chip_s"]["restart_downtime"] == pytest.approx(8.0)
        assert run["chip_seconds"] == pytest.approx(18.0)

    def test_rank_side_larger_than_event_side(self):
        store = GoodputStore()
        store.ingest("c", "n", {"events": [
            {"id": "r1", "kind": "restart_downtime", "run": "exp",
             "seconds": 2.0, "chips": 1.0, "ts": 0.0, "detail": {}}]})
        stats = _train_stats([_snap(phase_s={"restart_downtime": 6.0})])
        run = store.rollup(stats)["runs"]["exp"]
        assert run["phase_chip_s"]["restart_downtime"] == pytest.approx(6.0)

    def test_fleet_events_stay_fleet_scoped(self):
        store = GoodputStore()
        store.stamp("head_outage", None, 30.0, chips=2.0)
        out = store.rollup(_train_stats(
            [_snap(phase_s={GOOD_PHASE: 10.0})]))
        assert "head_outage" not in out["runs"]["exp"]["phase_chip_s"]
        assert out["fleet"]["phase_chip_s"]["head_outage"] == \
            pytest.approx(60.0)
        assert [e["kind"] for e in out["fleet"]["events"]] == ["head_outage"]

    def test_run_filter_and_unattributed_rollup(self):
        stats = _train_stats([
            _snap(run="a", phase_s={GOOD_PHASE: 1.0}, unattributed=0.25),
            _snap(run="b", phase_s={GOOD_PHASE: 1.0}),
        ])
        out = GoodputStore().rollup(stats, run="a")
        assert list(out["runs"]) == ["a"]
        assert out["runs"]["a"]["unattributed_s"] == pytest.approx(0.25)
        assert out["fleet"]["unattributed_s"] == pytest.approx(0.25)

    def test_serve_request_goodput_from_series(self):
        class FakeStore:
            def query(self, name=None, max_age_s=0.0):
                assert name == "serve_slo_tokens_total:rate"
                return [
                    {"name": name, "tags": {"deployment": "d"},
                     "source": "s1", "node_id": "n",
                     "points": [[1.0, 40.0]]},
                    {"name": name, "tags": {"deployment": "d"},
                     "source": "s2", "node_id": "n",
                     "points": [[1.0, 20.0]]},
                ]

        out = GoodputStore().rollup({}, series_store=FakeStore())
        dep = out["serve"]["d"]
        assert dep["slo_tokens_per_s"] == pytest.approx(60.0)
        assert dep["replicas"] == 2
        assert dep["request_goodput"] == pytest.approx(30.0)


# --------------------------------------------------------- badput watchdog
class _FakeWatchdog:
    def __init__(self):
        self.fired = []

    def record_event(self, rule, reason, detail=None):
        self.fired.append((rule, reason, detail))


class TestBadputRule:
    def test_fires_over_threshold_with_cooldown(self):
        store = GoodputStore()
        wd = _FakeWatchdog()
        stats = _train_stats([
            _snap(phase_s={GOOD_PHASE: 2.0, "input_wait": 18.0})])
        store.maybe_check(stats, wd)
        assert len(wd.fired) == 1
        rule, reason, detail = wd.fired[0]
        assert rule == "badput_over_threshold"
        assert detail["phase"] == "input_wait"
        assert detail["share_pct"] == pytest.approx(90.0)
        # Cooldown: an immediate re-check must not spam a second incident.
        store._last_check = 0.0  # defeat the ingest throttle only
        store.maybe_check(stats, wd)
        assert len(wd.fired) == 1

    def test_quiet_below_threshold_or_short_window(self):
        store = GoodputStore()
        wd = _FakeWatchdog()
        store.maybe_check(_train_stats([
            _snap(phase_s={GOOD_PHASE: 18.0, "input_wait": 2.0})]), wd)
        store2 = GoodputStore()
        store2.maybe_check(_train_stats([
            _snap(phase_s={"input_wait": 1.0})]), wd)  # < min_wall_s
        assert wd.fired == []


# -------------------------------------------------------- peak-FLOPs table
class TestPeakFlops:
    def test_table_and_aliases(self):
        from ray_tpu.accelerators import flops

        assert flops.peak_flops("v5e") == pytest.approx(197e12)
        assert flops.peak_flops("v5p", "int8") == pytest.approx(918e12)
        assert flops.peak_flops("v5litepod") == pytest.approx(197e12)
        assert flops.peak_flops("V6E") == pytest.approx(918e12)
        assert flops.peak_flops("v999") == 0.0
        assert flops.peak_flops("v4", "fp8") == 0.0

    def test_env_override_wins(self, monkeypatch):
        from ray_tpu.accelerators import flops

        monkeypatch.setenv("RTPU_PEAK_FLOPS", "1.5e14")
        assert flops.resolve_peak_flops() == pytest.approx(1.5e14)
        monkeypatch.setenv("RTPU_PEAK_FLOPS", "junk")
        flops._reset_for_tests()
        assert flops.resolve_peak_flops() == 0.0  # cpu backend: no TPU kind

    def test_session_report_uses_registry(self, monkeypatch):
        """session.report's MFU path resolves peak FLOPs through the
        registry (env override included) instead of an ad-hoc lookup."""
        import ray_tpu.train.session as session_mod

        monkeypatch.setenv("RTPU_PEAK_FLOPS", "2e14")
        src = open(session_mod.__file__).read()
        assert "resolve_peak_flops" in src
        from ray_tpu.accelerators.flops import resolve_peak_flops

        assert resolve_peak_flops() == pytest.approx(2e14)


# ------------------------------------------- sampler monotonic denominator
class TestSamplerMonotonicRates:
    def test_wall_clock_step_backwards_keeps_rates_sane(self, monkeypatch):
        """NTP steps the wall clock backwards between two flushes: the
        payload timestamp follows the wall clock, but the rate must be
        derived from the monotonic interval — never negative, never
        scaled by the step."""
        from ray_tpu.observability.sampler import SeriesSampler

        wall = [1000.0]
        mono = [50.0]
        monkeypatch.setattr(time, "time", lambda: wall[0])
        monkeypatch.setattr(time, "monotonic", lambda: mono[0])

        def snap(count):
            return {"metrics": [{
                "name": "serve_slo_tokens_total", "type": "counter",
                "tag_keys": ["deployment"],
                "points": [[["d"], float(count)]]}]}

        s = SeriesSampler()
        s.collect(snap(0))  # declare + establish cumulative state
        mono[0] += 10.0
        wall[0] -= 500.0  # the NTP step
        payload = s.collect(snap(30))
        assert payload is not None
        assert payload["t"] == pytest.approx(500.0)  # wall, as shipped
        rate_samples = [v for sid, v in payload["s"]
                        for d_sid, name, _ in payload["defs"]
                        if sid == d_sid and name.endswith(":rate")]
        assert rate_samples == [pytest.approx(3.0)]  # 30 / 10 mono-seconds

    def test_injected_clock_path_unchanged(self):
        from ray_tpu.observability.sampler import SeriesSampler

        s = SeriesSampler()
        snap = {"metrics": [{
            "name": "serve_slo_tokens_total", "type": "counter",
            "tag_keys": [], "points": [[[], 0.0]]}]}
        s.collect(snap, now=100.0)
        snap2 = {"metrics": [{
            "name": "serve_slo_tokens_total", "type": "counter",
            "tag_keys": [], "points": [[[], 5.0]]}]}
        payload = s.collect(snap2, now=110.0)
        vals = [v for _, v in payload["s"]]
        assert vals == [pytest.approx(0.5)]


# --------------------------------------------- tracing wraparound + spans
class TestTracingDrops:
    def test_flush_cursor_wraparound_meters_drops(self, monkeypatch):
        from ray_tpu.util import metrics, tracing

        def exported() -> float | None:
            # The registry is the process's: other tests have dropped spans
            # into this counter, so it is read before and after.
            for e in metrics.registry().snapshot()["metrics"]:
                if e["name"] == "tracing_spans_dropped":
                    return e["points"][0][1]
            return None

        tracing.clear()
        monkeypatch.setattr(tracing, "_spans", deque(maxlen=4))
        monkeypatch.setattr(tracing, "_spans_total", 0)
        monkeypatch.setattr(tracing, "_dropped_metered", 0)
        before = exported() or 0.0
        tracing.enable_tracing()
        try:
            for i in range(6):
                tracing.record_span(f"goodput.idle{i}", 1.0, 2.0,
                                    kind="goodput")
            spans, cursor = tracing.flush_new(0)
            # Ring wrapped: the flusher gets the surviving tail, the
            # cursor lands past everything, and the loss is counted.
            assert len(spans) == 4
            assert cursor == 6
            assert tracing.dropped_spans() == 2
            assert [s["name"] for s in spans] == \
                [f"goodput.idle{i}" for i in range(2, 6)]
            # Idempotent metering: a second flush adds no phantom drops.
            _, cursor = tracing.flush_new(cursor)
            assert tracing.dropped_spans() == 2
            after = exported()
            assert after is not None, "tracing_spans_dropped not exported"
            assert after - before == pytest.approx(2.0)
        finally:
            tracing.disable_tracing()
            tracing.clear()

    def test_record_span_shape(self):
        from ray_tpu.util import tracing

        tracing.clear()
        tracing.enable_tracing()
        try:
            tracing.record_span("goodput.compile", 10.0, 12.5,
                                kind="goodput",
                                attributes={"run": "exp", "rank": 3})
            spans, _ = tracing.flush_new(0)
            (s,) = [x for x in spans if x["name"] == "goodput.compile"]
            assert s["kind"] == "goodput"
            assert s["end_ts"] - s["start_ts"] == pytest.approx(2.5)
            # attribute values are stringified on the wire (span schema)
            assert s["attributes"] == {"run": "exp", "rank": "3"}
        finally:
            tracing.disable_tracing()
            tracing.clear()

    def test_goodput_lane_in_chrome_trace(self):
        from ray_tpu.profiling.merge import merge_chrome_trace

        doc = merge_chrome_trace([], spans=[
            {"span_id": "a", "trace_id": "t1", "name": "goodput.compile",
             "kind": "goodput", "start_ts": 1.0, "end_ts": 2.0,
             "attributes": {"run": "exp", "rank": 0}},
            {"span_id": "b", "trace_id": "t2", "name": "rpc.call",
             "kind": "client", "start_ts": 1.0, "end_ts": 2.0},
        ])
        rows = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        assert rows["goodput.compile"]["pid"] == "goodput"
        assert rows["goodput.compile"]["tid"] == "exp/r0"
        assert rows["rpc.call"]["pid"] == "spans"
        meta_pids = {e["pid"] for e in doc["traceEvents"]
                     if e.get("name") == "process_name"}
        assert {"spans", "goodput"} <= meta_pids


# ---------------------------------------------------- serve SLO token gate
class TestServeSloTokens:
    def test_deadline_gates_token_counting(self):
        from ray_tpu.serve.replica import ServeReplica

        class Stub:
            def __init__(self):
                self.n = 0

            def inc(self, v):
                self.n += v

        stub = Stub()
        fake = type("F", (), {"_b": {"slo_tokens": stub}})()
        ServeReplica._count_slo_tokens(fake, 1, None)
        ServeReplica._count_slo_tokens(fake, 2, time.time() + 60.0)
        ServeReplica._count_slo_tokens(fake, 4, time.time() - 1.0)  # blown
        assert stub.n == 3


# ------------------------------------------------------- CLI table render
class TestCliGoodputTable:
    def test_table_path_renders_top_badput(self, monkeypatch, capsys):
        # badput_chip_s is a DICT (phase -> chip-seconds); the table path
        # must rank its items, not slice it (regression: dict[:3] raised).
        from ray_tpu.scripts import cli

        rollup = {
            "enabled": True,
            "runs": {"r1": {
                "ranks": 2, "chip_seconds": 10.0, "goodput_pct": 62.5,
                "unattributed_s": 0.0,
                "badput_chip_s": {"input_wait": 2.0, "compile": 1.0,
                                  "checkpoint": 0.5, "idle": 0.25},
            }},
            "fleet": {"chip_seconds": 10.0, "goodput_pct": 62.5,
                      "unattributed_s": 0.0},
            "serve": {},
        }
        monkeypatch.setattr(cli, "_connect", lambda address: None)
        monkeypatch.setattr("ray_tpu.util.state.get_goodput",
                            lambda run=None: rollup)
        args = type("A", (), {"address": None, "run": None, "json": False})()
        assert cli.cmd_goodput(args) == 0
        out = capsys.readouterr().out
        assert "r1" in out and "62.5" in out
        assert "input_wait 2.0s, compile 1.0s, checkpoint 0.5s" in out
