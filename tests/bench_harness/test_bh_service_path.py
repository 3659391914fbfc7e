"""The service path's per-layer metrics (PR 50): ``host_phase_mean`` on a
hand-made ``.xplane.pb`` (the wire format written here, read back by
``jax.profiler.ProfileData`` as a run's is), the counters' metrics on
hand-built polls, and the nine entries in the manifest."""

import os

import pytest

from conftest import REPO
from rtbench import common, manifest
from rtbench.readers import counter_ratio, host_phase_mean, read_all


# ---- a minimal writer of tsl/profiler/protobuf/xplane.proto ---------------
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _xspace(planes: dict) -> bytes:
    """``{plane: {line: [(event name, start ns, duration ns, stats)]}}`` as a
    serialized XSpace: names and stats through the plane's metadata maps,
    as the profiler writes them."""
    out = b""
    for plane_name, lines in planes.items():
        events_meta: dict[str, int] = {}
        stats_meta: dict[str, int] = {}
        body = _field(2, plane_name)
        for k, (line_name, events) in enumerate(lines.items()):
            line = _field(1, k + 1) + _field(2, line_name)
            for name, start_ns, duration_ns, stats in events:
                ev = _field(1, events_meta.setdefault(
                    name, len(events_meta) + 1))
                ev += _field(2, start_ns * 1000) + _field(3, duration_ns * 1000)
                for key, value in stats.items():
                    ev += _field(4, _field(1, stats_meta.setdefault(
                        key, len(stats_meta) + 1)) + _field(4, value))
                line += _field(4, ev)
            body += _field(3, line)
        for table, num in ((events_meta, 4), (stats_meta, 5)):
            for name, ident in table.items():
                body += _field(num, _field(1, ident) + _field(
                    2, _field(1, ident) + _field(2, name)))
        out += _field(1, body)
    return out


CHUNK, CLOSE = "serve.chunk_out", "serve.close"
SERVED = {"/host:CPU": {
    "Thread-7": [(CHUNK, 1_000, 40_000, {"lag_us": 300, "bytes": 90}),
                 (CHUNK, 90_000, 60_000, {"lag_us": 500, "bytes": 14}),
                 (CLOSE, 160_000, 1_000, {"lag_us": 21_000})],
    "Thread-9": [(CHUNK, 5_000, 20_000, {"lag_us": 100, "bytes": 90}),
                 ("PjitFunction(decode_burst)", 9_000, 5_000, {})],
    "llm-engine": [("engine.tick", 0, 900_000, {}),
                   ("engine.decode_dispatch", 10, 50_000,
                    {"steps": 8, "slots": 3, "riders": 0})]},
    "/device:TPU:0": {"XLA Ops": [(CHUNK, 0, 7, {"lag_us": 9})]}}
PARENT = {"/host:CPU": {"llm-engine": [("engine.tick", 0, 900_000, {})]}}

LAG = {"events": [CHUNK], "stat": "lag_us", "scale": 0.001}
WRITE = {"events": [CHUNK], "scale": 1000.0}
CLOSE_LAG = {"events": [CLOSE], "stat": "lag_us", "scale": 0.001}


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """An observation of a traced run whose trace directory holds ``planes``
    written out."""
    def make(planes) -> dict:
        monkeypatch.setattr(common, "trace_dir", lambda fresh=False:
                            str(tmp_path))
        if planes is not None:
            d = tmp_path / "plugins" / "profile" / "run1"
            d.mkdir(parents=True)
            (d / "host.xplane.pb").write_bytes(_xspace(planes))
        return {"trace": object()}
    return make


def test_the_hand_made_trace_reads_as_the_profiler_s_does(traced):
    events = host_phase_mean.of(traced(SERVED))
    assert sorted(events) == ["engine.decode_dispatch", "engine.tick",
                              CHUNK, CLOSE]   # the host's, phases alone
    assert sorted(events[CHUNK]) == [
        (pytest.approx(20e-6), {"lag_us": 100, "bytes": 90}),
        (pytest.approx(40e-6), {"lag_us": 300, "bytes": 90}),
        (pytest.approx(60e-6), {"lag_us": 500, "bytes": 14})]
    assert events["engine.decode_dispatch"][0][1]["riders"] == 0


@pytest.mark.parametrize("params,want", [
    (LAG, 0.3), (WRITE, 0.04), (CLOSE_LAG, 21.0),
    ({"events": [CHUNK, CLOSE], "stat": "lag_us"}, 21_900 / 4),
], ids=["a-stat", "the-durations", "another-event", "two-names-no-scale"])
def test_a_mean_over_the_events_of_a_name(traced, params, want):
    assert host_phase_mean.read(traced(SERVED), params) == \
        pytest.approx(want)


def test_the_file_is_parsed_once_a_run(traced, monkeypatch):
    obs = traced(SERVED)
    assert host_phase_mean.read(obs, LAG) is not None
    monkeypatch.setattr(host_phase_mean, "load", lambda path: 1 / 0)
    assert host_phase_mean.read(obs, WRITE) == pytest.approx(0.04)
    assert host_phase_mean.read(obs, CLOSE_LAG) == pytest.approx(21.0)


@pytest.mark.parametrize("planes,params", [
    (PARENT, LAG), (PARENT, WRITE), (None, CLOSE_LAG),
    (SERVED, {"events": [CLOSE], "stat": "bytes"}),
], ids=["a-parent-s-trace", "a-parent-s-trace-durations", "no-file",
        "no-such-stat"])
def test_nothing_to_read_is_none_and_no_error(traced, planes, params):
    assert host_phase_mean.read(traced(planes), params) is None


def test_a_run_without_a_trace_reads_no_file(monkeypatch):
    monkeypatch.setattr(common, "trace_dir", lambda fresh=False: 1 / 0)
    assert host_phase_mean.read({}, LAG) is None
    assert host_phase_mean.read({"trace": None}, WRITE) is None
    # a test may hand the events in
    obs = {host_phase_mean.KEY: {CLOSE: [(1e-6, {"lag_us": 4000})]}}
    assert host_phase_mean.read(obs, CLOSE_LAG) == pytest.approx(4.0)


# ---- the manifest ----------------------------------------------------------
SERVICE = ("Service seen by the client (serve/http_proxy.py, router.py, "
           "replica.py)")
TPOT = ("tpot_mean_ms", ["mistral7b-serve-chat"])
TOK_S = ("serve_tok_s", ["mistral7b-serve-docqa", "mistral7b-serve-reason"])
NEW = {
    "ingress_mean_ms.tpot": (SERVICE, "program_counter", "counter_ratio",
                             *TPOT),
    "ingress_mean_ms.tok_s": (SERVICE, "program_counter", "counter_ratio",
                              *TOK_S),
    "egress_chunk_lag_mean_ms.tpot": (SERVICE, "program_span",
                                      "host_phase_mean", *TPOT),
    "egress_chunk_lag_mean_ms.tok_s": (SERVICE, "program_span",
                                       "host_phase_mean", *TOK_S),
    "egress_write_mean_ms.tpot": (SERVICE, "program_span",
                                  "host_phase_mean", *TPOT),
    "egress_write_mean_ms.tok_s": (SERVICE, "program_span",
                                   "host_phase_mean", *TOK_S),
    "stream_close_lag_mean_ms.tok_s": (SERVICE, "program_span",
                                       "host_phase_mean", *TOK_S),
    "last_frame_lag_mean_ms.tok_s": ("LLM server (llm/serving.py)",
                                     "program_counter", "counter_ratio",
                                     *TOK_S),
    "slot_vacant_mean_ms.tok_s": (
        "Engine scheduler (llm/engine.py _tick, _admit)", "program_counter",
        "counter_ratio", *TOK_S),
}


def test_the_nine_entries_are_appended_and_the_layers_are_the_manifest_s():
    m = manifest.load(REPO)
    tail = m["per_layer"][-len(NEW):]
    assert [x["name"] for x in tail] == list(NEW)   # at the end, in order
    layers = {x["layer"] for x in m["per_layer"][:-len(NEW)]}
    for x in tail:
        layer, source, _reader, moves, cells = NEW[x["name"]]
        assert layer in layers                        # letter for letter
        assert x == {"name": x["name"], "unit": "ms", "better": "lower",
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": cells}
    assert manifest.check(m, REPO) == []


@pytest.mark.parametrize("cell,mine", [
    ("mistral7b-serve-chat", [n for n in NEW if n.endswith(".tpot")]),
    ("mistral7b-serve-docqa", [n for n in NEW if n.endswith(".tok_s")]),
    ("mistral7b-serve-reason", [n for n in NEW if n.endswith(".tok_s")]),
    ("lfm2-24b-serve-extract-8k", []), ("mistral7b-train-4k", []),
])
def test_a_cell_loads_its_new_metrics_with_their_readers(cell, mine):
    specs = {x["name"]: x for x in manifest.load_cell(cell, REPO)["per_layer"]
             if x["name"] in NEW}
    assert sorted(specs) == sorted(mine)
    for name, x in specs.items():
        assert x["reader"] == NEW[name][2]
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "rtbench", "readers", x["reader"] + ".py"))


def test_a_traced_window_gives_all_six_of_a_closed_loop_cell_s(traced):
    """docqa's new metrics from one observation: counters polled in the
    window and the host plane written above."""
    obs = traced(SERVED)
    grown = {"ingress_s": 0.030, "ingress_requests": 10,
             "last_frame_lag_s": 0.002, "last_frames": 10,
             "slot_vacant_s": 0.450, "slot_refills": 9}
    obs.update(t_open=10.0, t_close=20.0, polls=[
        (11.0, dict.fromkeys(grown, 0)), (19.0, grown)])
    specs = [x for x in manifest.load_cell(
        "mistral7b-serve-docqa", REPO)["per_layer"] if x["name"] in NEW]
    assert read_all(specs, obs) == {
        "ingress_mean_ms.tok_s": pytest.approx(3.0),
        "egress_chunk_lag_mean_ms.tok_s": pytest.approx(0.3),
        "egress_write_mean_ms.tok_s": pytest.approx(0.04),
        "stream_close_lag_mean_ms.tok_s": pytest.approx(21.0),
        "last_frame_lag_mean_ms.tok_s": pytest.approx(0.2),
        "slot_vacant_mean_ms.tok_s": pytest.approx(50.0)}


def test_a_parent_s_run_gives_none_of_them(traced):
    obs = traced(PARENT)
    obs.update(t_open=10.0, t_close=20.0, polls=[
        (11.0, {"admitted": 3, "first_frames": 3}),
        (19.0, {"admitted": 9, "first_frames": 9})])
    for cell in ("mistral7b-serve-chat", "mistral7b-serve-reason"):
        specs = [x for x in manifest.load_cell(cell, REPO)["per_layer"]
                 if x["name"] in NEW]
        assert specs and read_all(specs, obs) == {}
    assert counter_ratio.read(obs, {"num": "slot_vacant_s",
                                    "den": "slot_refills"}) is None
