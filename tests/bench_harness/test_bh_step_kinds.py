"""A decode step by the kind of step it was (PR 66): ``step_kind_ms`` on a
hand-written ``.xplane.pb`` that is read back as a run's is (the device
plane by ``trace_reduce.load`` and ``xplane_meta.load``, the host's phases
by ``phases.load``), and the three entries in the manifest.

The trace: a burst of four steps of which two carried a chunk, a burst of
four plain steps, a prefill chunk between them, and a burst cut at each
edge of the span. Times in microseconds:

    mixed burst   [1000, 1100]  riding loop [1000, 1060]: 2 x (attn 20,
                                conv 5, mlp 3) and 4 of the loop's own;
                                plain loop [1060, 1090]: 2 x (attn 10,
                                mlp 4) and 2; the sampler's hand-over 5;
                                5 idle before the program ends
    plain burst   [1200, 1260]  its loop [1200, 1256]: 4 x (attn 10, mlp 4)
"""

import pytest

from conftest import REPO
from rtbench import common, manifest, trace_reduce, xplane_meta
from rtbench.readers import (part_share, program_per_count, read_all,
                             scope_share, step_kind_ms)
from test_bh_service_path import _field  # noqa: E402

CELL = "mistral7b-serve-docqa"
NAMES = ("mixed_step_ms.tok_s", "plain_step_ms.tok_s",
         "riding_step_share.tok_s")
BURST = "jit(decode_burst)/stack/"
LAYER = "while/body/closed_call/stack/while/body/closed_call/"


def _xspace(planes: dict, paths: dict) -> bytes:
    """test_bh_service_path's writer with one thing more: an event name in
    ``paths`` carries its name-stack path as the ``tf_op`` stat of its
    *metadata*, where the profiler puts what holds for every execution of
    an operation."""
    out = b""
    for plane_name, lines in planes.items():
        events_meta: dict[str, int] = {}
        stats_meta: dict[str, int] = {"tf_op": 1}
        body = _field(2, plane_name)
        for k, (line_name, events) in enumerate(lines.items()):
            line = _field(1, k + 1) + _field(2, line_name)
            for name, start_ns, duration_ns, stats in events:
                ev = _field(1, events_meta.setdefault(
                    name, len(events_meta) + 1))
                ev += _field(2, start_ns * 1000) + _field(
                    3, duration_ns * 1000)
                for key, value in stats.items():
                    ev += _field(4, _field(1, stats_meta.setdefault(
                        key, len(stats_meta) + 1)) + _field(4, value))
                line += _field(4, ev)
            body += _field(3, line)
        for name, ident in events_meta.items():
            meta = _field(1, ident) + _field(2, name)
            if name in paths:
                meta += _field(5, _field(1, 1) + _field(5, paths[name]))
            body += _field(4, _field(1, ident) + _field(2, meta))
        for name, ident in stats_meta.items():
            body += _field(5, _field(1, ident) + _field(
                2, _field(1, ident) + _field(2, name)))
        out += _field(1, body)
    return out


def _planes(kind: str = "mixed_step/"):
    """(planes, paths) of the trace above; ``kind`` is the segment the
    riding loop's paths carry (none, for a program without the scope)."""
    us = 1000
    ops, paths = [], {}

    def op(name, path, start, length):
        paths[name] = path
        ops.append((name, start * us, length * us, {}))

    def loop(name, prefix, start, steps, parts):
        at = start
        for _ in range(steps):
            for part, length in parts:
                op(f"%{part.replace('/', '_')}.{name} = bf16[8] fusion()",
                   prefix + LAYER + part + "/dot_general", at, length)
                at += length
        return at

    riding = [("attn", 20), ("attn/conv", 5), ("mlp", 3)]
    plain = [("attn", 10), ("mlp", 4)]
    # a while contains its body's operations; what is left is its own
    op("%while.1 = () while()", BURST + kind + "while", 1000, 60)
    assert loop("r", BURST + kind, 1000, 2, riding) == 1056
    op("%while.2 = () while()", BURST + "while", 1060, 30)
    assert loop("p", BURST, 1060, 2, plain) == 1088
    op("%copy.1 = s32[8] copy()", "jit(decode_burst)/sample/copy", 1090, 5)
    op("%chunk.1 = bf16[8] fusion()",
       "jit(prefill_chunk)/" + LAYER + "attn/dot_general", 1120, 40)
    op("%while.3 = () while()", BURST + "while", 1200, 56)
    assert loop("q", BURST, 1200, 4, plain) == 1256
    # the bursts the span cut: one was running when it began, one when it
    # ended, each with a riding loop's operations inside
    op("%cut.1 = bf16[8] fusion()", BURST + kind + LAYER + "attn/mul", 0, 50)
    op("%cut.2 = bf16[8] fusion()", BURST + kind + LAYER + "attn/mul", 1300,
       100)
    modules = [("jit_decode_burst(11)", 0, 50 * us, {}),
               ("jit_decode_burst(11)", 1000 * us, 100 * us, {}),
               ("jit_prefill_chunk(5)", 1120 * us, 40 * us, {}),
               ("jit_decode_burst(7)", 1200 * us, 60 * us, {}),
               ("jit_decode_burst(11)", 1300 * us, 100 * us, {})]

    def dispatch(start, steps, riders):
        return ("engine.decode_dispatch", start * us, 30 * us,
                {"steps": steps, "slots": 8, "riders": riders})

    host = [("engine.tick", 0, 1400 * us, {}), dispatch(0, 4, 1),
            dispatch(900, 4, 2),
            ("engine.prefill_dispatch", 1105 * us, 10 * us,
             {"tokens": 512, "bucket": 512}),
            dispatch(1150, 4, 0), dispatch(1270, 4, 3)]
    return ({"/device:TPU:0": {"XLA Modules": modules, "XLA Ops": ops},
             "/host:CPU": {"llm-engine": host}}, paths)


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """An observation of a traced run whose trace directory holds the
    planes written out, read as ``kinds/serve_common`` reads a run's (a
    second call puts its planes in the first's place)."""
    def make(planes, paths) -> dict:
        monkeypatch.setattr(common, "trace_dir", lambda fresh=False:
                            str(tmp_path))
        d = tmp_path / "plugins" / "profile" / "run1"
        d.mkdir(parents=True, exist_ok=True)
        (d / "host.xplane.pb").write_bytes(_xspace(planes, paths))
        return {"trace": trace_reduce.load(
            trace_reduce.find_xplane(str(tmp_path)))}
    return make


def _specs():
    return {x["name"]: x for x in manifest.load_cell(CELL, REPO)["per_layer"]
            if x["name"] in NAMES}


def test_the_hand_written_trace_reads_as_a_run_s_does(traced):
    obs = traced(*_planes())
    dev = xplane_meta.of(obs)
    assert [trace_reduce.module_base(e.name) for e in dev.modules] == [
        "jit_decode_burst", "jit_decode_burst", "jit_prefill_chunk",
        "jit_decode_burst", "jit_decode_burst"]
    by_name = {op.name.split(" ")[0]: op for op in dev.ops}
    assert by_name["%while.1"].tf_op == BURST + "mixed_step/while"
    assert by_name["%while.1"].self_s == pytest.approx(4e-6)
    assert by_name["%while.2"].self_s == pytest.approx(2e-6)
    assert by_name["%attn_conv.r"].tf_op == (
        BURST + "mixed_step/" + LAYER + "attn/conv/dot_general")
    # a part under a kind of step is the part it was
    assert by_name["%attn_conv.r"].part == "attn"
    assert by_name["%while.1"].part == "stack"
    assert obs["trace"].window() == (0.0, pytest.approx(1400e-6))


def test_the_three_readings_to_the_digit(traced):
    """R = 2 riders of S = 8 steps in the two whole bursts, T = 160 us of
    programs, M = 60 us under the scope (the riding loop and all in it)."""
    obs = traced(*_planes())
    got = read_all(list(_specs().values()), obs)
    assert got == {"mixed_step_ms.tok_s": pytest.approx(0.030),
                   "plain_step_ms.tok_s": pytest.approx(0.100 / 6),
                   "riding_step_share.tok_s": pytest.approx(25.0)}
    # the program's time over its steps is the two kinds' mean
    whole = program_per_count.read(obs, manifest.load_json(
        REPO, "layer_metrics", "decode_ms_per_step.tok_s.json")["params"])
    share = got["riding_step_share.tok_s"] / 100
    assert whole == pytest.approx(0.020)
    assert share * got["mixed_step_ms.tok_s"] \
        + (1 - share) * got["plain_step_ms.tok_s"] == pytest.approx(whole)


def test_a_program_cut_at_the_span_s_edge_is_left_out_on_both_sides(traced):
    """The bursts that were running when the span began and ended carry a
    rider and three, and 150 us under the scope: counted, the share would
    read 6 of 16 and a riding step 35 us."""
    planes, paths = _planes()
    obs = traced(planes, paths)
    specs = _specs()
    assert step_kind_ms.read(obs, specs[NAMES[2]]["params"]) \
        == pytest.approx(25.0)
    assert step_kind_ms.read(obs, specs[NAMES[0]]["params"]) \
        == pytest.approx(0.030)
    # nothing in the span but the cut programs: no pair, nothing to read
    for line in planes["/device:TPU:0"].values():
        line[:] = [e for e in line if not 900_000 <= e[1] < 1_290_000]
    assert read_all(list(specs.values()), traced(planes, paths)) == {}


def test_a_trace_without_the_scope_gives_the_share_and_neither_time(traced):
    """A commit before the scope dispatched riders all the same (the count
    is PR 50's): its line carries the share, and no time under a new name
    that would be the mean over both kinds of step."""
    obs = traced(*_planes(kind=""))
    assert read_all(list(_specs().values()), obs) == {
        "riding_step_share.tok_s": pytest.approx(25.0)}


def test_a_span_in_which_no_chunk_rode_has_plain_steps_alone(traced):
    planes, paths = _planes()
    host = planes["/host:CPU"]["llm-engine"]
    host[:] = [(n, s, d, {**st, "riders": 0} if "riders" in st else st)
               for n, s, d, st in host]
    obs = traced(planes, paths)
    got = read_all(list(_specs().values()), obs)
    assert "mixed_step_ms.tok_s" not in got
    assert got["riding_step_share.tok_s"] == 0.0


def test_a_run_without_a_trace_reads_no_file(monkeypatch):
    monkeypatch.setattr(common, "trace_dir", lambda fresh=False: 1 / 0)
    for spec in _specs().values():
        assert step_kind_ms.read({}, spec["params"]) is None
        assert step_kind_ms.read({"trace": None}, spec["params"]) is None


@pytest.mark.parametrize("reader,params", [
    (part_share, {"parts": ["attn", "cache"]}),
    (part_share, {"parts": ["mlp"]}),
    (part_share, {"parts": ["stack"]}),
    (part_share, {"parts": ["sample"]}),
    (part_share, {"parts": ["unnamed"]}),
    (scope_share, {"scopes": ["conv", "conv_state"]}),
], ids=["attn", "mlp", "stack", "sample", "unnamed", "a finer name"])
def test_the_partition_reads_what_it_read_with_the_new_segment(
        traced, reader, params):
    """``part_share`` and ``scope_share`` walk a path for the names they
    know and pass over one they do not: the same trace with and without
    ``mixed_step`` on the riding loop's paths reads the same."""
    with_kind = reader.read(traced(*_planes()), params)
    without = reader.read(traced(*_planes(kind="")), params)
    assert with_kind == pytest.approx(without)
    assert with_kind > 0 or params == {"parts": ["unnamed"]}


def test_the_three_entries_are_docqa_s_and_the_manifest_is_clean():
    m = manifest.load(REPO)
    assert manifest.check(m, REPO) == []
    entries = {x["name"]: x for x in m["per_layer"]}
    layer = entries["decode_ms_per_step.tok_s"]["layer"]
    for name, unit, better, source in zip(
            NAMES, ("ms", "ms", "%"), ("lower", "lower", "higher"),
            ("device_trace", "device_trace", "program_span")):
        x = entries[name]
        assert (x["unit"], x["better"], x["source"], x["layer"],
                x["moves"]) == (unit, better, source, layer, "serve_tok_s")
        assert CELL in x["workloads"]
    specs = _specs()
    assert sorted(specs) == sorted(NAMES)
    assert {x["reader"] for x in specs.values()} == {"step_kind_ms"}
    assert [specs[n]["params"]["kind"] for n in NAMES] == [
        "mixed", "plain", "share"]
    # the pairs are program_per_count's: the same programs and count
    whole = manifest.load_json(REPO, "layer_metrics",
                               "decode_ms_per_step.tok_s.json")["params"]
    for x in specs.values():
        assert {k: x["params"][k] for k in whole} == whole
    # the scope is the program's name for it
    from ray_tpu.util import tracing

    assert {x["params"]["scope"] for x in specs.values()} \
        <= set(tracing.STEP_KINDS)
    names = manifest.module_names(
        f"{REPO}/benchmark/rtbench/readers/step_kind_ms.py")
    assert "read" in names and "ADAPTER_NEEDS" not in names
