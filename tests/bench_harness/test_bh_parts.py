"""The parts of the model in a device trace: the decoder of the
``.xplane.pb`` metadata, the path parsing, and the two readers built on
them (``rtbench/xplane_meta.py``, ``readers/part_share.py``,
``readers/part_ms_per_count.py``).

Two recorded traces: ``small.xplane.pb`` (PR 23: a train step of a commit
without scopes, so no part anywhere) and ``parts.xplane.pb`` (PR 36: three
steps of a two-layer Llama train step with the scopes, recorded on one
v5e chip by ``devbench/trace_parts_probe.py record``)."""

import os

import pytest

from conftest import BENCH, REPO
from rtbench import manifest, trace_reduce as tr, xplane_meta as xm
from rtbench.readers import part_ms_per_count, part_share, phases

SMALL = os.path.join(BENCH, "testdata", "small.xplane.pb")
PARTS = os.path.join(BENCH, "testdata", "parts.xplane.pb")
REMAT = ("jit(f)/transpose(jvp(stack))/while/body/closed_call/checkpoint/"
         "rematted_computation/attn/dot_general")


# ---- path parsing -----------------------------------------------------------

@pytest.mark.parametrize("path,part,pass_", [
    (REMAT + ":", "attn", "remat"),
    # innermost wins: the body's own part over the scan's
    ("jit(decode_burst)/stack/while/body/stack/while/body/mlp/dot_general",
     "mlp", "fwd"),
    ("jit(decode_burst)/stack/while/body/attn/cache/pallas_call", "cache",
     "fwd"),
    # what lax.scan adds around a body has the scan's scope alone
    ("jit(prefill_chunk)/stack/while/body/dynamic_slice", "stack", "fwd"),
    # wrappers are unwrapped: a scope opened outside a transformed function
    ("jit(_step)/transpose(jvp(loss))/mul", "loss", "bwd"),
    ("jit(_step)/jvp(vmap(moe_dispatch))/gather", "moe_dispatch", "fwd"),
    ("jit(_step)/transpose(jvp(vmap(moe_combine)))/add_any", "moe_combine",
     "bwd"),
    ("jit(_step)/jvp(stack)/while/body/closed_call/attn/transpose", "attn",
     "fwd"),
    # jit(name) is a function's name, never a part; primitives are not parts
    ("jit(attn)/jit(mlp)/mul", "unnamed", "fwd"),
    ("jit(_step)/transpose(jvp())/while/body/closed_call/mul", "unnamed",
     "bwd"),
    ("jit(sample_tokens)/sample/jit(_where)/select_n", "sample", "fwd"),
    ("jit(_step)/optim/jit(clip)/mul", "optim", "fwd"),
    # no path at all: the compiler's own
    (None, "lowered", "fwd"),
    ("", "lowered", "fwd"),
])
def test_a_path_resolves_to_its_innermost_part_and_its_pass(path, part,
                                                            pass_):
    assert xm.part_of(path) == part
    assert xm.pass_of(path) == pass_


def test_the_benchmark_copy_of_the_vocabulary_is_the_program_s():
    from ray_tpu.util import tracing

    assert xm.PARTS == tracing.PARTS
    assert not {xm.UNNAMED, xm.LOWERED} & set(xm.PARTS)


# ---- the decoder on the trace of PR 23 (no scopes) --------------------------

@pytest.fixture(scope="module")
def small():
    return xm.load(SMALL)


def test_every_op_of_trace_reduce_is_matched_by_start_and_name(small):
    ops = tr.load(SMALL).devices[0].ops
    assert len(small.ops) == len(ops) == 1932
    mine = sorted(small.ops, key=lambda e: (e.start, -e.end))
    theirs = sorted(ops, key=lambda e: (e.start, -e.end))
    for a, b in zip(mine, theirs):
        assert (a.name, a.start, a.end) == (b.name, b.start, b.end)
        assert a.self_s == b.self_s and a.leaf == b.leaf
    assert small.busy_s() == pytest.approx(tr.load(SMALL).busy_s(), rel=1e-9)
    assert [(m.name, m.start) for m in small.modules] == [
        (m.name, m.start) for m in tr.load(SMALL).devices[0].modules]


def test_a_known_operation_has_its_known_metadata(small):
    op = next(o for o in small.ops if o.name.startswith("%fusion.418 = "))
    assert op.tf_op == "jit(_step)/jvp()/while/body/closed_call/mul:"
    assert op.source == "/root/repo/ray_tpu/ops/rope.py:46"
    assert op.program_id == 5763767953187319077
    assert small.program_names() == {5763767953187319077: "jit__step"}


def test_passes_come_from_the_path(small):
    by_pass = {p: [o for o in small.ops if o.pass_ == p] for p in xm.PASSES}
    assert all(by_pass.values())
    assert all("rematted_computation" in o.tf_op for o in by_pass["remat"])
    assert all("transpose(" in o.tf_op
               and "rematted_computation" not in o.tf_op
               for o in by_pass["bwd"])
    assert small.seconds(pass_="remat") / small.busy_s() == pytest.approx(
        0.0572, abs=1e-3)


def test_ops_without_a_path_are_the_compiler_s_own(small):
    lowered = [o for o in small.ops if o.part == xm.LOWERED]
    assert lowered and all(o.tf_op is None for o in lowered)
    assert {tr.op_base(o.name).split(".")[0] for o in lowered} <= {
        "copy-done", "slice-done", "copy-start", "slice-start", "broadcast",
        "while", "fusion", "custom-call"}          # AllocateBuffer
    assert sum(o.self_s for o in lowered) / small.busy_s() == pytest.approx(
        0.0696, abs=1e-3)
    # everything else has a path and, in this commit, no part on it
    assert {o.part for o in small.ops} == {xm.LOWERED, xm.UNNAMED}


@pytest.mark.parametrize("reader,params", [
    (part_share, {"parts": ["attn", "cache"]}),
    (part_share, {"parts": ["unnamed"]}),
    (part_share, {"parts": None, "pass": "remat"}),
    (part_ms_per_count, {"parts": ["moe_route"], "programs": ["jit__step"],
                         "phase": "engine.decode_dispatch",
                         "count": "steps"}),
])
def test_a_trace_without_parts_gives_none(small, reader, params):
    obs = {"trace": tr.load(SMALL), "device_ops": small, "phases": []}
    assert reader.read(obs, params) is None
    assert reader.read({"trace": None}, params) is None


# ---- the readers on hand-made operations ------------------------------------

def _op(name, start, end, tf_op, program_id=7):
    return xm.Op(name, start, end, tf_op=tf_op, program_id=program_id,
                 part=xm.part_of(tf_op), pass_=xm.pass_of(tf_op))


@pytest.fixture()
def made():
    """Two programs of 10 ms. In each: a loop of 8 ms (scope ``stack``)
    that holds 3 ms of ``attn``, 2 of ``moe_route`` and 1 with no part, so
    2 ms are the loop's own; 1 ms of a compiler's copy; 1 ms of ``head``,
    recomputed."""
    ops = []
    for t0 in (0.0, 0.020):
        ops += [
            _op("%while.1 = () while()", t0, t0 + 0.008,
                "jit(decode_burst)/stack/while"),
            _op("%fusion.1 = bf16[8]{0} fusion()", t0 + 0.001, t0 + 0.004,
                "jit(decode_burst)/stack/while/body/attn/dot_general"),
            _op("%fusion.2 = bf16[8]{0} fusion()", t0 + 0.004, t0 + 0.006,
                "jit(decode_burst)/stack/while/body/moe_route/top_k"),
            _op("%fusion.3 = bf16[8]{0} fusion()", t0 + 0.006, t0 + 0.007,
                "jit(decode_burst)/jit(helper)/mul"),
            _op("%copy-done.1 = bf16[8]{0} copy-done()", t0 + 0.008,
                t0 + 0.009, None),
            _op("%fusion.4 = f32[8]{0} fusion()", t0 + 0.009, t0 + 0.010,
                "jit(decode_burst)/checkpoint/rematted_computation/head/"
                "dot_general"),
        ]
    tr._self_times(ops)
    modules = [tr.Event("jit_decode_burst(7)", 0.0, 0.010),
               tr.Event("jit_decode_burst(7)", 0.020, 0.030)]
    dev = xm.DeviceOps(0, ops, modules)
    trace = tr.Trace([tr.DeviceTrace(0, ops, [], modules)], {})
    return {"trace": trace, "device_ops": dev}


def test_shares_partition_the_busy_time(made):
    share = lambda parts, **kw: part_share.read(  # noqa: E731
        made, {"parts": parts, **kw})
    assert made["device_ops"].busy_s() == pytest.approx(0.020)
    assert share(["attn", "cache"]) == pytest.approx(30.0)
    assert share(["moe_route", "moe_dispatch", "moe_combine"]) == \
        pytest.approx(20.0)
    assert share(["stack", "lowered"]) == pytest.approx(30.0)   # 2 + 1 ms
    assert share(["unnamed"]) == pytest.approx(10.0)
    assert share(["embed", "head", "loss", "sample", "loop"]) == \
        pytest.approx(10.0)
    assert share(None, **{"pass": "remat"}) == pytest.approx(10.0)
    assert share(["head"], **{"pass": "fwd"}) == 0.0
    assert sum(share([p]) for p in (*xm.PARTS, xm.UNNAMED, xm.LOWERED)) == \
        pytest.approx(100.0)


def test_part_ms_per_count_pairs_programs_with_their_dispatches(made):
    params = {"parts": ["moe_route", "moe_dispatch", "moe_combine"],
              "programs": ["jit_decode_burst", "jit_decode_step"],
              "phase": "engine.decode_dispatch", "count": "steps"}
    # The second program touches the window's edge and is left out, with
    # its dispatch: 2 ms of glue in one program of 4 steps.
    made["trace"].devices[0].modules.append(
        tr.Event("jit_other(9)", -0.001, -0.0005))
    made["phases"] = [
        phases.Phase("engine.decode_dispatch", -0.0001, 0.0, {"steps": 4}),
        phases.Phase("engine.decode_dispatch", 0.015, 0.016, {"steps": 4})]
    assert part_ms_per_count.read(made, params) == pytest.approx(0.5)
    made["phases"] = []
    assert part_ms_per_count.read(made, params) is None


# ---- the manifest -----------------------------------------------------------

def _new_metrics(m):
    return [x for x in m["per_layer"]
            if x["name"].startswith(("part_share_", "moe_glue_ms"))]


def test_the_manifest_is_clean_and_ouro_has_none_of_the_new_metrics():
    m = manifest.load(REPO)
    assert manifest.check(m, REPO) == []
    new = _new_metrics(m)
    assert len(new) == 22 and len(m["per_layer"]) == 64
    assert m["per_layer"][-22:] == new       # appended, nothing moved
    for x in new:
        assert "ouro2.6b-serve-solve" not in x["workloads"]
        assert x["source"] == "device_trace"
        assert x["unit"] == ("ms" if x["name"] == "moe_glue_ms_per_step"
                             else "%")
        better = "higher" if x["name"].startswith(
            ("part_share_mlp.", "part_share_moe_experts.")) else "lower"
        assert x["better"] == better


@pytest.mark.parametrize("cell", [
    "mistral7b-train-4k", "mixtral8x7b-train-4chip", "mistral7b-serve-chat",
    "mistral7b-serve-docqa", "mistral7b-serve-reason",
    "longcat-flash-serve-agent-8k"])
def test_a_cell_s_shares_name_every_part_once(cell):
    """``remat`` aside (it cuts by pass), a cell's ``part_share_*`` list
    every part it can have exactly once, ``lowered`` and ``unnamed`` among
    them: so they sum to 100."""
    specs = [x for x in manifest.load_cell(cell, REPO)["per_layer"]
             if x["reader"] == "part_share" and "pass" not in x["params"]]
    listed = [p for x in specs for p in x["params"]["parts"]]
    assert len(listed) == len(set(listed))
    everything = {*xm.PARTS, xm.UNNAMED, xm.LOWERED}
    assert set(listed) <= everything
    # What a cell leaves out is what its programs cannot hold.
    left_out = everything - set(listed)
    routed = {"moe_route", "moe_dispatch", "moe_experts", "moe_combine"}
    allowed = {"mistral7b-train-4k": routed,
               "mixtral8x7b-train-4chip": {"mlp"},
               "longcat-flash-serve-agent-8k": {"optim"}}.get(
                   cell, routed | {"optim"})
    assert left_out == allowed
    assert not [x for x in specs if not x["name"].endswith(
        {"train_tok_s_chip": ".train", "tpot_mean_ms": ".tpot",
         "serve_tok_s": ".tok_s"}[x["moves"]])]


def test_no_new_reader_asks_anything_of_an_adapter():
    for reader in ("part_share", "part_ms_per_count"):
        names = manifest.module_names(os.path.join(
            BENCH, "rtbench", "readers", f"{reader}.py"))
        assert "read" in names and "ADAPTER_NEEDS" not in names


# ---- the trace of PR 36 (with scopes) ---------------------------------------

@pytest.fixture(scope="module")
def scoped():
    return xm.load(PARTS)


def test_the_recorded_scoped_trace_is_small_and_has_parts(scoped):
    assert os.path.getsize(PARTS) < 1_500_000
    assert scoped.has_parts()
    assert scoped.program_names() and set(
        scoped.program_names().values()) == {"jit__step"}
    found = {o.part for o in scoped.ops}
    assert {"embed", "attn", "mlp", "head", "loss", "optim", "stack",
            xm.LOWERED} <= found
    assert not found & {"cache", "sample", "moe_route", "loop"}


def test_the_recorded_scoped_trace_reads_as_a_train_step_should(scoped):
    obs = {"trace": tr.load(PARTS), "device_ops": scoped}
    share = lambda parts, **kw: part_share.read(  # noqa: E731
        obs, {"parts": parts, **kw})
    groups = [["attn", "cache"], ["mlp"],
              ["embed", "head", "loss", "sample", "loop"], ["optim"],
              ["moe_experts"], ["moe_route", "moe_dispatch", "moe_combine"],
              ["stack", "lowered"], ["unnamed"]]
    values = [share(g) for g in groups]
    assert sum(values) == pytest.approx(100.0, abs=1e-6)
    attn, mlp, head, optim, experts, glue, lowering, unnamed = values
    assert experts == glue == 0.0
    assert unnamed < 5.0
    assert attn > 10 and mlp > 10 and head > 5 and optim > 1
    # full remat: the recomputed forward is there, under the parts' names
    remat = share(None, **{"pass": "remat"})
    assert 5 < remat < 35
    assert any(o.pass_ == "remat" and o.part in ("attn", "mlp")
               for o in scoped.ops)
    # the flash kernels sit in attn, forward, recomputed and backward
    flash = [o for o in scoped.ops if tr.op_base(o.name).startswith("flash")]
    assert flash and {o.part for o in flash} == {"attn"}
    assert {o.pass_ for o in flash} >= {"bwd"}
