"""The readers of the program's own counters and phases (PR 24), each on a
hand-built observation: polls of ``stats()``, a ``Trace`` of a few device
events, phases as the profiler would have recorded them."""

import pytest

from conftest import REPO
from rtbench import manifest, trace_reduce as tr
from rtbench.readers import (counter_ratio, decode_attention_roofline,
                             idle_in_phases, phases, program_per_count,
                             read_all, tpot_percentile)


def _polls(*rows):
    return [(t, dict(s, slots=4)) for t, s in rows]


WINDOW = {"t_open": 10.0, "t_close": 20.0}


def _obs(polls):
    return dict(WINDOW, polls=polls)


# ------------------------------------------------------------ counter_ratio
def test_a_mean_wait_is_growth_over_growth_inside_the_window():
    polls = _polls(
        (9.0, {"queue_wait_s": 1.0, "admitted": 5}),     # before the window
        (10.5, {"queue_wait_s": 2.0, "admitted": 10}),
        (15.0, {"queue_wait_s": 3.0, "admitted": 12}),
        (19.5, {"queue_wait_s": 5.0, "admitted": 20}),
        (21.0, {"queue_wait_s": 50.0, "admitted": 21}))  # after it
    got = counter_ratio.read(_obs(polls), {
        "num": "queue_wait_s", "den": "admitted", "scale": 1000.0})
    assert got == pytest.approx(1000.0 * 3.0 / 10)


def test_a_share_multiplies_the_denominator_by_a_constant_of_stats():
    polls = _polls((11.0, {"decode_tokens": 100, "decode_steps": 50}),
                   (19.0, {"decode_tokens": 400, "decode_steps": 150}))
    got = counter_ratio.read(_obs(polls), {
        "num": "decode_tokens", "den": "decode_steps",
        "den_times": "slots", "scale": 100.0})
    assert got == pytest.approx(100.0 * 300 / (100 * 4))


@pytest.mark.parametrize("polls", [
    [],                                                   # no poll at all
    _polls((12.0, {"queue_wait_s": 1.0, "admitted": 3})),  # a single poll
    _polls((11.0, {"waiting": 0}), (19.0, {"waiting": 1})),  # the parent
    _polls((11.0, {"queue_wait_s": 1.0, "admitted": 3}),
           (19.0, {"queue_wait_s": 1.0, "admitted": 3})),  # nothing admitted
], ids=["no-polls", "one-poll", "no-such-counter", "no-growth"])
def test_nothing_to_read_is_none_and_no_error(polls):
    assert counter_ratio.read(_obs(polls), {
        "num": "queue_wait_s", "den": "admitted", "scale": 1000.0}) is None


# ------------------------------------------------------------------ pairing
def _phase(name, start, end, **stats):
    return phases.Phase(name, start, end, stats)


def _trace(modules, ops):
    ops = [tr.Event(f"%fusion.{i} = f32[8]{{0}} fusion()", s, e)
           for i, (s, e) in enumerate(ops)]
    tr._self_times(ops)
    mods = [tr.Event(name, s, e) for name, s, e in modules]
    return tr.Trace([tr.DeviceTrace(0, ops, [], mods)], {})


def test_programs_pair_with_their_dispatches_and_the_cut_ends_drop():
    # The trace opens while a burst dispatched before it still runs (A),
    # and closes after a dispatch whose program it never saw (d4). A
    # chained twin (d2) is dispatched before its elder (B) has started.
    progs = [tr.Event("jit_decode_burst(1)", 0.0, 0.8),    # A: no dispatch
             tr.Event("jit_decode_burst(1)", 1.0, 1.8),    # B <- d1
             tr.Event("jit_decode_burst(1)", 1.8, 2.6),    # C <- d2
             tr.Event("jit_decode_burst(2)", 2.9, 3.3)]    # D <- d3
    d = [_phase("engine.decode_dispatch", 0.90, 0.91, steps=8),
         _phase("engine.decode_dispatch", 0.92, 0.93, steps=8, chained=1),
         _phase("engine.decode_dispatch", 2.80, 2.81, steps=4),
         _phase("engine.decode_dispatch", 3.35, 3.36, steps=2)]
    pairs = phases.pair_in_order(d, progs)
    assert [(p.stats["steps"], e.start) for p, e in pairs] == \
        [(8, 1.0), (8, 1.8), (4, 2.9)]
    assert phases.pair_in_order([], progs) == []
    assert phases.pair_in_order(d, []) == []


def test_decode_ms_per_step_counts_steps_the_engine_took():
    # As a trace of the chip shows them: the burst that was running when
    # the trace began and the one running when it ended are cut short.
    modules = [("jit_decode_burst(1)", 0.0, 0.1),       # cut at the start
               ("jit_decode_burst(1)", 1.0, 1.8),
               ("jit_prefill_chunk(7)", 1.8, 1.9),
               ("jit_decode_burst(2)", 2.0, 2.4),
               ("jit_decode_burst(1)", 2.5, 2.7)]       # cut at the end
    obs = {"trace": _trace(modules, [(0.0, 2.7)]), "phases": [
        _phase("engine.decode_dispatch", 0.95, 0.96, steps=8, slots=3),
        _phase("engine.prefill_dispatch", 0.97, 0.98, tokens=500,
               bucket=512),
        _phase("engine.decode_dispatch", 1.95, 1.96, steps=4, slots=3),
        _phase("engine.decode_dispatch", 2.45, 2.46, steps=8, slots=3)]}
    params = {"programs": ["jit_decode_burst", "jit_decode_step"],
              "phase": "engine.decode_dispatch", "count": "steps"}
    # 0.8 s over 8 steps and 0.4 s over 4. The first program has no
    # dispatch inside the trace; the last has one, and its 0.2 s are not
    # the time of 8 steps: neither counts, seconds or steps.
    assert program_per_count.read(obs, params) == pytest.approx(100.0)
    per_ktok = {"programs": ["jit_prefill_chunk"], "count": "tokens",
                "phase": "engine.prefill_dispatch", "per": 1000}
    assert program_per_count.read(obs, per_ktok) == pytest.approx(
        0.1 * 1e3 / 0.5)


def test_a_program_without_phases_gives_none():
    obs = {"trace": _trace([("jit_decode_burst(1)", 0.0, 0.8)],
                           [(0.0, 0.8)]), "phases": []}
    params = {"programs": ["jit_decode_burst"], "count": "steps",
              "phase": "engine.decode_dispatch"}
    assert program_per_count.read(obs, params) is None
    assert program_per_count.read({"trace": None}, params) is None
    assert idle_in_phases.read(obs, {"prefix": "engine.",
                                     "except": []}) is None


# ------------------------------------------------------------- idle by phase
def test_idle_goes_to_the_shortest_phase_over_its_middle():
    # busy 0-1, 1.2-2, 2.5-3, 3.1-4: gaps of 0.2, 0.5 and 0.1 in 4 s
    trace = _trace([], [(0.0, 1.0), (1.2, 2.0), (2.5, 3.0), (3.1, 4.0)])
    obs = {"trace": trace, "phases": [
        _phase("engine.tick", 0.9, 2.1),
        _phase("engine.emit", 1.05, 1.15, tokens=8),     # gap 1: emit
        _phase("engine.tick", 2.2, 2.9),
        _phase("engine.fetch", 2.21, 2.6, which="burst"),  # gap 2: fetch
        _phase("train.report", 3.0, 3.2)]}               # not engine.*
    params = {"prefix": "engine.",
              "except": ["engine.fetch", "engine.wait"]}
    assert idle_in_phases.read(obs, params) == pytest.approx(
        100.0 * 0.2 / 4.0)
    # with nothing excepted the fetch's gap counts too; the third gap
    # lies in no engine phase and never does
    assert idle_in_phases.read(obs, {"prefix": "engine.", "except": []}) \
        == pytest.approx(100.0 * 0.7 / 4.0)
    assert idle_in_phases.read(obs, {"prefix": "train.", "except": []}) \
        == pytest.approx(100.0 * 0.1 / 4.0)


def test_cover_follows_nesting_as_time_rises():
    cover = phases.Cover([_phase("engine.tick", 0.0, 10.0),
                          _phase("engine.admit", 1.0, 2.0),
                          _phase("engine.emit", 5.0, 6.0)])
    assert [getattr(cover.at(t), "name", None)
            for t in (0.5, 1.5, 3.0, 5.5, 9.0, 11.0)] == \
        ["engine.tick", "engine.admit", "engine.tick", "engine.emit",
         "engine.tick", None]


# ------------------------------------------------- from a recorded trace
def test_phases_load_from_a_recorded_trace_with_their_counts(tmp_path):
    import jax

    from ray_tpu.util import tracing

    jax.profiler.start_trace(str(tmp_path))
    with tracing.phase("engine.tick"):
        with tracing.phase("engine.decode_dispatch", steps=8, slots=3):
            jax.numpy.ones(4).block_until_ready()
        with tracing.phase("engine.emit") as ph:
            ph.set(tokens=24)
    with tracing.phase("train.report"):
        pass
    jax.profiler.stop_trace()
    got = phases.load(tr.find_xplane(str(tmp_path)))
    assert [(p.name, p.stats) for p in got] == [
        ("engine.tick", {}),
        ("engine.decode_dispatch", {"steps": 8, "slots": 3}),
        ("engine.emit", {"tokens": 24}), ("train.report", {})]
    assert all(a.start <= b.start for a, b in zip(got, got[1:]))
    tick, dispatch = got[0], got[1]
    assert tick.start <= dispatch.start <= dispatch.end <= tick.end
    obs = {"phases": got}
    assert [p.name for p in phases.of(obs, "train.")] == ["train.report"]


# ------------------------------------------------- decode_attention_roofline
def _kernel_obs(polls, kernel_events, layers=16):
    """A traced span of 10..14 s of the host's clock; kernel events on the
    trace's own clock; polls of ``stats()`` around the span."""
    ops = [tr.Event('%decode_attention.6 = bf16[32,8,16,128]{3,2,1,0} '
                    'custom-call()', s, e) for s, e in kernel_events]
    ops.append(tr.Event("%fusion.1 = f32[8]{0} fusion()", 0.0, 0.001))
    tr._self_times(ops)
    cell = {"config": {"adapter": "llama", "num_key_value_heads": 8,
                       "head_dim": 128,
                       "num_hidden_layers": {"serve": layers}},
            "traffic": {"use": "serve"}}
    return {"kind": "serve", "cell": cell, "trace_span": (10.0, 14.0),
            "trace": tr.Trace([tr.DeviceTrace(0, ops, [], [])], {}),
            "polls": polls, "peaks": {"hbm_bytes_per_s": 819e9}}


def test_decode_attention_roofline_is_bytes_a_step_over_time_a_step():
    # Between the polls that bracket the span the engine took 100 decode
    # steps and counted 100 x 20,000 positions; the poll inside the span
    # and those further out are not the brackets. A position is 2 x 8 x
    # 128 x 2 bytes = 4 KiB a layer. The trace saw 32 kernel calls (2
    # steps of 16 layers) of 0.25 ms each.
    polls = [(9.0, {"kv_positions_read": 1, "decode_steps": 1}),
             (9.95, {"kv_positions_read": 1_000_000, "decode_steps": 500}),
             (12.0, {"kv_positions_read": 2_200_000, "decode_steps": 560}),
             (14.05, {"kv_positions_read": 3_000_000, "decode_steps": 600}),
             (15.0, {"kv_positions_read": 9_000_000, "decode_steps": 900})]
    events = [(1.0 + i * 0.001, 1.0 + i * 0.001 + 0.00025)
              for i in range(32)]
    got = decode_attention_roofline.read(_kernel_obs(polls, events),
                                         {"kernel": "decode_attention"})
    bytes_a_step = 20_000 * 4096 * 16
    assert got == pytest.approx(
        100.0 * (bytes_a_step / 819e9) / (0.00025 * 16))
    assert 30 < got < 50
    # half the depth: half the bytes a step and half the calls a step
    assert decode_attention_roofline.read(
        _kernel_obs(polls, events, layers=8),
        {"kernel": "decode_attention"}) == pytest.approx(got)


@pytest.mark.parametrize("polls,events", [
    ([(9.9, {"kv_positions_read": 0, "decode_steps": 0}),
      (14.1, {"kv_positions_read": 9, "decode_steps": 3})], []),
    ([], [(1.0, 1.1)]),
    ([(9.9, {"kv_positions_read": 0, "decode_steps": 0})], [(1.0, 1.1)]),
    ([(10.5, {"kv_positions_read": 0, "decode_steps": 0}),
      (14.1, {"kv_positions_read": 9, "decode_steps": 3})], [(1.0, 1.1)]),
    ([(9.9, {"waiting": 0}), (14.1, {"waiting": 1})], [(1.0, 1.1)]),
    ([(9.9, {"kv_positions_read": 5, "decode_steps": 3}),
      (14.1, {"kv_positions_read": 5, "decode_steps": 3})], [(1.0, 1.1)]),
], ids=["no-kernel-events", "no-polls", "no-poll-after", "no-poll-before",
        "no-such-counter", "no-decode-step"])
def test_decode_attention_roofline_with_nothing_to_read_is_none(polls,
                                                                events):
    assert decode_attention_roofline.read(
        _kernel_obs(polls, events), {"kernel": "decode_attention"}) is None
    assert decode_attention_roofline.read(
        {"trace": None}, {"kernel": "decode_attention"}) is None


# ---------------------------------------------------------- tpot_percentile
@pytest.mark.parametrize("q,want", [(50, 150.0), (90, 190.0), (100, 200.0)])
def test_tpot_percentile_is_over_requests_that_finished_in_the_window(q,
                                                                      want):
    def rec(first_t, last_t, **kw):
        return dict({"abandoned": False, "error": None, "frames": 11,
                     "max_tokens": 11, "finish": "length",
                     "first_t": first_t, "last_t": last_t}, **kw)

    # 100 and 200 ms a token inside the window; one request ends after it
    obs = dict(WINDOW, kind="serve", records=[
        rec(11.0, 12.0), rec(11.0, 13.0), rec(11.0, 25.0)])
    assert tpot_percentile.read(obs, {"q": q}) == pytest.approx(want)


@pytest.mark.parametrize("obs", [
    {"kind": "train"}, dict(WINDOW, kind="serve", records=[])],
    ids=["not-a-serve-run", "no-finished-request"])
def test_tpot_percentile_with_nothing_to_read_is_none(obs):
    assert tpot_percentile.read(obs, {"q": 90}) is None


# --------------------------------------------------------------- the entries
NEW = {
    "mistral7b-serve-chat": {
        "queue_wait_mean_ms", "admit_to_first_token_mean_ms.tpot",
        "first_frame_lag_mean_ms", "decode_slot_use_share.tpot",
        "decode_ms_per_step.counted", "idle_in_scheduler_share.tpot"},
    "mistral7b-serve-docqa": {
        "admit_to_first_token_mean_ms.tok_s", "decode_slot_use_share.tok_s",
        "prefill_ms_per_ktok.counted", "idle_in_scheduler_share.tok_s",
        "decode_ms_per_step.tok_s"},
    "mistral7b-serve-reason": {
        "admit_to_first_token_mean_ms.tok_s", "decode_slot_use_share.tok_s",
        "prefill_ms_per_ktok.counted", "idle_in_scheduler_share.tok_s",
        "decode_ms_per_step.tok_s"},
}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_the_cells_report_the_new_metrics_from_one_observation(cell):
    assert manifest.check(manifest.load(REPO), REPO) == []
    loaded = manifest.load_cell(cell, REPO)
    specs = [x for x in loaded["per_layer"] if x["name"] in NEW[cell]]
    assert {x["name"] for x in specs} == NEW[cell]
    stats0 = {"slots": 4, "queue_wait_s": 0.0, "admitted": 0,
              "first_token_wait_s": 0.0, "first_tokens": 0,
              "first_frame_lag_s": 0.0, "first_frames": 0,
              "decode_tokens": 0, "decode_steps": 0}
    stats1 = dict(stats0, queue_wait_s=4.0, admitted=10,
                  first_token_wait_s=8.0, first_tokens=10,
                  first_frame_lag_s=0.05, first_frames=10,
                  decode_tokens=600, decode_steps=200)
    obs = dict(WINDOW, kind="serve", polls=[(11.0, stats0), (19.0, stats1)],
               trace=_trace([("jit_decode_burst(1)", 1.0, 1.8),
                             ("jit_prefill_chunk(2)", 2.0, 2.1)],
                            [(0.5, 0.6), (1.0, 1.8), (2.0, 2.1), (2.5, 2.6)]),
               phases=[
                   _phase("engine.tick", 0.9, 2.2),
                   _phase("engine.decode_dispatch", 0.95, 0.96, steps=8),
                   _phase("engine.emit", 1.85, 1.95, tokens=8),
                   _phase("engine.prefill_dispatch", 1.96, 1.97, tokens=400,
                          bucket=512)])
    want = {"queue_wait_mean_ms": 400.0,
            "admit_to_first_token_mean_ms": 800.0,
            "first_frame_lag_mean_ms": 5.0,
            "decode_slot_use_share": 75.0,
            "decode_ms_per_step.counted": 100.0,
            "decode_ms_per_step": 100.0,
            "prefill_ms_per_ktok.counted": 250.0,
            "idle_in_scheduler_share": 100.0 * 0.2 / 2.1}
    got = read_all(specs, obs)
    assert set(got) == NEW[cell]
    for name, value in got.items():
        base = name.removesuffix(".tpot").removesuffix(".tok_s")
        assert value == pytest.approx(want[base]), name
    # on a program without the counters and phases the lines leave the
    # metrics out and nothing raises
    bare = dict(obs, phases=[], polls=[(11.0, {"slots": 4, "waiting": 0}),
                                       (19.0, {"slots": 4, "waiting": 1})])
    assert read_all(specs, bare) == {}
