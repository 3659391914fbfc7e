"""The reduction from .xplane.pb on a small recorded trace: three steps of
a two-layer train step with the flash kernels on one v5e chip (my chip
run, PR 23)."""

import os

import pytest

from conftest import BENCH
from rtbench import trace_reduce as tr


@pytest.fixture(scope="module")
def trace():
    return tr.load(os.path.join(BENCH, "testdata", "small.xplane.pb"))


def test_busy_and_window(trace):
    assert len(trace.devices) == 1
    assert trace.window_s() == pytest.approx(0.0281596, rel=1e-4)
    assert trace.busy_s() == pytest.approx(0.00342174, rel=1e-4)
    assert trace.idle_share() == pytest.approx(0.87849, rel=1e-4)


def test_programs_by_name(trace):
    assert trace.module_counts() == {"jit__step": 3}
    assert trace.module_seconds()["jit__step"] == pytest.approx(
        0.00344895, rel=1e-4)


def test_self_times_do_not_count_a_loop_body_twice(trace):
    # while events contain their bodies' ops: self times add up to busy.
    assert sum(trace.op_self_seconds().values()) == pytest.approx(
        trace.busy_s(), rel=1e-6)
    assert any(not e.leaf for e in trace.devices[0].ops)


@pytest.mark.parametrize("kernel,calls,seconds", [
    ("flash_fwd", 6, 0.000227447), ("flash_bwd", 6, 0.000305557)])
def test_kernels_by_name(trace, kernel, calls, seconds):
    events = trace.kernel_events(kernel)
    assert len(events) == calls
    assert sum(e.end - e.start for e in events) == pytest.approx(
        seconds, rel=1e-4)


def test_breakdown_names_ops_and_idle_gaps(trace):
    b = trace.breakdown()
    assert b["device_ops"][0][0] == "flash_bwd.10_bf16_4_2_1024_128_"
    assert len(b["device_ops"]) == 10
    # the probe slept between steps: that is where the chip idled
    assert b["idle_gaps"][0][0] == "$time_sleep"
    assert b["idle_gaps"][0][1] == pytest.approx(0.0247183, rel=1e-4)


def test_one_chip_has_no_collectives(trace):
    assert trace.collective_seconds() == (0.0, 0.0)


def test_exposed_collective_time_on_hand_made_events():
    ops = [tr.Event("%fusion.1 = f32[8]{0} fusion()", 0.0, 1.0),
           tr.Event("%all-to-all.1 = f32[8]{0} all-to-all()", 1.0, 1.5),
           tr.Event("%fusion.2 = f32[8]{0} fusion()", 2.0, 3.0)]
    asyncs = [tr.Event("%all-reduce-start.1 = f32[8]{0} all-reduce-start()",
                       2.5, 3.5)]
    tr._self_times(ops)
    d = tr.DeviceTrace(0, ops, asyncs, [])
    total, exposed = tr.Trace([d, d], {}).collective_seconds()
    assert total == pytest.approx(1.5)      # 0.5 synchronous + 1.0 in flight
    assert exposed == pytest.approx(1.0)    # 0.5 + the 0.5 after fusion.2


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.short_op_name(
        "%fusion.4 = bf16[16,128]{1,0:T(8,128)} fusion(...)") == \
        "fusion.4_bf16_16_128_"
    assert tr.module_base("jit_prefill_chunk(123)") == "jit_prefill_chunk"


def test_a_trace_without_device_ops_is_refused(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    assert path is not None
    with pytest.raises(ValueError, match="no operation ran on a device"):
        tr.load(path)
