"""The trace reduction, on a trace recorded on one TPU v5e
(``data/tpu_probe.xplane.pb``, made by ``tools/record_trace.py``: three
calls each of ``chipbench_probe`` and ``chipbench_stream``), and on
hand-made planes."""
import os
from types import SimpleNamespace as NS

import pytest

from chipbench import devtrace
from chipbench.peaks import PEAKS, least_seconds, peaks_for

TRACE = os.path.join(os.path.dirname(__file__), "data", "tpu_probe.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return devtrace.reduce_trace(TRACE)


def test_programs_are_found_by_jitted_name(red):
    assert set(red.programs) == {"chipbench_probe", "chipbench_stream"}
    assert red.count("chipbench_probe") == 3
    assert red.count("chipbench_stream") == 3
    # the stream kernel is one Mosaic custom call of ~45 us per execution
    per_call = red.programs["chipbench_stream"].device_s / 3
    assert 30e-6 < per_call < 60e-6


def test_busy_is_the_union_of_ops_inside_the_window(red):
    assert 0 < red.busy_s < red.window_s
    # every op belongs to one program here, so busy is their sum
    assert red.busy_s == pytest.approx(
        red.device_s("chipbench_probe", "chipbench_stream"))
    # the window runs from start_trace's end to stop_trace's start
    assert 0.015 < red.window_s < 0.025


def test_breakdown_names_ops_and_gaps(red):
    assert red.device_ops[0][0] == "chipbench_stream/_stream.1"
    assert len(red.device_ops) <= 10 and len(red.idle_gaps) <= 10
    idle = sum(s for _, s in red.idle_gaps)
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6)
    # the script slept between calls: the host was in time.sleep
    assert "sleep" in red.idle_gaps[0][0]


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes():
    mods = [_ev("jit_step(1)", 1000, 400), _ev("jit_other(2)", 2000, 500)]
    ops = [_ev("%a = f32[] add()", 1000, 100), _ev("%b = f32[] mul()", 1050, 200),
           _ev("%c = f32[] dot()", 1300, 100), _ev("%d = f32[] add()", 2100, 300)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=mods),
                                          NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("chipbench_window_start", 0, 900),
        _ev("PjitFunction(step)", 950, 40),
        _ev("PjitFunction(other)", 1950, 40),
        _ev("chipbench_window_stop", 3000, 10)])])
    return [host, dev]


def test_union_and_attribution_on_hand_made_planes():
    r = devtrace.reduce_planes(_planes())
    # ops [1000,1250) u [1300,1400) inside step; [2100,2400) inside other
    assert r.programs["step"].device_s == pytest.approx(350e-9)
    assert r.programs["other"].device_s == pytest.approx(300e-9)
    assert r.busy_s == pytest.approx(650e-9)
    assert r.window_s == pytest.approx(2100e-9)


def test_merge_and_clip():
    assert devtrace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert devtrace.union_length([(0, 2), (1, 3), (10, 11)]) == 4
    assert devtrace.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]
    assert devtrace.program_name("jit__decode(123)") == "_decode"
    assert devtrace.op_name("%fusion.3 = f32[8]{0} fusion()") == "fusion.3"


def test_peaks_are_keyed_by_device_kind():
    p = peaks_for("TPU v5 lite")
    assert (p.flops_per_s, p.bytes_per_s) == (197e12, 819e9)
    with pytest.raises(KeyError):
        peaks_for("cpu")
    assert set(PEAKS) == {"TPU v5 lite"}


def test_least_time_is_the_larger_bound():
    p = peaks_for("TPU v5 lite")
    assert least_seconds(197e12, 0, p) == pytest.approx(1.0)
    assert least_seconds(1.0, 819e9, p) == pytest.approx(1.0)
    assert least_seconds(197e12, 2 * 819e9, p) == pytest.approx(2.0)
