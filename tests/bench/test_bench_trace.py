"""The benchmark's trace reduction, on events and on a recorded chip trace.

``chip_trace_qwen2_decode.json.gz`` is 0.25 s of a traced
``qwen2-decode-c16`` run on one TPU v5e (device op names cut to their HLO
instruction), flattened by ``bench/trace.events_from_xplane``.
"""
from pathlib import Path

import pytest

import benchtiny  # noqa: F401  (puts the benchmark on the path)

from bench import trace as TR

FIXTURE = Path(__file__).with_name("chip_trace_qwen2_decode.json.gz")
DEV = "/device:TPU:0"


def ev(line, name, start, dur, plane=DEV):
    return TR.Event(plane, line, name, float(start), float(dur))


def test_busy_is_the_union_of_overlapping_ops():
    events = [ev("host", "bench.window", 0, 100, plane="host"),
              ev(TR.OPS_LINE, "a", 10, 20), ev(TR.OPS_LINE, "b", 20, 20),
              ev(TR.OPS_LINE, "c", 60, 10),
              ev(TR.MODULES_LINE, "jit__unknown(7)", 10, 30),
              ev(TR.MODULES_LINE, "jit__prefill_pad_impl(9)", 60, 10),
              ev("host", "bench.harvest", 38, 20, plane="host")]
    s = TR.reduce(events, window_span="bench.window")
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(40e-9)          # [10,40) + [60,70)
    assert s.kind_time("decode_step") == (pytest.approx(30e-9), 1)
    assert s.kind_time("prefill") == (pytest.approx(10e-9), 1)
    gaps = dict((round(g * 1e9), n) for n, g in s.idle_gaps)
    assert gaps == {30: "no host span", 20: "bench.harvest", 10: "no host span"}
    assert s.device_ops[0] == ("a", pytest.approx(20e-9))


def test_busy_averages_over_chips_and_clips_to_the_window():
    events = [ev("host", "bench.window", 100, 100, plane="host"),
              ev(TR.OPS_LINE, "x", 50, 100),                   # half inside
              ev(TR.OPS_LINE, "y", 100, 100, plane="/device:TPU:1")]
    s = TR.reduce(events, window_span="bench.window")
    assert s.chips == 2
    assert s.busy_s == pytest.approx(75e-9)


def test_a_trace_without_device_work_is_refused():
    with pytest.raises(ValueError):
        TR.reduce([ev("host", "bench.window", 0, 10, plane="host")],
                  window_span="bench.window")


def test_program_names_strip_jit_and_id():
    assert TR.program_name("jit__paged_step_impl(1234)") == "_paged_step_impl"
    assert TR.program_name("jit_step(5)") == "step"
    assert TR.program_name("fusion") == "fusion"


def test_recorded_chip_trace_reduces():
    events = TR.load_events(str(FIXTURE))
    s = TR.reduce(events, window_span="bench.window")
    assert s.window_s == pytest.approx(0.25)
    assert 0 < s.busy_s <= s.window_s
    step_s, steps = s.kind_time("decode_step")
    assert steps == 2 and 0.05 < step_s / steps < 0.2
    assert s.kind_time("prefill")[1] == 1
    assert len(s.device_ops) == 10 and len(s.idle_gaps) <= 10
    assert all(sec > 0 for _, sec in s.device_ops + s.idle_gaps)
    idle = s.window_s - s.busy_s
    assert sum(sec for _, sec in s.idle_gaps) <= idle + 1e-9
    assert {n for n, _ in s.idle_gaps} <= {
        "bench.harvest", "bench.admit_waiting", "bench.grow",
        "bench.dispatch", "no host span"}
