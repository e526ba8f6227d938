"""The program's spans and scopes as the per-layer metrics read them
(``bench/spans.py``), on synthetic events and on recorded chip traces.

``chip_spans_qwen2_decode.json.gz`` and ``chip_spans_qwen2_train.json.gz``
are 0.25 s of traced ``qwen2-decode-c16`` and ``qwen2-train-s256`` runs on
one TPU v5e, flattened by ``bench/spans.events_from_xplane`` (device op
names cut to their HLO instruction; each op keeps its ``tf_op`` scope
path), windowed by a ``bench.window`` span.
"""
from pathlib import Path
from types import SimpleNamespace

import pytest

import benchtiny  # noqa: F401  (puts the benchmark on the path)

from bench import harness as H
from bench import spans as S
from bench import trace as TR

HERE = Path(__file__).parent
DEV = "/device:TPU:0"
NEW = ("decode_copy_ms", "page_grow_ms", "loop_host_ms", "train_update_ms",
       "train_host_ms")


def op(name, start, dur, scope="", plane=DEV):
    return S.Event(plane, TR.OPS_LINE, name, float(start), float(dur), scope)


def mod(name, start, dur, plane=DEV):
    return S.Event(plane, TR.MODULES_LINE, name, float(start), float(dur))


def host(name, start, dur):
    return S.Event("/host:CPU", "host", name, float(start), float(dur))


def read(metric, events):
    run = SimpleNamespace(trace=object(), spans=S.Spans(events))
    return H.load_module(H.BENCH_DIR / "metrics" / f"{metric}.py",
                         f"test_{metric}").read(run)


def decode_window():
    """Two decode steps of 100 ns each; the paged view's copies take 10 +
    20 ns of the first and 30 ns of the second; a growth blank and a row
    setter after the first step."""
    P = "jit(_paged_step_impl)"
    return [host("bench.window", 0, 1000),
            host("serve.loop", 0, 400),
            mod("jit__paged_step_impl(1)", 100, 100),
            op("%fusion.1", 100, 10, f"{P}/gather/gather:"),
            op("%while.2", 110, 70, f"{P}/verify/while:"),
            op("%fusion.3", 120, 20, f"{P}/verify/while/body/dot_general:"),
            op("%copy.4", 180, 20, f"{P}/scatter/scatter:"),
            mod("jit__blank_row_impl(2)", 210, 8),
            op("%scatter.5", 210, 8, "jit(_blank_row_impl)/scatter:"),
            mod("jit__set_table_row_impl(3)", 220, 2),
            op("%dus.6", 220, 2, "jit(_set_table_row_impl)/scatter:"),
            mod("jit__paged_step_impl(1)", 500, 100),
            op("%copy.7", 500, 30, f"{P}/scatter/transpose(jvp(x))/add:"),
            op("%gather.8", 530, 70, f"{P}/verify/gather:"),   # primitive
            op("%fusion.9", 700, 10, "jit(other)/gather/add:")]


def test_scoped_time_is_per_call_and_counts_nested_ops_once():
    events = decode_window()
    assert read("decode_copy_ms", events) == pytest.approx(
        1e3 * 60e-9 / 2)
    assert read("page_grow_ms", events) == pytest.approx(1e3 * 10e-9 / 2)
    sp = S.Spans(events)
    assert sp.calls("decode_step") == 2
    # the verify loop's body op lies inside the loop's own event
    assert sp.scoped_s(("_paged_step_impl",), ("verify",)) == pytest.approx(
        140e-9)


def test_copy_reads_zero_where_the_scopes_hold_no_op():
    P = "jit(_paged_step_impl)"
    events = [host("bench.window", 0, 1000), host("serve.loop", 0, 400),
              mod("jit__paged_step_impl(1)", 100, 100),
              op("%fusion.1", 100, 100, f"{P}/verify/add:")]
    assert read("decode_copy_ms", events) == 0.0


def test_loop_host_stretch_leaves_out_the_prefill_inside_it():
    events = [host("bench.window", 0, 10_000),
              host("serve.readback", 100, 50),    # ends 150
              host("serve.harvest", 150, 100),
              host("serve.admit", 300, 400),
              host("serve.prefill", 350, 300),
              host("serve.dispatch", 800, 40),    # stretch 650 - 300
              host("serve.readback", 900, 50),    # ends 950
              host("serve.dispatch", 1200, 40),   # stretch 250
              host("serve.readback", 9_990, 5),   # no dispatch after it
              mod("jit__paged_step_impl(1)", 0, 10)]
    assert read("loop_host_ms", events) == pytest.approx(
        1e3 * (350e-9 + 250e-9) / 2)


def test_train_metrics_per_step():
    events = [host("bench.window", 0, 1000),
              host("train.step", 0, 5), host("train.readback", 5, 95),
              host("train.batch", 120, 30), host("train.put", 150, 10),
              host("train.step", 200, 5), host("train.readback", 205, 95),
              host("train.step", 340, 5),
              mod("jit_step(4)", 10, 90), mod("jit_step(4)", 210, 90),
              op("%fusion.1", 10, 60, "jit(step)/drafter/dot_general:"),
              op("%fusion.2", 70, 30, "jit(step)/update/mul:"),
              op("%fusion.3", 270, 20, "jit(step)/update/mul:"),
              op("%fusion.4", 290, 10, "jit(step)/update/sqrt:")]
    assert read("train_update_ms", events) == pytest.approx(
        1e3 * 60e-9 / 2)
    # readback ends at 100 and 300; the next steps start at 200 and 340
    assert read("train_host_ms", events) == pytest.approx(
        1e3 * (100e-9 + 40e-9) / 2)


def test_a_program_without_spans_reads_nothing():
    events = [e for e in decode_window() if not e.name.startswith("serve.")]
    for metric in NEW:
        assert read(metric, events) is None
    untraced = SimpleNamespace(trace=None)
    assert S.of_run(untraced) is None


def test_idle_gaps_go_to_the_innermost_span():
    events = [host("bench.window", 0, 100),
              op("%a", 0, 10), op("%b", 60, 40),
              host("serve.loop", 0, 100),
              host("serve.harvest", 10, 20),
              host("serve.yield", 30, 20)]
    (dur, at, parts), = S.Spans(events).idle_gaps()
    assert dur == pytest.approx(50e-9) and at == pytest.approx(10e-9)
    assert parts[0][0] in ("serve.harvest", "serve.yield")
    assert dict(parts) == pytest.approx({"serve.harvest": 20e-9,
                                         "serve.yield": 20e-9,
                                         "serve.loop": 10e-9})


@pytest.mark.parametrize("fixture,metrics", [
    ("chip_spans_qwen2_decode.json.gz",
     ("decode_copy_ms", "page_grow_ms", "loop_host_ms")),
    ("chip_spans_qwen2_train.json.gz", ("train_update_ms", "train_host_ms")),
])
def test_every_new_metric_reads_a_recorded_chip_trace(fixture, metrics):
    events = S.load_events(str(HERE / fixture))
    sp = S.Spans(events)
    assert sp.window[1] - sp.window[0] == pytest.approx(0.25e9)
    assert sp.instrumented
    assert any(e.scope for e in sp.ops)
    for metric in metrics:
        value = read(metric, events)
        assert value is not None and value > 0, metric
    # the reduction the benchmark already had reads the same events
    s = TR.reduce(events, window_span=S.WINDOW_SPAN)
    assert 0 < s.busy_s <= s.window_s
