"""The reduction from trace events to busy time, operations and idle
gaps, on a small trace written by hand."""
import pytest

from benchtiny import harness  # noqa: F401  (puts the benchmark on the path)
import devtrace
from devtrace import Event

TPU0, TPU1, OPS = "/device:TPU:0", "/device:TPU:1", "XLA Ops"


def span(name, start, end):
    return Event("/host:CPU", "python", name, start, end - start)


def op(plane, name, start, end):
    return Event(plane, OPS, name, start, end - start)


EVENTS = [
    span("bench.traced_window", 0, 1000),
    span("bench.step", 0, 950),
    span("bench.submit", 420, 580),
    op(TPU0, "fusion.1", 100, 300),
    op(TPU0, "gather.2", 250, 400),        # overlaps fusion.1
    op(TPU0, "fusion.1", 600, 900),
    op(TPU0, "copy.3", 1100, 1200),        # after the window
    op(TPU1, "while.4", -50, 1050),        # covers the whole window
]


def test_busy_is_the_union_averaged_over_chips():
    r = devtrace.reduce(EVENTS)
    # chip 0: 100..400 and 600..900; chip 1: the whole window
    assert r["busy_s"] == pytest.approx((600 + 1000) / 2 / 1e9)
    assert r["window_s"] == pytest.approx(1000 / 1e9)


def test_operations_are_summed_by_name_inside_the_window():
    r = devtrace.reduce(EVENTS)
    assert r["device_ops"] == [["fusion.1", pytest.approx(500e-9)],
                               ["gather.2", pytest.approx(150e-9)]]


def test_gaps_are_named_by_the_innermost_span():
    r = devtrace.reduce(EVENTS)
    assert r["idle_gaps"] == [
        ["bench.submit", pytest.approx(200e-9)],
        ["bench.step", pytest.approx(100e-9)],
        ["bench.traced_window", pytest.approx(100e-9)]]


def test_top_keeps_the_longest():
    r = devtrace.reduce(EVENTS, top=1)
    assert len(r["device_ops"]) == 1 and len(r["idle_gaps"]) == 1


def test_nothing_to_read_gives_nothing():
    host_only = [e for e in EVENTS if not e.plane.startswith("/device")]
    assert devtrace.reduce(host_only) is None
    no_window = [e for e in EVENTS if e.name != "bench.traced_window"]
    assert devtrace.reduce(no_window) is None


def test_union_merges_overlaps_and_touching_intervals():
    assert devtrace.union([(5, 7), (0, 2), (2, 3), (6, 9)]) == [
        (0, 3), (5, 9)]


def test_a_recorded_trace_slice():
    # 3 ms either side of the longest idle gap of a traced HPCG run on
    # one v5e: a refill step, during which the device waits ~70 ms
    import json
    from pathlib import Path

    data = json.loads((Path(__file__).parent / "data"
                       / "hpcg27_trace_slice.json").read_text())
    events = [Event(*e) for e in data["events"]]
    lo, hi = data["lo_ns"], data["hi_ns"]
    events.append(span("bench.traced_window", lo, hi))
    r = devtrace.reduce(events)
    gaps = sum(s for _, s in r["idle_gaps"])
    assert r["busy_s"] + gaps == pytest.approx(r["window_s"])
    name, longest = r["idle_gaps"][0]
    assert name == "bench.step"
    assert longest == pytest.approx(0.0699, abs=1e-4)
    every_op = devtrace.reduce(events, top=100)["device_ops"]
    assert r["busy_s"] <= sum(s for _, s in every_op) + 1e-8
