"""The reduction from a profiler trace to device metrics."""

import json
import os

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _planes():
    ms = 1_000_000
    return [
        {"name": "/device:TPU:0", "lines": {
            "XLA Modules": [["jit_bm25_topk(7)", 10 * ms, 30 * ms],
                            ["jit_knn_nominate_batch(3)", 60 * ms, 10 * ms]],
            # overlapping ops count once
            "XLA Ops": [["fusion.1", 10 * ms, 20 * ms],
                        ["sort.2", 25 * ms, 15 * ms],
                        ["dot.3", 60 * ms, 10 * ms]]}},
        {"name": "/host:CPU", "lines": {"python": [
            [trace.CLOCK_MARK, 5 * ms, 1000],
            ["parse", 40 * ms, 15 * ms],
            ["readback", 42 * ms, 2 * ms],
            ["idle wait", 0, 100 * ms]]}},
    ]


def test_busy_is_the_union_of_op_intervals():
    ms = 1_000_000
    red = trace.reduce(_planes(), 0, 100 * ms)
    assert red["window_s"] == pytest.approx(0.1)
    # [10, 40) and [60, 70): 40 ms of 100
    assert red["busy_s"] == pytest.approx(0.040)
    assert red["programs_s"] == pytest.approx(
        {"jit_bm25_topk": 0.030, "jit_knn_nominate_batch": 0.010})
    assert trace.program_seconds(red, ("jit_bm25_",)) == pytest.approx(0.03)


def test_window_clips_events():
    ms = 1_000_000
    red = trace.reduce(_planes(), 20 * ms, 65 * ms)
    assert red["busy_s"] == pytest.approx(0.025)     # [20,40) + [60,65)
    assert red["programs_s"]["jit_bm25_topk"] == pytest.approx(0.020)


def test_idle_gaps_are_longest_first_and_named_by_the_host():
    ms = 1_000_000
    red = trace.reduce(_planes(), 0, 100 * ms)
    gaps = red["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([0.030, 0.020, 0.010])
    # the 20 ms gap [40, 60): "parse" covers 15 ms of it; the window-long
    # wait covers all of it but is a thread waiting
    assert gaps[1][0].startswith("parse at +40.000 ms")
    # the 10 ms gap [0, 10): only the wait overlaps it
    assert gaps[2][0].startswith("idle wait")


def test_no_device_plane_reads_nothing():
    assert trace.reduce([_planes()[1]], 0, 10) is None


def test_clock_offset_from_the_last_mark():
    planes = _planes()
    planes[1]["lines"]["python"].append([trace.CLOCK_MARK, 90_000_000, 10])
    assert trace.clock_offset(planes, [1_000, 85_001_000]) == 4_999_000
    with pytest.raises(ValueError):
        trace.clock_offset([planes[0]], [0])


def test_recorded_chip_trace():
    """A 20 ms slice of a trace recorded on the chip (PR 22, the kNN
    sweep at 50 queries/s): the device is busy for part of it, programs
    are named, and busy time and idle gaps never exceed the window."""
    path = os.path.join(DATA, "knn_trace_slice.json")
    with open(path) as fh:
        rec = json.load(fh)
    lo, hi = rec["window"]
    red = trace.reduce(rec["slice"], lo, hi)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["programs_s"]
    assert sum(g[1] for g in red["idle_gaps"]) <= red["window_s"] - (
        red["busy_s"]) + 1e-9
