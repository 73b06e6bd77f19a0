"""The trace reduction turns a small recorded trace into known busy
and idle numbers."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import tracing  # noqa: E402

MS = 1e6  # ns


def small_trace():
    """One device; two requests, 0-40 ms and 50-100 ms.  Device ops:
    10-20 and 15-30 (overlapping: 20 ms busy) in the first, a
    temporal_motif kernel 60-70 and an op 90-95 in the second."""
    return {
        "devices": {"/device:TPU:0": [
            (10 * MS, 20 * MS, "fusion.1", ""),
            (15 * MS, 30 * MS, "fusion.2", ""),
            (60 * MS, 70 * MS, "custom-call.3", "temporal_motif"),
            (90 * MS, 95 * MS, "fusion.1", ""),
        ]},
        "spans": [(0.0, 40 * MS, "req:pagerank:0"),
                  (50 * MS, 100 * MS, "req:triangles:1")],
    }


def test_busy_and_idle_of_a_known_trace():
    red = tracing.reduce(small_trace(), (0.0, 100 * MS))
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.035)
    assert red["idle_share"] == pytest.approx(0.65)


def test_device_time_per_request_and_kernel():
    red = tracing.reduce(small_trace(), (0.0, 100 * MS))
    per = red["per_span"]
    assert per["req:pagerank:0"]["device_s"] == pytest.approx(0.020)  # overlap once
    assert per["req:triangles:1"]["device_s"] == pytest.approx(0.015)
    assert per["req:triangles:1"]["kernels"] == {
        "temporal_motif": pytest.approx(0.010)}


def test_breakdown_names_ops_and_labels_gaps():
    red = tracing.reduce(small_trace(), (0.0, 100 * MS))
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.015)
    assert ops["temporal_motif"] == pytest.approx(0.010)
    gaps = red["breakdown"]["idle_gaps"]
    # gaps 0-10, 30-60, 70-90 and 95-100 ms, each labelled by the
    # request open at its midpoint
    assert gaps == [["between requests", pytest.approx(0.030)],
                    ["triangles", pytest.approx(0.020)],
                    ["pagerank", pytest.approx(0.010)],
                    ["triangles", pytest.approx(0.005)]]


def test_window_clips_ops_outside_it():
    red = tracing.reduce(small_trace(), (12 * MS, 65 * MS))
    assert red["busy_s"] == pytest.approx((30 - 12 + 65 - 60) / 1e3)


def test_no_device_planes_reads_all_idle():
    red = tracing.reduce({"devices": {}, "spans": []}, (0.0, 10 * MS))
    assert red["busy_s"] == 0.0 and red["idle_share"] == 1.0
