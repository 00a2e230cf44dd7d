"""The trace reduction, on hand-made events and on a small trace recorded
on a TPU v5e (three launches of the fused-kernel solve cell)."""

from __future__ import annotations

import gzip
import pathlib
import shutil

import pytest

from bench import trace_reduce

RECORDED = pathlib.Path(__file__).resolve().parent / "data" / "g16_solve.xplane.pb.gz"


def test_reduce_events_by_hand():
    ms = 1_000_000
    host = [("bench.window", 0, 100 * ms), ("bench.launch", 15 * ms, 60 * ms),
            ("bench.launch", 60 * ms, 100 * ms), ("bench.plan", 70 * ms, 80 * ms)]
    dev = {"/device:TPU:0": [
        ("fused_em_tick", 20 * ms, 30 * ms),
        ("fusion.1", 25 * ms, 40 * ms),       # overlaps the kernel: counted once in busy
        ("fused_em_tick", 90 * ms, 110 * ms),  # runs past the window: clipped
    ]}
    s = trace_reduce.reduce_events(dev, host)
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.030)
    assert s.idle_share() == pytest.approx(0.7)
    assert s.kernel("fused_em_tick") == (2, pytest.approx(0.020))
    # Gaps: [40, 90) is named by its midpoint, 65 ms, in the second launch;
    # [0, 20) by 10 ms, before the first; none after the clipped kernel.
    assert s.gaps == [("launch", pytest.approx(0.050)),
                      ("outside any span", pytest.approx(0.020))]
    assert s.breakdown()["device_ops"][0][0] == "fused_em_tick"


def test_window_span_required():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({"/device:TPU:0": [("op", 0, 1)]}, [])


def test_peaks_of_unknown_device_is_an_error():
    assert trace_reduce.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace_reduce.peaks_for("cpu")


def test_recorded_chip_trace(tmp_path):
    path = tmp_path / "trace.xplane.pb"
    with gzip.open(RECORDED) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    s = trace_reduce.reduce_events(*trace_reduce.read_xplane(path))
    # Three launches of four serial solves, 27 MAP iterations each; the
    # window also holds the host's result assembly between launches.
    assert s.window_s == pytest.approx(0.151556075)
    assert s.busy_s == pytest.approx(0.079604551)
    assert s.kernel("fused_em_tick") == (270, pytest.approx(0.027846582))
    assert all(not op.startswith("%while") for op in s.ops)
    assert s.breakdown()["device_ops"][0][0] == "%fusion.16"  # the label gather
    assert {g[0] for g in s.gaps} <= {"launch", "outside any span"}
