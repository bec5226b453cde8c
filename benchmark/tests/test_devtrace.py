"""The trace reduction, on synthetic events and on a short trace recorded
on a TPU v5e (tp5k-serial, 0.3 s of its window)."""

import gzip
import os
import shutil

import pytest

import devtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "tp5k_serial.xplane.pb.gz")


def test_union_merges_overlaps_and_nesting():
    assert devtrace.union([(5, 9), (0, 2), (1, 3), (6, 7)]) == [(0, 3), (5, 9)]
    assert devtrace.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_reduce_on_synthetic_events():
    events = {
        "host": [("bench:window", 0, 100), ("predicate:driver", 10, 35),
                 ("predicate:executor", 60, 30)],
        "devices": {"/device:TPU:0": {
            "ops": [("%while.1", 20, 20), ("%window_pack_pallas.3", 25, 10), ("%fusion", 70, 5)],
            "modules": [("jit__window_blob_pallas(1)", 20, 20), ("jit__other(2)", 70, 5)],
        }},
    }
    r = devtrace.reduce(events, "_window_blob")
    assert r["busy_s"] == pytest.approx(25e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["program_ms_per_run"] == pytest.approx(20e-6)
    assert r["program_runs"] == 1
    # Gaps, longest first: 40-70 (driver 40-45, executor 60-70), 75-100
    # (executor 75-90), 0-20 (driver 10-20).
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx([30e-9, 25e-9, 20e-9])
    assert [g[0] for g in r["idle_gaps"]] == ["predicate:executor", "predicate:executor",
                                              "predicate:driver"]


def test_reduce_on_a_recorded_chip_trace(tmp_path):
    path = tmp_path / "t.xplane.pb"
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    events = devtrace.load(str(path))
    assert list(events["devices"]) == ["/device:TPU:0"]
    r = devtrace.reduce(events, "_window_blob")
    lo, hi = next((s, s + d) for n, s, d in events["host"] if n == "bench:window")
    runs = [d for n, s, d in events["devices"]["/device:TPU:0"]["modules"]
            if "_window_blob" in n and lo <= s < hi]
    assert runs and r["program_runs"] == len(runs)
    assert r["program_ms_per_run"] == pytest.approx(sum(runs) / len(runs) / 1e6)
    assert 0 < r["busy_s"] < r["window_s"] <= (hi - lo) / 1e9 + 1e-12
    assert any("window_blob" in name for name, _ in r["device_ops"])
