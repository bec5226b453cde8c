"""Program spans on the device trace's clock: self time, the naming of
idle gaps, a recorded chip trace without program spans, the per-layer
readers of the recorder's solver phases, and one small run on the CPU
with the program's spans bridged into the capture (the CPU backend
writes no device plane, so that run names no gap)."""

import gzip
import os
import shutil
import time

import pytest

import devtrace
import harness
import spans

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tp5k_serial.xplane.pb.gz")


def events(host, ops):
    return {"host": [("bench:window", 0, 100)] + host,
            "devices": {"/device:TPU:0": {"ops": ops, "modules": []}}}


def test_self_time_leaves_out_children_on_the_same_line():
    program = [("solve", 1, 10, 50), ("fetch-wait", 1, 20, 40),
               ("predicate", 2, 0, 90), ("predicate:decode", 2, 0, 5)]
    got = sorted(spans.self_times(program))
    assert got == sorted([("solve", 10, 20), ("solve", 40, 50), ("fetch-wait", 20, 40),
                          ("predicate", 5, 90), ("predicate:decode", 0, 5)])


def test_gap_under_fetch_wait_is_named_by_it_and_a_bare_gap_keeps_the_client():
    # Device busy 0-10, 45-60 and 95-100: idle 10-45 (fetch-wait 15-40
    # inside solve on the dispatcher; the handler's root covers it too),
    # 60-95 (no program span: the client's executor call).
    ev = events([("predicate:executor", 62, 30)],
                [("%a", 0, 10), ("%b", 45, 15), ("%c", 95, 5)])
    program = [("predicate", 1, 5, 50), ("solve", 2, 12, 42), ("fetch-wait", 2, 15, 40)]
    gaps = spans.label_gaps(ev, program)
    assert gaps == [["fetch-wait", pytest.approx(35e-9)],
                    ["predicate:executor", pytest.approx(35e-9)]]
    red = spans.reduce_program(ev, program)
    assert red["span_ms"]["fetch-wait"] == pytest.approx(25e-6)
    # Idle 70 ns, of which 10-45 is under program spans: 35 outside.
    assert red["idle_outside_program_pct"] == pytest.approx(50.0)
    # Idle by span: 10-12 root alone, 12-15 solve, 15-40 fetch-wait,
    # 40-42 solve, 42-45 root alone, 60-95 outside.
    assert red["idle_by_span_pct"] == pytest.approx(
        {"outside program": 50.0, "fetch-wait": 25 / 70 * 100, "solve": 5 / 70 * 100,
         "predicate": 5 / 70 * 100})


def test_requests_split_by_role_and_the_solve_wait_splits_on_its_line():
    program = [
        # Handler line 1: a driver request 0-100, an executor one 200-260.
        ("predicate", 1, 0, 100), ("predicate:decode", 1, 0, 4), ("predicate:encode", 1, 90, 100),
        ("predicate", 1, 200, 260), ("predicate:decode", 1, 200, 202),
        ("predicate:encode", 1, 250, 251),
        # Dispatcher line 2: the driver's window, then the executor's lookup.
        ("featurize", 2, 5, 20), ("solve-dispatch", 2, 23, 30), ("solve", 2, 60, 61),
        ("fetch-wait", 2, 60, 61), ("commit", 2, 70, 85),
        ("select-node", 2, 210, 240), ("executor-lookup", 2, 212, 230),
    ]
    roles = spans.by_role(program, 0, 1000)
    assert roles["driver"] == pytest.approx(
        {"predicate": 100e-6, "predicate:decode": 4e-6, "predicate:encode": 10e-6})
    assert roles["executor"] == pytest.approx(
        {"predicate": 60e-6, "predicate:decode": 2e-6, "predicate:encode": 1e-6})
    assert spans.solve_split(program, 0, 1000) == pytest.approx(
        {"prep": 3e-6, "launch": 7e-6, "wait": 30e-6, "fetch": 1e-6, "rebuild": 9e-6})
    assert spans.solve_split(program, 100, 1000) is None


def test_root_names_a_gap_only_where_nothing_else_covers_it():
    ev = events([], [("%a", 0, 10), ("%b", 40, 60)])
    program = [("predicate", 1, 0, 100), ("predicate:decode", 1, 0, 12)]
    # The one gap, 10-40: decode covers 10-12, the root's self time the
    # rest; decode still names it.
    assert spans.label_gaps(ev, program) == [["predicate:decode", pytest.approx(30e-9)]]
    program = [("predicate", 1, 0, 100)]
    assert spans.label_gaps(ev, program) == [["predicate", pytest.approx(30e-9)]]


def test_a_trace_without_program_spans_keeps_the_client_labels(tmp_path):
    path = tmp_path / "t.xplane.pb"
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    program = spans.load_program(str(path))
    assert program == []
    ev = devtrace.load(str(path))
    assert spans.label_gaps(ev, program) == devtrace.reduce(ev, "_window_blob")["idle_gaps"]


@pytest.mark.parametrize("metric", ["dispatch_ms", "fetch_wait_ms"])
def test_solver_phase_readers(metric):
    phases = [{"solve_ms": 5.0, metric: 1.0}, {"solve_ms": 4.0, metric: 3.0}, {"solve_ms": 2.0}]
    assert harness.read_metric(metric, {"phases": phases}) == pytest.approx(2.0)
    # A program that records no such phase: nothing to read.
    assert harness.read_metric(metric, {"phases": [{"solve_ms": 5.0}]}) is None
    assert harness.read_metric(metric, {"phases": []}) is None


@pytest.mark.parametrize("metric,span", [("decode_ms", "predicate:decode"),
                                         ("encode_ms", "predicate:encode")])
def test_transport_readers_take_the_driver_calls_mean(metric, span):
    program = {"by_role_ms": {"driver": {span: 1.5}, "executor": {span: 0.5}}}
    assert harness.read_metric(metric, {"program": program}) == 1.5
    assert harness.read_metric(metric, {"program": {"by_role_ms": {}}}) is None


def test_traced_run_reads_the_program_spans_on_the_cpu():
    """A `--trace 1` run captures through the program: the per-layer
    readers of its spans and of the recorder read, those of the device
    (no device plane on the CPU) read nothing, and the run is correct."""
    res = harness.run_cell("tp5k-serial", 2**31 + 41, 2.0, True, t_start=time.time(),
                           require_tpu=False, overrides={"nodes": 120})
    assert res["correct"], res["checks"]
    got = res["metrics"]
    for name in ("decode_ms", "encode_ms", "featurize_ms", "commit_ms", "solve_wait_ms",
                 "dispatch_ms", "fetch_wait_ms"):
        assert got[name]["value"] > 0, name
    assert not {"window_device_ms", "window_roofline_pct", "device_idle_pct"} & set(got)
    assert "busy_s" not in res["device"] and "breakdown" not in res


def test_small_run_names_gaps_by_program_spans():
    line = spans.run_seed(harness, "tp5k-serial", 2**31 + 5, 2.0, require_tpu=False,
                          overrides={"nodes": 120})
    assert line["correct"]
    for name in ("predicate", "predicate:decode", "predicate:encode", "solve-dispatch",
                 "fetch-wait", "executor-lookup", "featurize", "commit"):
        assert line["span_ms"][name] > 0, name
    assert line["handoff_ms"] is not None and line["handoff_ms"] >= 0
    assert line["recorder_dispatch_ms"] is not None
    assert line["solve_account_ms"] <= line["solve_wait_ms"] + 0.5
    assert line["executor_account_ms"] <= line["executor_p50_ms"]
    # The CPU backend writes no device plane, so no gap to name here.
    assert line["idle_gaps"] == [] and line["device_idle_pct"] is None
