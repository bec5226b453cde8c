"""A configuration's semantics, found by name: `static_gang` drives the
cells exactly as the harness did before it was split out (the log and
the replay's counts pinned from that harness), the apiserver operations
a richer world needs, and a toy semantics, readers of a new span and of
a counter, and a cell that uses them, added as new files only to a copy
of the benchmark."""

import collections
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import harness

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "toy")
SMALL = {"nodes": 120, "traffic": {"pending_target": 8}}
CYCLES = 6
SEEDS = (2**31 + 23, 2**33 + 29)
SERIAL = {"drivers_checked": 48, "executors_checked": 384, "denials": 0, "admits": 48}
BACKLOG = {"drivers_checked": 48, "executors_checked": 48, "denials": 42, "admits": 6}
# (log digest, log length, replay counts) after CYCLES cycles at SMALL,
# as the harness before semantics modules gave them.
PINNED = {
    ("tp5k-serial", SEEDS[0]): ("26e56c5043bb797a", 528, {**SERIAL, "zone_ties": 0}),
    ("tp5k-serial", SEEDS[1]): ("3d22f42e6ac44408", 528, {**SERIAL, "zone_ties": 0}),
    ("tp5k-fifo-backlog", SEEDS[0]): ("03889feb0e6bbba4", 107, {**BACKLOG, "zone_ties": 0}),
    ("tp5k-fifo-backlog", SEEDS[1]): ("68fcf92a38f39855", 107, {**BACKLOG, "zone_ties": 0}),
    ("sazmf10k-fifo-backlog", SEEDS[0]): ("d3d2de8bc3385356", 107, {**BACKLOG, "zone_ties": 0}),
    ("sazmf10k-fifo-backlog", SEEDS[1]): ("1592ff4d1b714e27", 107, {**BACKLOG, "zone_ties": 0}),
    ("sazmf10k-serial", SEEDS[0]): ("ac9666431a69dda8", 528, {**SERIAL, "zone_ties": 5}),
    ("sazmf10k-serial", SEEDS[1]): ("47d882358ccfd8ad", 528, {**SERIAL, "zone_ties": 5}),
}
MISMATCHES = {"driver_mismatches": 0, "executor_mismatches": 0, "denials_bad_map": 0,
              "reference_overcommitted_nodes": 0}


def boot(cell, seed, overrides):
    """The cell's world, the program booted on it, and its generator."""
    import served as S

    _, _, config, traffic = harness.load_cell(cell)
    config["nodes"] = overrides["nodes"]
    traffic.update(overrides.get("traffic", {}))
    sem = harness.semantics_of(config)
    world = sem.build_world(config, traffic, seed, time.time())
    served = S.Served(dict(config["install"]), config.get("runtime", {}),
                      world.nodes, world.pods, world.apps)
    gen = sem.make_generator(world, config, traffic, served, None)
    return sem, world, served, gen


@pytest.mark.parametrize("cell,seed", sorted(PINNED))
def test_static_gang_logs_and_replays_as_before(cell, seed):
    sem, world, served, gen = boot(cell, seed, SMALL)
    try:
        for _ in range(CYCLES):
            gen.cycle()
    finally:
        gen.close()
        served.stop()
    digest, length, counts = PINNED[(cell, seed)]
    assert (hashlib.sha256(repr(gen.log).encode()).hexdigest()[:16], len(gen.log)) == (digest, length)
    assert sem.replay(world, gen.log, gen.bodies) == {**MISMATCHES, **counts}


def test_served_deletes_a_pod_and_counts():
    _, world, served, gen = boot("tp5k-serial", 2**31 + 31, {"nodes": 120})
    try:
        for _ in range(2):
            gen.cycle()
        before = served.counters()
        gen.cycle()  # one driver and its 8 executors
        after = served.counters()
        delta = {k: v - before.get(k, 0) for k, v in after.items()}
        assert delta["foundry.spark.scheduler.recorder.total_recorded"] == 9
        assert sum(v for k, v in delta.items()
                   if k.startswith("foundry.spark.scheduler.solver.window.dispatches;")) == 1
        assert delta["foundry.spark.scheduler.predicate.batcher.requests_served"] == 9
        app, pods = gen.running[-1]
        assert served.soft_reservation(app) == {}
        assert served.backend.get("pods", served.namespace, pods[1]) is not None
        served.delete_pod(pods[1])
        assert served.backend.get("pods", served.namespace, pods[1]) is None
        assert served.backend.get("pods", served.namespace, pods[2]) is not None
        # The app still holds its reservation, one executor fewer bound.
        inv = served.invariants(gen.expected_reservations())
        assert inv["apps_off_reservation"] == 0
    finally:
        gen.close()
        served.stop()


TOY_RUNS = r"""
import json, os, sys, time
ROOT = sys.argv[1]
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, ROOT)
import harness
from spark_scheduler_tpu.tracing import tracer


def tamper(served):
    # A span the program does not have yet, as a later change would add.
    solver = served.app.solver
    fetch = solver.pack_window_fetch

    def spanned(handle):
        with tracer().span("toy-fetch"):
            return fetch(handle)

    solver.pack_window_fetch = spanned


small = {"nodes": 120}
for run, over, trace in (("sound", {}, False), ("fault", {"toy_fault": True}, False),
                         ("traced", {}, True)):
    res = harness.run_cell("toy-serial", 2**31 + 37, 3.0, trace, t_start=time.time(),
                           require_tpu=False, overrides={**small, **over}, tamper=tamper)
    print("TOY " + json.dumps({"run": run, "correct": res["correct"],
                               "checks": {k: v["value"] for k, v in res["checks"].items()},
                               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
          flush=True)
"""


def test_a_semantics_and_readers_plug_in_as_new_files(tmp_path):
    """A copy of the benchmark gains a configuration with its own
    semantics, a cell and two readers, by new files and new entries in
    BENCHMARK.json alone; its extra check passes a sound run and fails
    a planted fault, and the readers see the new span and counter."""
    root = str(tmp_path)
    repo = os.path.dirname(os.path.dirname(HERE))
    shutil.copytree(os.path.join(repo, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(repo, "spark_scheduler_tpu"), os.path.join(root, "spark_scheduler_tpu"))
    added = {"semantics/toy_gang.py", "metrics/toy_fetch_ms.py", "metrics/toy_records.py"}
    for rel in added:
        dst = os.path.join(root, "benchmark", rel)
        assert not os.path.exists(dst)
        shutil.copy(os.path.join(TOY, rel), dst)
    with open(os.path.join(repo, "benchmark", "configs", "spark-tp-5k.json")) as f:
        config = {**json.load(f), "name": "toy-gang", "semantics": "toy_gang"}
    with open(os.path.join(root, "benchmark", "configs", "toy-gang.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy-gang", "source": "a test", "reduced": [],
                             "file": "benchmark/configs/toy-gang.json", "why": "a test"})
    bench["workloads"].append({"name": "toy-serial", "config": "toy-gang", "traffic": "serial",
                               "chips": 1, "why": "a test"})
    for name in ("toy_fetch_ms", "toy_records"):
        bench["per_layer"].append({"name": name, "unit": "ms", "better": "lower",
                                   "source": "program_span", "layer": "toy",
                                   "moves": "driver_p50_ms", "workloads": ["toy-serial"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", TOY_RUNS, root], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    runs = {r["run"]: r for r in (json.loads(line[4:]) for line in p.stdout.splitlines()
                                  if line.startswith("TOY "))}
    trace_logs = [json.loads(line) for line in p.stdout.splitlines()
                  if line.startswith('{"') and '"phase": "trace"' in line]
    sound, fault, traced = runs["sound"], runs["fault"], runs["traced"]
    assert sound["correct"] and sound["checks"]["apps_short_of_executors"] == 0, sound
    assert not fault["correct"], fault
    assert fault["checks"]["apps_short_of_executors"] > 0, fault
    assert collections.Counter(v for k, v in fault["checks"].items()
                               if k != "apps_short_of_executors") == {0: 10}, fault
    assert traced["correct"], traced
    assert traced["metrics"]["toy_fetch_ms"] > 0, traced
    assert traced["metrics"]["toy_records"] == trace_logs[-1]["records"] > 0, (traced, trace_logs)
