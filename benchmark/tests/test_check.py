"""The check that decides `correct`: sound runs pass it, and every
control and planted fault fails it. The harness's look for a chip is
skipped; everything else of a run is driven, at a small cluster."""

import time

import pytest

import faults
import harness

# A small cluster, and a short queue so that a CPU run sees many cycles.
SMALL = {"nodes": 120, "traffic": {"pending_target": 8}}
CELLS = ["tp5k-serial", "tp5k-fifo-backlog", "sazmf10k-fifo-backlog", "sazmf10k-serial"]


def run(cell, seed, overrides=None, tamper=None):
    return harness.run_cell(
        cell, seed, 4.0, False, t_start=time.time(), require_tpu=False,
        overrides={**SMALL, **(overrides or {})}, tamper=tamper,
    )


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = run(cell, 2**31 + 7)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


CONTROL_CASES = [
    ("tp5k-fifo-backlog", "fifo-off"),
    ("sazmf10k-fifo-backlog", "fifo-off"),
    ("tp5k-serial", "executor-slot-order"),
    ("tp5k-serial", "distribute-evenly"),
    # Not executor-slot-order here: minimal fragmentation puts an app's
    # executors on one node, where every slot order binds alike.
    ("sazmf10k-serial", "distribute-evenly"),
]


@pytest.mark.parametrize("cell,control", CONTROL_CASES)
def test_control_is_not_correct(cell, control):
    overrides, tamper = faults.CONTROLS[control]
    res = run(cell, 2**31 + 11, overrides, tamper)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_not_correct(cell, fault):
    overrides, tamper = faults.FAULTS[fault]
    res = run(cell, 2**31 + 13, overrides, tamper)
    assert not res["correct"], res["checks"]



def test_run_served_off_the_device_is_not_correct(monkeypatch):
    """Every dispatch of the window program fails as a lost device would,
    and degraded mode serves the same answers from the host: the run has
    to read not correct all the same, since its times are not the
    device's."""

    def lost(*_a, **_k):
        raise ConnectionError("planted: the device slot is lost")

    for name in ("_window_blob", "_window_blob_donated"):
        monkeypatch.setattr(f"spark_scheduler_tpu.core.solver.{name}", lost)
    res = run("tp5k-serial", 2**31 + 17)
    checks = {k: v["value"] for k, v in res["checks"].items()}
    assert not res["correct"], checks
    assert checks["degraded_engagements"] > 0 and checks["windows_off_device"] > 0, checks
    assert checks["driver_mismatches"] == 0, checks


def _compiles_every_window(served):
    import itertools

    import jax
    import jax.numpy as jnp

    solver = served.app.solver
    fetch = solver.pack_window_fetch
    sizes = itertools.count(1)

    def fetch_and_compile(handle):
        jax.jit(lambda x: x + 1)(jnp.zeros(next(sizes))).block_until_ready()
        return fetch(handle)

    solver.pack_window_fetch = fetch_and_compile


def test_run_that_compiles_in_its_window_is_not_correct():
    # One warm-up cycle at the least, so that warm-up gives up soon.
    res = run("tp5k-serial", 2**31 + 19, {"traffic": {"pending_target": 8, "warmup_cycles": 1}},
              _compiles_every_window)
    checks = {k: v["value"] for k, v in res["checks"].items()}
    assert not res["correct"], checks
    assert checks["compiles_in_window"] > 0 and checks["traces_in_window"] > 0, checks
    assert checks["driver_mismatches"] == 0, checks
