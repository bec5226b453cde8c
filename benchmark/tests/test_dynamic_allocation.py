"""The dynamic-allocation cell at a small size on the CPU: sound runs
replay correct with every check at 0, and each planted fault and the
control fail `correct` through the check they target."""

import time

import pytest

import harness

SMALL = {"nodes": 150, "traffic": {"warmup_cycles": 2}}
CELL = "da5k-rampup"


def _run(seed, overrides=None, tamper=None, seconds=2.0):
    res = harness.run_cell(CELL, seed, seconds, False, t_start=time.time(), require_tpu=False,
                           overrides={**SMALL, **(overrides or {})}, tamper=tamper)
    return res, {k: v["value"] for k, v in res["checks"].items()}


def _semantics():
    _, _, config, _ = harness.load_cell(CELL)
    return harness.semantics_of(config)


@pytest.mark.parametrize("seed", [2**31 + 41, 2**33 + 43])
def test_sound_runs_replay_correct(seed):
    res, checks = _run(seed)
    assert res["correct"], checks
    assert set(checks) >= {"extra_executor_mismatches", "soft_reservation_mismatches",
                           "compaction_mismatches"}
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault,targets", [
    ("compaction-skipped", {"compaction_mismatches"}),
    ("soft-write-dropped", {"soft_reservation_mismatches", "overcommitted_nodes"}),
    ("extra-next-node", {"extra_executor_mismatches"}),
])
def test_planted_faults_fail_through_their_check(fault, targets):
    overrides, tamper = _semantics().FAULTS[fault]
    res, checks = _run(2**31 + 47, overrides, tamper)
    assert not res["correct"], checks
    assert any(checks[t] > 0 for t in targets), checks


def test_distribute_evenly_control_fails_driver_placement():
    overrides, tamper = _semantics().CONTROLS["distribute-evenly"]
    res, checks = _run(2**31 + 53, overrides, tamper)
    assert not res["correct"], checks
    assert checks["driver_mismatches"] > 0, checks
