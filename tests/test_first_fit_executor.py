"""The single-executor first-fit program (`_first_fit_executor_blob`) and
`PlacementSolver.first_fit_executor` against the full gang pack.

A lone executor (a 1-executor gang with no driver) takes the first node in
tightly-pack's executor order with room for one executor. The first-fit
program finds that node by masked min-reductions; the gang pack
(`_pack_blob` with a zero driver and count 1) by two sorts and a fill.
Both rank zones with `zone_ranks`, checked here against int64 sums on the
host.
Both, and the host fallback, must give the same row on every input: ties
in memory and CPU broken by name rank, label-ranked and unranked nodes,
one and four zones, unschedulable, not-ready, padding and over-committed
rows, no candidate, no room, and candidates from one zone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_scheduler_tpu.core import solver as solver_mod
from spark_scheduler_tpu.core.fallback import GreedyFallbackSolver
from spark_scheduler_tpu.core.prune import zone_ranks_host
from spark_scheduler_tpu.core.solver import (
    PlacementSolver,
    _first_fit_executor_blob,
    _pack_blob,
)
from spark_scheduler_tpu.faults import DegradedModeController
from spark_scheduler_tpu.metrics import MetricRegistry
from spark_scheduler_tpu.models.cluster import ClusterTensors
from spark_scheduler_tpu.models.kube import Node, ZONE_LABEL
from spark_scheduler_tpu.models.resources import (
    CPU_DIM,
    INT32_INF,
    MEM_DIM,
    Resources,
)
from spark_scheduler_tpu.ops.sorting import zone_ranks
from spark_scheduler_tpu.observability.telemetry import (
    EXECUTOR_FIRST_FITS,
    SOLO_PACKS,
    SolverTelemetry,
)

N_REAL, N_PAD = 60, 64
DRAWS = 12
SCENARIOS = ("mixed", "ties", "unranked", "no-candidates", "no-room", "one-zone")


class _Registry:
    @staticmethod
    def name_of(i: int) -> int:
        return i


class _Stub:
    registry = _Registry()


def _draw(rng: np.random.Generator, zones: int, scenario: str):
    """A padded cluster, an executor request and a candidate mask."""
    n = N_PAD
    valid = np.arange(n) < N_REAL
    # Few distinct values, so rows tie on memory and CPU and the name rank
    # decides; some rows over-committed (below zero in a dimension).
    cpu = rng.choice([-2000, 0, 2000, 4000, 6000], n)
    mem = rng.choice([-(4 << 20), 0, 4 << 20, 8 << 20, 16 << 20], n)
    gpu = rng.choice([-1, 0, 0, 0, 1], n)
    if scenario == "ties":
        cpu = np.full(n, 4000)
        mem = np.full(n, 8 << 20)
        gpu = np.zeros(n, int)
    avail = np.stack([cpu, mem, gpu], axis=1).astype(np.int32)
    label = rng.choice([0, 1, 2, INT32_INF, INT32_INF], n).astype(np.int32)
    if scenario in ("unranked", "ties"):
        label = np.full(n, INT32_INF, np.int32)
    zone = rng.integers(0, zones, n).astype(np.int32)
    cluster = ClusterTensors(
        available=avail,
        schedulable=np.abs(avail) + 1000,
        zone_id=zone,
        name_rank=rng.permutation(n).astype(np.int32),
        label_rank_driver=rng.choice([0, 1, INT32_INF], n).astype(np.int32),
        label_rank_executor=label,
        unschedulable=rng.random(n) < 0.15,
        ready=rng.random(n) > 0.15,
        valid=valid,
    )
    ereq = np.array([2000, 4 << 20, int(rng.random() < 0.2)], np.int32)
    cand = rng.random(n) < 0.8
    if scenario == "no-candidates":
        cand = np.zeros(n, bool)
    elif scenario == "no-room":
        ereq = np.array([64000, 4 << 20, 0], np.int32)
    elif scenario == "one-zone":
        cand = cand & (zone == rng.integers(0, zones))
    return cluster, ereq, cand


_zone_ranks = jax.jit(zone_ranks, static_argnums=(2,))


def _host_zone_ranks(cluster, cand, num_zones):
    """Zone ranks from exact int64 per-zone sums of the candidate rows."""
    rows = cand & cluster.valid
    zone = cluster.zone_id[rows]
    avail = cluster.available[rows].astype(np.int64)
    mem = np.bincount(zone, avail[:, MEM_DIM], num_zones).astype(np.int64)
    cpu = np.bincount(zone, avail[:, CPU_DIM], num_zones).astype(np.int64)
    present = np.bincount(zone, minlength=num_zones) > 0
    return zone_ranks_host(mem, cpu, present)


def _gang_pack(cluster, ereq, cand, num_zones):
    blob = np.asarray(
        _pack_blob(
            cluster, jnp.zeros(3, jnp.int32), jnp.asarray(ereq), jnp.int32(1),
            jnp.asarray(cand), jnp.asarray(cand),
            fill="tightly-pack", emax=8, num_zones=num_zones,
        )
    )
    return int(blob[2]), bool(blob[1])


def _host_fallback(cluster, ereq, cand):
    p = GreedyFallbackSolver(_Stub()).pack(
        "tightly-pack", cluster, Resources.zero(), Resources.from_array(ereq),
        1, cand, cand,
    )
    return p.executor_nodes[0] if p.has_capacity else -1


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("zones,num_zones", [(1, 2), (4, 4)])
@pytest.mark.parametrize("seed", [5, 2**31 + 11])
def test_first_fit_matches_the_gang_pack_and_the_host_fallback(
    seed, zones, num_zones, scenario
):
    rng = np.random.default_rng(seed)
    found = 0
    for _ in range(DRAWS):
        cluster, ereq, cand = _draw(rng, zones, scenario)
        np.testing.assert_array_equal(
            _zone_ranks(cluster, cand, num_zones),
            _host_zone_ranks(cluster, cand, num_zones),
        )
        row = int(
            _first_fit_executor_blob(cluster, ereq, cand, num_zones=num_zones)
        )
        want_row, has_capacity = _gang_pack(cluster, ereq, cand, num_zones)
        assert row == want_row
        # With a zero driver the gang's has_capacity is "a row fits".
        assert has_capacity == (row >= 0)
        assert _host_fallback(cluster, ereq, cand) == row
        found += row >= 0
    if scenario in ("no-candidates", "no-room"):
        assert found == 0
    else:
        assert found > 0


# ------------------------------------------------------------ the solver


def _solver(degraded=None):
    solver = PlacementSolver(use_native=False)
    solver.telemetry = SolverTelemetry(MetricRegistry())
    solver.degraded = degraded
    rng = np.random.default_rng(3)
    nodes = [
        Node(
            name=f"n{i:02d}",
            allocatable=Resources.from_quantities(
                str(int(rng.choice([4, 8, 16]))), f"{int(rng.choice([16, 32]))}Gi"
            ),
            labels={ZONE_LABEL: f"zone-{'abc'[i % 3]}"},
        )
        for i in range(20)
    ]
    usage = {
        n.name: Resources.from_quantities(str(i % 4), f"{(i * 3) % 8}Gi")
        for i, n in enumerate(nodes)
    }
    tensors = solver.build_tensors(nodes, usage, {})
    return solver, tensors, [n.name for n in nodes]


def _counter(solver, name) -> float:
    snap = solver.telemetry.registry.snapshot()
    return sum(e["value"] for e in snap.get(name, []))


EXECUTOR = Resources.from_quantities("2", "4Gi")


@pytest.mark.parametrize("take", [20, 7, 0])
def test_first_fit_executor_answers_as_pack_and_keeps_the_mask_on_device(take):
    solver, tensors, names = _solver()
    names = names[:take]
    cand = solver.candidate_mask(tensors, names)
    want = solver.pack(
        "tightly-pack", tensors, Resources.zero(), EXECUTOR, 1, names,
        domain_mask=cand,
    )
    packs = _counter(solver, SOLO_PACKS)
    got = solver.first_fit_executor(tensors, EXECUTOR, list(names))
    assert got == (want.executor_nodes[0] if want.has_capacity else None)
    assert (got is None) == (take == 0)
    assert solver.last_solve_info["path"] == "xla-first-fit"
    dev = solver._dev_mask_memo.get(id(cand))[1]
    assert solver.first_fit_executor(tensors, EXECUTOR, list(names)) == got
    # The same candidate mask object, so the same device copy: no re-ship.
    assert solver._dev_mask_memo.get(id(cand))[1] is dev
    assert len(solver._dev_mask_memo) == 1
    assert _counter(solver, EXECUTOR_FIRST_FITS) == 2
    assert _counter(solver, SOLO_PACKS) == packs


def _halted(*args, **kwargs):
    raise jax.errors.JaxRuntimeError("INTERNAL: device halted")


def test_first_fit_device_failure_serves_the_same_node_degraded(monkeypatch):
    """A slot failure the classifier accepts serves this one decision from
    the host fallback, with the node the device gives, and engages the
    degraded controller."""
    solver, tensors, names = _solver(DegradedModeController(policy="greedy"))
    want = solver.first_fit_executor(tensors, EXECUTOR, names)
    assert want is not None
    monkeypatch.setattr(solver_mod, "_first_fit_executor_blob", _halted)
    assert solver.first_fit_executor(tensors, EXECUTOR, names) == want
    assert solver.last_solve_info["path"] == "greedy-fallback"
    assert solver.degraded.engagements == 1
    assert solver.degraded.fallback_decisions == 1
    assert _counter(solver, EXECUTOR_FIRST_FITS) == 1


@pytest.mark.parametrize(
    "error,degraded",
    [
        (ValueError("a programming error"), True),
        (jax.errors.JaxRuntimeError("INTERNAL: device halted"), False),
    ],
)
def test_first_fit_unclassified_or_unhandled_failure_propagates(
    monkeypatch, error, degraded
):
    """An error the classifier refuses propagates; so does a device
    failure with no degraded controller wired."""
    controller = DegradedModeController(policy="greedy") if degraded else None
    solver, tensors, names = _solver(controller)

    def fail(*args, **kwargs):
        raise error

    monkeypatch.setattr(solver_mod, "_first_fit_executor_blob", fail)
    with pytest.raises(type(error)):
        solver.first_fit_executor(tensors, EXECUTOR, names)
    if controller is not None:
        assert controller.engagements == 0
