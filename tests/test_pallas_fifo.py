"""Golden parity: the Pallas queue kernel == the XLA scan, decision for
decision.

`ops/pallas_fifo.fifo_pack_pallas` re-derives the executor fills as
iterative masked-argmin placement and runs the whole FIFO scan inside one
Mosaic kernel; these tests pin it bit-for-bit to `batched_fifo_pack` (which
is itself oracle-parity-tested in test_batched.py) across randomized
clusters and queues, in interpreter mode on the CPU suite. The same
comparison runs compiled on real silicon in hack/tpu_parity_smoke.py.
"""

import numpy as np
import pytest

from spark_scheduler_tpu.models.cluster import ClusterTensors
from spark_scheduler_tpu.ops.batched import batched_fifo_pack, make_app_batch
from spark_scheduler_tpu.ops.pallas_fifo import (
    PALLAS_FILLS,
    PALLAS_SINGLE_AZ,
    fifo_pack_auto,
    fifo_pack_pallas,
)

from tests.test_packing_golden import random_cluster

EMAX = 8
NUM_ZONES = 4


def random_apps(rng, b, pad_to=None):
    driver = rng.integers(1, 6, size=(b, 3)).astype(np.int32)
    driver[:, 2] = rng.integers(0, 2, size=b)
    execs = rng.integers(1, 8, size=(b, 3)).astype(np.int32)
    execs[:, 2] = rng.integers(0, 2, size=b)
    counts = rng.integers(0, EMAX + 3, size=b).astype(np.int32)  # incl. too-big
    skip = rng.random(b) < 0.3
    return make_app_batch(driver, execs, counts, pad_to=pad_to, skippable=skip)


def assert_same(got, want):
    for field in ("driver_node", "executor_nodes", "admitted", "packed",
                  "available_after"):
        g = np.asarray(getattr(got, field))
        w = np.asarray(getattr(want, field))
        np.testing.assert_array_equal(g, w, err_msg=field)


@pytest.mark.parametrize("fill", sorted(PALLAS_FILLS))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pallas_matches_xla_scan(fill, seed):
    rng = np.random.default_rng(seed)
    c = random_cluster(rng, 37, num_zones=NUM_ZONES)
    apps = random_apps(rng, 9, pad_to=12)
    want = batched_fifo_pack(c, apps, fill=fill, emax=EMAX, num_zones=NUM_ZONES)
    got = fifo_pack_pallas(
        c, apps, fill=fill, emax=EMAX, num_zones=NUM_ZONES, interpret=True
    )
    assert_same(got, want)


@pytest.mark.parametrize("fill", sorted(PALLAS_FILLS))
def test_pallas_strict_fifo_blocking(fill):
    """A huge non-skippable gang blocks everything behind it in both paths."""
    rng = np.random.default_rng(7)
    c = random_cluster(rng, 24, num_zones=NUM_ZONES)
    driver = np.ones((4, 3), np.int32)
    execs = np.ones((4, 3), np.int32)
    execs[1] = 1000  # unpackable
    counts = np.array([2, 8, 2, 2], np.int32)
    apps = make_app_batch(driver, execs, counts,
                          skippable=np.zeros(4, bool))
    want = batched_fifo_pack(c, apps, fill=fill, emax=EMAX, num_zones=NUM_ZONES)
    got = fifo_pack_pallas(
        c, apps, fill=fill, emax=EMAX, num_zones=NUM_ZONES, interpret=True
    )
    assert_same(got, want)
    assert not np.asarray(want.admitted)[2:].any()


def test_pallas_negative_availability_and_zero_count():
    """Overcommitted nodes (negative availability) and zero-executor gangs."""
    rng = np.random.default_rng(11)
    c = random_cluster(rng, 20, num_zones=NUM_ZONES)
    avail = np.asarray(c.available).copy()
    avail[3] = -5
    avail[7, 0] = -1
    import dataclasses

    c = dataclasses.replace(c, available=avail)
    driver = np.ones((3, 3), np.int32)
    execs = np.ones((3, 3), np.int32)
    counts = np.array([0, 3, 0], np.int32)
    apps = make_app_batch(driver, execs, counts)
    for fill in sorted(PALLAS_FILLS):
        want = batched_fifo_pack(c, apps, fill=fill, emax=EMAX,
                                 num_zones=NUM_ZONES)
        got = fifo_pack_pallas(
            c, apps, fill=fill, emax=EMAX, num_zones=NUM_ZONES, interpret=True
        )
        assert_same(got, want)


def test_pallas_sublane_folded_layout_matches():
    """Clusters at/above the fold threshold run the [8, cols] sublane
    layout; its decisions must equal the XLA scan exactly like the flat
    row's. The threshold is patched down so interpret mode stays fast —
    a fresh node count keeps the jit cache from reusing a flat-layout
    trace."""
    from spark_scheduler_tpu.ops import pallas_fifo as pf

    orig = pf._layout_rows
    pf._layout_rows = lambda n: pf._SUBLANES
    try:
        rng = np.random.default_rng(21)
        c = random_cluster(rng, 53, num_zones=NUM_ZONES)
        apps = random_apps(rng, 7)
        for fill in sorted(PALLAS_FILLS) + sorted(PALLAS_SINGLE_AZ):
            want = batched_fifo_pack(c, apps, fill=fill, emax=EMAX,
                                     num_zones=NUM_ZONES)
            got = fifo_pack_pallas(
                c, apps, fill=fill, emax=EMAX, num_zones=NUM_ZONES,
                interpret=True,
            )
            assert_same(got, want)
    finally:
        pf._layout_rows = orig


def test_pallas_single_az_gpu_scoring_parity():
    """Zone-efficiency scoring with GPU-bearing nodes: the per-node max
    includes the GPU ratio only where schedulable GPU exists
    (efficiency.go:139-144) — a GPU-heavy cluster exercises that branch of
    the in-kernel score."""
    rng = np.random.default_rng(37)
    c = random_cluster(rng, 29, num_zones=NUM_ZONES)
    import dataclasses

    sched = np.asarray(c.schedulable).copy()
    avail = np.asarray(c.available).copy()
    sched[::2, 2] = 4  # every other node carries schedulable GPU
    avail[::2, 2] = rng.integers(0, 5, size=len(avail[::2]))
    c = dataclasses.replace(
        c, schedulable=sched, available=np.minimum(avail, sched)
    )
    driver = np.ones((6, 3), np.int32)
    execs = np.ones((6, 3), np.int32)
    execs[:, 2] = rng.integers(0, 2, size=6)  # some gangs want GPUs
    counts = rng.integers(1, EMAX + 1, size=6).astype(np.int32)
    apps = make_app_batch(driver, execs, counts)
    for fill in sorted(PALLAS_SINGLE_AZ):
        want = batched_fifo_pack(c, apps, fill=fill, emax=EMAX,
                                 num_zones=NUM_ZONES)
        got = fifo_pack_pallas(
            c, apps, fill=fill, emax=EMAX, num_zones=NUM_ZONES,
            interpret=True,
        )
        assert_same(got, want)


@pytest.mark.parametrize("fill", sorted(PALLAS_SINGLE_AZ))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pallas_single_az_matches_xla_scan(fill, seed):
    """The in-kernel per-zone pack + efficiency-scored zone pick (VERDICT
    r3 #4) equals the XLA scan's pack_one_app_single_az step, decision for
    decision — including az-aware's plain fallback and the
    minimal-fragmentation driver-only reservation quirk."""
    rng = np.random.default_rng(seed * 11 + 2)
    c = random_cluster(rng, 37, num_zones=NUM_ZONES)
    apps = random_apps(rng, 9, pad_to=12)
    want = batched_fifo_pack(c, apps, fill=fill, emax=EMAX, num_zones=NUM_ZONES)
    got = fifo_pack_pallas(
        c, apps, fill=fill, emax=EMAX, num_zones=NUM_ZONES, interpret=True
    )
    assert_same(got, want)


def test_pallas_single_az_rejects_when_no_zone_fits():
    """single-az (no fallback): a gang no single zone can hold is
    rejected; az-aware admits it via the plain fallback."""
    rng = np.random.default_rng(29)
    c = random_cluster(rng, 24, num_zones=NUM_ZONES)
    driver = np.ones((1, 3), np.int32)
    execs = np.ones((1, 3), np.int32) * 4
    counts = np.array([EMAX], np.int32)  # spread wider than any one zone
    apps = make_app_batch(driver, execs, counts)
    for fill in ("single-az-tightly-pack", "az-aware-tightly-pack"):
        want = batched_fifo_pack(c, apps, fill=fill, emax=EMAX,
                                 num_zones=NUM_ZONES)
        got = fifo_pack_pallas(
            c, apps, fill=fill, emax=EMAX, num_zones=NUM_ZONES,
            interpret=True,
        )
        assert_same(got, want)


def test_pallas_rejects_masked():
    rng = np.random.default_rng(3)
    c = random_cluster(rng, 16, num_zones=NUM_ZONES)
    apps = random_apps(rng, 4)
    masked = apps._replace(domain=np.ones((4, 16), bool))
    with pytest.raises(ValueError):
        fifo_pack_pallas(c, masked, fill="tightly-pack",
                         emax=EMAX, num_zones=NUM_ZONES, interpret=True)


def test_pallas_empty_batch():
    """B=0 short-circuits (the grid would be empty): no admissions,
    availability unchanged — same as the XLA scan."""
    rng = np.random.default_rng(13)
    c = random_cluster(rng, 16, num_zones=NUM_ZONES)
    apps = make_app_batch(
        np.zeros((0, 3), np.int32), np.zeros((0, 3), np.int32),
        np.zeros(0, np.int32),
    )
    got = fifo_pack_pallas(
        c, apps, fill="tightly-pack", emax=EMAX, num_zones=NUM_ZONES,
        interpret=True,
    )
    assert got.driver_node.shape == (0,)
    assert got.executor_nodes.shape == (0, EMAX)
    np.testing.assert_array_equal(
        np.asarray(got.available_after), np.asarray(c.available)
    )


def test_grouped_pallas_fast_path_interpret():
    """The per-group slicing/stacking of the single-chip fast path
    (_grouped_pallas) must reproduce the vmapped scan's decisions — driven
    through the Pallas interpreter so the CPU suite covers the wiring, not
    just the fallback."""
    from spark_scheduler_tpu.parallel import (
        grouped_fifo_pack,
        grouped_fifo_pack_auto,  # noqa: F401 — fallback covered below
        make_solver_mesh,
        stack_groups,
    )
    from spark_scheduler_tpu.parallel.solve import _grouped_pallas

    rng = np.random.default_rng(29)
    # 24 nodes: divisible by the virtual mesh's 8-way node axis (the
    # `want` side shards over it).
    clusters = [random_cluster(rng, 24, num_zones=NUM_ZONES) for _ in range(3)]
    batches = [random_apps(rng, 5) for _ in range(3)]
    sc, sa = stack_groups(clusters, batches)
    mesh = make_solver_mesh(n_groups=1)
    want = grouped_fifo_pack(mesh, sc, sa, fill="tightly-pack", emax=EMAX,
                             num_zones=NUM_ZONES)
    got = _grouped_pallas(sc, sa, fill="tightly-pack", emax=EMAX,
                          num_zones=NUM_ZONES, g=3, interpret=True)
    assert_same(got, want)


def test_grouped_auto_falls_back_on_cpu():
    """On the CPU mesh (no Mosaic) grouped_fifo_pack_auto must produce the
    vmapped scan's decisions; on a multi-device mesh it must always use the
    GSPMD path regardless of backend."""
    from spark_scheduler_tpu.parallel import (
        grouped_fifo_pack,
        grouped_fifo_pack_auto,
        make_solver_mesh,
        stack_groups,
    )

    rng = np.random.default_rng(17)
    clusters = [random_cluster(rng, 16, num_zones=NUM_ZONES) for _ in range(2)]
    batches = [random_apps(rng, 4) for _ in range(2)]
    sc, sa = stack_groups(clusters, batches)
    mesh = make_solver_mesh(n_groups=1)
    want = grouped_fifo_pack(mesh, sc, sa, fill="tightly-pack", emax=EMAX,
                             num_zones=NUM_ZONES)
    got = grouped_fifo_pack_auto(mesh, sc, sa, fill="tightly-pack",
                                 emax=EMAX, num_zones=NUM_ZONES)
    assert_same(got, want)


def test_auto_routing_falls_back_on_cpu():
    """On the CPU suite Mosaic is unavailable: fifo_pack_auto must still
    return correct decisions via the XLA scan."""
    rng = np.random.default_rng(5)
    c = random_cluster(rng, 16, num_zones=NUM_ZONES)
    apps = random_apps(rng, 5)
    want = batched_fifo_pack(c, apps, fill="tightly-pack", emax=EMAX,
                             num_zones=NUM_ZONES)
    got = fifo_pack_auto(c, apps, fill="tightly-pack", emax=EMAX,
                         num_zones=NUM_ZONES)
    assert_same(got, want)


def test_mosaic_probe_raises_on_tpu_backend(monkeypatch):
    """On a backend reported as a TPU, a failing Mosaic probe raises its
    error instead of quietly routing everything to the XLA scan. (Here
    the probe really fails: the CPU backend cannot run a compiled Mosaic
    kernel.)"""
    import jax

    from spark_scheduler_tpu.ops import pallas_fifo as pf

    monkeypatch.setattr(pf, "_PALLAS_AVAILABLE", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(Exception):
        pf.pallas_available()
    assert pf._PALLAS_AVAILABLE is None  # never cached as "unavailable"


def test_mosaic_probe_is_false_on_cpu(monkeypatch):
    from spark_scheduler_tpu.ops import pallas_fifo as pf

    monkeypatch.setattr(pf, "_PALLAS_AVAILABLE", None)
    assert pf.pallas_available() is False


def test_kernels_are_routed_by_shape(monkeypatch):
    """Above PALLAS_MAX_NODES (the measured VMEM bound) or PALLAS_MAX_APPS
    (the queue kernel's SMEM bound) the routing sends work past the Mosaic
    kernels, decided from the shape alone."""
    from spark_scheduler_tpu.ops import pallas_fifo as pf
    from spark_scheduler_tpu.ops import pallas_window as pw

    monkeypatch.setattr(pw, "pallas_available", lambda: True)
    assert pw.window_pallas_eligible("tightly-pack", pf.PALLAS_MAX_NODES)
    assert not pw.window_pallas_eligible(
        "tightly-pack", 2 * pf.PALLAS_MAX_NODES
    )

    def queue(b):
        return make_app_batch(
            np.ones((b, 3)), np.ones((b, 3)), np.ones(b),
            skippable=np.zeros(b, bool),
        )

    assert pf.pallas_eligible(queue(pf.PALLAS_MAX_APPS), "tightly-pack")
    assert not pf.pallas_eligible(
        queue(pf.PALLAS_MAX_APPS + 1), "tightly-pack"
    )
