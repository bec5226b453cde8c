"""Batched FIFO gang admission: parity with a sequential oracle loop, strict
FIFO blocking semantics, and sharded == unsharded on the virtual device mesh.
"""

import numpy as np
import pytest

from spark_scheduler_tpu.models.cluster import ClusterTensors
from spark_scheduler_tpu.ops.batched import AppBatch, batched_fifo_pack, make_app_batch
from spark_scheduler_tpu.parallel import (
    grouped_fifo_pack,
    make_solver_mesh,
    sharded_fifo_pack,
    stack_groups,
)

from tests import greedy_oracle as G
from tests.test_packing_golden import random_cluster, oracle_orders

EMAX = 16
NUM_ZONES = 4


def random_apps(rng, b, pad_to=None):
    driver = rng.integers(1, 6, size=(b, 3)).astype(np.int32)
    driver[:, 2] = rng.integers(0, 2, size=b)
    execs = rng.integers(1, 8, size=(b, 3)).astype(np.int32)
    execs[:, 2] = rng.integers(0, 2, size=b)
    # Occasionally exceed EMAX: oversized gangs must be rejected, not truncated.
    counts = rng.integers(0, EMAX + 4, size=b).astype(np.int32)
    skip = rng.random(b) < 0.3
    return make_app_batch(driver, execs, counts, pad_to=pad_to, skippable=skip)


def oracle_batched(c: ClusterTensors, apps: AppBatch, fill):
    """Sequential reference loop: pack each app in FIFO order against the
    mutating availability, orders fixed from the starting availability
    (fitEarlierDrivers semantics, resource.go:221-258)."""
    avail = np.asarray(c.available).astype(np.int64).copy()
    valid = np.asarray(c.valid)
    e_elig = valid & ~np.asarray(c.unschedulable) & np.asarray(c.ready)
    d_mask = e_elig.copy()
    d_order, e_order = oracle_orders(c, d_mask, valid)
    # oracle_orders applies eligibility itself; driver eligibility here is
    # the executor eligibility (queue mode, no kube candidate list).
    blocked = False
    out = []
    for i in range(len(apps.app_valid)):
        dreq = np.asarray(apps.driver_req[i], np.int64)
        ereq = np.asarray(apps.exec_req[i], np.int64)
        too_big = int(apps.exec_count[i]) > EMAX
        count = int(min(apps.exec_count[i], EMAX))
        drv, execs, ok, _ = G.greedy_spark_bin_pack(
            avail, dreq, ereq, count, d_order, e_order, fill
        )
        packed = ok and bool(apps.app_valid[i]) and not too_big
        admitted = packed and not blocked
        if admitted:
            avail[drv] -= dreq
            for n in execs:
                avail[n] -= ereq
        else:
            drv, execs = -1, []
        if bool(apps.app_valid[i]) and not packed and not bool(apps.skippable[i]):
            blocked = True
        out.append((drv, list(execs), admitted, packed))
    return out, avail


@pytest.mark.parametrize("fill", ["tightly-pack", "distribute-evenly", "minimal-fragmentation"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_batched_matches_sequential_oracle(fill, seed):
    rng = np.random.default_rng(seed)
    c = random_cluster(rng, 40)
    apps = random_apps(rng, 12, pad_to=16)
    got = batched_fifo_pack(c, apps, fill=fill, emax=EMAX, num_zones=NUM_ZONES)
    want, want_avail = oracle_batched(c, apps, fill)
    for i, (drv, execs, admitted, packed) in enumerate(want):
        assert bool(got.admitted[i]) == admitted, f"app {i} admitted"
        assert bool(got.packed[i]) == packed, f"app {i} packed"
        assert int(got.driver_node[i]) == drv, f"app {i} driver"
        got_execs = [int(x) for x in np.asarray(got.executor_nodes[i]) if x >= 0]
        assert got_execs == execs, f"app {i} executors"
    live = np.asarray(c.valid)
    np.testing.assert_array_equal(
        np.asarray(got.available_after)[live], want_avail.astype(np.int32)[live]
    )


def random_masks(rng, b, n, p_cand=0.7, p_dom=0.85):
    """Per-app kube candidate lists + affinity domains, dense [B, N] bool."""
    dcand = rng.random((b, n)) < p_cand
    dom = rng.random((b, n)) < p_dom
    return dcand, dom


def oracle_masked(c: ClusterTensors, apps: AppBatch, fill):
    """Sequential serving-path oracle: each app is a standalone
    spark_bin_pack call with its own masks against the then-current
    availability (exactly what per-request /predicates does), with admitted
    usage subtracted between calls."""
    import dataclasses

    import jax.numpy as jnp

    from spark_scheduler_tpu.ops.packing import spark_bin_pack

    avail = np.asarray(c.available).copy()
    blocked = False
    out = []
    for i in range(len(apps.app_valid)):
        ci = dataclasses.replace(c, available=jnp.asarray(avail))
        count = int(apps.exec_count[i])
        p = spark_bin_pack(
            ci,
            jnp.asarray(apps.driver_req[i]),
            jnp.asarray(apps.exec_req[i]),
            jnp.int32(count),
            jnp.asarray(apps.driver_cand[i]),
            jnp.asarray(apps.domain[i]),
            fill=fill,
            emax=EMAX,
            num_zones=NUM_ZONES,
        )
        packed = bool(p.has_capacity) and bool(apps.app_valid[i])
        admitted = packed and not blocked
        if admitted:
            drv = int(p.driver_node)
            execs = [int(x) for x in np.asarray(p.executor_nodes) if int(x) >= 0]
            avail[drv] -= np.asarray(apps.driver_req[i])
            for nd in execs:
                avail[nd] -= np.asarray(apps.exec_req[i])
        else:
            drv, execs = -1, []
        if bool(apps.app_valid[i]) and not packed and not bool(apps.skippable[i]):
            blocked = True
        out.append((drv, execs, admitted, packed))
    return out, avail


@pytest.mark.parametrize("fill", ["tightly-pack", "distribute-evenly", "minimal-fragmentation"])
@pytest.mark.parametrize("seed", [0, 5])
def test_masked_batch_matches_sequential_spark_bin_pack(fill, seed):
    """VERDICT r1 #2: batched-with-masks == sequential spark_bin_pack with
    the same masks — the property that lets the serving path batch
    heterogeneous requests without changing any decision."""
    rng = np.random.default_rng(seed)
    c = random_cluster(rng, 40)
    n = np.asarray(c.available).shape[0]
    b = 10
    driver = rng.integers(1, 6, size=(b, 3)).astype(np.int32)
    driver[:, 2] = rng.integers(0, 2, size=b)
    execs = rng.integers(1, 8, size=(b, 3)).astype(np.int32)
    execs[:, 2] = rng.integers(0, 2, size=b)
    counts = rng.integers(0, EMAX + 1, size=b).astype(np.int32)
    skip = rng.random(b) < 0.3
    dcand, dom = random_masks(rng, b, n)
    apps = make_app_batch(
        driver, execs, counts, skippable=skip, driver_cand=dcand, domain=dom,
        pad_to=16,
    )
    got = batched_fifo_pack(c, apps, fill=fill, emax=EMAX, num_zones=NUM_ZONES)
    want, want_avail = oracle_masked(c, apps, fill)
    for i, (drv, execs_w, admitted, packed) in enumerate(want):
        assert bool(got.admitted[i]) == admitted, f"app {i} admitted"
        assert bool(got.packed[i]) == packed, f"app {i} packed"
        assert int(got.driver_node[i]) == drv, f"app {i} driver"
        got_execs = [int(x) for x in np.asarray(got.executor_nodes[i]) if x >= 0]
        assert got_execs == execs_w, f"app {i} executors"
    live = np.asarray(c.valid)
    np.testing.assert_array_equal(
        np.asarray(got.available_after)[live], want_avail[live]
    )


from spark_scheduler_tpu.ops.batched import _SINGLE_AZ_INNER as _AZ_INNER

AZ_STRATEGIES = sorted(_AZ_INNER)


def greedy_single_az_candidates(
    avail, sched, zone, d_order, e_order, dreq, ereq, count, strategy
):
    """All reference-acceptable single-AZ outcomes for one app against the
    given availability and FIXED priority orders (single_az.go:23-97): the
    per-zone greedy results whose avg efficiency is within float32 tie
    distance of the best. Returns (acceptable [(driver, execs)], packed)."""
    inner = _AZ_INNER[strategy]
    zones_in_order = []
    for i in d_order:
        if zone[i] not in zones_in_order:
            zones_in_order.append(zone[i])
    results = []
    for z in zones_in_order:
        d_o = [i for i in d_order if zone[i] == z]
        e_o = [i for i in e_order if zone[i] == z]
        if not e_o:
            continue
        d, ex, ok, _ = G.greedy_spark_bin_pack(
            avail, dreq, ereq, count, d_o, e_o, inner
        )
        if not ok:
            continue
        eff = G.greedy_avg_efficiency(
            avail, sched, d, ex, dreq, ereq,
            include_executors_in_reserved=(inner != "minimal-fragmentation"),
        )
        if eff > 0.0:
            results.append((eff, d, list(ex)))
    if results:
        best = max(r[0] for r in results)
        acceptable = [(d, ex) for eff, d, ex in results if eff >= best - 1e-5]
        return acceptable, True
    if strategy == "az-aware-tightly-pack":
        d, ex, ok, _ = G.greedy_spark_bin_pack(
            avail, dreq, ereq, count, d_order, e_order, "tightly-pack"
        )
        if ok:
            return [(d, list(ex))], True
    return [], False


@pytest.mark.parametrize("strategy", AZ_STRATEGIES)
@pytest.mark.parametrize("seed", [0, 1])
def test_batched_single_az_matches_sequential_oracle(strategy, seed):
    """Queue mode: the batched single-AZ scan must per-step produce a
    reference-acceptable zone pick against the mutating availability, with
    orders fixed from the start (fitEarlierDrivers semantics). On float32
    efficiency near-ties, the oracle follows the kernel's choice."""
    rng = np.random.default_rng(seed)
    c = random_cluster(rng, 40)
    apps = random_apps(rng, 12, pad_to=16)
    got = batched_fifo_pack(c, apps, fill=strategy, emax=EMAX, num_zones=NUM_ZONES)

    avail = np.asarray(c.available).astype(np.int64).copy()
    sched = np.asarray(c.schedulable).astype(np.int64)
    zone = np.asarray(c.zone_id)
    valid = np.asarray(c.valid)
    e_elig = valid & ~np.asarray(c.unschedulable) & np.asarray(c.ready)
    d_order, e_order = oracle_orders(c, e_elig, valid)
    blocked = False
    for i in range(len(apps.app_valid)):
        dreq = np.asarray(apps.driver_req[i], np.int64)
        ereq = np.asarray(apps.exec_req[i], np.int64)
        too_big = int(apps.exec_count[i]) > EMAX
        count = int(min(apps.exec_count[i], EMAX))
        acceptable, ok = greedy_single_az_candidates(
            avail, sched, zone, d_order, e_order, dreq, ereq, count, strategy
        )
        packed = ok and bool(apps.app_valid[i]) and not too_big
        admitted = packed and not blocked
        assert bool(got.packed[i]) == packed, f"app {i} packed"
        assert bool(got.admitted[i]) == admitted, f"app {i} admitted"
        drv = int(got.driver_node[i])
        execs = [int(x) for x in np.asarray(got.executor_nodes[i]) if x >= 0]
        if admitted:
            assert (drv, execs) in acceptable, (
                f"app {i}: kernel pick {(drv, execs)} not reference-acceptable "
                f"{acceptable}"
            )
            avail[drv] -= dreq
            for nd in execs:
                avail[nd] -= ereq
        else:
            assert drv == -1 and not execs, f"app {i} must be unplaced"
        if bool(apps.app_valid[i]) and not packed and not bool(apps.skippable[i]):
            blocked = True
    live = np.asarray(c.valid)
    np.testing.assert_array_equal(
        np.asarray(got.available_after)[live], avail.astype(np.int32)[live]
    )


@pytest.mark.parametrize("strategy", AZ_STRATEGIES)
def test_masked_batch_single_az_matches_standalone(strategy):
    """Masked (serving) mode: each row of the batched single-AZ solve must
    match a standalone BINPACK_FUNCTIONS[strategy] call with the same masks
    against the then-current availability (float32 efficiency near-ties
    resolved in the kernel's favor, as test_single_az_matches_oracle)."""
    import dataclasses

    import jax.numpy as jnp

    from spark_scheduler_tpu.ops import BINPACK_FUNCTIONS

    rng = np.random.default_rng(9)
    c = random_cluster(rng, 40)
    n = np.asarray(c.available).shape[0]
    b = 10
    driver = rng.integers(1, 6, size=(b, 3)).astype(np.int32)
    driver[:, 2] = rng.integers(0, 2, size=b)
    execs = rng.integers(1, 8, size=(b, 3)).astype(np.int32)
    execs[:, 2] = rng.integers(0, 2, size=b)
    counts = rng.integers(0, EMAX + 1, size=b).astype(np.int32)
    skip = rng.random(b) < 0.3
    dcand, dom = random_masks(rng, b, n)
    apps = make_app_batch(
        driver, execs, counts, skippable=skip, driver_cand=dcand, domain=dom,
        pad_to=16,
    )
    got = batched_fifo_pack(c, apps, fill=strategy, emax=EMAX, num_zones=NUM_ZONES)

    avail = np.asarray(c.available).copy()
    sched = np.asarray(c.schedulable).astype(np.int64)
    zone = np.asarray(c.zone_id)
    blocked = False
    for i in range(b):
        ci = dataclasses.replace(c, available=jnp.asarray(avail))
        p = BINPACK_FUNCTIONS[strategy](
            ci,
            jnp.asarray(apps.driver_req[i]),
            jnp.asarray(apps.exec_req[i]),
            jnp.int32(int(apps.exec_count[i])),
            jnp.asarray(apps.driver_cand[i]),
            jnp.asarray(apps.domain[i]),
            emax=EMAX,
            num_zones=NUM_ZONES,
        )
        packed = bool(p.has_capacity)
        admitted = packed and not blocked
        assert bool(got.packed[i]) == packed, f"app {i} packed"
        assert bool(got.admitted[i]) == admitted, f"app {i} admitted"
        drv = int(got.driver_node[i])
        got_execs = [int(x) for x in np.asarray(got.executor_nodes[i]) if x >= 0]
        if admitted:
            want_drv = int(p.driver_node)
            want_execs = [int(x) for x in np.asarray(p.executor_nodes) if x >= 0]
            if (drv, got_execs) != (want_drv, want_execs):
                # Different zone on a float32 efficiency tie: both picks must
                # score within tolerance.
                inner = _AZ_INNER[strategy]
                incl = inner != "minimal-fragmentation"
                eff_got = G.greedy_avg_efficiency(
                    avail.astype(np.int64), sched, drv, got_execs,
                    np.asarray(apps.driver_req[i], np.int64),
                    np.asarray(apps.exec_req[i], np.int64),
                    include_executors_in_reserved=incl,
                )
                eff_want = G.greedy_avg_efficiency(
                    avail.astype(np.int64), sched, want_drv, want_execs,
                    np.asarray(apps.driver_req[i], np.int64),
                    np.asarray(apps.exec_req[i], np.int64),
                    include_executors_in_reserved=incl,
                )
                assert abs(eff_got - eff_want) < 1e-5, (
                    f"app {i}: {(drv, got_execs)} vs {(want_drv, want_execs)}"
                )
            avail[drv] -= np.asarray(apps.driver_req[i])
            for nd in got_execs:
                avail[nd] -= np.asarray(apps.exec_req[i])
        if bool(apps.app_valid[i]) and not packed and not bool(apps.skippable[i]):
            blocked = True


def test_masked_sharded_matches_unsharded():
    """Per-step sorts + masks must survive GSPMD node-axis sharding."""
    rng = np.random.default_rng(17)
    c = random_cluster(rng, 64)
    n = np.asarray(c.available).shape[0]
    b = 6
    driver = rng.integers(1, 5, size=(b, 3)).astype(np.int32)
    execs = rng.integers(1, 6, size=(b, 3)).astype(np.int32)
    counts = rng.integers(1, 9, size=b).astype(np.int32)
    dcand, dom = random_masks(rng, b, n)
    apps = make_app_batch(driver, execs, counts, driver_cand=dcand, domain=dom)
    mesh = make_solver_mesh()
    want = batched_fifo_pack(c, apps, fill="tightly-pack", emax=EMAX, num_zones=NUM_ZONES)
    got = sharded_fifo_pack(mesh, c, apps, fill="tightly-pack", emax=EMAX, num_zones=NUM_ZONES)
    np.testing.assert_array_equal(np.asarray(got.driver_node), np.asarray(want.driver_node))
    np.testing.assert_array_equal(
        np.asarray(got.executor_nodes), np.asarray(want.executor_nodes)
    )
    np.testing.assert_array_equal(np.asarray(got.admitted), np.asarray(want.admitted))


def test_strict_fifo_blocking():
    rng = np.random.default_rng(7)
    c = random_cluster(rng, 20)
    # App 1 requests an impossible gang and is NOT skippable: apps 2.. must
    # be rejected even though they'd fit (resource.go:241-249).
    driver = np.ones((3, 3), np.int32)
    execs = np.ones((3, 3), np.int32)
    counts = np.array([1, 10**6, 1], np.int32)
    counts = np.minimum(counts, EMAX)
    execs[1] = 10**6  # impossible request instead
    apps = make_app_batch(driver, execs, counts, skippable=[False, False, False])
    got = batched_fifo_pack(c, apps, fill="tightly-pack", emax=EMAX, num_zones=NUM_ZONES)
    assert bool(got.admitted[0])
    assert not bool(got.admitted[1])
    assert not bool(got.admitted[2])
    assert bool(got.packed[2])  # would fit; blocked only by FIFO

    # Same queue but app 1 skippable: app 2 goes through (resource.go:260-270).
    apps2 = make_app_batch(driver, execs, counts, skippable=[False, True, False])
    got2 = batched_fifo_pack(c, apps2, fill="tightly-pack", emax=EMAX, num_zones=NUM_ZONES)
    assert bool(got2.admitted[2])


def _random_window_batch(rng, c, n_segments, pad_to=None):
    """A segmented WINDOW batch: each segment is 1-3 rows (hypothetical
    prefix + committing request row), with per-row candidate/domain
    masks — the shape core/solver.py pack_window dispatches."""
    n = c.available.shape[0]
    drv, exc, counts, skip, cand, dom, commit, reset = (
        [], [], [], [], [], [], [], [],
    )
    for _ in range(n_segments):
        seg_rows = int(rng.integers(1, 4))
        cand_mask = rng.random(n) < 0.8
        dom_mask = rng.random(n) < 0.9
        for j in range(seg_rows):
            d = rng.integers(1, 5, size=3).astype(np.int32)
            e = rng.integers(1, 5, size=3).astype(np.int32)
            d[2] = e[2] = 0
            drv.append(d)
            exc.append(e)
            counts.append(int(rng.integers(1, 5)))
            skip.append(bool(rng.random() < 0.4))
            cand.append(cand_mask)
            dom.append(dom_mask)
            commit.append(j == seg_rows - 1)
            reset.append(j == 0)
    return make_app_batch(
        np.stack(drv), np.stack(exc), np.asarray(counts, np.int32),
        pad_to=pad_to, skippable=skip,
        driver_cand=np.stack(cand), domain=np.stack(dom),
        commit=commit, reset=reset,
    )


def test_fuse_app_batches_matches_sequential_carry():
    """The fused multi-window identity at the ops layer: ONE scan over
    fuse_app_batches(K windows) == K sequential batched_fifo_pack calls
    with available_after threaded between them, row for row — including
    when the input batches carry padding rows that fusing must strip."""
    import dataclasses

    from spark_scheduler_tpu.ops.batched import fuse_app_batches

    rng = np.random.default_rng(21)
    c = random_cluster(rng, 24)
    batches = [
        _random_window_batch(rng, c, 3, pad_to=None),
        _random_window_batch(rng, c, 2, pad_to=9),  # padding rows stripped
        _random_window_batch(rng, c, 4, pad_to=None),
    ]

    # Sequential: thread the committed base across the K windows.
    cur = c
    seq = []
    for b in batches:
        out = batched_fifo_pack(
            cur, b, fill="tightly-pack", emax=EMAX, num_zones=NUM_ZONES
        )
        valid = np.asarray(b.app_valid)
        for i in np.flatnonzero(valid):
            seq.append(
                (
                    int(out.driver_node[i]),
                    [int(x) for x in np.asarray(out.executor_nodes[i])],
                    bool(out.admitted[i]),
                    bool(out.packed[i]),
                )
            )
        cur = dataclasses.replace(cur, available=out.available_after)
    seq_after = np.asarray(cur.available)

    fused = fuse_app_batches(batches)
    out = batched_fifo_pack(
        c, fused, fill="tightly-pack", emax=EMAX, num_zones=NUM_ZONES
    )
    got = [
        (
            int(out.driver_node[i]),
            [int(x) for x in np.asarray(out.executor_nodes[i])],
            bool(out.admitted[i]),
            bool(out.packed[i]),
        )
        for i in np.flatnonzero(np.asarray(fused.app_valid))
    ]
    assert got == seq
    np.testing.assert_array_equal(
        np.asarray(out.available_after), seq_after
    )


def test_fuse_app_batches_requires_segmented():
    from spark_scheduler_tpu.ops.batched import fuse_app_batches

    rng = np.random.default_rng(2)
    plain = random_apps(rng, 3)
    with pytest.raises(ValueError, match="segmented"):
        fuse_app_batches([plain])


def test_sharded_matches_unsharded():
    rng = np.random.default_rng(3)
    c = random_cluster(rng, 64)  # divisible by the 8-device "nodes" axis
    apps = random_apps(rng, 8)
    mesh = make_solver_mesh()  # all devices on "nodes"
    want = batched_fifo_pack(c, apps, fill="tightly-pack", emax=EMAX, num_zones=NUM_ZONES)
    got = sharded_fifo_pack(mesh, c, apps, fill="tightly-pack", emax=EMAX, num_zones=NUM_ZONES)
    np.testing.assert_array_equal(np.asarray(got.driver_node), np.asarray(want.driver_node))
    np.testing.assert_array_equal(
        np.asarray(got.executor_nodes), np.asarray(want.executor_nodes)
    )
    np.testing.assert_array_equal(np.asarray(got.admitted), np.asarray(want.admitted))
    np.testing.assert_array_equal(
        np.asarray(got.available_after), np.asarray(want.available_after)
    )


def test_grouped_2d_parallel_matches_per_group():
    rng = np.random.default_rng(11)
    clusters = [random_cluster(rng, 32) for _ in range(4)]
    batches = [random_apps(rng, 6, pad_to=8) for _ in range(4)]
    mesh = make_solver_mesh(n_groups=2, n_nodes_shards=4)
    stacked_c, stacked_a = stack_groups(clusters, batches)
    got = grouped_fifo_pack(
        mesh, stacked_c, stacked_a, fill="tightly-pack", emax=EMAX, num_zones=NUM_ZONES
    )
    for gi in range(4):
        want = batched_fifo_pack(
            clusters[gi], batches[gi], fill="tightly-pack", emax=EMAX, num_zones=NUM_ZONES
        )
        np.testing.assert_array_equal(
            np.asarray(got.driver_node[gi]), np.asarray(want.driver_node)
        )
        np.testing.assert_array_equal(
            np.asarray(got.executor_nodes[gi]), np.asarray(want.executor_nodes)
        )
        np.testing.assert_array_equal(
            np.asarray(got.admitted[gi]), np.asarray(want.admitted)
        )
