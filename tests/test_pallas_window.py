"""Golden parity: the segmented-window Pallas path (ops/pallas_window) must
match the segmented XLA scan (ops/batched window mode) decision-for-decision
— same drivers, same executor slot sequences, same admitted/packed flags,
same committed base. The XLA scan is itself pinned to the greedy oracle, so
transitively the Mosaic path carries reference semantics
(resource.go:221-258 + binpack fills).

Runs the Pallas interpreter on CPU (tests/conftest.py pins jax to cpu); the
on-silicon equivalence runs as the first phase of `chip_smoke.py`
(hack/tpu_parity_smoke.py)."""

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from spark_scheduler_tpu.models.cluster import ClusterTensors, INT32_INF
from spark_scheduler_tpu.ops.batched import batched_fifo_pack, make_app_batch
from spark_scheduler_tpu.ops import pallas_window as pw
from spark_scheduler_tpu.ops.pallas_window import (
    PALLAS_SINGLE_AZ,
    SegmentedWindow,
    make_segmented_window,
    window_pack_pallas,
)

from tests.test_packing_golden import random_cluster

FILLS = ("tightly-pack", "distribute-evenly", "minimal-fragmentation")
# All six (r5): the single-AZ wrappers run in-kernel on the window path too.
STRATEGIES = FILLS + (
    "single-az-tightly-pack",
    "single-az-minimal-fragmentation",
    "az-aware-tightly-pack",
)


def _cluster(rng, n, num_zones=4):
    avail = rng.integers(0, 24, size=(n, 3)).astype(np.int32)
    avail[:, 2] = rng.integers(0, 3, size=n)
    return ClusterTensors(
        available=jnp.asarray(avail),
        schedulable=jnp.asarray(avail.copy()),
        zone_id=jnp.asarray(rng.integers(0, num_zones, size=n), jnp.int32),
        name_rank=jnp.asarray(rng.permutation(n), jnp.int32),
        label_rank_driver=jnp.full(n, INT32_INF, jnp.int32),
        label_rank_executor=jnp.full(n, INT32_INF, jnp.int32),
        unschedulable=jnp.asarray(rng.random(n) < 0.1),
        ready=jnp.asarray(rng.random(n) > 0.05),
        valid=jnp.ones(n, bool),
    )


def _window_pair(requests, cands, doms, pad_rows=None):
    """The same requests as the segmented XLA scan's flat AppBatch and as
    the Pallas SegmentedWindow. Returns (apps, win, flat_map)."""
    flat = [row for rows in requests for row in rows]
    commit, reset, cand_rows, dom_rows = [], [], [], []
    for i, rows in enumerate(requests):
        for j in range(len(rows)):
            commit.append(j == len(rows) - 1)
            reset.append(j == 0)
            cand_rows.append(cands[i])
            dom_rows.append(doms[i])
    apps = make_app_batch(
        np.stack([r[0] for r in flat]),
        np.stack([r[1] for r in flat]),
        np.asarray([r[2] for r in flat], np.int32),
        skippable=[r[3] for r in flat],
        driver_cand=np.stack(cand_rows),
        domain=np.stack(dom_rows),
        commit=commit,
        reset=reset,
    )
    win = make_segmented_window(requests, cands, doms, pad_rows=pad_rows)
    flat_map = [
        (s, j) for s, rows in enumerate(requests) for j in range(len(rows))
    ]
    return apps, win, flat_map


def _random_window(rng, n, n_requests, max_rows, emax):
    """Random segmented window: per-request FIFO rows + masks. Returns
    (xla AppBatch args, pallas SegmentedWindow, flat row map)."""
    requests = []
    cands, doms = [], []
    for _ in range(n_requests):
        rows = []
        for _ in range(rng.integers(1, max_rows + 1)):
            dr = rng.integers(0, 5, size=3).astype(np.int32)
            er = rng.integers(1, 4, size=3).astype(np.int32)
            dr[2] = 0
            er[2] = rng.integers(0, 2)
            cnt = int(rng.integers(0, emax + 1))
            rows.append((dr, er, cnt, bool(rng.random() < 0.3)))
        requests.append(rows)
        cands.append(rng.random(n) < (0.95 if rng.random() < 0.7 else 0.4))
        doms.append(rng.random(n) < (1.0 if rng.random() < 0.6 else 0.6))
    return _window_pair(requests, cands, doms)


def _queue_window(rng, n, emax, masked):
    """A FIFO backlog's window: ONE segment of 9 rows padded to 12, gangs
    up to emax + 2 (too big to fit) and 30% of rows skippable. `masked`
    adds the backlog's real masks: kube-scheduler's candidate list drops
    ~40% of nodes, and the instance-group domain holds one of two groups."""
    rows = []
    for _ in range(9):
        dr = rng.integers(1, 6, size=3).astype(np.int32)
        dr[2] = rng.integers(0, 2)
        er = rng.integers(1, 8, size=3).astype(np.int32)
        er[2] = rng.integers(0, 2)
        rows.append((dr, er, int(rng.integers(0, emax + 3)),
                     bool(rng.random() < 0.3)))
    cand = np.ones(n, bool)
    dom = np.ones(n, bool)
    if masked:
        cand = rng.random(n) >= 0.4
        dom = rng.integers(0, 2, size=n) == 0
    return _window_pair([rows], [cand], [dom], pad_rows=12)


def _assert_matches_scan(cluster, apps, win, flat_map, fill, emax):
    """Pallas (interpret mode) == the segmented XLA scan, field for field:
    per real row the driver, admitted, packed and executor slots, and the
    committed base after the window."""
    ref = batched_fifo_pack(cluster, apps, fill=fill, emax=emax, num_zones=4)
    meta, execs, base_after = window_pack_pallas(
        cluster, win, fill=fill, emax=emax, num_zones=4, interpret=True
    )
    meta = np.asarray(meta)
    execs = np.asarray(execs)
    ref_drivers = np.asarray(ref.driver_node)
    ref_execs = np.asarray(ref.executor_nodes)
    ref_admitted = np.asarray(ref.admitted)
    ref_packed = np.asarray(ref.packed)
    for bi, (s, j) in enumerate(flat_map):
        assert meta[s, j, 1] == ref_admitted[bi], (fill, bi, "admitted")
        assert meta[s, j, 2] == ref_packed[bi], (fill, bi, "packed")
        assert meta[s, j, 0] == ref_drivers[bi], (fill, bi, "driver")
        np.testing.assert_array_equal(
            execs[s, j], ref_execs[bi], err_msg=f"{fill} row={bi}"
        )
    np.testing.assert_array_equal(
        np.asarray(base_after),
        np.asarray(ref.available_after),
        err_msg=f"{fill} base",
    )
    return meta, execs, base_after


@pytest.mark.parametrize("fill", STRATEGIES)
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("shape", ["window", "queue", "masked-queue"])
def test_window_pallas_matches_xla_scan(shape, seed, fill):
    """`window`: several requests of 1-4 rows on 24 nodes. `queue` and
    `masked-queue`: the FIFO backlog's one long segment (_queue_window) on
    37 nodes with invalid, unschedulable and not-ready nodes."""
    rng = np.random.default_rng(seed * 7 + 3)
    emax = 8
    if shape == "window":
        n = 24
        cluster = _cluster(rng, n)
        apps, win, flat_map = _random_window(
            rng, n, n_requests=5, max_rows=4, emax=emax
        )
    else:
        n = 37
        cluster = random_cluster(rng, n, num_zones=4)
        apps, win, flat_map = _queue_window(
            rng, n, emax, masked=shape == "masked-queue"
        )
    _assert_matches_scan(cluster, apps, win, flat_map, fill, emax)


@pytest.mark.parametrize("fill", FILLS)
def test_window_pallas_strict_fifo_blocking_is_segment_local(fill):
    """A non-skippable failure blocks LATER rows of its own segment only;
    the next segment starts unblocked (each request's solo solve starts
    fresh) — under every plain fill."""
    rng = np.random.default_rng(11)
    n, emax = 16, 8
    cluster = _cluster(rng, n)
    big = (np.full(3, 500, np.int32), np.ones(3, np.int32), 4, False)
    small = (np.ones(3, np.int32), np.ones(3, np.int32), 2, False)
    requests = [[big, small], [small]]
    cands = [np.ones(n, bool)] * 2
    doms = [np.ones(n, bool)] * 2
    apps, win, flat_map = _window_pair(requests, cands, doms)
    meta, _, _ = _assert_matches_scan(cluster, apps, win, flat_map, fill, emax)
    assert meta[0, 0, 2] == 0  # big does not pack
    assert meta[0, 1, 1] == 0  # same-segment follower is FIFO-blocked
    assert meta[1, 0, 1] == 1  # next segment starts unblocked


def test_window_pallas_commit_rows_thread_the_base():
    """Only COMMIT rows persist into the base: two identical segments on a
    one-gang cluster -> first admits, second sees the committed usage and
    rejects; hypothetical rows never leak across segments."""
    n, emax = 8, 8
    avail = np.zeros((n, 3), np.int32)
    avail[0] = (4, 4, 0)
    cluster = ClusterTensors(
        available=jnp.asarray(avail),
        schedulable=jnp.asarray(avail.copy()),
        zone_id=jnp.zeros(n, jnp.int32),
        name_rank=jnp.arange(n, dtype=jnp.int32),
        label_rank_driver=jnp.full(n, INT32_INF, jnp.int32),
        label_rank_executor=jnp.full(n, INT32_INF, jnp.int32),
        unschedulable=jnp.zeros(n, bool),
        ready=jnp.ones(n, bool),
        valid=jnp.ones(n, bool),
    )
    gang = (np.ones(3, np.int32) * np.array([1, 1, 0], np.int32),
            np.array([1, 1, 0], np.int32), 3, False)
    requests = [[gang], [gang]]
    ones = [np.ones(n, bool)] * 2
    win = make_segmented_window(requests, ones, ones)
    meta, _, base_after = window_pack_pallas(
        cluster, win, fill="tightly-pack", emax=emax, num_zones=2,
        interpret=True,
    )
    meta = np.asarray(meta)
    assert meta[0, 0, 1] == 1  # first request admitted (1+3 = 4 CPU)
    assert meta[1, 0, 1] == 0  # second sees the committed base: full
    assert np.asarray(base_after)[0, 0] == 0


def test_window_pallas_empty_candidates_and_emax_edges():
    """A segment whose candidate mask excludes every node rejects without
    disturbing its neighbors; count == emax and count == 0 rows match the
    XLA scan exactly."""
    rng = np.random.default_rng(31)
    n, emax = 16, 8
    cluster = _cluster(rng, n)
    one = np.ones(3, np.int32)
    requests = [
        [(one, one, emax, False)],  # full-width gang
        [(one, one, 0, False)],  # zero-executor gang
        [(one, one, 2, False)],  # starved: empty candidate mask
    ]
    cands = [np.ones(n, bool), np.ones(n, bool), np.zeros(n, bool)]
    doms = [np.ones(n, bool)] * 3
    win = make_segmented_window(requests, cands, doms)
    # XLA twin
    flat = [r for rows in requests for r in rows]
    apps = make_app_batch(
        np.stack([r[0] for r in flat]),
        np.stack([r[1] for r in flat]),
        np.asarray([r[2] for r in flat], np.int32),
        skippable=[r[3] for r in flat],
        driver_cand=np.stack([cands[i] for i in range(3)]),
        domain=np.stack([doms[i] for i in range(3)]),
        commit=[True] * 3,
        reset=[True] * 3,
    )
    ref = batched_fifo_pack(
        cluster, apps, fill="tightly-pack", emax=emax, num_zones=4
    )
    meta, execs, base_after = window_pack_pallas(
        cluster, win, fill="tightly-pack", emax=emax, num_zones=4,
        interpret=True,
    )
    meta = np.asarray(meta)
    for bi in range(3):
        assert meta[bi, 0, 1] == np.asarray(ref.admitted)[bi], bi
        assert meta[bi, 0, 0] == np.asarray(ref.driver_node)[bi], bi
        np.testing.assert_array_equal(
            np.asarray(execs)[bi, 0], np.asarray(ref.executor_nodes)[bi]
        )
    assert meta[2, 0, 1] == 0  # starved segment rejected
    np.testing.assert_array_equal(
        np.asarray(base_after), np.asarray(ref.available_after)
    )


def test_solver_window_route_parity(monkeypatch):
    """The solver's Pallas window route (pack_window dispatch/fetch through
    _window_blob_pallas) returns byte-identical decisions to the XLA route
    for the same window."""
    import spark_scheduler_tpu.ops.pallas_window as pw
    from functools import partial as _p

    from spark_scheduler_tpu.core.solver import PlacementSolver, WindowRequest
    from spark_scheduler_tpu.models.kube import Node
    from spark_scheduler_tpu.models.resources import Resources

    def mk_solver():
        s = PlacementSolver(use_native=False)
        nodes = [
            Node(
                name=f"n{i}",
                allocatable=Resources.from_quantities("8", "8Gi"),
            )
            for i in range(12)
        ]
        t = s.build_tensors(nodes, {}, {})
        return s, t, [n.name for n in nodes]

    one = Resources.from_quantities("1", "1Gi")
    two = Resources.from_quantities("2", "2Gi")

    def mk_requests(names):
        return [
            WindowRequest(
                rows=[(one, one, 3, False)],
                driver_candidate_names=names,
            ),
            WindowRequest(
                rows=[(one, one, 3, False), (two, one, 2, False)],
                driver_candidate_names=names,
            ),
            WindowRequest(
                rows=[(one, two, 4, True), (one, one, 1, False)],
                driver_candidate_names=names[:8],
            ),
        ]

    s_x, t_x, names = mk_solver()
    ref = s_x.pack_window("tightly-pack", t_x, mk_requests(names))

    monkeypatch.setattr(pw, "window_pallas_eligible", lambda fill, n: True)
    monkeypatch.setattr(
        pw, "window_pack_pallas", _p(pw.window_pack_pallas, interpret=True)
    )
    s_p, t_p, names_p = mk_solver()
    got = s_p.pack_window("tightly-pack", t_p, mk_requests(names_p))
    assert s_p.window_path_counts.get("pallas") == 1

    assert len(ref) == len(got)
    for r, g in zip(ref, got):
        assert r.admitted == g.admitted
        assert r.earlier_blocked == g.earlier_blocked
        assert r.packing.driver_node == g.packing.driver_node
        assert r.packing.executor_nodes == g.packing.executor_nodes


def _one_segment(cluster, driver, execs, counts, skip=None):
    """One request whose segment holds every row, all nodes offered."""
    n = np.asarray(cluster.available).shape[0]
    skip = np.zeros(len(counts), bool) if skip is None else skip
    rows = [
        (np.asarray(d, np.int32), np.asarray(e, np.int32), int(c), bool(k))
        for d, e, c, k in zip(driver, execs, counts, skip)
    ]
    return _window_pair([rows], [np.ones(n, bool)], [np.ones(n, bool)])


def test_window_pallas_negative_availability_and_zero_count():
    """Overcommitted nodes (negative availability) and zero-executor gangs."""
    rng = np.random.default_rng(11)
    c = random_cluster(rng, 20, num_zones=4)
    avail = np.asarray(c.available).copy()
    avail[3] = -5
    avail[7, 0] = -1
    c = dataclasses.replace(c, available=avail)
    apps, win, flat_map = _one_segment(
        c, np.ones((3, 3)), np.ones((3, 3)), [0, 3, 0]
    )
    for fill in FILLS:
        _assert_matches_scan(c, apps, win, flat_map, fill, emax=8)


def test_window_pallas_sublane_folded_layout():
    """At _SUBLANE_FOLD_MIN_NODES nodes the node axis folds into the
    [8, cols] tile; its decisions equal the XLA scan for every strategy,
    as the flat [1, Np] row's do."""
    n = pw._SUBLANE_FOLD_MIN_NODES
    assert pw._layout_rows(n) == pw._SUBLANES
    rng = np.random.default_rng(21)
    c = random_cluster(rng, n, num_zones=4)
    driver = rng.integers(1, 6, size=(7, 3))
    execs = rng.integers(1, 8, size=(7, 3))
    apps, win, flat_map = _one_segment(
        c, driver, execs, rng.integers(0, 11, size=7), rng.random(7) < 0.3
    )
    for fill in STRATEGIES:
        _assert_matches_scan(c, apps, win, flat_map, fill, emax=8)


def test_window_pallas_single_az_gpu_scoring():
    """Zone-efficiency scoring with GPU-bearing nodes: the per-node max
    includes the GPU ratio only where schedulable GPU exists
    (efficiency.go:139-144) — a GPU-heavy cluster exercises that branch of
    the in-kernel score."""
    rng = np.random.default_rng(37)
    c = random_cluster(rng, 29, num_zones=4)
    sched = np.asarray(c.schedulable).copy()
    avail = np.asarray(c.available).copy()
    sched[::2, 2] = 4  # every other node carries schedulable GPU
    avail[::2, 2] = rng.integers(0, 5, size=len(avail[::2]))
    c = dataclasses.replace(
        c, schedulable=sched, available=np.minimum(avail, sched)
    )
    execs = np.ones((6, 3), np.int32)
    execs[:, 2] = rng.integers(0, 2, size=6)  # some gangs want GPUs
    apps, win, flat_map = _one_segment(
        c, np.ones((6, 3)), execs, rng.integers(1, 9, size=6)
    )
    for fill in sorted(PALLAS_SINGLE_AZ):
        _assert_matches_scan(c, apps, win, flat_map, fill, emax=8)


def test_window_pallas_single_az_rejects_when_no_zone_fits():
    """single-az (no fallback): a gang no single zone can hold is
    rejected; az-aware admits it via the plain fallback."""
    rng = np.random.default_rng(29)
    c = random_cluster(rng, 24, num_zones=4)
    apps, win, flat_map = _one_segment(
        c, np.ones((1, 3)), np.full((1, 3), 4), [8]
    )
    for fill in ("single-az-tightly-pack", "az-aware-tightly-pack"):
        _assert_matches_scan(c, apps, win, flat_map, fill, emax=8)


def test_window_pallas_padding_segments_commit_nothing():
    """Segments of padding only (row_count 0, as the server's segment
    bucket adds) report nothing and leave the base as the real segments
    left it; a window of padding alone leaves availability unchanged."""
    rng = np.random.default_rng(13)
    n, emax = 16, 8
    c = random_cluster(rng, n, num_zones=4)
    one = np.ones(3, np.int32)
    requests = [[(one, one, 2, False)]]
    ones = [np.ones(n, bool)]
    apps, _, flat_map = _window_pair(requests, ones, ones)
    win = make_segmented_window(requests, ones, ones, pad_segments=3)
    meta, execs, _ = _assert_matches_scan(c, apps, win, flat_map,
                                          "tightly-pack", emax)
    assert (meta[1:] == 0).all()
    assert (execs[1:] == -1).all()

    empty = SegmentedWindow(*[np.zeros_like(x) for x in win])
    meta, execs, base_after = window_pack_pallas(
        c, empty, fill="tightly-pack", emax=emax, num_zones=4,
        interpret=True,
    )
    assert (np.asarray(meta) == 0).all()
    assert (np.asarray(execs) == -1).all()
    np.testing.assert_array_equal(
        np.asarray(base_after), np.asarray(c.available)
    )


def test_mosaic_probe_raises_on_tpu_backend(monkeypatch):
    """On a backend reported as a TPU, a failing Mosaic probe raises its
    error instead of quietly routing everything to the XLA scan. (Here
    the probe really fails: the CPU backend cannot run a compiled Mosaic
    kernel.)"""
    import jax

    monkeypatch.setattr(pw, "_PALLAS_AVAILABLE", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(Exception):
        pw.pallas_available()
    assert pw._PALLAS_AVAILABLE is None  # never cached as "unavailable"


def test_mosaic_probe_is_false_on_cpu(monkeypatch):
    monkeypatch.setattr(pw, "_PALLAS_AVAILABLE", None)
    assert pw.pallas_available() is False


def test_kernels_are_routed_by_shape(monkeypatch):
    """Above PALLAS_MAX_NODES (the measured VMEM bound) the routing sends
    work past the Mosaic kernel, decided from the shape alone."""
    monkeypatch.setattr(pw, "pallas_available", lambda: True)
    assert pw.window_pallas_eligible("tightly-pack", pw.PALLAS_MAX_NODES)
    assert not pw.window_pallas_eligible(
        "tightly-pack", 2 * pw.PALLAS_MAX_NODES
    )
