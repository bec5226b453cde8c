"""The plain reference against a second witness: the greedy packing the
program keeps for its degraded mode, on random small clusters."""

import numpy as np
import pytest

import cluster as C
import reference as R

STRATEGIES = ["tightly-pack", "minimal-fragmentation",
              "single-az-tightly-pack", "single-az-minimal-fragmentation"]


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_reference_agrees_with_the_greedy_witness(strategy):
    from spark_scheduler_tpu.core.greedy import greedy_strategy_pack

    rng = np.random.default_rng(STRATEGIES.index(strategy))
    for _ in range(60):
        n = int(rng.integers(5, 40))
        nz = int(rng.integers(1, 4))
        alloc = np.stack([rng.choice([4000, 8000, 16000, 32000], n),
                          rng.choice([16, 32, 64, 128], n) * (1 << 30)], 1).astype(np.int64)
        zone = rng.integers(0, nz, n)
        gang = C.Gang(driver=np.array([1000, 1 << 30]), executor=np.array([2000, 4 << 30]),
                      count=int(rng.integers(0, 12)))
        ref = R.Reference(alloc, zone, nz, strategy, gang)
        avail = alloc.copy()
        avail[:, 0] -= rng.integers(0, 4, n) * 1000
        avail[:, 1] -= rng.integers(0, 8, n) * (1 << 30)
        avail = np.maximum(avail, 0)
        kib = np.array([1, 1024])
        a3 = np.concatenate([avail // kib, np.zeros((n, 1), np.int64)], 1)
        s3 = np.concatenate([alloc // kib, np.zeros((n, 1), np.int64)], 1)
        ones = np.ones(n, bool)
        d, ex, ok = greedy_strategy_pack(
            strategy, avail=a3, schedulable=s3, zone_of=[f"zone-{z}" for z in zone],
            names=[f"node-{i:05d}" for i in range(n)], valid=ones, unschedulable=~ones,
            ready=ones, label_rank_driver=None, label_rank_executor=None, cand_mask=ones,
            domain_mask=ones, driver_req=np.array([1000, 1 << 20, 0]),
            exec_req=np.array([2000, 4 << 20, 0]), count=gang.count,
        )
        got = ref.pack(avail, ref.order_key(avail))
        if not ok:
            assert got == []
        else:
            assert R.Placement(int(d), tuple(sorted(int(e) for e in ex))) in got


def test_fifo_blocks_behind_an_earlier_driver_that_does_not_fit():
    alloc = np.array([[17000, 40 << 30]] * 2, np.int64)
    gang = C.Gang(driver=np.array([1000, 1 << 30]), executor=np.array([2000, 4 << 30]), count=8)
    ref = R.Reference(alloc, np.zeros(2, np.int64), 1, "tightly-pack", gang)
    state = R.State(ref)
    state.reserve("running", ref.pack(state.avail, ref.order_key(state.avail))[0])
    for app in ("a", "b", "c"):
        state.arrive(app)
    first = state.decide_driver("a")
    assert first.deny is None and len(first.placements) == 1
    state.admit("a", first.placements[0])
    assert state.decide_driver("b").deny == R.DENY_FIT
    assert state.decide_driver("c").deny == R.DENY_EARLIER


def test_packed_order_key_matches_the_sorted_order():
    rng = np.random.default_rng(3)
    n = 500
    alloc = np.stack([rng.choice([4000, 8000, 16000, 32000], n),
                      rng.choice([16, 32, 64, 128], n) * (1 << 30)], 1).astype(np.int64)
    gang = C.Gang(driver=np.array([1000, 1 << 30]), executor=np.array([2000, 4 << 30]), count=8)
    ref = R.Reference(alloc, np.arange(n) % 3, 3, "tightly-pack", gang)
    avail = alloc - rng.integers(0, 3, (n, 1)) * np.array([1000, 4 << 30])
    packed = ref.order_key(avail)
    ref._packed_key = False
    sorted_key = ref.order_key(avail)
    assert (np.argsort(packed) == np.argsort(sorted_key)).all()
