"""On-device parity smoke: golden oracle checks on the default backend.

The pytest suite pins JAX to a virtual CPU mesh (tests/conftest.py), so the
golden parity proofs normally never execute on the chip. This module runs
a reduced randomized sweep of THE SAME checks — it imports
`random_cluster` / `check_case` straight from tests/test_packing_golden.py,
so the on-device smoke and the CPU golden suite are provably the same
assertions — on whatever backend JAX resolves. `chip_smoke.py` calls
`run(require_tpu=True)` in its own process as its first phase on the chip;
tests/test_tpu_parity.py runs the sweep on the CPU backend. One shape
bucket keeps the compile count low.

Run directly (prints one JSON verdict line):
    python hack/tpu_parity_smoke.py
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

N_NODES = 64  # one shape bucket: a single compile per (fill, program)
TRIALS = 12


def run(require_tpu: bool = False) -> dict:
    """Run the sweep; returns {"device", "cases_checked", "parity"} (raises
    on any parity violation). With `require_tpu`, refuses any backend but
    a TPU instead of checking the CPU programs under the chip's name."""
    import jax

    if require_tpu and jax.devices()[0].platform != "tpu":
        raise RuntimeError(
            f"parity sweep needs a TPU, JAX backend is "
            f"{jax.devices()[0].platform}"
        )

    from tests import greedy_oracle as G
    from tests import test_packing_golden as TG
    from spark_scheduler_tpu.ops.batched import batched_fifo_pack, make_app_batch

    emax, num_zones = TG.EMAX, TG.NUM_ZONES
    device = str(jax.devices()[0])
    rng = np.random.default_rng(1234)
    checked = 0

    # -- single-app kernels vs oracle, on device: the golden suite's own
    #    fixtures and slot-exact assertions (test_packing_golden.check_case)
    for fill in ("tightly-pack", "distribute-evenly", "minimal-fragmentation"):
        for trial in range(TRIALS):
            c = TG.random_cluster(rng, N_NODES, with_labels=trial % 3 == 0)
            driver_req = rng.integers(0, 12, size=3).astype(np.int32)
            exec_req = rng.integers(0, 10, size=3).astype(np.int32)
            count = int(rng.integers(0, emax + 1))
            driver_mask = rng.random(N_NODES) < 0.7
            domain = rng.random(N_NODES) < 0.9
            TG.check_case(c, driver_req, exec_req, count, driver_mask, domain, fill)
            checked += 1

    # -- batched FIFO program: admitted rows equal the sequential oracle
    #    threading availability (queue-mode eligibility: valid & schedulable
    #    & ready for drivers too, ops/batched.py queue mode)
    for _ in range(TRIALS // 2):
        c = TG.random_cluster(rng, N_NODES)
        b = 6
        drivers = rng.integers(1, 6, size=(b, 3)).astype(np.int32)
        execs = rng.integers(1, 6, size=(b, 3)).astype(np.int32)
        counts = rng.integers(0, emax + 1, size=b).astype(np.int32)
        apps = make_app_batch(drivers, execs, counts, skippable=np.ones(b, bool))
        out = jax.device_get(
            batched_fifo_pack(c, apps, fill="tightly-pack", emax=emax, num_zones=num_zones)
        )
        avail = np.asarray(c.available).astype(np.int64).copy()
        dom = np.asarray(c.valid)
        e_elig = dom & ~np.asarray(c.unschedulable) & np.asarray(c.ready)
        d_order = G.greedy_priority_order(
            np.asarray(c.available), np.asarray(c.zone_id), np.asarray(c.name_rank),
            e_elig, domain=dom, label_rank=np.asarray(c.label_rank_driver),
        )
        e_order = G.greedy_priority_order(
            np.asarray(c.available), np.asarray(c.zone_id), np.asarray(c.name_rank),
            e_elig, domain=dom, label_rank=np.asarray(c.label_rank_executor),
        )
        for i in range(b):
            g_driver, g_execs, g_ok, _ = G.greedy_spark_bin_pack(
                avail, drivers[i].astype(np.int64), execs[i].astype(np.int64),
                int(counts[i]), d_order, e_order, "tightly-pack",
            )
            assert bool(out.admitted[i]) == g_ok, (i, device)
            if g_ok:
                assert int(out.driver_node[i]) == g_driver, (i, device)
                got_execs = [int(x) for x in out.executor_nodes[i] if x >= 0]
                assert got_execs == list(g_execs), (i, device)
                avail[g_driver] -= drivers[i]
                for e in g_execs:
                    avail[e] -= execs[i]
        checked += 1

    # -- single-AZ batched admission on silicon: every admitted row must be
    #    a reference-acceptable zone pick against the threaded availability
    #    (the same acceptance-set oracle as
    #    tests/test_batched.py::test_batched_single_az_matches_sequential_oracle)
    from tests.test_batched import greedy_single_az_candidates

    for strategy in ("az-aware-tightly-pack", "single-az-tightly-pack"):
        c = TG.random_cluster(rng, N_NODES)
        b = 5
        drivers = rng.integers(1, 5, size=(b, 3)).astype(np.int32)
        execs = rng.integers(1, 5, size=(b, 3)).astype(np.int32)
        counts = rng.integers(1, emax + 1, size=b).astype(np.int32)
        apps = make_app_batch(drivers, execs, counts, skippable=np.ones(b, bool))
        out = jax.device_get(
            batched_fifo_pack(c, apps, fill=strategy, emax=emax, num_zones=num_zones)
        )
        avail = np.asarray(c.available).astype(np.int64).copy()
        sched = np.asarray(c.schedulable).astype(np.int64)
        zone = np.asarray(c.zone_id)
        dom = np.asarray(c.valid)
        e_elig = dom & ~np.asarray(c.unschedulable) & np.asarray(c.ready)
        d_order = G.greedy_priority_order(
            np.asarray(c.available), zone, np.asarray(c.name_rank),
            e_elig, domain=dom, label_rank=np.asarray(c.label_rank_driver),
        )
        e_order = G.greedy_priority_order(
            np.asarray(c.available), zone, np.asarray(c.name_rank),
            e_elig, domain=dom, label_rank=np.asarray(c.label_rank_executor),
        )
        for i in range(b):
            acceptable, ok = greedy_single_az_candidates(
                avail, sched, zone, d_order, e_order,
                drivers[i].astype(np.int64), execs[i].astype(np.int64),
                int(counts[i]), strategy,
            )
            assert bool(out.admitted[i]) == ok, (strategy, i, device)
            if ok:
                drv = int(out.driver_node[i])
                got_execs = [int(x) for x in out.executor_nodes[i] if x >= 0]
                assert (drv, got_execs) in acceptable, (strategy, i, device)
                avail[drv] -= drivers[i]
                for e in got_execs:
                    avail[e] -= execs[i]
        checked += 1

    # -- segmented serving windows on silicon: multi-segment scan equals
    #    per-segment solves threaded through the committed base (the
    #    windowed == solo serving property, core/solver.py pack_window)
    import dataclasses

    from tests.test_window_serving import _random_segments, _segment_batch

    for _ in range(2):
        c = TG.random_cluster(rng, N_NODES)
        segments = _random_segments(rng, 4, N_NODES)
        apps, real_row_of = _segment_batch(segments, N_NODES)
        got = jax.device_get(
            batched_fifo_pack(c, apps, fill="tightly-pack", emax=8, num_zones=num_zones)
        )
        base = np.asarray(c.available).copy()
        for s_idx, seg in enumerate(segments):
            sub, sub_real = _segment_batch([seg], N_NODES)
            ci = dataclasses.replace(c, available=base.astype(np.int32))
            want = jax.device_get(
                batched_fifo_pack(ci, sub, fill="tightly-pack", emax=8,
                                  num_zones=num_zones)
            )
            last = sub_real[0]
            real = real_row_of[s_idx]
            assert bool(got.admitted[real]) == bool(want.admitted[last]), (s_idx, device)
            assert int(got.driver_node[real]) == int(want.driver_node[last]), (s_idx, device)
            assert np.array_equal(
                np.asarray(got.executor_nodes[real]),
                np.asarray(want.executor_nodes[last]),
            ), (s_idx, device)
            if bool(want.admitted[last]):
                drv = int(want.driver_node[last])
                base[drv] -= np.asarray(seg["rows"][-1][0])
                for e in np.asarray(want.executor_nodes[last]):
                    if e >= 0:
                        base[e] -= np.asarray(seg["rows"][-1][1])
        checked += 1

    # -- Pallas SEGMENTED WINDOW kernel on silicon: the scan-over-segments
    #    Mosaic program must equal the segmented XLA scan
    #    decision-for-decision for all six strategies (plain fills, and the
    #    single-AZ wrappers through make_gang_solver).
    from spark_scheduler_tpu.ops.pallas_window import (
        PALLAS_FILLS,
        PALLAS_SINGLE_AZ,
        pallas_available,
        window_pack_pallas,
    )

    if pallas_available():
        from tests.test_pallas_window import _cluster as _pw_cluster
        from tests.test_pallas_window import _random_window as _pw_window

        for fill in PALLAS_FILLS + tuple(PALLAS_SINGLE_AZ):
            prng = np.random.default_rng(97 + len(fill))
            c = _pw_cluster(prng, N_NODES)
            apps, win, flat_map = _pw_window(
                prng, N_NODES, n_requests=4, max_rows=4, emax=emax
            )
            want = jax.device_get(
                batched_fifo_pack(c, apps, fill=fill, emax=emax,
                                  num_zones=num_zones)
            )
            meta, execs_w, base_after = (
                jax.device_get(x)
                for x in window_pack_pallas(
                    c, win, fill=fill, emax=emax, num_zones=num_zones
                )
            )
            for bi, (s, j) in enumerate(flat_map):
                assert meta[s, j, 1] == want.admitted[bi], (
                    "pallas-window", fill, bi, device)
                assert meta[s, j, 0] == want.driver_node[bi], (
                    "pallas-window", fill, bi, device)
                assert np.array_equal(
                    execs_w[s, j], np.asarray(want.executor_nodes[bi])
                ), ("pallas-window", fill, bi, device)
            assert np.array_equal(
                np.asarray(base_after), np.asarray(want.available_after)
            ), ("pallas-window", fill, "base", device)
            checked += 1

    return {"device": device, "cases_checked": checked, "parity": "ok"}


def main() -> int:
    print(json.dumps(run()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
