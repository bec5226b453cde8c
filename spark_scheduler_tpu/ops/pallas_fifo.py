"""The FIFO gang-admission queue as ONE Pallas TPU kernel.

`ops/batched.batched_fifo_pack` expresses queue admission as a `lax.scan`
whose per-step body is a handful of O(N) vector ops. At 10k nodes the step
body is ~microseconds of VPU work, so the scan is dominated by loop-trip
overhead (HBM round-trips for the carried availability between steps and
XLA's per-iteration scheduling). This module removes that overhead the
TPU-native way: the ENTIRE queue runs inside one Mosaic kernel with

  - the availability tensor resident in VMEM scratch across grid steps
    (TPU grid iterations execute sequentially on a core, so scratch carries
    the scan state chip-side — it never round-trips to HBM);
  - per-app parameters (requests, counts, flags) delivered via scalar
    prefetch into SMEM;
  - the executor fills re-derived as iterative masked-argmin placement
    (`emax` rounds of "first open position") instead of
    cumsum + searchsorted, because a short static loop of VPU reductions
    beats a 10k-lane prefix scan and Mosaic has no native searchsorted.

Semantics are bit-identical to `batched_fifo_pack` in queue mode (shared
eligibility, priority orders fixed from the starting availability — the
`fitEarlierDrivers` semantics of resource.go:221-258): the golden-parity
suite (tests/test_pallas_fifo.py) and the on-silicon smoke
(hack/tpu_parity_smoke.py) compare the two paths decision-for-decision.

Fill derivations (reference loops -> argmin keys):

  tightly-pack (pack_tightly.go:45-61): fill each node before moving on
      == every slot goes to the FIRST position with remaining capacity
      -> key = position.
  distribute-evenly (distribute_evenly.go:49-71): one executor per open
      node per round, rounds in position order
      == every slot goes to the open position with lexicographically
      smallest (slots already placed there, position)
      -> key = placed * Npad + position  (placed <= emax, so no overflow).
  minimal-fragmentation (minimal_fragmentation.go:68-98): smallest single
      node fitting the whole gang, else consume nodes in (capacity desc,
      position asc) order while the running clamped total stays <= count,
      remainder on the smallest not-consumed node that fits it
      -> <= emax consume rounds of masked max + two masked-min reductions.

This kernel is the queue-mode hot path (the north-star 10k-node x 1k-app
batched admission), covering all six strategies — the single-AZ wrappers
run their per-zone fill + efficiency-scored zone pick in-kernel. Segmented
serving windows run on their own Mosaic path (ops/pallas_window, sharing
this module's fill/driver closures); per-app-masked batches keep the XLA
scan.

Documented deviation (single-AZ zone scoring): the zone efficiency is a
float32 mean, and this kernel sums it as a weighted tile reduction while
the XLA scan sums gathered per-entry values — different summation orders
can differ in the last ulp, so a cross-zone tie closer than ~1 ulp may
break differently between the two paths (same class of deviation as the
module-documented Go-rounding difference in ops/efficiency.py; bit-exact
float reductions across different programs are not guaranteeable). The
parity suites use fixed seeds and are deterministic per jax build.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from spark_scheduler_tpu.models.cluster import ClusterTensors, INT32_INF
from spark_scheduler_tpu.ops.batched import (
    AppBatch,
    BatchedPacking,
    queue_mode_orders,
)

PALLAS_FILLS = ("tightly-pack", "distribute-evenly", "minimal-fragmentation")

# Single-AZ strategies the queue kernel serves (VERDICT r3 #4):
# strategy -> (inner fill, az-aware plain fallback, executors counted in
# the zone-efficiency reservation — the minimalFragmentation quirk,
# ops/efficiency.py avg_packing_efficiency docstring).
PALLAS_SINGLE_AZ = {
    "single-az-tightly-pack": ("tightly-pack", False, True),
    "single-az-minimal-fragmentation": ("minimal-fragmentation", False, False),
    "az-aware-tightly-pack": ("tightly-pack", True, True),
}

_LANES = 128  # int32 lane width
_SUBLANES = 8  # VPU sublanes
# Above this node count the position axis folds row-major into an
# [8, Np/8] tile so vector ops drive all 8 VPU sublanes (measured ~15%
# faster at 10k+ nodes); below it the flat [1, Np] row wins on fixed
# overhead (measured ~35% faster at 1k nodes on a v5e).
_SUBLANE_FOLD_MIN_NODES = 4096


# Both Mosaic kernels keep the whole node axis resident in VMEM, so their
# size is bounded by it. Compiled for a described v5e (PR 21, padded node
# buckets): at 131,072 nodes every served window bucket compiled, up to
# 256 segments x 256 rows; at 262,144 the window kernel compiled with 16
# and 64 rows but not 256, and 524,288 and 1,048,576 were refused for
# both kernels ("Ran out of memory in memory space vmem"). The queue
# kernel prefetches its per-app requests into SMEM: 1,024 apps overflowed
# it ("Used 1.01M of 1.00M smem"), 512 fit. Larger shapes route to the XLA
# scan: a rule on the shape, never a caught error.
PALLAS_MAX_NODES = 131_072
PALLAS_MAX_APPS = 512


def _layout_rows(n: int) -> int:
    return _SUBLANES if n >= _SUBLANE_FOLD_MIN_NODES else 1


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pallas_eligible(apps: "AppBatch", fill: str) -> bool:
    """THE single definition of what the Pallas queue kernel supports:
    plain queue mode (no per-app masks, no segmented windows) with any of
    the six strategies — the three plain fills, and since r4 the
    single-AZ wrappers (per-zone fill + efficiency-scored zone pick
    in-kernel), up to PALLAS_MAX_APPS apps. Shared by every routing site
    so eligibility cannot drift when the kernel learns new shapes.
    (Segmented serving windows have their own Mosaic path,
    ops/pallas_window.)"""
    return (
        (fill in PALLAS_FILLS or fill in PALLAS_SINGLE_AZ)
        and apps.commit is None
        and apps.driver_cand is None
        and apps.domain is None
        and apps.driver_req.shape[-2] <= PALLAS_MAX_APPS
    )


def make_driver_selector(count, cap_e, cap_wd, fit_d, elig_d, drank):
    """Shared driver-selection closure for the Mosaic kernels (queue AND
    segmented-window paths — ONE implementation so the two cannot drift).
    Picks the best-ranked feasible driver via the feasibility identity
    (ops/packing.py pack_one_app): reserving the driver on node i only
    changes node i's executor capacity."""
    INF = INT32_INF

    def select_driver(zone_mask):
        cap_e_m = jnp.where(zone_mask, cap_e, 0)
        cap_wd_m = jnp.where(zone_mask, cap_wd, 0)
        cap_e_c = jnp.minimum(cap_e_m, count)
        cap_wd_c = jnp.minimum(cap_wd_m, count)
        total_base = jnp.sum(cap_e_c)
        total_if = total_base - cap_e_c + cap_wd_c
        feasible = elig_d & zone_mask & fit_d & (total_if >= count)
        best_rank = jnp.min(jnp.where(feasible, drank, INF))
        found = best_rank < INF
        # drank is a permutation rank -> at most one position matches.
        is_drv = feasible & (drank == best_rank)
        # Executor capacities with the chosen driver reserved.
        caps_fill = jnp.where(is_drv, cap_wd_m, cap_e_m)
        return found, is_drv, caps_fill

    return select_driver


def make_fill_runner(
    inner_fill, emax, n_pad, shape, count, key, node_val, slot_iota
):
    """Shared executor-fill closure for the Mosaic kernels: `emax` rounds
    of masked-argmin placement, parameterized by the priority KEY tensor —
    `iota` itself for the queue kernel (whose node axis is pre-permuted
    into priority order) and the per-segment executor rank for the window
    kernel. `key` must be a permutation over real positions padded with
    INF; `node_val` holds the output node id per position. ONE
    implementation serves both kernels so fill semantics cannot drift."""
    INF = INT32_INF

    def run_fill(ok, caps_fill, elig_mask):
        execs_row = jnp.full((1, emax), -1, jnp.int32)
        exec_counts = jnp.zeros(shape, jnp.int32)
        if inner_fill == "tightly-pack":
            remaining = caps_fill
            for j in range(emax):
                place = ok & (j < count)
                k_sel = jnp.min(jnp.where(remaining > 0, key, INF))
                hit = (key == k_sel) & (remaining > 0) & place
                node_j = jnp.sum(jnp.where(hit, node_val, 0))
                execs_row = jnp.where(
                    (slot_iota == j) & place, node_j, execs_row
                )
                remaining = remaining - hit
                exec_counts = exec_counts + hit
        elif inner_fill == "distribute-evenly":
            # dkey = placed * Npad + key over open positions; placed never
            # exceeds emax and key < Npad at open positions, so the key
            # stays far below int32 range.
            for j in range(emax):
                place = ok & (j < count)
                open_ = elig_mask & (exec_counts < caps_fill)
                dkey = exec_counts * n_pad + key
                k_min = jnp.min(jnp.where(open_, dkey, INF))
                hit = open_ & (dkey == k_min) & place
                node_j = jnp.sum(jnp.where(hit, node_val, 0))
                execs_row = jnp.where(
                    (slot_iota == j) & place, node_j, execs_row
                )
                exec_counts = exec_counts + hit
        elif inner_fill == "minimal-fragmentation":
            cap_ok = caps_fill > 0
            caps_c = jnp.minimum(caps_fill, count)
            # Branch A: smallest single node fitting the whole gang
            # (minimal_fragmentation.go:68-78): min capacity, then best
            # priority (earliest key) on capacity ties.
            mask_a = cap_ok & (caps_fill >= count)
            exists_a = jnp.any(mask_a)
            min_cap_a = jnp.min(jnp.where(mask_a, caps_fill, INF))
            tie_a = mask_a & (caps_fill == min_cap_a)
            rank_a = jnp.min(jnp.where(tie_a, key, INF))
            sel_a = tie_a & (key == rank_a)
            # Branch B: consume (clamped capacity desc, priority asc) while
            # the running total stays <= count (the maximal prefix of the
            # reference's desc sort), remainder on the smallest
            # not-consumed node with UNCLAMPED capacity >= remainder
            # (minimal_fragmentation.go:80-98).
            use_b = ok & ~exists_a
            consumed = jnp.zeros(shape, jnp.bool_)
            placed_total = jnp.int32(0)
            for _ in range(emax):
                open_b = cap_ok & ~consumed
                c_max = jnp.max(jnp.where(open_b, caps_c, -1))
                tie_k = open_b & (caps_c == c_max)
                rank_k = jnp.min(jnp.where(tie_k, key, INF))
                take = use_b & (c_max > 0) & (placed_total + c_max <= count)
                hit = tie_k & (key == rank_k) & take
                node_k = jnp.sum(jnp.where(hit, node_val, 0))
                in_span = (
                    (slot_iota >= placed_total)
                    & (slot_iota < placed_total + c_max)
                    & take
                )
                execs_row = jnp.where(in_span, node_k, execs_row)
                exec_counts = exec_counts + jnp.where(hit, c_max, 0)
                consumed = consumed | hit
                placed_total = placed_total + jnp.where(take, c_max, 0)
            remainder = count - placed_total
            mask_fin = cap_ok & ~consumed & (caps_fill >= remainder)
            min_cap_f = jnp.min(jnp.where(mask_fin, caps_fill, INF))
            tie_f = mask_fin & (caps_fill == min_cap_f)
            rank_f = jnp.min(jnp.where(tie_f, key, INF))
            sel_f = tie_f & (key == rank_f)
            need_fin = use_b & (remainder > 0)
            fin_take = ok & (exists_a | need_fin)
            # Logical blend, not jnp.where: Mosaic cannot select between
            # two i1 vectors.
            fin_sel = (sel_a & exists_a) | (sel_f & ~exists_a)
            fin_count = jnp.where(exists_a, count, remainder)
            fin_hit = fin_sel & fin_take
            node_fin = jnp.sum(jnp.where(fin_hit, node_val, 0))
            fin_start = jnp.where(exists_a, 0, placed_total)
            in_fin = (
                (slot_iota >= fin_start)
                & (slot_iota < fin_start + fin_count)
                & fin_take
            )
            # Branch A overwrites any branch-B spans (it is exclusive).
            execs_row = jnp.where(
                exists_a & (slot_iota < count) & ok,
                node_fin,
                jnp.where(in_fin, node_fin, execs_row),
            )
            exec_counts = jnp.where(
                exists_a & ok,
                jnp.where(sel_a, count, 0),
                exec_counts + jnp.where(fin_hit, fin_count, 0),
            )
        else:  # pragma: no cover — guarded by the kernel builders
            raise ValueError(f"unsupported fill for pallas: {inner_fill}")
        return execs_row, exec_counts

    return run_fill


def make_gang_solver(
    fill: str,
    *,
    num_zones: int,
    emax: int,
    n_pad: int,
    shape,
    count,
    cap_e,
    cap_wd,
    fit_d,
    elig_e,
    elig_d,
    drank,
    key,
    node_val,
    slot_iota,
    zone,
    sched3,
    avail3,
    dreq3,
    ereq3,
):
    """THE per-gang solve shared by BOTH Mosaic kernels (queue and
    segmented-window): driver selection + executor fill for the plain
    fills, and for the single-AZ wrappers the per-zone pack,
    efficiency-scored strictly-greater zone pick, and az-aware plain
    fallback (single_az.go:23-97 / az_aware_pack_tightly.go:27-38) —
    ONE implementation so the two kernels cannot drift.

    `key`/`node_val` parameterize the priority walk exactly as
    make_fill_runner documents (position iota for the pre-permuted queue
    kernel, the per-segment executor rank for the window kernel);
    `zone`/`sched3`/`avail3` feed the zone loop and its efficiency scoring
    (`sched3`/`avail3`/`dreq3`/`ereq3` are per-dim reads hoisted by the
    caller — nothing here mutates between zones).

    Returns ``solve() -> (ok, is_drv, execs_row, exec_counts,
    driver_node)``."""
    INF = INT32_INF
    single_az = fill in PALLAS_SINGLE_AZ
    if single_az:
        inner_fill, az_fallback, include_exec_in_reserved = (
            PALLAS_SINGLE_AZ[fill]
        )
    else:
        inner_fill, az_fallback, include_exec_in_reserved = fill, False, True

    select_driver = make_driver_selector(
        count, cap_e, cap_wd, fit_d, elig_d, drank
    )
    run_fill = make_fill_runner(
        inner_fill, emax, n_pad, shape, count, key, node_val, slot_iota
    )

    def solve():
        if not single_az:
            found, is_drv, caps_fill = select_driver(
                jnp.ones(shape, jnp.bool_)
            )
            ok = found  # the feasibility identity guarantees the fill
            execs_row, exec_counts = run_fill(ok, caps_fill, elig_e)
        else:
            # --- per-zone pack + strictly-greater efficiency selection
            # (single_az.go:23-97). Zone "first appearance" rank in driver
            # priority order breaks efficiency ties (single_az.go:58-73);
            # zones with no executor-eligible node are skipped
            # (single_az.go:40-43).
            best_eff = jnp.float32(-1.0)
            best_first = jnp.int32(INF)
            any_valid = jnp.bool_(False)
            is_drv = jnp.zeros(shape, jnp.bool_)
            execs_row = jnp.full((1, emax), -1, jnp.int32)
            exec_counts = jnp.zeros(shape, jnp.int32)
            for z in range(num_zones):
                zmask = zone == z
                zone_first = jnp.min(
                    jnp.where(elig_d & zmask, drank, INF)
                )
                zone_has_exec = jnp.any(elig_e & zmask)
                found_z, is_drv_z, caps_z = select_driver(zmask)
                execs_z, counts_z = run_fill(
                    found_z, caps_z, elig_e & zmask
                )
                # Zone score: mean over ENTRIES (driver + one per executor
                # occurrence) of per-node max dim efficiency with the
                # tentative reservation applied (efficiency.go:85-144).
                w = counts_z + is_drv_z
                eff_cpu = jnp.zeros(shape, jnp.float32)
                eff_mem = jnp.zeros(shape, jnp.float32)
                eff_gpu = jnp.zeros(shape, jnp.float32)
                for d in range(3):
                    sched_d = sched3[d]
                    new_res = jnp.where(is_drv_z, dreq3[d], 0)
                    if include_exec_in_reserved:
                        new_res = new_res + counts_z * ereq3[d]
                    reserved = (sched_d - avail3[d]) + new_res
                    denom = jnp.maximum(sched_d, 1).astype(jnp.float32)
                    eff_d = reserved.astype(jnp.float32) / denom
                    if d == 0:
                        eff_cpu = eff_d
                    elif d == 1:
                        eff_mem = eff_d
                    else:
                        gpu_node = sched_d != 0
                        eff_gpu = jnp.where(gpu_node, eff_d, 0.0)
                node_max = jnp.maximum(
                    eff_gpu, jnp.maximum(eff_cpu, eff_mem)
                )
                entries = (count + 1).astype(jnp.float32)
                eff_z = (
                    jnp.sum(node_max * w.astype(jnp.float32)) / entries
                )
                valid_z = found_z & (zone_first < INF) & zone_has_exec
                better = valid_z & (
                    (eff_z > best_eff)
                    | ((eff_z == best_eff) & (zone_first < best_first))
                )
                best_eff = jnp.where(better, eff_z, best_eff)
                best_first = jnp.where(better, zone_first, best_first)
                any_valid = any_valid | valid_z
                is_drv = (is_drv_z & better) | (is_drv & ~better)
                execs_row = jnp.where(better, execs_z, execs_row)
                exec_counts = jnp.where(better, counts_z, exec_counts)
            # chooseBestResult starts from WorstAvgPackingEfficiency
            # (Max=0.0) and replaces only on strictly-greater, so a zone
            # whose best efficiency is exactly 0.0 is rejected entirely
            # (single_az.go:84-97).
            ok = any_valid & (best_eff > 0.0)
            if az_fallback:
                # az-aware: plain pack when no single zone fits
                # (az_aware_pack_tightly.go:27-38).
                found_p, is_drv_p, caps_p = select_driver(
                    jnp.ones(shape, jnp.bool_)
                )
                execs_p, counts_p = run_fill(found_p, caps_p, elig_e)
                use_p = ~ok & found_p
                is_drv = (is_drv_p & use_p) | (is_drv & ~use_p)
                execs_row = jnp.where(use_p, execs_p, execs_row)
                exec_counts = jnp.where(use_p, counts_p, exec_counts)
                ok = ok | found_p
            is_drv = is_drv & ok
            execs_row = jnp.where(ok, execs_row, -1)
            exec_counts = jnp.where(ok, exec_counts, 0)
        driver_node = jnp.sum(jnp.where(is_drv, node_val, 0))
        return ok, is_drv, execs_row, exec_counts, driver_node

    return solve


def _make_kernel(
    fill: str,
    emax: int,
    n_pad: int,
    n_apps: int,
    rows: int,
    *,
    num_zones: int = 0,
):
    """Build the kernel body. Everything static (fill, emax, padding,
    layout) is closed over; per-app scalars arrive via prefetch refs.

    The position axis is laid out 2D row-major — position p lives at
    [p // cols, p % cols] of a [rows, cols] tile (`_layout_rows`).

    `fill` may be a plain fill OR a PALLAS_SINGLE_AZ strategy: the
    single-AZ path runs the inner fill once per zone (restricted to the
    zone's positions), scores each feasible zone's average packing
    efficiency against the live availability, and keeps the
    strictly-greatest (ties to the zone appearing first in driver
    priority order) — single_az.go:23-97 semantics, entirely in-kernel
    (make_gang_solver, shared with the segmented-window kernel)."""

    INF = INT32_INF
    cols = n_pad // rows
    shape = (rows, cols)

    def kernel(
        dreq_ref,  # SMEM [B, 3] i32 — driver request
        ereq_ref,  # SMEM [B, 3] i32 — executor request
        cnt_ref,  # SMEM [B] i32 — gang size
        valid_ref,  # SMEM [B] i32 — app_valid
        skip_ref,  # SMEM [B] i32 — skippable
        avail_ref,  # VMEM [3, rows, cols] i32 — starting availability (position order)
        elig_e_ref,  # VMEM [rows, cols] i32 — executor eligibility
        elig_d_ref,  # VMEM [rows, cols] i32 — driver eligibility
        drank_ref,  # VMEM [rows, cols] i32 — driver-priority rank per position
        nodeid_ref,  # VMEM [rows, cols] i32 — original node index per position
        zone_ref,  # VMEM [rows, cols] i32 — zone id per position (single-AZ)
        sched_ref,  # VMEM [3, rows, cols] i32 — schedulable (single-AZ scoring)
        meta_out,  # VMEM [B, 4] i32 — (driver_node, admitted, packed, 0)
        execs_out,  # VMEM [B, emax] i32
        avail_out,  # VMEM [3, rows, cols] i32 — availability after all admits
        avail_scr,  # VMEM [3, rows, cols] i32 scratch — the scan carry
        blocked_scr,  # SMEM [1] i32 scratch — strict-FIFO blocked flag
    ):
        b = pl.program_id(0)

        @pl.when(b == 0)
        def _():
            avail_scr[:] = avail_ref[:]
            blocked_scr[0] = 0

        iota = (
            jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) * cols
            + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
        )
        elig_e = elig_e_ref[:] != 0
        elig_d = elig_d_ref[:] != 0
        drank = drank_ref[:]
        node_id = nodeid_ref[:]

        raw_count = cnt_ref[b]
        too_big = raw_count > emax
        count = jnp.minimum(raw_count, emax)
        valid = valid_ref[b] != 0
        skippable = skip_ref[b] != 0
        blocked_in = blocked_scr[0] != 0

        # --- node capacities (ops/capacity.py node_capacities, exact
        # integer semantics: per dim 0 if reserved > avail, INF if req == 0,
        # else floor((avail-reserved)/req); node cap = max(min over dims, 0))
        cap_e = jnp.full(shape, INF, jnp.int32)  # no reservation
        cap_wd = jnp.full(shape, INF, jnp.int32)  # driver reserved
        fit_d = jnp.ones(shape, jnp.bool_)
        for d in range(3):
            a = avail_scr[d]
            er = ereq_ref[b, d]
            dr = dreq_ref[b, d]
            safe = jnp.maximum(er, 1)
            per_e = jnp.where(
                0 > a, 0, jnp.where(er == 0, INF, jnp.floor_divide(a, safe))
            )
            per_wd = jnp.where(
                dr > a,
                0,
                jnp.where(er == 0, INF, jnp.floor_divide(a - dr, safe)),
            )
            cap_e = jnp.minimum(cap_e, per_e)
            cap_wd = jnp.minimum(cap_wd, per_wd)
            fit_d = fit_d & (dr <= a)
        cap_e = jnp.where(elig_e, jnp.maximum(cap_e, 0), 0)
        cap_wd = jnp.where(elig_e, jnp.maximum(cap_wd, 0), 0)

        slot_iota = jax.lax.broadcasted_iota(jnp.int32, (1, emax), 1)
        # The queue kernel's node axis is pre-permuted into executor
        # priority order, so the priority KEY is the position itself.
        solve = make_gang_solver(
            fill,
            num_zones=num_zones, emax=emax, n_pad=n_pad, shape=shape,
            count=count, cap_e=cap_e, cap_wd=cap_wd, fit_d=fit_d,
            elig_e=elig_e, elig_d=elig_d, drank=drank,
            key=iota, node_val=node_id, slot_iota=slot_iota,
            zone=zone_ref[:],
            sched3=[sched_ref[0], sched_ref[1], sched_ref[2]],
            avail3=[avail_scr[0], avail_scr[1], avail_scr[2]],
            dreq3=[dreq_ref[b, 0], dreq_ref[b, 1], dreq_ref[b, 2]],
            ereq3=[ereq_ref[b, 0], ereq_ref[b, 1], ereq_ref[b, 2]],
        )
        ok, is_drv, execs_row, exec_counts, driver_node = solve()

        packed = ok & valid & ~too_big
        admitted = packed & ~blocked_in

        # --- scatter-subtract the admitted gang (resource.go:251-255)
        for d in range(3):
            delta = exec_counts * ereq_ref[b, d] + jnp.where(
                is_drv, dreq_ref[b, d], 0
            )
            a = avail_scr[d]
            avail_scr[d] = jnp.where(admitted, a - delta, a)

        # Strict FIFO: a non-skippable valid failure blocks the rest
        # (resource.go:241-249).
        blocked_scr[0] = jnp.where(
            blocked_in | (valid & ~packed & ~skippable), 1, 0
        ).astype(jnp.int32)

        m_iota = jax.lax.broadcasted_iota(jnp.int32, (1, 4), 1)
        out_driver = jnp.where(admitted, driver_node, -1)
        meta = jnp.where(
            m_iota == 0,
            out_driver,
            jnp.where(
                m_iota == 1,
                admitted.astype(jnp.int32),
                jnp.where(m_iota == 2, packed.astype(jnp.int32), 0),
            ),
        )
        meta_out[pl.ds(b, 1), :] = meta
        execs_out[pl.ds(b, 1), :] = jnp.where(admitted, execs_row, -1)

        @pl.when(b == n_apps - 1)
        def _():
            avail_out[:] = avail_scr[:]

    return kernel


@partial(
    jax.jit, static_argnames=("fill", "emax", "num_zones", "interpret")
)
def fifo_pack_pallas(
    cluster: ClusterTensors,
    apps: AppBatch,
    *,
    fill: str = "tightly-pack",
    emax: int,
    num_zones: int,
    interpret: bool = False,
) -> BatchedPacking:
    """Queue-mode `batched_fifo_pack`, executed as one Pallas kernel.

    All six strategies are supported (plain fills + the single-AZ
    wrappers, whose per-zone pack and efficiency-scored zone pick run
    in-kernel), in queue mode only (no per-app masks, no segmented
    windows) — exactly the shape of the north-star batched admission.
    Callers should route through `fifo_pack_auto`, which falls back to
    the XLA scan everywhere else.
    """
    if not pallas_eligible(apps, fill):
        raise ValueError(
            f"pallas path supports queue mode with "
            f"{PALLAS_FILLS + tuple(PALLAS_SINGLE_AZ)}, got "
            f"fill={fill!r} masked={apps.driver_cand is not None or apps.domain is not None} "
            f"segmented={apps.commit is not None} "
            f"apps={apps.driver_req.shape[0]} (max {PALLAS_MAX_APPS})"
        )

    n = cluster.available.shape[0]
    b = apps.driver_req.shape[0]
    if b == 0:
        # An empty queue admits nothing and leaves availability unchanged
        # (the grid would be (0,) and the kernel would never run).
        return BatchedPacking(
            driver_node=jnp.zeros((0,), jnp.int32),
            executor_nodes=jnp.zeros((0, emax), jnp.int32),
            admitted=jnp.zeros((0,), jnp.bool_),
            packed=jnp.zeros((0,), jnp.bool_),
            available_after=jnp.asarray(cluster.available, jnp.int32),
        )
    rows = _layout_rows(n)
    tile = rows * _LANES
    n_pad = _round_up(max(n, tile), tile)
    cols = n_pad // rows

    (driver_elig, exec_elig, d_order, d_rank, e_order, _zrank) = (
        queue_mode_orders(cluster, num_zones)
    )

    # Re-arrange the node axis into executor-priority position order so the
    # kernel's "first open position" argmin IS the executor priority walk,
    # then fold positions row-major into [rows, cols] (position p at
    # [p // cols, p % cols]) per the sublane layout rule.
    pad_cols = n_pad - n

    def pos_row(x, fill_value):
        row = x[e_order]
        return jnp.pad(row, (0, pad_cols), constant_values=fill_value).reshape(
            rows, cols
        )

    avail_pos = (
        jnp.pad(cluster.available[e_order].T, ((0, 0), (0, pad_cols)))
        .astype(jnp.int32)
        .reshape(3, rows, cols)
    )
    elig_e_pos = pos_row(exec_elig.astype(jnp.int32), 0)
    elig_d_pos = pos_row(driver_elig.astype(jnp.int32), 0)
    drank_pos = pos_row(d_rank, INT32_INF)
    nodeid_pos = pos_row(jnp.arange(n, dtype=jnp.int32), 0)
    # Zone ids padded with an out-of-range id (padding matches no zone);
    # schedulable feeds the single-AZ zone-efficiency scoring.
    zone_pos = pos_row(cluster.zone_id.astype(jnp.int32), num_zones)
    sched_pos = (
        jnp.pad(cluster.schedulable[e_order].T, ((0, 0), (0, pad_cols)))
        .astype(jnp.int32)
        .reshape(3, rows, cols)
    )

    kernel = _make_kernel(fill, emax, n_pad, b, rows, num_zones=num_zones)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 7,
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((3, rows, cols), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    meta, execs, avail_after_pos = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b, 4), jnp.int32),
            jax.ShapeDtypeStruct((b, emax), jnp.int32),
            jax.ShapeDtypeStruct((3, rows, cols), jnp.int32),
        ],
        grid_spec=grid_spec,
        interpret=interpret,
    )(
        apps.driver_req.astype(jnp.int32),
        apps.exec_req.astype(jnp.int32),
        apps.exec_count.astype(jnp.int32),
        apps.app_valid.astype(jnp.int32),
        apps.skippable.astype(jnp.int32),
        avail_pos,
        elig_e_pos,
        elig_d_pos,
        drank_pos,
        nodeid_pos,
        zone_pos,
        sched_pos,
    )

    # Un-permute the availability back into node order.
    avail_after = (
        jnp.zeros_like(cluster.available)
        .at[e_order]
        .set(avail_after_pos.reshape(3, n_pad)[:, :n].T)
    )
    return BatchedPacking(
        driver_node=meta[:, 0],
        executor_nodes=execs,
        admitted=meta[:, 1] != 0,
        packed=meta[:, 2] != 0,
        available_after=avail_after,
    )


_PALLAS_AVAILABLE: bool | None = None


def pallas_available() -> bool:
    """Whether the routing layer may send work to the Mosaic kernels.

    False on any backend but a TPU (the tests' CPU backend keeps the XLA
    scan). On a TPU a trivial kernel is compiled and run once and the
    answer cached; if that probe fails, its error propagates: a broken
    Mosaic toolchain on the chip must stop the program, never turn into
    a quiet switch to the slower scan."""
    global _PALLAS_AVAILABLE
    if _PALLAS_AVAILABLE is None:
        if jax.default_backend() != "tpu":
            _PALLAS_AVAILABLE = False
            return False

        def _probe(x_ref, o_ref):
            o_ref[:] = x_ref[:] + 1

        out = pl.pallas_call(
            _probe,
            out_shape=jax.ShapeDtypeStruct((8, _LANES), jnp.int32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        )(jnp.zeros((8, _LANES), jnp.int32))
        if not np.all(np.asarray(out) == 1):
            raise RuntimeError("Mosaic probe kernel returned a wrong value")
        _PALLAS_AVAILABLE = True
    return _PALLAS_AVAILABLE


def fifo_pack_auto(
    cluster: ClusterTensors,
    apps: AppBatch,
    *,
    fill: str = "tightly-pack",
    emax: int,
    num_zones: int,
    prefer_pallas: bool = True,
) -> BatchedPacking:
    """Route a queue solve to the Pallas kernel when the backend supports
    Mosaic, the request is queue-mode and the cluster fits the kernel
    (PALLAS_MAX_NODES); otherwise the XLA scan. Decisions are identical
    either way (golden-parity tested)."""
    from spark_scheduler_tpu.ops.batched import batched_fifo_pack

    if (
        prefer_pallas
        and pallas_eligible(apps, fill)
        and cluster.available.shape[0] <= PALLAS_MAX_NODES
        and pallas_available()
    ):
        return fifo_pack_pallas(
            cluster, apps, fill=fill, emax=emax, num_zones=num_zones
        )
    return batched_fifo_pack(
        cluster, apps, fill=fill, emax=emax, num_zones=num_zones
    )
