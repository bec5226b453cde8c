"""Segmented serving windows as one Pallas TPU (Mosaic) kernel.

`core/solver.pack_window` expresses a serving window as SEGMENTS — each
/predicates request is its FIFO-earlier hypothetical rows followed by its
own committing row, availability rewinding to the committed base between
segments and the node priority orders re-sorted per segment from the
segment-start availability (the sort at resource.go:299). Mosaic has no
in-kernel sort, so no single node layout can bake in one priority order
for every segment.

The TPU-native factoring here splits the work by what each engine is good
at:

  - XLA, per segment: the eligibility masks and the priority SORTS from the
    committed base (fused device sorts — recomputing them per segment is
    exactly what the reference does per request);
  - Mosaic, per segment: the sequential row walk (hypothetical earlier
    drivers + the committing row) with availability resident in VMEM
    scratch across rows (TPU grid iterations execute sequentially on a
    core, so scratch carries the walk's state chip-side and it never
    round-trips to HBM — the loop-trip overhead the XLA scan pays).
    Per-row requests, counts and flags arrive by scalar prefetch into
    SMEM. The kernel takes the priority orders as per-position RANK
    tensors and every "first in priority order" reduction is an argmin
    over the rank key, so ONE kernel serves every segment's (fresh) orders.

The executor fills are re-derived as iterative masked-argmin placement
(`emax` rounds of "first open position") instead of cumsum + searchsorted,
because a short static loop of VPU reductions beats a 10k-lane prefix scan
and Mosaic has no native searchsorted. Fill derivations (reference loops ->
argmin keys over the segment's executor rank):

  tightly-pack (pack_tightly.go:45-61): fill each node before moving on
      == every slot goes to the best-ranked node with remaining capacity
      -> key = rank.
  distribute-evenly (distribute_evenly.go:49-71): one executor per open
      node per round, rounds in priority order
      == every slot goes to the open node with lexicographically
      smallest (slots already placed there, rank)
      -> key = placed * Npad + rank  (placed <= emax, so no overflow).
  minimal-fragmentation (minimal_fragmentation.go:68-98): smallest single
      node fitting the whole gang, else consume nodes in (capacity desc,
      rank asc) order while the running clamped total stays <= count,
      remainder on the smallest not-consumed node that fits it
      -> <= emax consume rounds of masked max + two masked-min reductions.

All six strategies run in-kernel: the single-AZ wrappers run their per-zone
fill and efficiency-scored zone pick in the kernel too (make_gang_solver).

A `lax.scan` over segments threads the committed base: the commit row's
placement (the kernel reports per-row driver/executor picks) is
scatter-subtracted in XLA between segments. Decisions are bit-identical to
the segmented XLA scan (`ops/batched.batched_fifo_pack` window mode) — the
parity suite (tests/test_pallas_window.py) compares the two paths
decision-for-decision, and the serving integration reuses the solver's
existing blob/fetch contract unchanged.

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
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from spark_scheduler_tpu.models.cluster import ClusterTensors, INT32_INF
from spark_scheduler_tpu.ops.packing import _rank_of_position
from spark_scheduler_tpu.ops.sorting import priority_order, zone_ranks

PALLAS_FILLS = ("tightly-pack", "distribute-evenly", "minimal-fragmentation")

# Single-AZ strategies the kernel serves:
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


# The kernel keeps the whole node axis resident in VMEM, so its size is
# bounded by it. Compiled for a described v5e (padded node buckets): at
# 131,072 nodes every served window bucket compiled, up to 256 segments x
# 256 rows; at 262,144 the kernel compiled with 16 and 64 rows but not
# 256, and 524,288 and 1,048,576 were refused ("Ran out of memory in
# memory space vmem"). Larger shapes route to the XLA scan: a rule on the
# shape, never a caught error.
PALLAS_MAX_NODES = 131_072


def _layout_rows(n: int) -> int:
    return _SUBLANES if n >= _SUBLANE_FOLD_MIN_NODES else 1


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def make_driver_selector(count, cap_e, cap_wd, fit_d, elig_d, drank):
    """Driver-selection closure of the kernel's gang solve: picks the
    best-ranked feasible driver via the feasibility identity
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
    """Executor-fill closure of the kernel's gang solve: `emax` rounds of
    masked-argmin placement, parameterized by the priority KEY tensor (the
    segment's executor rank). `key` must be a permutation over real
    positions padded with INF; `node_val` holds the output node id per
    position."""
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
    """THE per-gang solve of the kernel: driver selection + executor fill
    for the plain fills, and for the single-AZ wrappers the per-zone pack,
    efficiency-scored strictly-greater zone pick, and az-aware plain
    fallback (single_az.go:23-97 / az_aware_pack_tightly.go:27-38).

    `key`/`node_val` parameterize the priority walk exactly as
    make_fill_runner documents;
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


_PALLAS_AVAILABLE: bool | None = None


def pallas_available() -> bool:
    """Whether the routing layer may send work to the Mosaic kernel.

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


class SegmentedWindow(NamedTuple):
    """A serving window re-shaped segment-major for the Pallas path.

    S segments (one per /predicates request), each padded to R rows; row
    [s, r] is the r-th FIFO row of request s (its pending earlier drivers,
    then — at index row_count[s]-1 — the request's own application).
    Padding rows carry valid=False."""

    driver_req: jnp.ndarray  # [S, R, 3] i32
    exec_req: jnp.ndarray  # [S, R, 3] i32
    exec_count: jnp.ndarray  # [S, R] i32
    valid: jnp.ndarray  # [S, R] bool
    skippable: jnp.ndarray  # [S, R] bool
    row_count: jnp.ndarray  # [S] i32 — real rows per segment
    driver_cand: jnp.ndarray  # [S, N] bool — the request's kube candidates
    domain: jnp.ndarray  # [S, N] bool — the request's affinity domain


def _make_window_kernel(
    fill: str, emax: int, n_pad: int, rows: int, *, num_zones: int = 0
):
    """Per-SEGMENT row walk in NODE order with rank-key argmins.

    Per row: node capacities, then make_gang_solver (driver feasibility
    identity, the three executor fills, the single-AZ zone loop), then
    the admitted gang's subtraction and strict-FIFO blocking. Positions
    are node indices, and every priority walk keys on the segment's rank
    tensors (drank/erank)."""

    INF = INT32_INF
    cols = n_pad // rows

    def kernel(
        dreq_ref,  # SMEM [R, 3] i32
        ereq_ref,  # SMEM [R, 3] i32
        cnt_ref,  # SMEM [R] i32
        valid_ref,  # SMEM [R] i32
        skip_ref,  # SMEM [R] i32
        avail_ref,  # VMEM [3, rows, cols] i32 — segment-start availability
        elig_e_ref,  # VMEM [rows, cols] i32
        elig_d_ref,  # VMEM [rows, cols] i32
        drank_ref,  # VMEM [rows, cols] i32 — driver priority rank per node
        erank_ref,  # VMEM [rows, cols] i32 — executor priority rank per node
        zone_ref,  # VMEM [rows, cols] i32 — zone id per node (single-AZ)
        sched_ref,  # VMEM [3, rows, cols] i32 — schedulable (single-AZ)
        meta_out,  # VMEM [R, 4] i32
        execs_out,  # VMEM [R, emax] i32 (node ids)
        avail_scr,  # VMEM [3, rows, cols] i32 scratch
        blocked_scr,  # SMEM [1] i32 scratch
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
        erank = erank_ref[:]

        raw_count = cnt_ref[b]
        too_big = raw_count > emax
        count = jnp.minimum(raw_count, emax)
        valid = valid_ref[b] != 0
        skippable = skip_ref[b] != 0
        blocked_in = blocked_scr[0] != 0

        # --- node capacities (ops/capacity.py node_capacities, exact
        # integer semantics: per dim 0 if reserved > avail, INF if req == 0,
        # else floor((avail-reserved)/req); node cap = max(min over dims, 0))
        shape = (rows, cols)
        cap_e = jnp.full(shape, INF, jnp.int32)
        cap_wd = jnp.full(shape, INF, jnp.int32)
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

        # Gang math (make_gang_solver), keyed on the segment's rank
        # tensors.
        slot_iota = jax.lax.broadcasted_iota(jnp.int32, (1, emax), 1)
        solve = make_gang_solver(
            fill,
            num_zones=num_zones, emax=emax, n_pad=n_pad, shape=shape,
            count=count, cap_e=cap_e, cap_wd=cap_wd, fit_d=fit_d,
            elig_e=elig_e, elig_d=elig_d, drank=drank,
            key=erank, node_val=iota, slot_iota=slot_iota,
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

        # Strict FIFO: a non-skippable valid failure blocks the rest of the
        # segment (resource.go:241-249).
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

    return kernel


@partial(
    jax.jit,
    static_argnames=("fill", "emax", "num_zones", "interpret"),
)
def window_pack_pallas(
    cluster: ClusterTensors,
    win: SegmentedWindow,
    *,
    fill: str,
    emax: int,
    num_zones: int,
    interpret: bool = False,
):
    """Serve a segmented window: scan over segments, XLA sorts per segment
    from the committed base, Mosaic row walk per segment.

    Returns (meta [S,R,4] i32, execs [S,R,emax] i32, base_after [N,3]) —
    meta rows are (driver_node, admitted, packed, 0), in node indices."""
    if fill not in PALLAS_FILLS and fill not in PALLAS_SINGLE_AZ:
        raise ValueError(
            f"pallas window path supports "
            f"{PALLAS_FILLS + tuple(PALLAS_SINGLE_AZ)}"
        )
    n = cluster.available.shape[0]
    s, r = win.exec_count.shape
    rows = _layout_rows(n)
    tile = rows * _LANES
    n_pad = _round_up(max(n, tile), tile)
    cols = n_pad // rows
    pad = n_pad - n

    kernel = _make_window_kernel(fill, emax, n_pad, rows, num_zones=num_zones)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(r,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 7,
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((3, rows, cols), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )

    def fold(x, fill_value):
        """[N] -> [rows, cols] node-order tile."""
        return jnp.pad(x, (0, pad), constant_values=fill_value).reshape(
            rows, cols
        )

    def step(base, seg):
        dreq, ereq, cnt, valid, skip, row_count, cand, domain = seg

        def live_segment():
            # Per-segment eligibility + priority sorts from the committed
            # base (ops/batched.py masked mode, resource.go:299 semantics).
            dom = domain & cluster.valid
            driver_elig = dom & cand
            exec_elig = dom & ~cluster.unschedulable & cluster.ready
            zrank = zone_ranks(cluster, dom, num_zones, available=base)
            d_order, _ = priority_order(
                cluster, driver_elig, zrank, cluster.label_rank_driver,
                available=base,
            )
            e_order, _ = priority_order(
                cluster, exec_elig, zrank, cluster.label_rank_executor,
                available=base,
            )
            drank = _rank_of_position(d_order)
            erank = _rank_of_position(e_order)

            avail_tile = (
                jnp.pad(base.T.astype(jnp.int32), ((0, 0), (0, pad)))
                .reshape(3, rows, cols)
            )
            # Zone ids padded with an out-of-range id (padding matches no
            # zone); schedulable feeds the single-AZ zone-efficiency
            # scoring — node order, same fold as every other tile.
            zone_tile = fold(cluster.zone_id.astype(jnp.int32), num_zones)
            sched_tile = (
                jnp.pad(
                    jnp.asarray(cluster.schedulable).T.astype(jnp.int32),
                    ((0, 0), (0, pad)),
                ).reshape(3, rows, cols)
            )
            return pl.pallas_call(
                kernel,
                out_shape=[
                    jax.ShapeDtypeStruct((r, 4), jnp.int32),
                    jax.ShapeDtypeStruct((r, emax), jnp.int32),
                ],
                grid_spec=grid_spec,
                interpret=interpret,
            )(
                dreq.astype(jnp.int32),
                ereq.astype(jnp.int32),
                cnt.astype(jnp.int32),
                valid.astype(jnp.int32),
                skip.astype(jnp.int32),
                avail_tile,
                fold(exec_elig.astype(jnp.int32), 0),
                fold(driver_elig.astype(jnp.int32), 0),
                fold(drank, INT32_INF),
                fold(erank, INT32_INF),
                zone_tile,
                sched_tile,
            )

        def dead_segment():
            # S is BUCKETED: padding segments skip the sorts and the kernel
            # outright, so a small window's device cost tracks its real
            # request count, not the bucket.
            return (
                jnp.zeros((r, 4), jnp.int32),
                jnp.full((r, emax), -1, jnp.int32),
            )

        meta, execs = jax.lax.cond(row_count > 0, live_segment, dead_segment)
        # Commit the REQUEST row's placement (the last real row) into the
        # base for the next segment (ops/batched.py window mode).
        ci = jnp.maximum(row_count - 1, 0)
        c_admit = (meta[ci, 1] != 0) & (row_count > 0)
        c_driver = meta[ci, 0]
        c_execs = execs[ci]
        exec_counts = (
            jnp.zeros(n, jnp.int32)
            .at[jnp.clip(c_execs, 0, n - 1)]
            .add(jnp.where(c_execs >= 0, 1, 0))
        )
        delta = exec_counts[:, None] * ereq[ci][None, :] + jnp.where(
            (jnp.arange(n) == c_driver)[:, None] & (c_driver >= 0),
            dreq[ci][None, :],
            0,
        )
        base = jnp.where(c_admit, base - delta.astype(base.dtype), base)
        return base, (meta, execs)

    base_after, (meta, execs) = jax.lax.scan(
        step,
        jnp.asarray(cluster.available),
        (
            win.driver_req, win.exec_req, win.exec_count,
            win.valid, win.skippable, win.row_count,
            win.driver_cand, win.domain,
        ),
    )
    return meta, execs, base_after


def segmented_window_from_flat(
    drv_arr,  # [B, 3] int — flat rows, segment-major
    exc_arr,  # [B, 3] int
    counts,  # [B] int
    skip_arr,  # [B] bool
    row_counts,  # [S] int — rows per segment (sum == B)
    cand_masks,  # list/array of [N] bool — per segment
    domain_masks,  # list/array of [N] bool — per segment
    *,
    pad_segments: int,
    pad_rows: int,
):
    """THE SegmentedWindow layout builder (single owner): scatter flat
    segment-major row arrays into the padded [S, R] shape in a handful of
    vectorized assignments (per-row Python here would sit on the serving
    hot path). Returns (SegmentedWindow, seg_idx, row_idx) — the flat->
    [S, R] index map the fetch side uses to flatten the device blob."""
    s = len(row_counts)
    rc = np.asarray(row_counts, np.int64)
    seg_idx = np.repeat(np.arange(s, dtype=np.int64), rc)
    row_idx = np.concatenate(
        [np.arange(k, dtype=np.int64) for k in rc]
    ) if s else np.zeros(0, np.int64)
    n = len(cand_masks[0])
    dreq = np.zeros((pad_segments, pad_rows, 3), np.int32)
    ereq = np.zeros((pad_segments, pad_rows, 3), np.int32)
    cnt = np.zeros((pad_segments, pad_rows), np.int32)
    valid = np.zeros((pad_segments, pad_rows), bool)
    skip = np.zeros((pad_segments, pad_rows), bool)
    row_count = np.zeros(pad_segments, np.int32)
    cand = np.zeros((pad_segments, n), bool)
    dom = np.zeros((pad_segments, n), bool)
    dreq[seg_idx, row_idx] = drv_arr
    ereq[seg_idx, row_idx] = exc_arr
    cnt[seg_idx, row_idx] = counts
    valid[seg_idx, row_idx] = True
    skip[seg_idx, row_idx] = skip_arr
    row_count[:s] = rc
    cand[:s] = np.stack(cand_masks)
    dom[:s] = np.stack(domain_masks)
    win = SegmentedWindow(
        driver_req=dreq, exec_req=ereq, exec_count=cnt, valid=valid,
        skippable=skip, row_count=row_count, driver_cand=cand, domain=dom,
    )
    return win, seg_idx, row_idx


def make_segmented_window(
    requests_rows,  # list of list[(driver_req[3], exec_req[3], count, skip)]
    cand_masks,  # list of [N] bool — per request
    domain_masks,  # list of [N] bool — per request
    *,
    row_bucket: int = 16,
    pad_segments: int | None = None,
    pad_rows: int | None = None,
) -> SegmentedWindow:
    """List-of-rows convenience front-end over `segmented_window_from_flat`
    (tests, smoke). `pad_segments`/`pad_rows` override the defaults for
    callers with their own bucketing policy; padding segments have
    row_count 0 and are skipped at runtime."""
    s = len(requests_rows)
    r = 1
    for rws in requests_rows:
        r = max(r, len(rws))
    r = pad_rows if pad_rows is not None else _round_up(r, row_bucket)
    s_pad = pad_segments if pad_segments is not None else s
    rc = [len(rws) for rws in requests_rows]
    flat = [row for rws in requests_rows for row in rws]
    win, _, _ = segmented_window_from_flat(
        np.asarray([row[0] for row in flat], np.int32).reshape(-1, 3),
        np.asarray([row[1] for row in flat], np.int32).reshape(-1, 3),
        np.asarray([row[2] for row in flat], np.int32),
        np.asarray([bool(row[3]) for row in flat]),
        rc,
        cand_masks,
        domain_masks,
        pad_segments=s_pad,
        pad_rows=r,
    )
    return win


def window_pallas_eligible(fill: str, n_nodes: int) -> bool:
    """Whether the segmented serving-window Pallas path serves this
    strategy at this (padded) node count on this backend — all six
    strategies (the plain fills, and the single-AZ wrappers: per-zone
    fill + efficiency-scored zone pick through the shared
    make_gang_solver), up to the VMEM bound PALLAS_MAX_NODES."""
    return (
        (fill in PALLAS_FILLS or fill in PALLAS_SINGLE_AZ)
        and n_nodes <= PALLAS_MAX_NODES
        and pallas_available()
    )
