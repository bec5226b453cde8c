"""Vectorized bin-packing strategies with slot-exact reference semantics.

The reference's five strategies (internal/extender/binpack.go:39-54) are
order-dependent greedy loops; here each becomes a closed-form tensor program
built on one observation: for all three executor-distribution kernels, the
per-node capacity vector `cap[i] = floor((avail-reserved)/req)` fully
determines the greedy outcome, so placement = prefix-sums / sorts /
searchsorted over `cap`, and gang feasibility = `sum(cap) >= count`.

  tightly-pack       (binpack/pack_tightly.go:34-63): fill nodes to capacity
      in priority order -> executor slot j lands on the first node whose
      cumulative capacity exceeds j: `searchsorted(cumsum(cap), j, 'right')`.

  distribute-evenly  (binpack/distribute_evenly.go:34-73): round-robin one
      executor per open node per round -> slot j's (round r_j, intra-round
      index k_j) come from searchsorted over the cumulative round sizes
      M[r] = #{i: cap_i > r}; the node is the (k_j+1)-th position with
      cap > r_j.

  minimal-fragmentation (binpack/minimal_fragmentation.go:49-205): if one
      node fits the whole gang, the smallest such node (earliest priority on
      ties) takes it; otherwise consume nodes in (capacity desc, priority
      asc) order while the running total stays <= count, then place the
      remainder on the smallest not-yet-consumed node that fits it.

  single-az-* (binpack/single_az.go:23-97): run the inner packer per zone
      (zones in driver-priority first-appearance order), keep feasible zone
      results, pick the highest average packing efficiency (strictly-greater
      replacement => earliest zone wins ties).

  az-aware-tightly-pack (binpack/az_aware_pack_tightly.go:27-38): single-AZ
      tightly-pack, falling back to plain tightly-pack.

Driver selection (binpack/binpack.go:60-87 SparkBinPack) — "first driver
candidate, in priority order, on which the driver fits and the executors
still pack" — uses the feasibility identity: placing the driver on node d
only changes node d's executor capacity, so total capacity with the driver on
d is `total - cap[d] + cap_with_driver[d]`, an O(N) vectorized check over ALL
driver candidates at once instead of a re-pack per candidate.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from spark_scheduler_tpu.models.cluster import ClusterTensors, INT32_INF
from spark_scheduler_tpu.ops.capacity import node_capacities, fits
from spark_scheduler_tpu.ops.sorting import priority_order, zone_ranks
from spark_scheduler_tpu.ops import efficiency as eff_ops


class Packing(NamedTuple):
    """Device-side PackingResult (binpack/binpack.go:25-31): node indices
    instead of names, -1 for "no node" / padding."""

    driver_node: jnp.ndarray  # i32 scalar
    executor_nodes: jnp.ndarray  # [Emax] i32
    has_capacity: jnp.ndarray  # bool scalar

    @staticmethod
    def empty(emax: int) -> "Packing":
        return Packing(
            driver_node=jnp.int32(-1),
            executor_nodes=jnp.full((emax,), -1, jnp.int32),
            has_capacity=jnp.bool_(False),
        )


def _rank_of_position(order: jnp.ndarray) -> jnp.ndarray:
    """rank[node] = position of node in `order`."""
    n = order.shape[0]
    return jnp.zeros(n, jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))


# ---------------------------------------------------------------------------
# Executor-distribution kernels.
# Each takes capacities *arranged by executor-priority position* plus the
# position->node map, and returns ([Emax] node indices, feasible).
# ---------------------------------------------------------------------------


def _check_cumsum_bound(n: int, emax: int) -> None:
    """Clamping caps to `count` bounds every cumsum at n*emax; guard the int32
    accumulator explicitly rather than overflowing silently. Clusters beyond
    this bound must shard the node axis (parallel/) — which also keeps each
    shard's prefix sums within range."""
    if n * emax >= 2**31:
        raise ValueError(
            f"n_nodes*emax = {n}*{emax} >= 2^31: int32 prefix sums would "
            "overflow; shard the node axis across devices instead of packing "
            "a single flat tensor"
        )


def _fill_tightly(caps_pos, order, count, emax):
    n = caps_pos.shape[0]
    _check_cumsum_bound(n, emax)
    caps = jnp.minimum(caps_pos, count)  # bounds cumsum at n*count
    cum = jnp.cumsum(caps)
    ok = cum[-1] >= count
    j = jnp.arange(emax, dtype=jnp.int32)
    pos = jnp.clip(jnp.searchsorted(cum, j, side="right"), 0, n - 1)
    nodes = jnp.where(j < count, order[pos], -1)
    return nodes.astype(jnp.int32), ok


def _fill_distribute_evenly(caps_pos, order, count, emax):
    n = caps_pos.shape[0]
    _check_cumsum_bound(n, emax)
    caps = jnp.minimum(caps_pos, count)
    ok = jnp.sum(caps) >= count
    # m[r] = number of nodes still open in round r = #{i: cap_i > r}.
    sorted_caps = jnp.sort(caps)
    r = jnp.arange(emax, dtype=jnp.int32)
    m = (n - jnp.searchsorted(sorted_caps, r, side="right")).astype(jnp.int32)
    M = jnp.cumsum(m)  # slots placed through round r
    j = jnp.arange(emax, dtype=jnp.int32)
    r_j = jnp.clip(jnp.searchsorted(M, j, side="right"), 0, emax - 1)
    prev = jnp.where(r_j > 0, M[jnp.maximum(r_j - 1, 0)], 0)
    k_j = j - prev  # index within round r_j (0-based, in priority order)
    open_ = caps[None, :] > r_j[:, None]  # [Emax, N]
    rank = jnp.cumsum(open_, axis=1)
    hit = open_ & (rank == (k_j + 1)[:, None])
    pos_j = jnp.argmax(hit, axis=1)
    nodes = jnp.where(j < count, order[pos_j], -1)
    return nodes.astype(jnp.int32), ok


def _fill_minimal_fragmentation(caps_pos, order, count, emax):
    n = caps_pos.shape[0]
    _check_cumsum_bound(n, emax)
    pos = jnp.arange(n, dtype=jnp.int32)
    cap_ok = caps_pos > 0
    caps_c = jnp.minimum(caps_pos, count)
    ok = jnp.sum(caps_c) >= count

    # Branch A: some node fits the whole gang -> smallest such (cap, pos).
    mask_a = cap_ok & (caps_pos >= count)
    exists_a = jnp.any(mask_a)
    min_cap_a = jnp.min(jnp.where(mask_a, caps_pos, INT32_INF))
    pos_a = jnp.min(jnp.where(mask_a & (caps_pos == min_cap_a), pos, INT32_INF))
    pos_a = jnp.clip(pos_a, 0, n - 1)

    # Branch B: consume (cap desc, pos asc) while cumulative <= count.
    desc = jnp.lexsort((pos, -caps_c, jnp.where(cap_ok, 0, 1)))
    caps_desc = jnp.where(cap_ok[desc], caps_c[desc], 0)
    cum = jnp.cumsum(caps_desc)
    consumed = cum <= count
    total = jnp.sum(jnp.where(consumed, caps_desc, 0))
    remainder = count - total
    consumed_pos = jnp.zeros(n, jnp.bool_).at[desc].set(consumed)
    mask_fin = cap_ok & ~consumed_pos & (caps_pos >= remainder)
    min_cap_f = jnp.min(jnp.where(mask_fin, caps_pos, INT32_INF))
    pos_f = jnp.min(jnp.where(mask_fin & (caps_pos == min_cap_f), pos, INT32_INF))
    pos_f = jnp.clip(pos_f, 0, n - 1)

    j = jnp.arange(emax, dtype=jnp.int32)
    idx = jnp.clip(jnp.searchsorted(cum, j, side="right"), 0, n - 1)
    pos_b = jnp.where(j < total, desc[idx], pos_f)

    chosen_pos = jnp.where(exists_a, pos_a, pos_b)
    nodes = jnp.where(j < count, order[chosen_pos], -1)
    return nodes.astype(jnp.int32), ok


_FILLS = {
    "tightly-pack": _fill_tightly,
    "distribute-evenly": _fill_distribute_evenly,
    "minimal-fragmentation": _fill_minimal_fragmentation,
}


# ---------------------------------------------------------------------------
# SparkBinPack: driver selection + executor distribution.
# ---------------------------------------------------------------------------


from functools import partial


def pack_one_app(
    avail: jnp.ndarray,  # [N,3] i32 — current availability
    exec_elig: jnp.ndarray,  # [N] bool
    driver_elig: jnp.ndarray,  # [N] bool
    d_order: jnp.ndarray,  # [N] i32 driver priority order
    d_rank: jnp.ndarray,  # [N] i32 rank of each node in d_order
    e_order: jnp.ndarray,  # [N] i32 executor priority order
    driver_req: jnp.ndarray,  # [3] i32
    exec_req: jnp.ndarray,  # [3] i32
    count: jnp.ndarray,  # i32 scalar
    fill_fn,
    emax: int,
):
    """Core gang pack against a given availability (binpack.go:60-87):
    driver selection via the feasibility identity (module docstring) + one
    executor fill with the chosen driver tentatively reserved. Shared by the
    single-app path (`spark_bin_pack`) and the batched FIFO scan body
    (ops/batched.py) so their semantics cannot diverge.

    Returns (driver_node, driver_one_hot[N,1], exec_nodes[Emax], ok).
    """
    n = avail.shape[0]
    zero = jnp.zeros_like(avail)
    cap_base = jnp.where(exec_elig, node_capacities(avail, zero, exec_req), 0)
    cap_base_c = jnp.minimum(cap_base, count)
    total_base = jnp.sum(cap_base_c)

    # Capacity of node i for executors if the driver were reserved on i.
    driver_reserved = jnp.broadcast_to(driver_req[None, :], avail.shape)
    cap_with_driver = jnp.where(
        exec_elig, node_capacities(avail, driver_reserved, exec_req), 0
    )
    total_if_driver = total_base - cap_base_c + jnp.minimum(cap_with_driver, count)

    driver_fit = driver_elig & fits(avail, driver_req)
    feasible = driver_fit & (total_if_driver >= count)
    best_rank = jnp.min(jnp.where(feasible, d_rank, INT32_INF))
    found = best_rank < INT32_INF
    driver_node = jnp.where(found, d_order[jnp.clip(best_rank, 0, n - 1)], -1).astype(
        jnp.int32
    )

    one_hot = (jnp.arange(n) == driver_node)[:, None]
    reserved = jnp.where(one_hot, driver_req[None, :], 0).astype(avail.dtype)
    caps = jnp.where(exec_elig, node_capacities(avail, reserved, exec_req), 0)
    exec_nodes, fill_ok = fill_fn(caps[e_order], e_order, count, emax)
    return driver_node, one_hot, exec_nodes, found & fill_ok


@partial(jax.jit, static_argnames=("fill", "emax", "num_zones"))
def spark_bin_pack(
    cluster: ClusterTensors,
    driver_req: jnp.ndarray,  # [3] i32
    exec_req: jnp.ndarray,  # [3] i32
    count: jnp.ndarray,  # i32 scalar — number of executors
    driver_candidate_mask: jnp.ndarray,  # [N] bool (kube-scheduler candidates)
    domain_mask: jnp.ndarray,  # [N] bool (instance-group metadata domain)
    *,
    fill: str,
    emax: int,
    num_zones: int,
    zrank: jnp.ndarray | None = None,
) -> Packing:
    """Gang-pack one app (binpack/binpack.go:60-87).

    Driver candidates are `domain & driver_candidate_mask` in driver priority
    order; executor-eligible nodes are `domain & schedulable & ready`
    (sort/nodesorting.go:51-58). Feasibility identity (see module docstring)
    finds the first driver node for which the executors still pack without
    re-running the fill per candidate.
    """
    fill_fn = _FILLS[fill]
    avail = cluster.available
    n = avail.shape[0]

    domain = domain_mask & cluster.valid
    driver_elig = domain & driver_candidate_mask
    exec_elig = domain & ~cluster.unschedulable & cluster.ready

    if zrank is None:
        zrank = zone_ranks(cluster, domain, num_zones)
    d_order, _ = priority_order(cluster, driver_elig, zrank, cluster.label_rank_driver)
    e_order, _ = priority_order(cluster, exec_elig, zrank, cluster.label_rank_executor)
    d_rank = _rank_of_position(d_order)

    driver_node, _, exec_nodes, has_cap = pack_one_app(
        avail, exec_elig, driver_elig, d_order, d_rank, e_order,
        driver_req, exec_req, count, fill_fn, emax,
    )
    return Packing(
        driver_node=jnp.where(has_cap, driver_node, -1).astype(jnp.int32),
        executor_nodes=jnp.where(has_cap, exec_nodes, -1).astype(jnp.int32),
        has_capacity=has_cap,
    )


def single_az_orders(
    cluster,
    driver_elig: jnp.ndarray,  # [N] bool
    exec_elig: jnp.ndarray,  # [N] bool
    zrank: jnp.ndarray,  # [num_zones] i32
    num_zones: int,
    available: jnp.ndarray | None = None,
):
    """Per-zone priority orders for the single-AZ packers: restrict each
    eligibility vector to one zone and sort (what spark_bin_pack does
    internally when called with zone-masked masks, single_az.go:44-56).
    Returns ([Z,N] driver orders, [Z,N] driver ranks, [Z,N] exec orders)."""
    zmask_all = cluster.zone_id[None, :] == jnp.arange(num_zones, dtype=jnp.int32)[:, None]
    d_elig_z = driver_elig[None, :] & zmask_all
    e_elig_z = exec_elig[None, :] & zmask_all
    d_order_z = jax.vmap(
        lambda e: priority_order(
            cluster, e, zrank, cluster.label_rank_driver, available=available
        )[0]
    )(d_elig_z)
    e_order_z = jax.vmap(
        lambda e: priority_order(
            cluster, e, zrank, cluster.label_rank_executor, available=available
        )[0]
    )(e_elig_z)
    d_rank_z = jax.vmap(_rank_of_position)(d_order_z)
    return d_elig_z, e_elig_z, d_order_z, d_rank_z, e_order_z


def pack_one_app_single_az(
    zone_id: jnp.ndarray,  # [N] i32
    schedulable: jnp.ndarray,  # [N,3] i32
    avail: jnp.ndarray,  # [N,3] i32 — CURRENT availability
    driver_elig: jnp.ndarray,  # [N] bool (domain & candidates & valid)
    exec_elig: jnp.ndarray,  # [N] bool
    d_rank_global: jnp.ndarray,  # [N] i32 — rank in the FULL driver order
    d_elig_z,  # [Z,N] bool
    e_elig_z,  # [Z,N] bool
    d_order_z,  # [Z,N] i32
    d_rank_z,  # [Z,N] i32
    e_order_z,  # [Z,N] i32
    driver_req: jnp.ndarray,  # [3] i32
    exec_req: jnp.ndarray,  # [3] i32
    count: jnp.ndarray,  # i32 scalar
    fill_fn,
    emax: int,
    num_zones: int,
    include_executors_in_reserved: bool,
):
    """Single-AZ gang pack against a given availability (single_az.go:23-97):
    pack every zone (vmapped pack_one_app over zone-restricted orders), keep
    feasible zones, pick the best average packing efficiency — strictly-
    greater replacement, so the earliest zone (by first appearance in driver
    priority order) wins ties. Shared by the standalone `_single_az_pack`
    and the batched FIFO scan body (ops/batched.py) so their semantics
    cannot diverge.

    Returns (driver_node, driver_one_hot[N,1], exec_nodes[Emax], ok)."""
    # Zone first-appearance rank in driver priority order (single_az.go:58-73).
    zone_first = jnp.full(num_zones, INT32_INF, jnp.int32).at[zone_id].min(
        jnp.where(driver_elig, d_rank_global, INT32_INF)
    )
    # Zones with no executor-order nodes are skipped (single_az.go:40-43).
    zone_has_exec = jnp.zeros(num_zones, jnp.bool_).at[zone_id].max(exec_elig)

    def one(d_e, e_e, d_o, d_r, e_o):
        return pack_one_app(
            avail, e_e, d_e, d_o, d_r, e_o, driver_req, exec_req, count,
            fill_fn, emax,
        )

    drivers, one_hots, exec_nodes, oks = jax.vmap(one)(
        d_elig_z, e_elig_z, d_order_z, d_rank_z, e_order_z
    )

    effs = jax.vmap(
        lambda dn, en: eff_ops.avg_packing_efficiency_arrays(
            schedulable,
            avail,
            dn,
            en,
            driver_req,
            exec_req,
            # minimalFragmentation never adds executors to reservedResources
            # in the reference, so its zone scores are driver-only (see
            # efficiency.avg_packing_efficiency docstring).
            include_executors_in_reserved=include_executors_in_reserved,
        ).max
    )(drivers, exec_nodes)
    valid_zone = oks & (zone_first < INT32_INF) & zone_has_exec
    effs = jnp.where(valid_zone, effs, -jnp.inf)
    best_eff = jnp.max(effs)
    # chooseBestResult starts from WorstAvgPackingEfficiency (Max=0.0) and
    # replaces only on strictly-greater, so a zone whose best efficiency is
    # exactly 0.0 is rejected entirely (single_az.go:84-97).
    any_valid = jnp.any(valid_zone) & (best_eff > 0.0)
    tie = valid_zone & (effs == best_eff)
    best_zone = jnp.argmin(jnp.where(tie, zone_first, INT32_INF))

    driver_node = jnp.where(any_valid, drivers[best_zone], -1).astype(jnp.int32)
    execs = jnp.where(any_valid, exec_nodes[best_zone], -1).astype(jnp.int32)
    one_hot = one_hots[best_zone] & any_valid
    return driver_node, one_hot, execs, any_valid


@partial(jax.jit, static_argnames=("fill", "emax", "num_zones"))
def _single_az_pack(
    cluster,
    driver_req,
    exec_req,
    count,
    driver_candidate_mask,
    domain_mask,
    *,
    fill,
    emax,
    num_zones,
):
    """Single-AZ wrapper (binpack/single_az.go:23-97): per-zone SparkBinPack,
    best feasible zone by average packing efficiency."""
    domain = domain_mask & cluster.valid
    driver_elig = domain & driver_candidate_mask
    exec_elig = domain & ~cluster.unschedulable & cluster.ready
    zrank = zone_ranks(cluster, domain, num_zones)
    d_order, _ = priority_order(cluster, driver_elig, zrank, cluster.label_rank_driver)
    d_rank = _rank_of_position(d_order)

    d_elig_z, e_elig_z, d_order_z, d_rank_z, e_order_z = single_az_orders(
        cluster, driver_elig, exec_elig, zrank, num_zones
    )
    driver_node, _, execs, ok = pack_one_app_single_az(
        cluster.zone_id,
        cluster.schedulable,
        cluster.available,
        driver_elig,
        exec_elig,
        d_rank,
        d_elig_z,
        e_elig_z,
        d_order_z,
        d_rank_z,
        e_order_z,
        driver_req,
        exec_req,
        count,
        _FILLS[fill],
        emax,
        num_zones,
        include_executors_in_reserved=(fill != "minimal-fragmentation"),
    )
    return Packing(driver_node=driver_node, executor_nodes=execs, has_capacity=ok)


# ---------------------------------------------------------------------------
# Public strategy entry points (internal/extender/binpack.go:39-54 registry).
# ---------------------------------------------------------------------------


def tightly_pack(cluster, driver_req, exec_req, count, driver_mask, domain_mask, *, emax, num_zones):
    return spark_bin_pack(
        cluster, driver_req, exec_req, count, driver_mask, domain_mask,
        fill="tightly-pack", emax=emax, num_zones=num_zones,
    )


def distribute_evenly(cluster, driver_req, exec_req, count, driver_mask, domain_mask, *, emax, num_zones):
    return spark_bin_pack(
        cluster, driver_req, exec_req, count, driver_mask, domain_mask,
        fill="distribute-evenly", emax=emax, num_zones=num_zones,
    )


def minimal_fragmentation(cluster, driver_req, exec_req, count, driver_mask, domain_mask, *, emax, num_zones):
    return spark_bin_pack(
        cluster, driver_req, exec_req, count, driver_mask, domain_mask,
        fill="minimal-fragmentation", emax=emax, num_zones=num_zones,
    )


def single_az_tightly_pack(cluster, driver_req, exec_req, count, driver_mask, domain_mask, *, emax, num_zones):
    return _single_az_pack(
        cluster, driver_req, exec_req, count, driver_mask, domain_mask,
        fill="tightly-pack", emax=emax, num_zones=num_zones,
    )


def single_az_minimal_fragmentation(cluster, driver_req, exec_req, count, driver_mask, domain_mask, *, emax, num_zones):
    return _single_az_pack(
        cluster, driver_req, exec_req, count, driver_mask, domain_mask,
        fill="minimal-fragmentation", emax=emax, num_zones=num_zones,
    )


def az_aware_tightly_pack(cluster, driver_req, exec_req, count, driver_mask, domain_mask, *, emax, num_zones):
    """Try single-AZ tightly-pack, fall back to plain tightly-pack
    (binpack/az_aware_pack_tightly.go:27-38)."""
    az = single_az_tightly_pack(
        cluster, driver_req, exec_req, count, driver_mask, domain_mask,
        emax=emax, num_zones=num_zones,
    )
    plain = tightly_pack(
        cluster, driver_req, exec_req, count, driver_mask, domain_mask,
        emax=emax, num_zones=num_zones,
    )
    pick_az = az.has_capacity
    return Packing(
        driver_node=jnp.where(pick_az, az.driver_node, plain.driver_node),
        executor_nodes=jnp.where(pick_az, az.executor_nodes, plain.executor_nodes),
        has_capacity=pick_az | plain.has_capacity,
    )


# Strategy registry (internal/extender/binpack.go:21-54). Keys match the
# reference's config strings; values are (fn, is_single_az).
BINPACK_FUNCTIONS = {
    "tightly-pack": tightly_pack,
    "distribute-evenly": distribute_evenly,
    "minimal-fragmentation": minimal_fragmentation,
    "single-az-tightly-pack": single_az_tightly_pack,
    "single-az-minimal-fragmentation": single_az_minimal_fragmentation,
    "az-aware-tightly-pack": az_aware_tightly_pack,
}
SINGLE_AZ_PACKERS = frozenset(
    {"single-az-tightly-pack", "single-az-minimal-fragmentation"}
)
DEFAULT_BINPACK = "tightly-pack"


# ---------------------------------------------------------------------------
# Vectorized preemption search (policy subsystem).
# ---------------------------------------------------------------------------

# The single-az fills run the plain inner fill per zone; for the preemption
# *search* (a feasibility probe — the actual admission re-runs the real
# strategy after eviction) each strategy maps to its plain inner fill.
PREEMPTION_FILL = {
    "tightly-pack": "tightly-pack",
    "distribute-evenly": "distribute-evenly",
    "minimal-fragmentation": "minimal-fragmentation",
    "single-az-tightly-pack": "tightly-pack",
    "single-az-minimal-fragmentation": "minimal-fragmentation",
    "az-aware-tightly-pack": "tightly-pack",
}


@partial(jax.jit, static_argnames=("fill", "emax", "num_zones"))
def preemption_batched_fit(
    cluster: ClusterTensors,
    freed_cum: jnp.ndarray,  # [C,N,3] i32 — capacity freed by each candidate eviction set
    driver_req: jnp.ndarray,  # [3] i32
    exec_req: jnp.ndarray,  # [3] i32
    count: jnp.ndarray,  # i32 scalar
    driver_candidate_mask: jnp.ndarray,  # [N] bool
    domain_mask: jnp.ndarray,  # [N] bool
    *,
    fill: str,
    emax: int,
    num_zones: int,
):
    """Masked gang fit for ALL candidate eviction sets in one batched pass.

    Candidate c's availability is `cluster.available + freed_cum[c]` — the
    cluster with eviction set c's reservations released. The node priority
    orders are availability-dependent (ops/sorting.py lexsorts on free
    cpu/mem), so the whole per-candidate program — zone ranks, both priority
    orders, and the `pack_one_app` feasibility identity — is vmapped over
    the candidate axis and compiled once: no per-candidate Python loop over
    kernel calls, which is what makes the search affordable at 100k nodes.

    Eligibility masks are availability-independent and computed once.
    Returns (ok[C] bool, driver_node[C] i32, exec_nodes[C,Emax] i32). With
    nested candidate sets (set c = victims[0..c]) the first ok index is the
    minimal eviction set.
    """
    fill_fn = _FILLS[fill]
    n = cluster.available.shape[0]
    _check_cumsum_bound(n, emax)

    domain = domain_mask & cluster.valid
    driver_elig = domain & driver_candidate_mask
    exec_elig = domain & ~cluster.unschedulable & cluster.ready

    def fit_one(freed):
        avail = cluster.available + freed
        zrank = zone_ranks(cluster, domain, num_zones, available=avail)
        d_order, _ = priority_order(
            cluster, driver_elig, zrank, cluster.label_rank_driver, available=avail
        )
        e_order, _ = priority_order(
            cluster, exec_elig, zrank, cluster.label_rank_executor, available=avail
        )
        d_rank = _rank_of_position(d_order)
        driver_node, _one_hot, exec_nodes, ok = pack_one_app(
            avail, exec_elig, driver_elig, d_order, d_rank, e_order,
            driver_req, exec_req, count, fill_fn, emax,
        )
        return ok, driver_node, exec_nodes

    ok, driver_node, exec_nodes = jax.vmap(fit_one)(freed_cum)
    return ok, driver_node.astype(jnp.int32), exec_nodes.astype(jnp.int32)
