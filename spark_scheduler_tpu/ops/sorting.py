"""Node-priority ordering as a lexicographic sort kernel.

Rebuilds internal/sort/nodesorting.go as one XLA sort over composite keys:

  1. AZ priority: zones ranked ascending by total available (memory first,
     then CPU) over the metadata domain (nodesorting.go:97-121,
     `resourcesLessThan` :74-81).
  2. Within a zone: available memory asc, then CPU asc, then node name
     (nodesorting.go:84-95; the reference's `sort.Slice` is unstable when
     mem+cpu tie but GPU differs — any order is reference-compatible there,
     we pin it with the name rank).
  3. Optional configured label priority as a FINAL stable re-sort
     (nodesorting.go:62-64,160-185), i.e. the label rank becomes the most
     significant key, missing labels rank last.

Ineligible nodes sort to the end; callers get `(order, count)`.
"""

from __future__ import annotations

import jax.numpy as jnp

from spark_scheduler_tpu.models.cluster import ClusterTensors, INT32_INF
from spark_scheduler_tpu.models.resources import CPU_DIM, MEM_DIM


def zone_ranks(
    cluster: ClusterTensors,
    domain_mask: jnp.ndarray,  # [N] bool — nodes in the metadata domain
    num_zones: int,  # static upper bound on zone-id space
    available: jnp.ndarray | None = None,  # [N,3] override (defaults to cluster's)
    zone_base: tuple | None = None,  # pruned-solve zone-sum offsets (see below)
) -> jnp.ndarray:  # [num_zones] i32: rank of each zone (0 = highest priority)
    """Zones ordered ascending by (total available memory, total CPU)
    (nodesorting.go:101-104, 124-134). Zones with no domain nodes rank last.

    `available` lets callers rank against a mutated availability (the batched
    FIFO scan threads availability through admissions) without rebuilding the
    whole ClusterTensors.

    `zone_base` is the candidate-pruning contract (core/prune.py): a gathered
    top-K sub-cluster must still rank zones by the FULL domain's availability
    sums, so the host ships the pruned-away rows' per-zone sums as a constant
    (mem_hi, mem_lo, cpu_hi, cpu_lo, present) tuple of [num_zones] arrays —
    each int64 sum S split into int32 limbs hi = S >> 24, lo = S & 0xFFFFFF
    (exact for |S| < 2^55, i.e. any 100k-node cluster of int32 rows). The
    offsets stay constant across the window's scan because a certified pruned
    solve never places on an excluded row.

    Offset DERIVATION contract (ISSUE 12): the host derives each excluded
    sum as `zone total − Σ kept rows` from resident, event-maintained
    per-zone totals (core/zone_aggregates.ZoneAggregates) — exact int64
    integer sums, never a per-window O(N) re-aggregation — so the identity
    `chunks(kept) + limbs(total − kept) ≡ chunks(full domain)` holds in the
    carry-normal form this kernel compares (the subset-domain sweep derives
    the same limbs by direct summation; both are pinned by the planner
    exactness oracle and the offset-identity test).

    Each per-zone sum is a masked [num_zones, N] reduction, O(Z*N) work,
    not a scatter-add: on the TPU scatter-adds serialize over the rows
    (TPU v5e, 8,192 rows, 4 zones: ~576 us of device time against ~3 us)."""
    if available is None:
        available = cluster.available
    mask = domain_mask & cluster.valid
    member = (
        cluster.zone_id[None, :] == jnp.arange(num_zones, dtype=jnp.int32)[:, None]
    )

    def seg(x):
        return jnp.sum(jnp.where(member, x[None, :], 0), axis=1, dtype=jnp.int32)

    def _zone_sum_chunks(vals: jnp.ndarray, base_hi=None, base_lo=None) -> list[jnp.ndarray]:
        # Exact int32-safe aggregation without x64: split each value into
        # four 8-bit chunks (top chunk keeps the sign via arithmetic shift),
        # segment-sum each, then normalize carries upward. Each low-chunk
        # sum is <= n*255, exact for n < 2^23 nodes; the top-chunk sum is
        # bounded by n*2^7 after the shift. Chunks returned most-significant
        # first, comparable lexicographically. Excluded-row base offsets add
        # into the chunks BEFORE carry normalization, so the normal form
        # (and therefore the rank order) equals the unpruned sums exactly.
        v = jnp.where(mask, vals, 0)
        s3 = seg(v >> 24)
        s2 = seg((v >> 16) & 0xFF)
        s1 = seg((v >> 8) & 0xFF)
        s0 = seg(v & 0xFF)
        if base_hi is not None:
            s3 = s3 + base_hi
            s2 = s2 + ((base_lo >> 16) & 0xFF)
            s1 = s1 + ((base_lo >> 8) & 0xFF)
            s0 = s0 + (base_lo & 0xFF)
        s1 = s1 + (s0 >> 8)
        s0 = s0 & 0xFF
        s2 = s2 + (s1 >> 8)
        s1 = s1 & 0xFF
        s3 = s3 + (s2 >> 8)
        s2 = s2 & 0xFF
        return [s3, s2, s1, s0]

    if zone_base is not None:
        mem_hi, mem_lo, cpu_hi, cpu_lo, base_present = zone_base
        mem_k = _zone_sum_chunks(available[:, MEM_DIM], mem_hi, mem_lo)
        cpu_k = _zone_sum_chunks(available[:, CPU_DIM], cpu_hi, cpu_lo)
    else:
        base_present = None
        mem_k = _zone_sum_chunks(available[:, MEM_DIM])
        cpu_k = _zone_sum_chunks(available[:, CPU_DIM])
    present = jnp.any(member & mask[None, :], axis=1)
    if base_present is not None:
        present = present | base_present
    # Absent zones last; ties between zones are unordered in the reference
    # (map iteration); pin with zone id. lexsort: last key is primary.
    keys = (
        [jnp.arange(num_zones)]
        + list(reversed(cpu_k))
        + list(reversed(mem_k))
        + [jnp.where(present, 0, 1)]
    )
    order = jnp.lexsort(keys)
    ranks = jnp.zeros(num_zones, jnp.int32).at[order].set(
        jnp.arange(num_zones, dtype=jnp.int32)
    )
    return ranks


def priority_order(
    cluster: ClusterTensors,
    eligible: jnp.ndarray,  # [N] bool
    zrank: jnp.ndarray,  # [num_zones] i32 from zone_ranks
    label_rank: jnp.ndarray,  # [N] i32 (INT32_INF = unranked)
    available: jnp.ndarray | None = None,  # [N,3] override (defaults to cluster's)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(order[N] node indices, count) — eligible nodes in priority order,
    ineligible pushed to the end."""
    if available is None:
        available = cluster.available
    elig = eligible & cluster.valid
    az = zrank[cluster.zone_id]
    mem = available[:, MEM_DIM]
    cpu = available[:, CPU_DIM]
    # lexsort: last key is primary.
    order = jnp.lexsort(
        (cluster.name_rank, cpu, mem, az, label_rank, jnp.where(elig, 0, 1))
    )
    count = jnp.sum(elig).astype(jnp.int32)
    return order.astype(jnp.int32), count
