"""Batched FIFO gang admission — the whole scheduling queue as ONE XLA program.

The reference admits drivers one HTTP request at a time, re-running a Go
greedy loop per app (resource.go:125-189) and, for FIFO, re-packing every
earlier driver inside the request (`fitEarlierDrivers`, resource.go:221-258).
This module is the TPU-native replacement: the FIFO-sorted queue of B apps is
a tensor batch, and admission is a `lax.scan` over the app axis threading the
cluster availability tensor — each step is a fully vectorized O(N) gang pack
(driver selection via the feasibility identity + executor fill via prefix
sums, see ops/packing.py), and the scatter-subtract of an admitted app's
usage replaces the reference's `metadata.SubtractUsageIfExists`
(resource.go:251-255).

Reference-faithful FIFO semantics:
  - apps are processed in FIFO order (creation time; host sorts before the
    call, sparkpods.go:60-77);
  - an admitted app's usage is subtracted before the next app packs
    (resource.go:251-255);
  - a *non-skippable* app that fails blocks everything behind it — strict
    FIFO (resource.go:241-249); `skippable[i]` marks apps the age-based
    enforcement lets later apps jump over (resource.go:260-270,
    config/config.go:57-64);
  - node priority orders are computed ONCE from the starting availability
    and reused for every app, exactly as `fitEarlierDrivers` reuses the
    orders computed at resource.go:299 while only availability mutates.

Cost: B scan steps, each O(N) vector work + an O(Emax) fill — ~B*N total,
laid out as dense int32 vector ops XLA maps onto the VPU. The 10k-node x
1k-app north star (BASELINE.md) is one invocation of `batched_fifo_pack`.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from spark_scheduler_tpu.models.cluster import ClusterTensors
from spark_scheduler_tpu.ops.packing import (
    _FILLS,
    _check_cumsum_bound,
    _rank_of_position,
    pack_one_app,
    pack_one_app_single_az,
    single_az_orders,
)
from spark_scheduler_tpu.ops.sorting import priority_order, zone_ranks

# Single-AZ strategies run the per-zone pack + efficiency-scored zone pick
# inside the scan step; az-aware additionally computes the plain fallback
# (az_aware_pack_tightly.go:27-38). Values are the inner executor fill.
_SINGLE_AZ_INNER = {
    "single-az-tightly-pack": "tightly-pack",
    "single-az-minimal-fragmentation": "minimal-fragmentation",
    "az-aware-tightly-pack": "tightly-pack",
}


class AppBatch(NamedTuple):
    """FIFO-ordered queue of gang requests (one row per Spark application).

    The tensor form of `sparkApplicationResources` (sparkpods.go:29-35) x B,
    already sorted by creation time host-side (`filterToEarliestAndSort`,
    sparkpods.go:60-77). Rows past the real queue length are padding with
    `app_valid=False`.

    `driver_cand` / `domain` are OPTIONAL per-app node masks. When both are
    None the kernel runs in queue mode: every app sees the same eligibility
    and the node priority orders are computed once from the starting
    availability (fitEarlierDrivers semantics, resource.go:221-258). When
    set, each app packs exactly as a standalone `spark_bin_pack` call with
    those masks against the then-current availability — the serving path's
    per-request decisions, batched (SURVEY.md §2d row 1).
    """

    driver_req: jnp.ndarray  # [B, 3] i32
    exec_req: jnp.ndarray  # [B, 3] i32
    exec_count: jnp.ndarray  # [B] i32 — gang size (min executors)
    app_valid: jnp.ndarray  # [B] bool — padding mask
    skippable: jnp.ndarray  # [B] bool — FIFO age-based skip (resource.go:260-270)
    driver_cand: jnp.ndarray | None = None  # [B, N] bool — kube candidate list
    domain: jnp.ndarray | None = None  # [B, N] bool — node-affinity domain
    # Segmented WINDOW mode (both set together; core/solver.py pack_window
    # is the caller): each serving request is a segment of rows (its
    # FIFO-earlier drivers, then itself). `reset` marks a segment's first
    # row — availability rewinds to the committed base; `commit` marks the
    # request row — its admission persists into the base. Hypothetical
    # (non-commit) rows subtract only within their segment, replicating the
    # reference's fitEarlierDrivers exactly — INCLUDING its double-count of
    # an admitted-but-still-unbound earlier driver (usage already carries
    # its reservation AND it is re-packed hypothetically,
    # resource.go:221-258 + GetReservedResources) — so windowed == solo
    # serving, decision for decision.
    #
    # FUSED MULTI-WINDOW batches (solver.pack_windows_dispatch) are
    # ordinary segmented batches: K serving windows concatenated in
    # dispatch order need no device-side window marker, because a window
    # boundary IS a segment boundary — the scan's committed base carries
    # across it exactly as `available_after` would be threaded between K
    # sequential dispatches (fuse_app_batches pins the identity). That is
    # what lets K queued windows ride ONE h2d + ONE dispatch + ONE d2h.
    commit: jnp.ndarray | None = None  # [B] bool
    reset: jnp.ndarray | None = None  # [B] bool


def queue_mode_orders(cluster: ClusterTensors, num_zones: int):
    """Queue-mode eligibility + priority orders, fixed from the starting
    availability (fitEarlierDrivers reuses the orders computed at
    resource.go:299 while only availability mutates).

    Returns (driver_elig, exec_elig, d_order, d_rank, e_order, zrank)."""
    domain0 = cluster.valid
    exec_elig = domain0 & ~cluster.unschedulable & cluster.ready
    driver_elig = exec_elig  # no kube candidate filter in queue mode
    zrank = zone_ranks(cluster, domain0, num_zones)
    d_order, _ = priority_order(
        cluster, driver_elig, zrank, cluster.label_rank_driver
    )
    e_order, _ = priority_order(
        cluster, exec_elig, zrank, cluster.label_rank_executor
    )
    d_rank = _rank_of_position(d_order)
    return driver_elig, exec_elig, d_order, d_rank, e_order, zrank


class BatchedPacking(NamedTuple):
    """Per-app gang placement for the whole queue."""

    driver_node: jnp.ndarray  # [B] i32, -1 = not admitted
    executor_nodes: jnp.ndarray  # [B, Emax] i32, -1 = padding / not admitted
    admitted: jnp.ndarray  # [B] bool — packed AND not FIFO-blocked
    packed: jnp.ndarray  # [B] bool — would fit, ignoring FIFO blocking
    available_after: jnp.ndarray  # [N, 3] i32 — availability after all admits


@partial(jax.jit, static_argnames=("fill", "emax", "num_zones", "unroll"))
def batched_fifo_pack(
    cluster: ClusterTensors,
    apps: AppBatch,
    *,
    fill: str = "tightly-pack",
    emax: int,
    num_zones: int,
    unroll: int = 2,
    zone_base: tuple | None = None,
) -> BatchedPacking:
    """Admit a FIFO queue of gang requests in one compiled program.

    `emax` is the static executor-slot padding (>= max(exec_count));
    `num_zones` the static zone-id bound. Strict-FIFO blocking: once a
    non-skippable valid app fails to pack, every later app is rejected
    (`failure-earlier-driver`, resource.go:241-249) but its hypothetical
    packing is still reported in `packed` for demand creation.

    All six strategies batch: the single-AZ wrappers run their per-zone
    pack + efficiency-scored zone pick (single_az.go:23-97) INSIDE the scan
    step (VERDICT r2 #2), with the zone efficiencies always computed against
    the then-current availability.

    `zone_base` (candidate pruning, core/prune.py): constant excluded-row
    zone-sum offsets forwarded to every per-segment zone_ranks call, so a
    gathered top-K sub-cluster ranks zones byte-identically to the full
    solve. Plain fills only — the single-AZ wrappers additionally score
    zones by subset-dependent efficiencies, so the pruned path never routes
    them here with offsets.
    """
    single_az = fill in _SINGLE_AZ_INNER
    if zone_base is not None and single_az:
        raise ValueError(
            "zone_base offsets are only sound for plain fills; "
            f"got single-AZ strategy {fill!r}"
        )
    az_fallback = fill == "az-aware-tightly-pack"
    fill_fn = _FILLS[_SINGLE_AZ_INNER.get(fill, fill)]
    include_exec_in_reserved = _SINGLE_AZ_INNER.get(fill) != "minimal-fragmentation"
    n = cluster.available.shape[0]
    _check_cumsum_bound(n, emax)

    segmented = apps.commit is not None
    # Segmented windows always run with per-row masks (synthesized all-true
    # when absent): each segment is one serving request.
    masked = segmented or apps.driver_cand is not None or apps.domain is not None
    if not masked:
        (driver_elig0, exec_elig0, d_order0, d_rank0, e_order0, zrank0) = (
            queue_mode_orders(cluster, num_zones)
        )
        if single_az:
            zone_orders0 = single_az_orders(
                cluster, driver_elig0, exec_elig0, zrank0, num_zones
            )

    if masked:
        b = apps.driver_req.shape[0]
        ones = jnp.ones((b, n), jnp.bool_)
        extra = (
            apps.driver_cand if apps.driver_cand is not None else ones,
            apps.domain if apps.domain is not None else ones,
        )
    else:
        extra = ()

    def _fresh_orders(avail, driver_elig, exec_elig, domain):
        """Priority orders from the given availability (the sort at
        resource.go:299)."""
        zrank = zone_ranks(
            cluster, domain, num_zones, available=avail, zone_base=zone_base
        )
        d_order, _ = priority_order(
            cluster, driver_elig, zrank, cluster.label_rank_driver,
            available=avail,
        )
        e_order, _ = priority_order(
            cluster, exec_elig, zrank, cluster.label_rank_executor,
            available=avail,
        )
        d_rank = _rank_of_position(d_order)
        out = (d_order, d_rank, e_order)
        if single_az:
            out = out + single_az_orders(
                cluster, driver_elig, exec_elig, zrank, num_zones,
                available=avail,
            )
        return out

    def _orders_placeholder():
        z = jnp.zeros(n, jnp.int32)
        out = (z, z, z)
        if single_az:
            zb = jnp.zeros((num_zones, n), jnp.bool_)
            zi = jnp.zeros((num_zones, n), jnp.int32)
            out = out + (zb, zb, zi, zi, zi)
        return out

    def step(carry, app):
        if segmented:
            base, avail, blocked, carried_orders = carry
            (driver_req, exec_req, count, valid, skippable,
             commit, reset, *masks) = app
            # Segment boundary: rewind to the committed base; FIFO blocking
            # is segment-local (each request's solo solve starts unblocked).
            avail = jnp.where(reset, base, avail)
            blocked = jnp.where(reset, jnp.bool_(False), blocked)
        else:
            avail, blocked = carry
            driver_req, exec_req, count, valid, skippable, *masks = app
        cand_i, dom_i = masks if masked else (None, None)
        # A gang larger than the static slot padding cannot be represented —
        # reject it outright rather than silently truncating it. Callers
        # size emax to the queue's max gang (make_app_batch knows it).
        too_big = count > emax
        count = jnp.minimum(count, emax)

        if masked:
            domain = dom_i & cluster.valid
            driver_elig = domain & cand_i
            exec_elig = domain & ~cluster.unschedulable & cluster.ready

        if segmented:
            # One sort per SEGMENT (= per serving request), computed from
            # the segment-start availability and reused for every row of
            # the segment — exactly the reference, which sorts once per
            # request (resource.go:299) and reuses the orders across
            # fitEarlierDrivers and the final pack while only availability
            # mutates. lax.cond executes the sort only on reset rows.
            orders = jax.lax.cond(
                reset,
                lambda: _fresh_orders(avail, driver_elig, exec_elig, domain),
                lambda: carried_orders,
            )
            d_order, d_rank, e_order = orders[:3]
            if single_az:
                zone_orders = orders[3:]
        elif masked:
            # Per-app masks without segments: each row reproduces a
            # standalone spark_bin_pack call with these masks against the
            # CURRENT availability — ordering and zone ranks recomputed per
            # step exactly as each serving request recomputes them from
            # post-admission usage.
            orders = _fresh_orders(avail, driver_elig, exec_elig, domain)
            d_order, d_rank, e_order = orders[:3]
            if single_az:
                zone_orders = orders[3:]
        else:
            driver_elig, exec_elig = driver_elig0, exec_elig0
            d_order, d_rank, e_order = d_order0, d_rank0, e_order0
            if single_az:
                zone_orders = zone_orders0

        if single_az:
            driver_node, one_hot, exec_nodes, ok = pack_one_app_single_az(
                cluster.zone_id, cluster.schedulable, avail,
                driver_elig, exec_elig, d_rank, *zone_orders,
                driver_req, exec_req, count, fill_fn, emax, num_zones,
                include_executors_in_reserved=include_exec_in_reserved,
            )
            if az_fallback:
                # az-aware: plain tightly-pack when no single zone fits
                # (az_aware_pack_tightly.go:27-38).
                p_driver, p_hot, p_execs, p_ok = pack_one_app(
                    avail, exec_elig, driver_elig, d_order, d_rank, e_order,
                    driver_req, exec_req, count, fill_fn, emax,
                )
                driver_node = jnp.where(ok, driver_node, p_driver)
                one_hot = jnp.where(ok, one_hot, p_hot)
                exec_nodes = jnp.where(ok, exec_nodes, p_execs)
                ok = ok | p_ok
        else:
            driver_node, one_hot, exec_nodes, ok = pack_one_app(
                avail, exec_elig, driver_elig, d_order, d_rank, e_order,
                driver_req, exec_req, count, fill_fn, emax,
            )

        packed = ok & valid & ~too_big
        admitted = packed & ~blocked

        # Scatter-subtract the admitted gang's usage (resource.go:251-255).
        exec_counts = (
            jnp.zeros(n, jnp.int32)
            .at[jnp.clip(exec_nodes, 0, n - 1)]
            .add(jnp.where(exec_nodes >= 0, 1, 0))
        )
        delta = exec_counts[:, None] * exec_req[None, :] + jnp.where(
            one_hot, driver_req[None, :], 0
        )
        new_avail = jnp.where(admitted, avail - delta.astype(avail.dtype), avail)

        # Strict FIFO: a non-skippable valid failure blocks the rest
        # (resource.go:241-249).
        blocked = blocked | (valid & ~packed & ~skippable)

        out_driver = jnp.where(admitted, driver_node, -1).astype(jnp.int32)
        out_execs = jnp.where(admitted, exec_nodes, -1).astype(jnp.int32)
        if segmented:
            base = jnp.where(
                admitted & commit, base - delta.astype(base.dtype), base
            )
            new_carry = (base, new_avail, blocked, orders)
        else:
            new_carry = (new_avail, blocked)
        return new_carry, (out_driver, out_execs, admitted, packed)

    xs = (
        apps.driver_req,
        apps.exec_req,
        apps.exec_count,
        apps.app_valid,
        apps.skippable,
    )
    if segmented:
        xs = xs + (apps.commit, apps.reset)
        init = (
            cluster.available,
            cluster.available,
            jnp.bool_(False),
            _orders_placeholder(),
        )
    else:
        init = (cluster.available, jnp.bool_(False))
    final_carry, (drivers, execs, admitted, packed) = jax.lax.scan(
        step,
        init,
        xs + extra,
        # The step body is tiny relative to loop-trip overhead at 10k nodes
        # (~100 us/step, overhead-bound); unroll=2 lets XLA fuse step pairs
        # for a measurably lower window service time on TPU v5e. Higher
        # unrolls regress, and so does unrolling under vmap (grouped_fifo_pack
        # passes unroll=1). Results are unchanged — unrolling only
        # restructures the loop.
        unroll=unroll,
    )
    avail_after = final_carry[0]
    return BatchedPacking(
        driver_node=drivers,
        executor_nodes=execs,
        admitted=admitted,
        packed=packed,
        available_after=avail_after,
    )


@partial(
    jax.jit,
    static_argnames=("fill", "emax", "num_zones", "unroll"),
    donate_argnums=(0,),
)
def batched_fifo_pack_carry(
    available,
    statics: tuple,
    apps: AppBatch,
    *,
    fill: str = "tightly-pack",
    emax: int,
    num_zones: int,
    unroll: int = 2,
) -> BatchedPacking:
    """`batched_fifo_pack` with the base-capacity carry split out and
    DONATED: `available` is consumed and `available_after` reuses its
    buffer in place instead of copy-on-write, so a caller threading the
    committed base across back-to-back windows (the pipelined serving
    engine, the bench's window chains) never pays an [N, 3] copy per
    window. `statics` is `models.cluster.cluster_statics(cluster)` — the
    resident, never-donated fields. The input availability is DEAD after
    the call (jax marks it deleted); callers must thread
    `available_after` forward, never the input."""
    from spark_scheduler_tpu.models.cluster import cluster_from_statics

    return batched_fifo_pack(
        cluster_from_statics(available, statics),
        apps,
        fill=fill,
        emax=emax,
        num_zones=num_zones,
        unroll=unroll,
    )


def make_app_batch(
    driver_reqs,  # [B,3] array-like
    exec_reqs,  # [B,3] array-like
    exec_counts,  # [B] array-like
    *,
    pad_to: int | None = None,
    skippable=None,
    driver_cand=None,  # [B,N] bool — per-app kube candidate masks
    domain=None,  # [B,N] bool — per-app node-affinity domains
    commit=None,  # [B] bool — window mode: request rows (persist into base)
    reset=None,  # [B] bool — window mode: segment-start rows
) -> AppBatch:
    """Host helper: pad a queue to a bucketed batch size. Padding rows get
    all-False masks (they are already app_valid=False)."""
    import numpy as np

    driver_reqs = np.asarray(driver_reqs, np.int32)
    exec_reqs = np.asarray(exec_reqs, np.int32)
    exec_counts = np.asarray(exec_counts, np.int32)
    b = driver_reqs.shape[0]
    if skippable is None:
        skippable = np.zeros(b, bool)
    else:
        skippable = np.asarray(skippable, bool)
    pad = max(pad_to or b, b)
    valid = np.zeros(pad, bool)
    valid[:b] = True

    def _pad_mask(m):
        if m is None:
            return None
        m = np.asarray(m, bool)
        return np.pad(m, ((0, pad - b), (0, 0)))

    def _pad_vec(v, fill=0, dtype=None):
        if v is None:
            return None
        v = np.asarray(v, dtype)
        return np.pad(v, (0, pad - b), constant_values=fill)

    window = commit is not None or reset is not None
    if window and (commit is None or reset is None):
        # Partial window args would silently mis-default (a commit default of
        # True on hypothetical rows would double-subtract them) — refuse.
        raise ValueError("window mode requires commit AND reset together")
    return AppBatch(
        driver_req=np.pad(driver_reqs, ((0, pad - b), (0, 0))),
        exec_req=np.pad(exec_reqs, ((0, pad - b), (0, 0))),
        exec_count=np.pad(exec_counts, (0, pad - b)),
        app_valid=valid,
        skippable=np.pad(skippable, (0, pad - b)),
        driver_cand=_pad_mask(driver_cand),
        domain=_pad_mask(domain),
        commit=_pad_vec(commit, fill=False, dtype=bool),
        reset=_pad_vec(reset, fill=False, dtype=bool),
    )


def fuse_app_batches(batches, *, pad_to: int | None = None) -> AppBatch:
    """Concatenate K segmented WINDOW batches into ONE fused segmented
    batch — the ops-layer contract of the fused multi-window dispatch
    engine (core/solver.py pack_windows_dispatch).

    The fused scan's decisions are IDENTICAL to running the K batches
    sequentially with `available_after` threaded between them: a window
    boundary is just a segment boundary (the next window's first row has
    reset=True, rewinding working availability to the committed base the
    previous window left), FIFO blocking is already segment-local, and
    priority orders are already re-sorted per segment. Each input batch's
    padding rows (app_valid=False) are stripped before concatenation and
    the fused batch re-pads once at the end, so fused row count is the sum
    of REAL rows, not of padded buckets.

    Every batch must be segmented (commit/reset set) and share the node
    axis; per-row masks are synthesized all-true for batches that carried
    none when any other batch carries them (matching the kernel's own
    synthesis, so decisions cannot shift)."""
    import numpy as np

    if not batches:
        raise ValueError("fuse_app_batches requires at least one batch")
    n = None
    for b in batches:
        if b.commit is None or b.reset is None:
            raise ValueError(
                "fuse_app_batches requires segmented window batches"
            )
        for m in (b.driver_cand, b.domain):
            if m is not None:
                m_n = np.asarray(m).shape[1]
                if n is None:
                    n = m_n
                elif n != m_n:
                    raise ValueError("node axes differ across batches")
    any_cand = any(b.driver_cand is not None for b in batches)
    any_dom = any(b.domain is not None for b in batches)

    def _real(b, field, synth_mask=False):
        arr = getattr(b, field)
        sel = np.flatnonzero(np.asarray(b.app_valid))
        if arr is None:
            if not synth_mask:
                return None
            return np.ones((len(sel), n), bool)
        return np.asarray(arr)[sel]

    cat = lambda field, synth=False: np.concatenate(
        [_real(b, field, synth) for b in batches]
    )
    return make_app_batch(
        cat("driver_req"),
        cat("exec_req"),
        cat("exec_count"),
        pad_to=pad_to,
        skippable=cat("skippable"),
        driver_cand=cat("driver_cand", any_cand) if any_cand else None,
        domain=cat("domain", any_dom) if any_dom else None,
        commit=cat("commit"),
        reset=cat("reset"),
    )


def pad_app_batch(apps: AppBatch, pad_to: int) -> AppBatch:
    """Re-pad a host-side batch to a LARGER row bucket (fleet stacking:
    windows grouped into one dispatch must share the app axis, so every
    member grows to the group max). New rows are pure padding
    (app_valid=False, all-zero/False) — identical to what make_app_batch
    would have emitted at the bigger bucket, so decisions cannot shift
    (pad-invariance pinned by tests/test_replay_sweep.py)."""
    import numpy as np

    b = np.asarray(apps.driver_req).shape[0]
    if pad_to <= b:
        return apps
    grow = pad_to - b

    def _rows(a):
        if a is None:
            return None
        a = np.asarray(a)
        return np.pad(a, [(0, grow)] + [(0, 0)] * (a.ndim - 1))

    return AppBatch(*(_rows(getattr(apps, f)) for f in AppBatch._fields))


def stack_app_batches(batches) -> AppBatch:
    """Stack M same-shape batches along a new leading arm axis ([M, B, ...])
    for `bucket_stacked_fifo_pack`. Optional masks must be uniformly set or
    uniformly absent across the group — the fleet coordinator groups serving
    windows, which always carry all fields, so a mix means a caller bug."""
    import numpy as np

    def _stack(field):
        vals = [getattr(b, field) for b in batches]
        if all(v is None for v in vals):
            return None
        if any(v is None for v in vals):
            raise ValueError(
                f"cannot stack batches with mixed None-ness in {field!r}"
            )
        return np.stack([np.asarray(v) for v in vals])

    return AppBatch(*(_stack(f) for f in AppBatch._fields))


@partial(
    jax.jit,
    static_argnames=("fills", "emax", "num_zones"),
    donate_argnums=(0,),
)
def arm_stacked_fifo_pack(
    avail_stack,  # [M, N, 3] i32 — per-arm committed availability, DONATED
    statics: tuple,
    apps: AppBatch,
    *,
    fills: tuple,  # per-arm fill strategy, EQUAL FILLS ADJACENT
    emax: int,
    num_zones: int,
):
    """One window solved for M config arms in ONE device dispatch — the
    replay sweep's stacked-arm kernel (ISSUE 18). The window's app batch,
    statics, and masks are arm-invariant (node events are inputs, not
    decisions); only the availability carry differs per arm, so it stacks
    as `[M, N, 3]` and each arm's solve is a vmap lane over the shared
    segmented scan.

    `fills` selects the binpack strategy PER ARM. Strategy is a static
    (compile-time) property of the scan body, so arm selection does NOT
    ride `lax.switch`: under vmap every switch branch executes select-ized
    — measured 39.5 s/window vs 1.2 s for the vmapped single-fill program
    at 10k nodes on the 2-core CPU rig, a 30x pathology. Instead the
    caller sorts arms so equal fills are adjacent and this kernel vmaps
    each same-fill sub-stack with its own statically-specialized body,
    concatenating along the arm axis — still one jitted program, one
    dispatch, one fetch.

    Returns `(blob, avail_after)`: `blob` is `[M, B, 3+emax]` in the
    `_window_blob` column layout (driver, admitted, packed, exec slots),
    `avail_after` is the `[M, N, 3]` per-arm committed base. Decisions are
    bit-identical per arm to a sequential `batched_fifo_pack` under that
    arm's fill: the kernel is integer-only and vmap/unroll changes only
    restructure the loop (pad-invariance + arm-equivalence pinned by
    tests/test_replay_sweep.py).
    """
    from spark_scheduler_tpu.models.cluster import cluster_from_statics

    if len(fills) != avail_stack.shape[0]:
        raise ValueError(
            f"fills ({len(fills)}) must match the arm axis "
            f"({avail_stack.shape[0]})"
        )

    def solve_one(avail, *, fill):
        out = batched_fifo_pack(
            cluster_from_statics(avail, statics), apps,
            fill=fill, emax=emax, num_zones=num_zones, unroll=1,
        )
        blob = jnp.concatenate(
            [
                out.driver_node[:, None],
                out.admitted[:, None].astype(jnp.int32),
                out.packed[:, None].astype(jnp.int32),
                out.executor_nodes,
            ],
            axis=1,
        )
        return blob, out.available_after

    blobs, avails = [], []
    i = 0
    while i < len(fills):
        j = i
        while j < len(fills) and fills[j] == fills[i]:
            j += 1
        if j > i + 1:
            blob, avail = jax.vmap(partial(solve_one, fill=fills[i]))(
                avail_stack[i:j]
            )
        else:
            blob, avail = solve_one(avail_stack[i], fill=fills[i])
            blob, avail = blob[None], avail[None]
        blobs.append(blob)
        avails.append(avail)
        i = j
    if len(blobs) == 1:
        return blobs[0], avails[0]
    return jnp.concatenate(blobs), jnp.concatenate(avails)


@partial(
    jax.jit,
    static_argnames=("fills", "emax", "num_zones"),
    donate_argnums=(0,),
)
def bucket_stacked_fifo_pack(
    avail_stack,  # [M, N, 3] i32 — per-cluster availability, DONATED
    statics_stack: tuple,  # cluster_statics stacked per field: each [M, N]
    apps_stack: AppBatch,  # fields stacked [M, B, ...]
    *,
    fills: tuple,  # per-member fill strategy, EQUAL FILLS ADJACENT
    emax: int,
    num_zones: int,
):
    """M *different clusters'* windows solved in ONE device dispatch — the
    fleet-serving generalization of `arm_stacked_fifo_pack` (ISSUE 20).
    The sweep stacked M availability carries against ONE shared cluster and
    app batch (arms differ only in config); fleet clusters differ in
    everything, so here statics AND apps stack too and the vmap maps over
    all three. Members need only agree on the padded shapes — `(bucket_n,
    emax, num_zones)` plus the app row bucket, which the coordinator
    equalizes via `pad_app_batch` — not on content: each lane sees its own
    cluster's statics, masks, and availability, so per-member decisions are
    bit-identical to that cluster's standalone `batched_fifo_pack` solve
    (the same vmap-identity PR 18 pinned, extended over the new mapped
    axes).

    `fills` is per member with equal fills adjacent; as in the arm kernel,
    strategy stays a static property of each sub-stack's scan body — never
    `lax.switch`, which select-izes every branch under vmap (30x pathology,
    see arm_stacked_fifo_pack).

    The jitted name carries the `stacked_fifo_pack` donation marker
    (server/config.py JAX_CACHE_DONATION_MARKERS): donated executables
    must not be served from the persistent compile cache.

    Returns `(blob, avail_after)`: `blob` `[M, B, 3+emax]` in the
    `_window_blob` column layout, `avail_after` `[M, N, 3]`.
    """
    from spark_scheduler_tpu.models.cluster import cluster_from_statics

    if len(fills) != avail_stack.shape[0]:
        raise ValueError(
            f"fills ({len(fills)}) must match the member axis "
            f"({avail_stack.shape[0]})"
        )

    def solve_one(avail, statics, apps, *, fill):
        out = batched_fifo_pack(
            cluster_from_statics(avail, statics), apps,
            fill=fill, emax=emax, num_zones=num_zones, unroll=1,
        )
        blob = jnp.concatenate(
            [
                out.driver_node[:, None],
                out.admitted[:, None].astype(jnp.int32),
                out.packed[:, None].astype(jnp.int32),
                out.executor_nodes,
            ],
            axis=1,
        )
        return blob, out.available_after

    _slice = lambda tree, i, j: jax.tree_util.tree_map(
        lambda x: x[i:j], tree
    )
    _pick = lambda tree, i: jax.tree_util.tree_map(lambda x: x[i], tree)

    blobs, avails = [], []
    i = 0
    while i < len(fills):
        j = i
        while j < len(fills) and fills[j] == fills[i]:
            j += 1
        if j > i + 1:
            blob, avail = jax.vmap(partial(solve_one, fill=fills[i]))(
                avail_stack[i:j],
                _slice(statics_stack, i, j),
                _slice(apps_stack, i, j),
            )
        else:
            blob, avail = solve_one(
                avail_stack[i],
                _pick(statics_stack, i),
                _pick(apps_stack, i),
                fill=fills[i],
            )
            blob, avail = blob[None], avail[None]
        blobs.append(blob)
        avails.append(avail)
        i = j
    if len(blobs) == 1:
        return blobs[0], avails[0]
    return jnp.concatenate(blobs), jnp.concatenate(avails)
