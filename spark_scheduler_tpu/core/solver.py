"""PlacementSolver — the host <-> device boundary of the scheduler.

Everything above this module speaks names and Resources; everything below it
(ops/) speaks int32 tensors over a stable node-index space. The solver:

  - interns nodes into the NodeRegistry and builds ClusterTensors with
    padded (bucketed) shapes so XLA compile caches stay warm across node
    count / executor count jitter (SURVEY.md §7 "Dynamic shapes");
  - dispatches to the jitted packing kernels;
  - maps Packing index results back to node names.

This replaces the reference's per-request map-building + sort + greedy loops
(resource.go:287-323) with one device program per request.
"""

from __future__ import annotations

import dataclasses
import itertools as _itertools
import os as _os
import threading
import time as _time
import warnings as _warnings
import weakref as _weakref
from typing import NamedTuple, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from spark_scheduler_tpu import native
from spark_scheduler_tpu.faults.errors import (
    AllSlotsQuarantinedError,
    DegradedUnavailableError,
    classify_slot_failure,
)
from spark_scheduler_tpu.models.cluster import (
    pad_bucket,
    ClusterTensors,
    NodeRegistry,
    build_cluster_tensors,
    cluster_from_statics,
    cluster_statics,
)
from spark_scheduler_tpu.models.kube import Node
from spark_scheduler_tpu.models.resources import INT32_INF, NUM_DIMS, Resources
from spark_scheduler_tpu.ops import BINPACK_FUNCTIONS
from spark_scheduler_tpu.ops.batched import batched_fifo_pack, make_app_batch
from spark_scheduler_tpu.ops.efficiency import avg_packing_efficiency_np

# Every strategy batches: the plain fills run as the scan's executor fill,
# and the single-AZ wrappers run their per-zone pack + efficiency-scored
# zone selection inside the scan step (ops/batched.py _SINGLE_AZ_INNER,
# VERDICT r2 #2). Derived, not enumerated — a new strategy registered in
# BINPACK_FUNCTIONS must also be taught to the batched scan.
BATCHABLE_STRATEGIES = frozenset(BINPACK_FUNCTIONS)

# Simulated-RTT device shim (testing/rtt_shim.py). When installed, the
# serving path calls it with "h2d" on the dispatcher thread at every
# window-batch upload/dispatch, "dispatch" on the thread running a pooled
# slot's program launch, and "d2h" on the thread paying a decision-blob
# pull — each call sleeps its configured share of a device round trip, so
# the fused dispatch's RTT amortization is benchable on CPU. None keeps
# every hot-path hook a single global read.
_DEVICE_SHIM = None


def set_device_shim(shim) -> None:
    """Install (or clear, with None) the process-wide device shim."""
    global _DEVICE_SHIM
    _DEVICE_SHIM = shim


def _shim(kind: str) -> None:
    s = _DEVICE_SHIM
    if s is not None:
        s(kind)


def _shimmed_device_get(x):
    """jax.device_get with the simulated d2h boundary, on the calling
    (fetch-pool) thread — concurrent pulls overlap exactly as concurrent
    device_get transfers do."""
    _shim("d2h")
    return jax.device_get(x)

def _build_segmented_window(
    requests, drv_arr, exc_arr, counts, skip_arr, cand_per_req, dom_per_req
):
    """Segment-major [S, R] arrays for the Pallas window path
    (ops/pallas_window.make_segmented_window), with S and R BUCKETED
    coarsely: every (s_pad, r_pad) pair is a separate scan-over-segments
    compile, and padding segments are skipped at runtime (lax.cond on
    row_count) so coarse S padding costs no device time. Returns
    (SegmentedWindow, seg_idx, row_idx) — host numpy index arrays mapping
    each flat row to its [S, R] position (used by pack_window_fetch to
    flatten the fetched blob)."""
    from spark_scheduler_tpu.ops.pallas_window import (
        segmented_window_from_flat,
    )

    s = len(requests)
    rc = np.asarray([len(req.rows) for req in requests], np.int32)
    s_pad = 4
    while s_pad < s:
        s_pad *= 8
    r_pad = 16
    while r_pad < int(rc.max()):
        r_pad *= 4
    win, seg_idx, row_idx = segmented_window_from_flat(
        drv_arr, exc_arr, counts, skip_arr, rc, cand_per_req, dom_per_req,
        pad_segments=s_pad, pad_rows=r_pad,
    )
    return win, seg_idx, row_idx, s_pad, r_pad


# THE shared sizing function (models/cluster.pad_bucket): store masters
# and solver pads must agree byte-for-byte for the zero-copy fast paths.
_bucket = pad_bucket


def _host_view(tensors) -> ClusterTensors:
    """Host-resident numpy view of cluster tensors. Device-cached tensors
    (build_tensors_cached) carry their numpy source as `.host`; using it for
    host-side math (efficiency, masks, reconstruction) avoids pulling full
    arrays back from the device."""
    return getattr(tensors, "host", tensors)


def _tensors_nbytes(host) -> int:
    """Total byte size of a host ClusterTensors — what a full device upload
    ships (telemetry's h2d accounting)."""
    total = 0
    for f in dataclasses.fields(host):
        arr = getattr(host, f.name, None)
        total += getattr(arr, "nbytes", 0)
    return total


def _gather_statics_host(host, keep: np.ndarray, k_real: int) -> tuple:
    """Host-side gather of the static cluster fields onto a (padded) kept
    row set for the pruned sub-cluster upload. Padding repeats keep[0];
    the padded rows' `valid` is forced False so they are transparent to
    the kernel (eligibility, zone sums, capacity all mask on valid)."""
    fields = [np.asarray(f)[keep] for f in cluster_statics(host)]
    valid = fields[-1].copy()  # cluster_statics order ends with `valid`
    valid[k_real:] = False
    fields[-1] = valid
    return tuple(fields)


# Fields that force a full re-upload when they change (node topology /
# attribute changes — rare next to availability churn).
_STATIC_FIELDS = (
    "schedulable",
    "zone_id",
    "name_rank",
    "label_rank_driver",
    "label_rank_executor",
    "unschedulable",
    "ready",
    "valid",
)


@jax.jit
def _scatter_rows(avail, idx, rows):
    """Jitted row update for the device-resident availability tensor.
    Duplicate indices carry identical rows (bucketing pads by repeating a
    dirty row), so .set is deterministic."""
    return avail.at[idx].set(rows)


class _DaemonFetchPool:
    """Minimal fetch pool with DAEMON workers: a device transfer stuck on a
    dead device must never block interpreter exit, which
    ThreadPoolExecutor's non-daemon workers (joined by its atexit hook)
    would. Futures are concurrent.futures.Future — result()/done()
    compatible with the executor API the handles expose.

    ONE pool is shared by every solver in the process
    (_shared_fetch_pool): the workers run stateless jax.device_get calls,
    so there is nothing per-solver about them, and a pool per solver
    accumulates leaked daemon threads wherever solvers are created without
    a paired close() (each test harness, every rebuilt app). A full test
    run leaked 100+ such threads and died with a native-thread segfault;
    the shared pool bounds the cost at `workers` threads per process."""

    def __init__(self, workers: int = 4, name: str = "window-blob-fetch"):
        import queue as _queue

        self._q: "_queue.Queue" = _queue.Queue()
        self._name = name
        self._threads = []
        for _ in range(workers):
            self._spawn_worker()

    def _spawn_worker(self) -> None:
        t = threading.Thread(
            target=self._run, daemon=True,
            name=f"{self._name}-{len(self._threads)}",
        )
        t.start()
        self._threads.append(t)

    def ensure_workers(self, n: int) -> None:
        """Grow the pool to at least `n` daemon workers (never shrinks:
        threads are parked on a queue and cost nothing idle). Lets the
        solve pool size itself to the DEVICE pool that actually exists
        instead of a hardcoded worst case (ISSUE 15 satellite)."""
        while len(self._threads) < n:
            self._spawn_worker()

    @property
    def worker_count(self) -> int:
        return len(self._threads)

    def _run(self) -> None:
        while True:
            fut, fn = self._q.get()
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn())
            except BaseException as exc:  # delivered via future.result()
                fut.set_exception(exc)
                if isinstance(exc, KeyboardInterrupt):
                    # Interpreter-exit signal: deliver to the waiter AND
                    # re-raise it in the main thread — a bare raise here
                    # would only kill this worker (the process-wide pool
                    # never replenishes, so fetches would hang forever)
                    # without interrupting anything (ISSUE 9 satellite).
                    import _thread

                    _thread.interrupt_main()

    def submit(self, fn, *args):
        from concurrent.futures import Future

        fut: Future = Future()
        self._q.put((fut, lambda: fn(*args)))
        return fut


_shared_pool: _DaemonFetchPool | None = None
_shared_pool_lock = threading.Lock()


def _shared_fetch_pool() -> _DaemonFetchPool:
    """The process-wide blob-fetch pool, created on first use. Never shut
    down: the workers are daemon threads idling on a queue, so they cost
    nothing and cannot block interpreter exit. Solver.close() fail-fasts
    new submits at the solver level instead of tearing the pool down under
    other solvers."""
    global _shared_pool
    with _shared_pool_lock:
        if _shared_pool is None:
            # Several workers: concurrent device_get transfers overlap, so
            # a depth-N serving pipeline divides the device round trip.
            _shared_pool = _DaemonFetchPool(workers=4)
        return _shared_pool


@jax.jit
def _add_rows(avail, idx, delta_rows):
    """Jitted ADDITIVE row update for the pipelined device availability:
    ships host-side deltas without clobbering gang subtractions the device
    threaded from still-in-flight windows. Padding rows carry zero deltas,
    so duplicate padded indices are harmless."""
    return avail.at[idx].add(delta_rows)


from functools import partial as _partial


@_partial(jax.jit, static_argnames=("fill", "emax", "num_zones"))
def _window_blob(cluster, apps, *, fill, emax, num_zones):
    """batched_fifo_pack with every per-row output packed into ONE int32
    array [B, 3+Emax]: (driver, admitted, packed, exec slots...). Each
    fetched array is its own device round trip, so the serving path pulls
    a single blob instead of four arrays. Also returns
    the threaded committed-base availability so a PIPELINED caller can
    dispatch the next window from it without fetching this one."""
    out = batched_fifo_pack(
        cluster, apps, fill=fill, emax=emax, num_zones=num_zones
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


@_partial(jax.jit, static_argnames=("kernel", "fill", "emax", "num_zones"))
def _window_blob_pallas(cluster, win, *, kernel, fill, emax, num_zones):
    """Segmented-window solve on the Pallas path (ops/pallas_window). The
    blob stays [S, R, 3+emax] — pack_window_fetch flattens the real rows
    host-side via the handle's seg_map, so the device program's shape
    depends ONLY on the (segments, rows) buckets, never on the window's
    flat row count (a third shape dimension would cross-multiply the
    compile cache)."""
    meta, execs, base_after = kernel(
        cluster, win, fill=fill, emax=emax, num_zones=num_zones
    )
    blob = jnp.concatenate([meta[:, :, :3], execs], axis=2)
    return blob, base_after


def _window_blob_split(avail, statics, apps, *, fill, emax, num_zones):
    """`_window_blob` with the availability split from the static cluster
    fields (models.cluster.cluster_statics): the multi-device engine keeps
    only the STATIC fields resident per pool slot and threads the base
    availability as its own argument, so the donated variant can consume
    the carry in place without deleting the resident replica."""
    out = batched_fifo_pack(
        cluster_from_statics(avail, statics), apps,
        fill=fill, emax=emax, num_zones=num_zones,
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


def _window_blob_pruned_split(
    avail, statics, apps, zone_base, *, fill, emax, num_zones
):
    """Pruned-window solve over a GATHERED top-K sub-cluster (core/prune.py):
    `avail`/`statics` hold only the kept rows, `zone_base` carries the
    excluded rows' per-zone availability sums so zone ranks stay byte-exact
    with the full solve (ops/sorting.zone_ranks). Returns the decision blob
    plus the availability DELTA (after - before): padding rows and
    duplicate padded indices then scatter back into the resident [N,3]
    carry as additive zeros — deterministic where a .set of padded values
    would race."""
    out = batched_fifo_pack(
        cluster_from_statics(avail, statics), apps,
        fill=fill, emax=emax, num_zones=num_zones, zone_base=zone_base,
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
    return blob, out.available_after - avail


_window_blob_pruned = jax.jit(
    _window_blob_pruned_split, static_argnames=("fill", "emax", "num_zones")
)


_window_blob_statics = jax.jit(
    _window_blob_split, static_argnames=("fill", "emax", "num_zones")
)


def _window_blob_split_donated(avail, statics, apps, *, fill, emax, num_zones):
    """`_window_blob_split` under a DONATION-MARKED module name. The
    persistent compilation cache must never serve a donated program from
    disk: reloaded donated executables intermittently returned WRONG
    window decisions (spurious failure-fit / shifted placements —
    reproduced 4/4 on hack/ha_shard_bench.py's chaos soak whenever the
    donated `jit__window_blob_split` entry was a cache HIT, never on a
    miss; PR 8 ran that bench cache-free as the workaround). Donation is
    invisible in the cache-key string, so the jitted wrapper gets its own
    function name and InstallConfig.serialize_jax_cache_io() gates every
    donation-marked module out of cache reads AND writes — donated
    programs always compile in-process (a few seconds once per process),
    while the expensive undonated kernels keep the cache."""
    return _window_blob_split(
        avail, statics, apps, fill=fill, emax=emax, num_zones=num_zones
    )


# Double-buffered committed base: the carry is DONATED, so available_after
# reuses the input buffer in place instead of copy-on-write. The input base
# is DEAD after the call — the pipeline threads available_after forward and
# nothing else may read the consumed buffer (tests pin the deletion).
_window_blob_donated = jax.jit(
    _window_blob_split_donated,
    static_argnames=("fill", "emax", "num_zones"),
    donate_argnums=(0,),
)


@jax.jit
def _take_rows(arr, idx):
    """Row gather for partitioned window solves: the sub-cluster's CURRENT
    availability pulled out of the threaded device base (runs on the base's
    device; the small [n_g, 3] result then moves to the partition's slot)."""
    return arr[idx]


@_partial(jax.jit, donate_argnums=(0,))
def _scatter_rows_exact_donated(base, idx, rows):
    """Scatter a partition's committed sub-base back into the (DONATED)
    global base. `idx` is the partition's EXACT domain index list — no
    padding, no duplicates — so .set is deterministic and in-place.
    The "_donated" function name is load-bearing: it marks the module for
    the persistent-cache donation gate (see _window_blob_split_donated)."""
    return base.at[idx].set(rows)


@_partial(jax.jit, donate_argnums=(0,))
def _add_rows_donated(avail, idx, delta_rows):
    """`_add_rows` with the pipelined base DONATED: external availability
    deltas update the committed base in place. The input buffer is dead
    after the call; only the returned array may be threaded forward.
    "_donated" in the name feeds the persistent-cache donation gate."""
    return avail.at[idx].add(delta_rows)


_solve_pool: "_DaemonFetchPool | None" = None
_solve_pool_lock = threading.Lock()


def _shared_solve_pool(min_workers: int = 2) -> "_DaemonFetchPool":
    """Process-wide worker pool for the multi-device engine's window solves.

    On backends whose dispatch is effectively synchronous (jax CPU runs the
    program inside the jit call), concurrent per-slot solves need their own
    host threads; on async backends the worker just owns the block+fetch.
    Shared and daemon for the same reasons as the fetch pool (see
    _DaemonFetchPool): workers run stateless jit applies and device_get
    calls, and per-solver pools would leak threads across rebuilt apps.

    SIZED TO THE DEVICE POOL, not a hardcoded 8 (ISSUE 15 satellite): the
    caller passes `min(8, 2 * pool_slots)` — two workers per slot keeps the
    upload-N+1-while-N-solves overlap engaged at pipeline depth 2 — and the
    pool grows monotonically to the largest request, so a pool-1 mesh
    solver stops carrying 7 idle daemon threads."""
    global _solve_pool
    with _solve_pool_lock:
        if _solve_pool is None:
            _solve_pool = _DaemonFetchPool(
                workers=max(1, min_workers), name="window-solve"
            )
        else:
            _solve_pool.ensure_workers(min_workers)
        return _solve_pool


class _PoolSlot:
    """One slot of the window-solve device pool: a plain device, or a
    single-axis ("nodes",) sub-mesh sharding the node axis (the GSPMD
    serving mode). Keeps the slot's resident STATIC replica (and gathered
    sub-replicas per partition domain), upload stats, and in-flight count."""

    __slots__ = (
        "placement", "label", "is_mesh", "statics", "statics_epoch",
        "sub_statics", "uploads", "last_full_upload", "inflight",
        "quarantined", "quarantined_at", "last_probe", "failure_count",
        "avail", "avail_epoch", "avail_token", "mirror",
    )

    def __init__(self, placement):
        self.placement = placement
        self.is_mesh = hasattr(placement, "devices")  # jax.sharding.Mesh
        if self.is_mesh:
            devs = list(placement.devices.flat)
            self.label = (
                f"{devs[0].platform}:{devs[0].id}-{devs[-1].id}"
            )
        else:
            self.label = f"{placement.platform}:{placement.id}"
        self.statics = None  # resident static-field tuple (full cluster)
        self.statics_epoch = -1
        # idx_key -> (epoch, statics tuple, idx device array) for gathered
        # partition sub-clusters.
        self.sub_statics: dict = {}
        # Per-slot replica decisions: "full" (statics uploaded), "delta"
        # (lagging replica caught up by scattering the journal's changed
        # rows), "reuse" (resident copy served). Availability DELTAS are
        # pipeline-level (one thread for the whole pool), counted in
        # device_state_stats.
        self.uploads = {"full": 0, "delta": 0, "reuse": 0}
        self.last_full_upload = 0.0
        self.inflight = 0
        # Slot-failure quarantine (ISSUE 9): a quarantined slot takes no
        # new dispatches until a periodic probe program succeeds on it.
        self.quarantined = False
        self.quarantined_at = 0.0
        self.last_probe = 0.0
        self.failure_count = 0
        # Per-slot delta-synced availability mirror (ISSUE 15): the last
        # full-base replica this slot held, its availability epoch, and
        # the pipeline-generation token it belongs to. A lagging slot
        # whose missed epochs are all journaled catches up by ROW-SCATTER
        # from the canonical base (the PR 11 epoch-journal pattern,
        # extended from statics to availability) instead of re-shipping
        # the full [N,3] base. INVARIANT: `avail` never aliases the
        # pipeline's canonical buffer — the canonical is donated through
        # solves, and a donated buffer must have exactly one referent.
        self.avail = None
        self.avail_epoch = -1
        self.avail_token = -1
        # Mirror sync counters: delta catch-ups (events + rows scattered),
        # full re-ships ("dense" syncs), and zero-transfer reuses.
        self.mirror = {"catchup": 0, "delta_rows": 0, "dense": 0, "reuse": 0}

    def _put(self, arr):
        if self.is_mesh:
            from spark_scheduler_tpu.parallel.solve import node_sharding

            a = jnp.asarray(arr)
            return jax.device_put(
                a, node_sharding(self.placement, a.ndim)
            )
        return jax.device_put(arr, self.placement)

    def place_avail(self, avail):
        """Move the threaded base (or a gathered sub-base) onto this slot.
        A same-device put is a no-op view, so the single-slot pool costs
        nothing extra."""
        return self._put(avail)

    def place_apps(self, apps):
        """Mesh slots shard the app batch's node-axis masks with the
        cluster; plain devices let the jit follow its committed inputs."""
        if not self.is_mesh:
            return apps
        from spark_scheduler_tpu.parallel.solve import shard_apps

        return shard_apps(apps, self.placement)

    def resident_statics(self, host, epoch, clock, telemetry, journal=None):
        """The slot's resident full-cluster static replica.

        Epoch current: serve the resident copy. Epoch behind with every
        missed epoch present in `journal` (the solver's statics-delta
        journal): catch up by scattering just the union of changed rows —
        a node event costs each slot O(changed) upload bytes instead of
        the full multi-MB blob. Anything else — first touch, a shape
        change, an evicted journal epoch (delta against a stale epoch
        must NEVER silently skew), a full upload having cleared the
        journal, or a mesh slot (sharded scatter stays out of scope) —
        re-uploads the full statics."""
        if self.statics is not None and self.statics_epoch == epoch:
            self.uploads["reuse"] += 1
            if telemetry is not None:
                telemetry.on_device_upload(self.label, "reuse", 0)
            return self.statics
        statics_np = cluster_statics(host)
        if (
            self.statics is not None
            and not self.is_mesh
            and journal
            and 0 <= self.statics_epoch < epoch
            and getattr(self.statics[0], "shape", (None,))[0]
            == np.asarray(statics_np[0]).shape[0]
            and all(
                e in journal for e in range(self.statics_epoch + 1, epoch + 1)
            )
        ):
            rows = np.unique(
                np.concatenate(
                    [
                        journal[e]
                        for e in range(self.statics_epoch + 1, epoch + 1)
                    ]
                )
            )
            idx = np.resize(rows, _bucket(len(rows), 16)).astype(np.int32)
            idx_dev = self._put(idx)
            nbytes = idx.nbytes
            updated = []
            for dev_f, host_f in zip(self.statics, statics_np):
                vals = np.asarray(host_f)[idx]
                updated.append(
                    _scatter_rows(dev_f, idx_dev, self._put(vals))
                )
                nbytes += vals.nbytes
            self.statics = tuple(updated)
            self.statics_epoch = epoch
            self.uploads["delta"] += 1
            if telemetry is not None:
                telemetry.on_device_upload(self.label, "delta", nbytes)
            return self.statics
        self.statics = tuple(self._put(f) for f in statics_np)
        self.statics_epoch = epoch
        self.uploads["full"] += 1
        self.last_full_upload = clock()
        if telemetry is not None:
            nbytes = sum(getattr(f, "nbytes", 0) for f in statics_np)
            telemetry.on_device_upload(self.label, "full", nbytes)
        return self.statics

    def sub_replica(self, host, idx_key, idx, epoch, clock, telemetry):
        """Gathered static sub-cluster for a partition domain, cached per
        (domain, statics epoch). `idx` is the host-side numpy index list."""
        cached = self.sub_statics.get(idx_key)
        if cached is not None and cached[0] == epoch:
            self.uploads["reuse"] += 1
            if telemetry is not None:
                telemetry.on_device_upload(self.label, "reuse", 0)
            return cached[1]
        statics = tuple(
            self._put(np.asarray(f)[idx]) for f in cluster_statics(host)
        )
        if len(self.sub_statics) >= 64:
            self.sub_statics.clear()
        self.sub_statics[idx_key] = (epoch, statics)
        self.uploads["full"] += 1
        self.last_full_upload = clock()
        if telemetry is not None:
            nbytes = sum(getattr(f, "nbytes", 0) for f in statics)
            telemetry.on_device_upload(self.label, "full", nbytes)
        return statics

    def release(self):
        """Drop every resident device buffer (close()/discard_pipeline():
        repeated server rebuilds in one process must not accumulate dead
        replicas on the devices). In-flight accounting resets too: a
        release accompanies dropping the pipeline, and a discarded
        window's parts are never fetched — without the reset the
        DEVICE_INFLIGHT gauge would report phantom solves forever."""
        self.statics = None
        self.statics_epoch = -1
        self.sub_statics.clear()
        self.inflight = 0
        self.avail = None
        self.avail_epoch = -1
        self.avail_token = -1


class _DevicePool:
    """Slot allocator for the multi-device window-solve engine:
    least-loaded first (round-robin tiebreak), so a fresh window-batch
    UPLOADS to an idle slot while the busy slots keep SOLVING — the
    upload/solve/fetch double-buffer across slots. Slot choice never
    affects decisions (every slot serves the same resident statics), so
    pure round-robin and least-loaded are byte-identical; least-loaded
    just keeps the overlap engaged when solve times are uneven."""

    def __init__(self, slots):
        self.slots = [_PoolSlot(s) for s in slots]
        self._next = 0

    def next_slot(self) -> _PoolSlot:
        """Least-loaded HEALTHY slot (round-robin tiebreak); quarantined
        slots take no new work. Raises AllSlotsQuarantinedError when the
        pool has no healthy slot left — the degraded-mode trigger."""
        n = len(self.slots)
        best, best_i = None, 0
        for off in range(n):
            i = (self._next + off) % n
            s = self.slots[i]
            if s.quarantined:
                continue
            if best is None or s.inflight < best.inflight:
                best, best_i = s, i
                if s.inflight == 0:
                    break
        if best is None:
            raise AllSlotsQuarantinedError(
                f"all {n} device slot(s) quarantined"
            )
        self._next = (best_i + 1) % n
        return best

    def healthy_slots(self) -> "list[_PoolSlot]":
        return [s for s in self.slots if not s.quarantined]

    def quarantined_slots(self) -> "list[_PoolSlot]":
        return [s for s in self.slots if s.quarantined]

    def quarantine(self, slot: _PoolSlot, now: float) -> None:
        """Take the slot out of rotation and drop its resident buffers —
        the device is suspect, so the replicas on it are
        unreachable state, not a cache."""
        slot.quarantined = True
        slot.quarantined_at = now
        slot.last_probe = now
        slot.failure_count += 1
        slot.release()

    def reinstate(self, slot: _PoolSlot) -> None:
        """Probe succeeded: back into rotation. Resident state was
        released at quarantine, so the next dispatch re-uploads statics."""
        slot.quarantined = False

    def occupancy(self) -> float:
        """Fraction of slots with at least one in-flight solve — the
        overlap-occupancy telemetry sample taken at each dispatch."""
        busy = sum(1 for s in self.slots if s.inflight > 0)
        return busy / max(1, len(self.slots))

    def health(self) -> dict:
        q = [s.label for s in self.slots if s.quarantined]
        return {
            "slots": len(self.slots),
            "healthy": len(self.slots) - len(q),
            "quarantined": q,
        }

    def release(self):
        for s in self.slots:
            s.release()

    def stats(self) -> dict:
        return {
            s.label: {
                **s.uploads,
                "inflight": s.inflight,
                "quarantined": s.quarantined,
                "failures": s.failure_count,
                "mirror": dict(s.mirror),
            }
            for s in self.slots
        }


class _PendingBase:
    """A pooled window's committed-base combine, deferred until the next
    pipelined build resolves it ON THE BUILD THREAD. Running the combine
    lazily (instead of as a worker task) means combines can never park
    pool workers waiting on other pool tasks — the classic bounded-pool
    deadlock — and the scatter work is tiny next to the solves it waits
    on. Duck-typed to Future.result() for _resolve_base."""

    __slots__ = ("_fn", "_done", "_val", "_exc")

    def __init__(self, fn):
        self._fn = fn
        self._done = False
        self._val = None
        self._exc = None

    def result(self):
        if not self._done:
            # Exception (not BaseException): KeyboardInterrupt/SystemExit
            # propagate to the build thread instead of being parked as the
            # combine's "result" (ISSUE 9 satellite).
            try:
                self._val = self._fn()
            except Exception as exc:  # surfaced by _resolve_base
                self._exc = exc
            self._done = True
            self._fn = None
        if self._exc is not None:
            raise self._exc
        return self._val


class _WindowPart:
    """One partition of a pooled window: its request slice, the worker
    future resolving to the fetched blob + timings, the EARLY future
    carrying just the committed sub-base (set the moment the solve
    finishes, BEFORE the blob d2h — the next window's base combine must
    not wait out a decision-blob transfer), and the global-node index map
    when the partition solved a gathered sub-cluster."""

    __slots__ = (
        "future", "after_future", "req_ids", "requests", "row_drv",
        "row_exc", "row_skip", "idx", "slot", "rows", "idx_key", "apps",
        "prune", "base_kept",
    )

    def __init__(self, *, future, after_future, req_ids, requests, row_drv,
                 row_exc, row_skip, idx, slot, rows, idx_key=None,
                 apps=None, prune=None, base_kept=None):
        self.future = future
        self.after_future = after_future
        self.req_ids = req_ids  # original positions in the window
        self.requests = requests
        self.row_drv = row_drv  # int64 [b_g, 3]
        self.row_exc = row_exc
        self.row_skip = row_skip
        self.idx = idx  # np int32 global node indices, None = full cluster
        self.slot = slot
        self.rows = rows
        # Re-dispatch inputs (slot-failure recovery): the HOST-side app
        # batch and the sub-replica cache key — enough to re-run this
        # part's solve on a surviving slot byte-identically.
        self.idx_key = idx_key
        self.apps = apps
        # PrunePlan when this part solved a pruned top-K gather of its
        # domain (core/prune.py): its after_future then carries a DELTA
        # (combined additively), and the fetch runs the certificate.
        self.prune = prune
        # Gathered-part dispatch-time base: the [len(idx), 3] int64
        # availability of this part's rows, captured AT DISPATCH (the
        # resident host buffer mutates in place afterwards) — the
        # compact fetch reconstructs in part-local space against this,
        # never touching an [N]-wide array (ISSUE 15).
        self.base_kept = base_kept


@_partial(jax.jit, static_argnames=("fill", "emax", "num_zones"))
def _pack_blob(cluster, dreq, ereq, count, dmask, dom, *, fill, emax, num_zones):
    """Single-app pack with the Packing flattened to one int32 [2+Emax]
    array: (driver, has_capacity, exec slots...) — one device fetch."""
    p = BINPACK_FUNCTIONS[fill](
        cluster, dreq, ereq, count, dmask, dom, emax=emax, num_zones=num_zones
    )
    return jnp.concatenate(
        [p.driver_node[None], p.has_capacity.astype(jnp.int32)[None], p.executor_nodes]
    )


class _NameRankSpace:
    """Order-maintenance name ranks for the native arena (the node-ADD
    cold-rebuild fix, ISSUE 11).

    Every kernel and certificate consumes name_rank as a lexsort KEY —
    rank order matters, values never do (the native builder already
    documents global-vs-subset value deviation). So ranks need not be
    dense: values are assigned with GAPS, and an added node takes the
    midpoint between its lexicographic neighbours' values — O(log n)
    bisect + one arena scatter, where the dense scheme renumbered every
    slot per add (the measured ~96 ms at 100k). Gap exhaustion (adds
    landing repeatedly in one interval) triggers a full renumber, counted
    in `renumbers`.

    Values stay under 2^29 < kInt32Inf/2, so they can never collide with
    the arena's invalid-slot sentinel."""

    _SPAN = 1 << 29

    __slots__ = ("names", "ranks", "renumbers", "rebalances")

    def __init__(self):
        self.names: list[str] = []  # lexicographically sorted
        self.ranks: list[int] = []  # parallel gapped values, ascending
        self.renumbers = 0
        self.rebalances = 0

    def assign_all(self, names_sorted) -> None:
        self.names = list(names_sorted)
        gap = max(1, self._SPAN // (len(self.names) + 1))
        self.ranks = [(i + 1) * gap for i in range(len(self.names))]
        self.renumbers += 1

    def insert(self, name: str):
        """Insert one name. Returns the list of names whose rank VALUES
        changed — just `name` for a clean gap insert, a small rebalanced
        neighborhood when the local gap exhausted — or None when the
        whole space had to renumber (the caller re-scatters EVERY rank).
        A sequential append pattern (node-ADD bursts land adjacent names
        in ONE gap) used to exhaust its gap every ~log(gap) inserts and
        pay the O(n log n) full renumber each time — the measured
        tier-dependent full-snapshot spikes of ISSUE 13; the local
        relabel bounds that to an O(window) scatter."""
        import bisect as _bisect

        i = _bisect.bisect_left(self.names, name)
        if i < len(self.names) and self.names[i] == name:
            return []  # already ranked (idempotent re-add)
        lo = self.ranks[i - 1] if i > 0 else 0
        hi = (
            self.ranks[i]
            if i < len(self.ranks)
            else min(lo + 2 * max(1, self._SPAN // (len(self.names) + 2)),
                     self._SPAN)
        )
        if hi - lo < 2:
            self.names.insert(i, name)
            self.ranks.insert(i, lo)  # placeholder; _rebalance assigns
            return self._rebalance(i)
        self.names.insert(i, name)
        self.ranks.insert(i, (lo + hi) // 2)
        return [name]

    def _rebalance(self, i: int):
        """Order-maintenance local relabel: spread a geometrically grown
        neighborhood of position `i` evenly across its enclosing value
        interval. Returns the names whose values moved, or None when no
        enclosing interval had room (genuine exhaustion: full renumber)."""
        n = len(self.names)
        half = 4
        while True:
            a = max(0, i - half)
            b = min(n, i + half)
            lo = self.ranks[a - 1] if a > 0 else 0
            hi = self.ranks[b] if b < n else self._SPAN
            count = b - a
            if hi - lo >= 4 * (count + 1):
                gap = (hi - lo) // (count + 1)
                changed: list[str] = []
                for k in range(a, b):
                    val = lo + (k - a + 1) * gap
                    if self.ranks[k] != val:
                        self.ranks[k] = val
                        changed.append(self.names[k])
                self.rebalances += 1
                return changed
            if a == 0 and b == n:
                self.assign_all(self.names)
                return None
            half *= 2

    def remove(self, name: str) -> None:
        """Drop one name (node DELETE tombstone): its rank value simply
        leaves the space — neighbours keep their values, and the freed
        gap makes future inserts cheaper. Never renumbers."""
        import bisect as _bisect

        i = _bisect.bisect_left(self.names, name)
        if i < len(self.names) and self.names[i] == name:
            self.names.pop(i)
            self.ranks.pop(i)

    def rank_of(self, name: str) -> int:
        import bisect as _bisect

        i = _bisect.bisect_left(self.names, name)
        return self.ranks[i]


class HostPacking(NamedTuple):
    driver_node: Optional[str]
    executor_nodes: list[str]
    has_capacity: bool
    efficiency_max: float
    efficiency_cpu: float
    efficiency_memory: float
    efficiency_gpu: float


class WindowRequest(NamedTuple):
    """One serving request inside a coalesced /predicates window
    (see PlacementSolver.pack_window)."""

    # (driver_resources, executor_resources, executor_count, skippable) in
    # FIFO order; the LAST row is the request's own application, earlier
    # rows are its pending earlier drivers (fitEarlierDrivers semantics —
    # every unscheduled earlier driver re-packs hypothetically, even one
    # whose own admission this window just committed; the reference does
    # the same, resource.go:221-258 + sparkpods.go:60-77).
    rows: Sequence[tuple]
    driver_candidate_names: Sequence[str]
    domain_node_names: Sequence[str] | None = None  # None = all valid nodes
    domain_mask: "np.ndarray | None" = None  # precomputed [N] bool override


class WindowDecision(NamedTuple):
    """Outcome of one window request (see PlacementSolver.pack_window)."""

    packing: HostPacking
    admitted: bool
    # A non-skippable, still-pending earlier driver failed to fit => the
    # request fails FAILURE_EARLIER_DRIVER instead of FAILURE_FIT
    # (resource.go:241-249).
    earlier_blocked: bool


class PipelineDrainRequired(RuntimeError):
    """Raised by build_tensors_pipelined when node topology/attributes
    changed while a dispatched window is still un-fetched: the caller must
    fetch (complete) the pending window first, then retry — the fresh full
    upload would otherwise discard the in-flight window's threaded base."""


class WindowHandle:
    """A dispatched-but-not-yet-fetched window solve
    (PlacementSolver.pack_window_dispatch -> pack_window_fetch)."""

    __slots__ = (
        "strategy", "blob", "blob_future", "requests", "flat_rows",
        "host_avail", "host_avail32", "host_schedulable", "priors",
        "placements", "placement_rows", "placement_vals", "n",
        "row_driver_req", "row_exec_req", "row_skippable", "seg_map",
        "info", "parts", "request_device", "dispatch_id", "dispatched_at",
        "fused_decisions", "released", "host_tensors", "use_fallback",
        "prune", "fallback_reason", "base_kept", "avail_gen",
        "avail_note_epoch", "dispatch_ms", "fetch_wait_ms", "__weakref__",
    )

    def __init__(self, *, strategy, blob, requests, flat_rows, host_avail,
                 host_schedulable, priors, n):
        self.strategy = strategy
        # Multi-device engine: list[_WindowPart] when the window was served
        # by the device pool (possibly partitioned); None on the classic
        # single-device path. request_device[i] names the slot that solved
        # request i (flight-recorder attribution).
        self.parts = None
        self.request_device = None
        # Device blob, not yet transferred: flat [B, 3+emax] int32 on the
        # XLA path; [S, R, 3+emax] on the Pallas window path (seg_map set
        # — pack_window_fetch flattens the real rows after the pull).
        self.blob = blob
        # Device->host transfer started EAGERLY on a side thread at dispatch
        # (pipelined path): the ~RTT-bound pull elapses concurrently with
        # the dispatcher's host work instead of serializing after it.
        self.blob_future = None
        self.requests = requests
        self.flat_rows = flat_rows
        # Host availability view at dispatch (int64 [N,3]); the device base
        # additionally lacks the placements of `priors` (windows dispatched
        # earlier but un-fetched at this dispatch). Pruned windows skip the
        # per-dispatch int64 materialization: host_avail is None and
        # host_avail32 references the int32 host view (ISSUE 12 —
        # _dense_base materializes lazily on the rare dense paths).
        self.host_avail = host_avail
        self.host_avail32 = None
        self.host_schedulable = host_schedulable
        self.priors = priors  # tuple[WindowHandle] — fetched before this one
        self.placements = None  # int64 [N,3], filled at DENSE fetches only
        # Sparse committed placements (pruned and pooled fetches):
        # `placement_rows` [P] sorted global rows + `placement_vals`
        # [P,3] int64 — later windows subtract priors sparsely and the
        # dense [N,3] placements tensor is never materialized on the hot
        # path at the 1M tier (ISSUE 15).
        self.placement_rows = None
        self.placement_vals = None
        self.n = n
        self.row_driver_req = None  # int64 [B,3], set after dispatch
        self.row_exec_req = None
        self.row_skippable = None
        self.seg_map = None  # pallas window path: (seg_idx, row_idx)
        # Flight-recorder dispatch info: {"path", "nodes", "rows",
        # "row_bucket", "emax", "compile_cache_hit", "dispatch_id",
        # "fused_k"} — set at dispatch.
        self.info = None
        # Monotone per-solver id of the device dispatch that solved this
        # window. Every FusedWindowView of one fused batch shares its
        # umbrella's id — the serving loop's pipeline-depth accounting
        # counts DISPATCHES, not windows.
        self.dispatch_id = None
        self.dispatched_at = 0.0
        # Fused umbrella only: memoized ("ok", decisions) / ("err", exc)
        # of the one real fetch, shared by every view's pack_window_fetch.
        self.fused_decisions = None
        self.released = False
        # Host ClusterTensors view at dispatch (static fields + masks):
        # what slot-failure re-dispatch and the greedy degraded fallback
        # re-solve from. A reference, not a copy — the host arrays are
        # immutable between builds.
        self.host_tensors = None
        # True: no device solved this window (every slot quarantined at
        # dispatch); pack_window_fetch serves it via the greedy fallback.
        self.use_fallback = False
        # Candidate-pruning state (core/prune.PrunePlan) when this window
        # was solved over a gathered top-K sub-cluster; pack_window_fetch
        # maps the blob's local indices back and runs the certificate.
        self.prune = None
        # Why use_fallback was set ("prune-escalation" = a sibling window's
        # failed certificate invalidated the carry this window solved on;
        # None = degraded-mode serving — only the latter counts against the
        # degraded controller's decision gauges).
        self.fallback_reason = None
        # Pruned dispatch (ISSUE 13): the [k_real, 3] int64 dispatch-time
        # availability of the kept rows, gathered AT DISPATCH — the
        # resident host buffer mutates in place afterwards, so the fetch
        # path must never gather from it. `avail_gen` is the resident
        # buffer's generation at dispatch (the undo-journal replay point
        # for the rare dense reconstructions).
        self.base_kept = None
        self.avail_gen = None
        # Pooled idx-None dispatch: the availability epoch this window
        # journaled as UNKNOWABLE — its fetch patches the entry with the
        # exact commit rows so slot mirrors can cross the epoch.
        self.avail_note_epoch = None
        # Host milliseconds of the `solve-dispatch` launch, and of the
        # fetch's blocking wait on the decision pull (`fetch-wait`): the
        # flight recorder's dispatch_ms / fetch_wait_ms phases.
        self.dispatch_ms = None
        self.fetch_wait_ms = None

    def release_buffers(self) -> None:
        """Drop the dispatch's staging buffers: the device decision blob
        and any in-flight pulls (close()/discard_pipeline() — a discarded
        fused batch must not keep its [K, ...] device blob alive through
        view handles parked in the serving pipeline). A later fetch of a
        released handle fails fast instead of pulling freed state."""
        self.released = True
        self.blob = None
        fut = self.blob_future
        if fut is not None:
            fut.cancel()
        if self.parts:
            for p in self.parts:
                p.future.cancel()

    def fetch_ready(self) -> bool:
        """True when every decision pull this window started eagerly has
        landed — completing it costs no blocking wait. False when no eager
        pull exists (the caller decides whether to block)."""
        if self.parts is not None:
            return all(p.future.done() for p in self.parts)
        return self.blob_future is not None and self.blob_future.done()

    def has_eager_fetch(self) -> bool:
        """Whether a decision pull is in flight on a side thread (the
        serving loop sleeps on it instead of blocking in result())."""
        if self.parts is not None:
            return True
        return self.blob_future is not None


class FusedWindowView:
    """One sub-window of a fused K-window dispatch
    (PlacementSolver.pack_windows_dispatch): a slice view over the
    umbrella WindowHandle that solved the K windows' concatenated
    segmented batch in one device program. Duck-typed to the WindowHandle
    surface the serving loop and extender consume (fetch_ready /
    has_eager_fetch / requests / request_device / info / dispatch_id);
    pack_window_fetch on a view fetches the umbrella ONCE (memoized on
    the owner) and returns the view's request slice — the first completed
    view pays the single d2h, the rest are free."""

    __slots__ = ("owner", "lo", "hi", "index", "fused_k", "info")

    def __init__(self, owner: "WindowHandle", lo: int, hi: int,
                 index: int, fused_k: int):
        self.owner = owner
        self.lo = lo
        self.hi = hi
        self.index = index
        self.fused_k = fused_k
        # Per-view copy so a record's solve_info names the view's position
        # inside the fused batch without mutating the shared owner info.
        self.info = {**(owner.info or {}), "fused_index": index}

    @property
    def dispatch_id(self):
        return self.owner.dispatch_id

    @property
    def strategy(self):
        return self.owner.strategy

    @property
    def requests(self):
        return self.owner.requests[self.lo:self.hi]

    @property
    def request_device(self):
        rd = self.owner.request_device
        return rd[self.lo:self.hi] if rd is not None else None

    @property
    def dispatch_ms(self):
        return self.owner.dispatch_ms

    @property
    def fetch_wait_ms(self):
        return self.owner.fetch_wait_ms

    # Serving-loop eager-fetch surface (server/http.py eager_futures).
    @property
    def parts(self):
        return self.owner.parts

    @property
    def blob_future(self):
        return self.owner.blob_future

    def fetch_ready(self) -> bool:
        if self.owner.fused_decisions is not None:
            return True
        return self.owner.fetch_ready()

    def has_eager_fetch(self) -> bool:
        return self.owner.has_eager_fetch()


class PlacementSolver:
    def __init__(
        self,
        driver_label_priority: tuple[str, list[str]] | None = None,
        executor_label_priority: tuple[str, list[str]] | None = None,
        use_native: bool = True,
        device_pool: int = 1,
        mesh: tuple[int, int] | None = None,
        quarantine_probe_s: float = 5.0,
        prune_top_k: int = 0,
        prune_slack: float = 2.0,
        delta_statics: bool = True,
        scale_tier: bool = False,
        build_oracle: bool = False,
        lazy_warm_start: bool = True,
    ):
        self.registry = NodeRegistry()
        # Delta STATIC uploads (`solver.delta-statics`, ISSUE 11): a node
        # event that touches few rows ships a row-scatter of the changed
        # static-field rows instead of the full multi-MB blob (and pool
        # replicas catch up from the epoch journal). Default ON — pinned
        # byte-identical to the full upload by the delta-equivalence
        # suite; False restores the full-upload-per-statics-change paths.
        self._delta_statics = bool(delta_statics)
        # Statics-epoch journal: epoch -> the rows that changed in that
        # epoch's delta. A pool slot whose resident replica is E epochs
        # behind scatters the union of those rows; a slot whose needed
        # epochs were evicted (or that predates a full upload, which
        # clears the journal) must full re-upload — the torn-update
        # contract.
        self._static_journal: dict[int, np.ndarray] = {}
        # Scale-tier serving (`solver.scale-tier`): certificate
        # escalations and cold full-tensor re-solves run as a node-sharded
        # device solve over the mesh of local devices instead of the
        # host-Python greedy walk — the [N] escalation path stops being a
        # host O(N x rows) cost at the million-node tier. Decisions are
        # byte-identical (same kernels; parity-suite pinned); any failure
        # falls back to the host greedy oracle. Default OFF.
        self._scale_tier = bool(scale_tier)
        self._scale_mesh = None  # lazy ("nodes",) mesh over local devices
        self.scale_tier_stats = {"resolves": 0, "sharded": 0, "fallbacks": 0}
        # Candidate pruning (`solver.prune-top-k` / `solver.prune-slack`,
        # core/prune.py): when top-k > 0, eligible pipelined windows solve
        # a gathered top-K sub-cluster and every decision is certified
        # against the full solve at fetch (escalating to the exact host
        # re-solve on a failed certificate). 0 = off (the default): the
        # classic full-tensor paths byte-for-byte.
        self._prune_top_k = int(prune_top_k)
        self._prune_slack = float(prune_slack)
        self._planner = None  # lazy core/prune.PrunePlanner
        # Statics-gather reuse (ISSUE 12 tentpole (c), generalized per
        # domain in ISSUE 15): gathered statics sub-blobs keyed by the
        # kept-row array's identity (per-domain plan reuse re-serves the
        # same keep object; each entry pins its keep, so ids cannot
        # recycle), re-served while no static row-delta touches a kept
        # row. Single-device entries also carry the device buffers; pool
        # slots cache their device copies per (keep, generation).
        self._prune_gather_cache: dict = {}
        self._gather_gen = _itertools.count(1)
        # (domain key, epochs) -> "is the full valid mask" memo — gates
        # the planner's resident-aggregate path for named full-roster
        # domains without an O(N) compare per window.
        self._full_dom_memo: dict = {}
        self.prune_stats = {
            "windows": 0,
            "escalations": 0,
            "kept_rows": 0,
            "window_rows": 0,
            "candidate_rows": 0,
            "reasons": {},
            # O(K + changed) planning evidence (ISSUE 12): rows the
            # planner actually examined (zone re-scans), the cold-build
            # rows, the legacy subset-domain sweeps, resync compares,
            # cache activity, and the per-phase wall-time accumulators.
            "planner_rows_scanned": 0,
            "planner_cold_rows": 0,
            "planner_sweep_rows": 0,
            "planner_resync_rows": 0,
            "planner_zone_rescans": 0,
            "planner_zone_refreshes": 0,
            "planner_merges": 0,
            "planner_boundary_inserts": 0,
            "plan_reuse": 0,
            "gather_reuse": 0,
            "plan_ms": 0.0,
            "gather_ms": 0.0,
            "offset_ms": 0.0,
        }
        # Multi-device window-solve engine (`solver.device-pool` /
        # `solver.mesh` install keys): `mesh=(groups, node_shards)` builds
        # `groups` pool slots of `node_shards` devices each (node_shards>1
        # = the GSPMD sharded serving mode); `device_pool=P` is shorthand
        # for mesh (P, 1). Default (pool 1, no mesh) keeps the classic
        # single-device serving path byte-for-byte.
        self._pool: _DevicePool | None = None
        pool_spec = mesh if mesh is not None else (device_pool, 1)
        if pool_spec and (pool_spec[0] > 1 or pool_spec[1] > 1):
            from spark_scheduler_tpu.parallel.mesh import make_pool_slots

            slots = make_pool_slots(pool_spec[0], pool_spec[1])
            if len(slots) > 1 or pool_spec[1] > 1:
                self._pool = _DevicePool(slots)
        if (
            self._pool is not None
            and any(s.is_mesh for s in self._pool.slots)
            and jax.default_backend() != "tpu"
        ):
            # Startup warning, not an error: the config is legal, but
            # node-axis GSPMD sharding needs an ICI-class interconnect —
            # the CPU mesh measured 0.5x the plain pool (PR 4) and used
            # to degrade silently.
            _warnings.warn(
                "solver.mesh node-shards="
                f"{pool_spec[1]} on backend {jax.default_backend()!r}: "
                "node-axis sharding needs an ICI-class interconnect "
                "(measured 0.5x on a CPU mesh); serving will be slower "
                "than an unsharded pool of the same devices",
                RuntimeWarning,
                stacklevel=2,
            )
        # Statics epoch: bumped on every full host upload (topology or
        # attribute change); pool replicas re-upload when their epoch lags.
        self._static_epoch = 0
        # Fused multi-window dispatch (pack_windows_dispatch): monotone
        # dispatch ids for the serving loop's depth accounting, and weak
        # refs to live fused umbrellas so close()/discard_pipeline() can
        # release their [K, ...] staging buffers even while view handles
        # are still parked in the serving pipeline.
        self._dispatch_seq = _itertools.count(1)
        # Pipeline-generation tokens for the per-slot availability
        # mirrors (ISSUE 15): a slot's resident full-base replica is only
        # a valid catch-up base within the pipeline generation that wrote
        # it — a full re-upload starts a new generation and every replica
        # goes stale at once.
        self._pipe_tokens = _itertools.count(1)
        self._fused_owners: "_weakref.WeakSet[WindowHandle]" = (
            _weakref.WeakSet()
        )
        # How the LAST pipelined/cached build reached the device
        # ("full" | "delta" | "reuse") — flight-recorder state_upload.
        self.last_state_upload: str | None = None
        # Deferred-dispatch lane (ISSUE 18 replay/sweep.py, ISSUE 20
        # fleet/dispatch.py) — None on the plain serving path. A lane is a
        # coordinator that intercepts the pipelined XLA window solve and
        # defers it into a stacked multi-window dispatch: the sweep stacks
        # the SAME window across config arms at its lockstep barrier
        # (arm_stacked_fifo_pack); the fleet coordinator stacks CONCURRENT
        # windows from different clusters inside a short gather window
        # (bucket_stacked_fifo_pack). Lane protocol: `accepts(solver)`
        # gates per-dispatch deferral (the fleet lane declines when fewer
        # than two clusters are live, so those windows take the normal
        # path untouched), `row_bucket_quantum` (None = use the solver's)
        # sets the app-row bucket for DEFERRED windows only, and
        # `defer_window(...)` parks the window, returning lazy blob/avail
        # stand-ins resolved at flush. `_sweep_shared` is the sweep's
        # cross-lane candidate-mask memo (roster state is arm-invariant,
        # so lane 2..M reuse lane 1's mask build). `_row_bucket_quantum`
        # stays 32 for serving (compile-cache coarseness on live
        # traffic); sweep lanes drop it to 8 — under vmap padding rows
        # EXECUTE (lax.cond lowers to select), so tight buckets are pure
        # win there and the sweep pre-compiles its buckets up front.
        self._dispatch_lane = None
        self._sweep_shared: dict | None = None
        self._row_bucket_quantum = 32
        # In-flight worker/fetch futures, cancelled (if unstarted) on
        # close() so repeated server restarts drain the shared pools'
        # queues instead of leaking device buffers through parked closures.
        self._inflight_futures: set = set()
        self._clock = _time.time
        self._driver_label_priority = driver_label_priority
        self._executor_label_priority = executor_label_priority
        # Native C++ arena (native/runtime.cpp): per-node state is upserted
        # only when a node object actually changes, and the dense tensor
        # inputs are materialized in one C call per request instead of a
        # Python walk over every node.
        self._arena = None
        self._node_seen: dict[str, Node] = {}
        self._rank_epoch = -1
        # Deleted-node registry rows awaiting recycling (ISSUE 12): a
        # tombstoned row re-enters the registry free list only once its
        # reservation usage/overhead drained to zero AND no window is in
        # flight that could still name it — until then it stays parked
        # (masked invalid) and is retried every build.
        self._pending_tombstones: set[str] = set()
        self.tombstones_recycled = 0
        # Gapped name-rank order (see _NameRankSpace): a node ADD inserts
        # one rank value instead of renumbering every slot.
        self._rank_space = _NameRankSpace()
        if use_native and native.available():
            self._arena = native.ClusterArena()
        # Device-resident cluster state (VERDICT r2 #3): the last uploaded
        # tensors + their numpy source. build_tensors_cached diffs against
        # the mirror and ships only changed availability rows.
        self._dev: dict | None = None
        # Pipelined serving state (build_tensors_pipelined /
        # pack_window_dispatch / pack_window_fetch): the device availability
        # threaded ACROSS windows, an int64 mirror of it in host terms, and
        # the dispatched-but-unfetched handles. Single-threaded by contract
        # (the predicate batcher is the serialization point); the fetch pool
        # only runs stateless jax.device_get calls.
        self._pipe: dict | None = None
        self._closed = False
        # Candidate-mask memo: serving windows pass the same (usually
        # cluster-wide) candidate list once per request, and building the
        # [N] bool mask is a walk over every name. Keyed by the full
        # name tuple + registry epoch + padded size, so a stale mapping can
        # never serve (collision-safe: dict equality compares the tuple).
        # LRU-evicting: a 65th live signature must not wipe the 64 hottest.
        from spark_scheduler_tpu.core.lru import LRUCache

        self._cand_cache: LRUCache = LRUCache(64)
        # (domain mask, valid mask) -> their AND, identity-keyed with the
        # operands pinned alive: the per-window `dom & valid` product is
        # an O(N) allocation, and — more importantly — a STABLE result
        # object is what lets the prune planner's per-domain contexts
        # recognize an unchanged domain across windows (ISSUE 15).
        self._dom_and_memo: LRUCache = LRUCache(32)
        # Per-names patch bases for the epoch-journal candidate-mask
        # patch (ISSUE 13): names-key -> (epoch, n, mask, unresolved
        # names, removed member names) — see _cand_try_patch.
        self._cand_patch: LRUCache = LRUCache(16)
        # Topology-version memo (see build_tensors' topo_version contract):
        # lets the native tensor build skip its O(nodes) sync walk between
        # requests when no node changed.
        self._topo_seen = None
        self._topo_request_mask = None  # ((version, pad, n), [pad] bool)
        self.device_state_stats = {
            "full_uploads": 0,
            "delta_uploads": 0,
            "delta_rows": 0,
            "reuse_hits": 0,
            # Delta STATIC uploads (row-scatters of changed static-field
            # rows — node events that used to force the full blob).
            "static_delta_uploads": 0,
            "static_delta_rows": 0,
            # Total h2d bytes of every state upload above (full blobs +
            # both delta kinds) — upload_bytes / (full + delta uploads)
            # is the bench's upload_bytes_per_event.
            "upload_bytes": 0,
        }
        # Which device path served each dispatched window (pallas | xla).
        self.window_path_counts: dict[str, int] = {}
        # Compiled Pallas window programs (_pallas_window_program), keyed
        # by kernel, statics and argument shapes: a handful per
        # deployment (strategy x node, segment and row buckets).
        self._pallas_programs: dict = {}
        # SolverTelemetry hook surface (observability/telemetry.py) — wired
        # by build_scheduler_app; None keeps every hot-path hook a single
        # attribute test.
        self.telemetry = None
        # Dispatch info of the most recent SOLO pack() ({"path", "nodes",
        # "emax", "compile_cache_hit"}) for the flight recorder.
        # Single-threaded by the same contract as the pipeline state.
        self.last_solve_info: dict | None = None
        # Device-slot fault recovery (ISSUE 9): how often a quarantined
        # slot is probed for reinstatement, the degraded-mode controller
        # (faults/degraded.py, wired by build_scheduler_app; None =
        # device failures propagate as before), and the lazy host-side
        # greedy fallback the degraded "greedy" policy serves through.
        self.quarantine_probe_s = quarantine_probe_s
        self.degraded = None
        self._fallback = None
        self.redispatch_count = 0
        # Resident native tensor build (ISSUE 13): the nine host field
        # buffers stay RESIDENT between serving builds — the feature
        # store's availability journal + the arena's upsert feed name
        # exactly which rows changed, `arena_snapshot_rows` recomputes
        # just those at C speed, and static fields copy-on-write so
        # in-flight window handles keep their dispatch-time view.
        self._snap_res: dict | None = None
        # Arena rows upserted since the resident arrays last absorbed
        # them (any build path may upsert; the next resident build
        # patches the union). The full flag marks un-nameable static
        # drift (rank renumber, cold identity walk) — resident rebuild.
        self._res_pending: list = []
        self._res_full_pending = False
        # In-place availability patches are UNDO-journaled while pruned
        # handles are in flight: (gen, buffer, rows, old int32 rows) —
        # escalation/fallback re-solves reconstruct their dispatch-time
        # dense view by replaying entries in reverse (_avail_at_dispatch).
        # The hot fetch path never needs it (base_kept gathers at
        # dispatch).
        self._avail_gen = 0
        self._avail_undo: list = []
        self._avail_handles: "_weakref.WeakSet[WindowHandle]" = (
            _weakref.WeakSet()
        )
        # (usage rows, static rows) the LAST build patched; None = the
        # build could not name them (full snapshot / python builder).
        self._last_build_rows: "tuple | None" = None
        # Union of rows EVERY build patched since the pipelined statics
        # last synced (None = some build could not name its rows): the
        # O(changed) candidate set for _plan_static_delta's field diff —
        # robust to solo builds interleaving between pipelined ones.
        self._static_acc: "list | None" = []
        # `solver.build-oracle`: after every dirty-set mirror sync, run
        # the dense compare as an ORACLE and fail loudly if the event-fed
        # candidate set missed a changed row (equivalence suites turn
        # this on; SPARK_SCHEDULER_BUILD_ORACLE=1 forces it).
        self.build_oracle = bool(build_oracle) or (
            _os.environ.get("SPARK_SCHEDULER_BUILD_ORACLE", "")
            not in ("", "0")
        )
        # `solver.lazy-warm-start`: a full device upload whose host-side
        # change feed stayed exact KEEPS the prune planner resident (a
        # warm restart skips the O(N log N) cold replan); False restores
        # the hard invalidate.
        self._lazy_warm_start = bool(lazy_warm_start)
        self.build_stats = {
            "builds": 0,
            "build_ms": 0.0,
            "incremental_builds": 0,
            "full_snapshots": 0,
            # Rows examined by the DENSE mirror sweep (the fallback; 0 in
            # steady state — CI-pinned) vs rows the event-fed dirty-set
            # sync examined.
            "mirror_rows_compared": 0,
            "mirror_dense_syncs": 0,
            "dirty_rows": 0,
            # Rows pooled fetches debited sparsely into the mirror +
            # pending ledger (ISSUE 15 — the pooled path's O(placed)
            # claim as a counter; /debug/state surfaces it).
            "pooled_debit_rows": 0,
            "oracle_checks": 0,
        }

    @property
    def fallback(self):
        if self._fallback is None:
            from spark_scheduler_tpu.core.fallback import (
                GreedyFallbackSolver,
            )

            self._fallback = GreedyFallbackSolver(self)
        return self._fallback

    # -- candidate pruning (core/prune.py) --------------------------------

    def _prune_eligible(self, strategy: str) -> bool:
        """Static gate for the two-tier solve: plain fills only (single-AZ
        wrappers score zones by subset-dependent efficiencies) and no
        configured label priorities (the prefilter/certificate keys assume
        a uniform label rank)."""
        from spark_scheduler_tpu.core.prune import PLAIN_FILLS

        return (
            self._prune_top_k > 0
            and strategy in PLAIN_FILLS
            and self._driver_label_priority is None
            and self._executor_label_priority is None
        )

    def _prune_planner(self):
        """The lazy PrunePlanner (resident per-zone rank index + zone
        aggregates + plan cache, core/prune.py)."""
        if self._planner is None:
            from spark_scheduler_tpu.core.prune import PrunePlanner

            self._planner = PrunePlanner(self.prune_stats)
        return self._planner

    def _prune_invalidate(self) -> None:
        """Drop every resident prefilter artifact (planner state + the
        statics-gather cache's device buffers) — the full-upload /
        topology-change contract."""
        if self._planner is not None:
            self._planner.invalidate()
        self._prune_gather_cache.clear()

    def _prune_note_rows(self, rows) -> None:
        """Feed EXACT changed rows to the planner (O(changed) sync)."""
        if self._planner is not None and len(rows):
            self._planner.note_dirty(rows)

    def _prune_full_upload(self) -> None:
        """A full DEVICE upload is happening. The statics-gather cache's
        device buffers die with it unconditionally; the PLANNER, though,
        keys on HOST state — when the build that triggered this upload
        named its changed rows exactly (the resident tensor build), the
        per-zone orders and aggregates are still exact once those rows are
        fed through the note paths, so a warm restart (discard_pipeline →
        full re-upload of unchanged host state) re-serves WITHOUT paying
        the O(N log N) cold replan (ISSUE 13 tentpole (d)). Any build that
        could not name its rows keeps the hard invalidate."""
        self._prune_gather_cache.clear()
        planner = self._planner
        if planner is None:
            return
        rows = self._last_build_rows
        if self._lazy_warm_start and rows is not None:
            arows, srows = rows
            if len(arows):
                planner.note_dirty(arows)
            if len(srows):
                planner.note_static(srows)
        else:
            planner.invalidate()

    def _prune_mark_unknown(self) -> None:
        """A path that cannot name its changed rows touched availability:
        the planner's next sync diff-scans the snapshots instead."""
        if self._planner is not None:
            self._planner.mark_unknown()

    def _prune_gather_entry(self, host, plan) -> dict:
        """Host-side gathered-statics cache entry for a plan's kept rows,
        keyed by the keep array's IDENTITY (per-domain plan reuse
        re-serves the same object; the entry pins it, so the id cannot
        recycle). Entries drop on static row-deltas touching their kept
        rows, full uploads, and close(); the device-side copies ride the
        entry's generation (single-device: stored here; pool slots: in
        their sub-statics cache)."""
        cache = self._prune_gather_cache
        ent = cache.get(id(plan.keep))
        if ent is not None and ent["keep"] is plan.keep:
            return ent
        while len(cache) >= 17:
            # Evict the oldest entry only: a >16-domain rotation must not
            # wipe every warm gather (and every slot's generation-checked
            # device copy) on each new keep set.
            cache.pop(next(iter(cache)))
        ent = {
            "keep": plan.keep,
            "statics_np": _gather_statics_host(host, plan.keep, plan.k_real),
            "gen": next(self._gather_gen),
        }
        cache[id(plan.keep)] = ent
        return ent

    def _plan_prune(
        self, host, dom_mask, cand_per_req, drv_arr, exc_arr, counts,
        dom_key=None, dom_ref=None,
    ):
        """Build a PrunePlan for one window/partition, or None.

        A full-valid-mask domain — by identity (no names pinned) or by
        memoized content equality (a named domain enumerating the whole
        roster) — takes the O(K + changed) resident-aggregate path;
        genuine subset domains take the counted legacy sweep."""
        planner = self._prune_planner()
        planner.sync(host, self._num_zones_bucket())
        if self._is_full_domain(
            dom_mask, np.asarray(host.valid), dom_key, dom_ref
        ):
            plan = planner.plan_full_domain(
                host,
                cand_per_req=cand_per_req,
                drv_arr=drv_arr,
                exc_arr=exc_arr,
                counts=counts,
                num_zones=self._num_zones_bucket(),
                top_k=self._prune_top_k,
                slack=self._prune_slack,
            )
        else:
            plan = planner.plan_with_masks(
                host,
                dom_mask=np.asarray(dom_mask, bool),
                cand_per_req=cand_per_req,
                drv_arr=drv_arr,
                exc_arr=exc_arr,
                counts=counts,
                num_zones=self._num_zones_bucket(),
                top_k=self._prune_top_k,
                slack=self._prune_slack,
                # Per-domain plan contexts (ISSUE 15 tentpole (b)): the
                # pooled partition path re-serves cached kept sets per
                # instance group instead of re-sweeping O(N) per window.
                dom_key=dom_key,
            )
        if plan is not None:
            st = self.prune_stats
            st["plan_ms"] += plan.plan_ms
            st["offset_ms"] += plan.offset_ms
        return plan

    def _shared_prune_domain(self, requests, dom_keys, dom_per_req):
        """(domain mask, domain key) of the single shared window domain,
        or (None, None) when requests pin distinct domains (the pooled
        partition path prunes per-partition instead; a mixed single-device
        window solves full)."""
        if any(r.domain_mask is not None for r in requests):
            return None, None
        keys = set(dom_keys)
        if len(keys) != 1:
            return None, None
        return dom_per_req[0], dom_keys[0]

    def _is_full_domain(self, dom, valid_np, dom_key, dom_ref) -> bool:
        """Whether a window's shared domain covers the ENTIRE valid mask —
        the gate for the planner's O(K + changed) resident-aggregate path.
        The default (no names pinned) is the valid mask by identity; a
        named domain that happens to enumerate the whole roster (the
        common serving request carries the full node list as its
        instance-group domain) is detected by ONE content compare memoized
        on (domain key, registry epoch, statics epoch) — both epochs pin
        the compared arrays' content, so the O(N) compare runs once per
        roster generation, not per window. `dom_ref` (the names object
        behind the key) is held ALIVE by the memo entry: identity-derived
        keys (digest tickets, huge plain lists) must never be re-matched
        after their object's id is recycled — a subset domain
        misclassified as full would desynchronize the certificate from
        the kernel's domain mask."""
        if dom is valid_np:
            return True
        if dom_key is None:
            return False
        memo_key = (
            dom_key, self.registry.epoch, self._static_epoch,
            valid_np.shape[0],
        )
        hit = self._full_dom_memo.get(memo_key)
        if hit is None:
            if len(self._full_dom_memo) > 16:
                self._full_dom_memo.clear()
            hit = (dom_ref, bool(np.array_equal(dom, valid_np)))
            self._full_dom_memo[memo_key] = hit
        return hit[1]

    def _note_prune_dispatch(self, plan, window_rows: int) -> None:
        st = self.prune_stats
        st["windows"] += 1
        st["kept_rows"] += plan.k_real
        st["window_rows"] += window_rows
        st["candidate_rows"] += plan.dom_rows
        if self.telemetry is not None:
            self.telemetry.on_prune_dispatch(plan.k_real, plan.dom_rows)

    def _note_prune_escalation(self, handle, reason: str) -> None:
        st = self.prune_stats
        st["escalations"] += 1
        st["reasons"][reason] = st["reasons"].get(reason, 0) + 1
        if self._planner is not None:
            # Re-scan to exactness: the failed certificate may trace to
            # conservative drift in a cached entry — an escalation must
            # never loop on the same stale summaries (ISSUE 15).
            self._planner.reset_plan_entries()
        if handle.info is not None:
            handle.info["prune_escalated"] = reason
        if self.telemetry is not None:
            self.telemetry.on_prune_escalation(reason)
            self.telemetry.on_pipeline_event("prune-escalation")
        # The carry embodies the pruned (now-discarded) placements: every
        # window dispatched on it re-solves from its exact host
        # reconstruction, and the next build full-uploads host truth.
        p = self._pipe
        if p is not None:
            if handle in p["unfetched"]:
                p["unfetched"].remove(handle)
            for h in p["unfetched"]:
                h.use_fallback = True
                h.fallback_reason = "prune-escalation"
            self._pipe = None

    def _collect_priors(self, handle, strict: bool):
        """Sparse union (rows, summed deltas) of in-flight prior windows'
        committed placements — O(placed), not O(N): pruned/pooled priors
        carry (placement_rows, placement_vals). `strict` (the
        certificate's contract): a prior whose placements are UNKNOWN
        (failed fetch) returns None — the caller escalates. Lenient (the
        dense-base reconstruction contract): an unknown prior contributes
        nothing — its capacity returns via the next full upload."""
        rows_list: list[np.ndarray] = []
        deltas_list: list[np.ndarray] = []
        for prior in handle.priors:
            pr = prior.placement_rows
            if pr is not None and prior.placement_vals is not None:
                rows_list.append(pr)
                deltas_list.append(prior.placement_vals)
                continue
            if prior.placements is None:
                if strict:
                    return None
                continue
            if pr is None:
                pr = np.flatnonzero(prior.placements.any(axis=1))
            rows_list.append(pr)
            deltas_list.append(prior.placements[pr])
        if not rows_list:
            return (
                np.empty(0, np.int64),
                np.empty((0, NUM_DIMS), np.int64),
            )
        rows = np.concatenate(rows_list)
        deltas = np.concatenate(deltas_list)
        uniq, inv = np.unique(rows, return_inverse=True)
        out = np.zeros((uniq.size, deltas.shape[1]), np.int64)
        np.add.at(out, inv, deltas)
        return uniq.astype(np.int64), out

    def _prior_sparse(self, handle):
        """The certificate's excluded-row-integrity input: strict prior
        collection (None when any prior's placements are unknown, which
        the caller maps to an escalation)."""
        return self._collect_priors(handle, strict=True)

    @staticmethod
    def _commit_rows(requests, drivers, admitted, execs) -> np.ndarray:
        """Global rows a window's COMMITTED placements touched, read
        straight from the decision blob in O(B · emax):
        `_reconstruct_requests` only mutates `placements` at each admitted
        request's final (committing) row — its driver and executor
        indices — so this is exactly the dense placement tensor's
        support. Feeds the sparse mirror debit and the planner's
        dirty-row feed on the dense fetch paths (ISSUE 15)."""
        rows: list[int] = []
        r = 0
        for req in requests:
            real = r + len(req.rows) - 1
            r += len(req.rows)
            if not bool(admitted[real]):
                continue
            d = int(drivers[real])
            if d >= 0:
                rows.append(d)
            ev = np.asarray(execs[real])
            rows.extend(int(x) for x in ev[ev >= 0])
        if not rows:
            return np.empty(0, np.int64)
        return np.unique(np.asarray(rows, np.int64))

    def _dense_base(self, handle) -> np.ndarray:
        """The dense [N,3] int64 fetch-side base reconstruction (host view
        at dispatch minus in-flight priors' placements). Pruned handles
        skip the per-dispatch int64 materialization and pay it only here
        (escalations, fallback re-solves, dense fetch paths); priors with
        known placement rows subtract sparsely."""
        if handle.host_avail is not None:
            base = handle.host_avail.copy()
        else:
            base = self._avail_at_dispatch(handle).astype(np.int64)
        for prior in handle.priors:
            pr = prior.placement_rows
            if pr is not None and prior.placement_vals is not None:
                if pr.size:
                    base[pr] -= prior.placement_vals
                continue
            if prior.placements is None:
                continue
            if pr is not None:
                if pr.size:
                    base[pr] -= prior.placements[pr]
            else:
                base -= prior.placements
        return base

    def _avail_at_dispatch(self, handle) -> np.ndarray:
        """The int32 host availability AS OF `handle`'s dispatch. The
        resident build patches the live buffer in place, journaling each
        patch while pruned handles are in flight — replaying the entries
        newer than the handle's generation in reverse reconstructs the
        dispatch-time view exactly. Rare paths only (escalations, fallback
        re-solves, dense fetches); the hot pruned fetch reads the [K,3]
        base gathered at dispatch."""
        arr = handle.host_avail32
        gen = handle.avail_gen
        if gen is None or not self._avail_undo:
            return arr
        entries = [
            e for e in self._avail_undo if e[1] is arr and e[0] >= gen
        ]
        if not entries:
            return arr
        out = arr.copy()
        for _g, _buf, rows, old in reversed(entries):
            out[rows] = old
        return out

    def device_health(self) -> dict:
        """{slots, healthy, quarantined: [labels]} — /debug/state and the
        readiness probe's degraded view."""
        if self._pool is None:
            return {"slots": 1, "healthy": 1, "quarantined": []}
        return self._pool.health()

    def _on_slot_event(self, event: str, label: str) -> None:
        if self.telemetry is not None:
            self.telemetry.on_slot_event(event, label)
            if self._pool is not None:
                self.telemetry.on_quarantine_count(
                    len(self._pool.quarantined_slots())
                )

    def _quarantine_slot(self, slot, exc) -> None:
        self._pool.quarantine(slot, self._clock())
        self._on_slot_event("quarantine", slot.label)
        from spark_scheduler_tpu.tracing import svc1log

        svc1log().warn(
            "device slot quarantined",
            device=slot.label,
            error=f"{type(exc).__name__}: {exc}",
            failures=slot.failure_count,
        )

    def probe_quarantined(self, force: bool = False) -> int:
        """Run a tiny device program on each quarantined slot whose probe
        interval elapsed; success reinstates the slot (statics re-upload
        on its next dispatch). Returns the number reinstated. Called at
        every pooled dispatch — cheap when nothing is quarantined."""
        pool = self._pool
        if pool is None:
            return 0
        reinstated = 0
        now = self._clock()
        for s in pool.quarantined_slots():
            if not force and now - s.last_probe < self.quarantine_probe_s:
                continue
            s.last_probe = now
            try:
                # The probe pays the same boundaries a real dispatch
                # would (shim'd, so injected device partitions keep the
                # slot down until the plan's window ends).
                _shim("dispatch")
                arr = s._put(np.arange(8, dtype=np.int32))
                np.asarray(jax.device_get(arr + 1))
            except Exception as exc:
                if classify_slot_failure(exc):
                    self._on_slot_event("probe-failed", s.label)
                    continue
                raise
            pool.reinstate(s)
            reinstated += 1
            self._on_slot_event("reinstate", s.label)
        if reinstated and self.degraded is not None and pool.healthy_slots():
            self.degraded.clear()
        return reinstated

    def _degraded_or_raise(self, exc):
        """A device failure with no healthy slot to retry on: consult the
        degraded policy. Returns True when the caller should serve via
        the greedy fallback; raises DegradedUnavailableError (shed) or
        re-raises `exc` (no controller wired)."""
        d = self.degraded
        if d is None:
            raise exc
        d.engage(f"{type(exc).__name__}: {exc}")
        if d.sheds:
            d.on_shed()
            raise DegradedUnavailableError(
                f"no device slot available: {exc}", d.retry_after_s
            ) from exc
        return True

    def _device_recovered(self) -> None:
        """A device solve completed: if degraded mode was engaged by a
        transient single-device failure, serving recovered — clear it.
        (Pool-quarantine degradation clears via probe reinstatement.)"""
        d = self.degraded
        if d is not None and d.active:
            if self._pool is None or self._pool.healthy_slots():
                d.clear()

    @property
    def uses_native_arena(self) -> bool:
        return self._arena is not None

    @property
    def pool_size(self) -> int:
        """Slot count of the multi-device window-solve engine (1 = the
        classic single-device serving path)."""
        return len(self._pool.slots) if self._pool is not None else 1

    def device_pool_stats(self) -> dict:
        """Per-slot resident-state stats ({label: {full, reuse,
        inflight}}) — surfaced by bench.py's multi-device section."""
        return self._pool.stats() if self._pool is not None else {}


    def build_tensors(
        self,
        nodes: Sequence[Node],
        usage,
        overhead,
        *,
        full_node_list: bool = False,
        topo_version: Optional[int] = None,
        roster_rows: "np.ndarray | None" = None,
        dirty_hint: "tuple | None" = None,
        avail_epoch: "int | None" = None,
        avail_journal: "dict | None" = None,
    ):
        """`usage` / `overhead` are either {node: Resources} maps (the
        reference's shape) or dense int64 [cap, 3] arrays indexed by this
        solver's registry (the incremental-tracker fast path — no
        per-reservation host walk).

        `avail_epoch` / `avail_journal` are the feature store's
        availability-input change journal (ISSUE 13): when the chain of
        epochs since the resident build's last sync is fully present, the
        nine host field buffers are PATCHED at the named rows instead of
        re-materialized over every slot — the per-window O(N) arena
        snapshot becomes O(K + changed). Absent or gapped, one full
        materialization runs (fresh buffers; in-flight handles keep the
        old ones).

        `full_node_list` asserts `nodes` is the backend's complete current
        node list (the serving contract of the cached/pipelined builders).
        `topo_version` is the backend's node-mutation counter
        (store/backend.py nodes_version) captured by the caller BEFORE
        listing `nodes` — capture-before-list means a concurrent mutation
        makes the version look stale (extra walk, safe) and never fresh
        (skipped walk over unsynced state, unsafe). Both together enable
        skipping the O(nodes) sync walk and memoizing the request mask.

        `roster_rows` / `dirty_hint` are the HostFeatureStore's cold-path
        accelerators (FeatureSnapshot fields): the registry row of each
        node (the request mask becomes one scatter instead of an O(nodes)
        name->index walk), and the changed Node objects since
        `dirty_hint[0]` (an update-only node event upserts O(changed)
        arena rows instead of the O(nodes) identity walk). Both optional
        and verified before use — a mismatched hint falls back to the
        full walk."""
        if self._arena is not None:
            # `nodes` is passed as-is (tuple/list/store-owned roster): the
            # fast paths only take len(); copying a million-entry list per
            # window was a measured cost.
            return self._build_tensors_native(
                nodes, usage, overhead,
                full_node_list=full_node_list, topo_version=topo_version,
                roster_rows=roster_rows, dirty_hint=dirty_hint,
                avail_epoch=avail_epoch, avail_journal=avail_journal,
            )
        self._last_build_rows = None
        self._acc_build_rows()
        self._note_consumers_unknown()
        for n in nodes:
            self.registry.intern(n.name)
        pad = _bucket(self.registry.capacity, 8)
        return build_cluster_tensors(
            list(nodes),
            usage,
            overhead,
            self.registry,
            driver_label_priority=self._driver_label_priority,
            executor_label_priority=self._executor_label_priority,
            pad_to=pad,
        )

    def build_tensors_cached(
        self,
        nodes: Sequence[Node],
        usage,
        overhead,
        topo_version: Optional[int] = None,
        roster_rows=None,
        dirty_hint=None,
        avail_epoch=None,
        avail_journal=None,
    ) -> ClusterTensors:
        """Device-resident cluster state with delta updates (VERDICT r2 #3).

        Builds the host tensors exactly like `build_tensors`, then keeps the
        device copy ALIVE between requests: when only availability rows
        changed since the previous call (reservation deltas, overhead
        drift), a jitted row-scatter ships just those rows; unchanged state
        re-uses the resident arrays outright; topology/attribute changes
        (any non-availability field) trigger a full upload. The numpy source
        rides along as `.host` so host-side consumers (efficiency, masks)
        never pull arrays back off the device.

        Callers should pass the FULL current node list and express
        per-request affinity/candidate filtering through the kernels'
        domain/candidate masks — that keeps the cached topology stable
        across requests (SURVEY.md §7 "persistent device state + small
        delta updates")."""
        host = self.build_tensors(
            nodes, usage, overhead,
            full_node_list=True, topo_version=topo_version,
            roster_rows=roster_rows, dirty_hint=dirty_hint,
            avail_epoch=avail_epoch, avail_journal=avail_journal,
        )
        stats = self.device_state_stats
        dev = self._dev
        tensors = None
        if dev is not None and dev["host"].available.shape == host.available.shape:
            prev = dev["host"]
            if all(
                getattr(prev, f) is getattr(host, f)
                or np.array_equal(getattr(prev, f), getattr(host, f))
                for f in _STATIC_FIELDS
            ):
                if prev.available is host.available:
                    # Resident build: the buffer is patched in place, so
                    # a value diff sees nothing — the pending ledger
                    # carries the patched rows instead (None = a build
                    # could not name them: full availability re-upload).
                    pend = dev.get("pending")
                    if pend is None:
                        dirty = None
                    elif pend:
                        dirty = np.unique(
                            np.concatenate([np.asarray(c) for c in pend])
                        )
                        dirty = dirty[dirty < host.available.shape[0]]
                    else:
                        dirty = np.empty(0, np.int64)
                else:
                    dirty = np.flatnonzero(
                        np.any(prev.available != host.available, axis=1)
                    )
                if dirty is None:
                    k = host.available.shape[0]  # unknown: ship all rows
                else:
                    k = len(dirty)
                if dirty is not None and k == 0:
                    tensors = dev["tensors"]
                    stats["reuse_hits"] += 1
                    self.last_state_upload = "reuse"
                elif dirty is not None and k <= max(
                    32, host.available.shape[0] // 8
                ):
                    # Bucket the row count so the scatter program compiles
                    # once per bucket; padding repeats dirty rows (set with
                    # identical values — deterministic).
                    idx = np.resize(dirty, _bucket(k, 16))
                    rows = host.available[idx]
                    new_avail = _scatter_rows(
                        dev["tensors"].available,
                        jnp.asarray(idx.astype(np.int32)),
                        jnp.asarray(rows),
                    )
                    tensors = dataclasses.replace(
                        dev["tensors"], available=new_avail
                    )
                    stats["delta_uploads"] += 1
                    stats["delta_rows"] += k
                    stats["upload_bytes"] += rows.nbytes + idx.nbytes
                    self.last_state_upload = "delta"
                    if self.telemetry is not None:
                        self.telemetry.on_transfer(
                            "h2d", rows.nbytes + idx.nbytes
                        )
                else:
                    # COPY before upload: CPU device_put may zero-copy
                    # an aligned buffer, and this one is patched in
                    # place by the resident build (see the pipelined
                    # full upload's aliasing note).
                    tensors = dataclasses.replace(
                        dev["tensors"],
                        available=jax.device_put(host.available.copy()),
                    )
                    stats["full_uploads"] += 1
                    stats["upload_bytes"] += host.available.nbytes
                    self.last_state_upload = "full"
                    if self.telemetry is not None:
                        self.telemetry.on_transfer(
                            "h2d", host.available.nbytes
                        )
        if tensors is None:
            tensors = jax.device_put(
                dataclasses.replace(host, available=host.available.copy())
            )
            stats["full_uploads"] += 1
            stats["upload_bytes"] += _tensors_nbytes(host)
            self.last_state_upload = "full"
            if self.telemetry is not None:
                self.telemetry.on_transfer("h2d", _tensors_nbytes(host))
        tensors.host = host
        self._dev = {"host": host, "tensors": tensors, "pending": []}
        return tensors

    def close(self) -> None:
        """Stop accepting new pipelined fetch submits (they would enqueue a
        Future whose result nobody will pull), CANCEL any queued-but-unrun
        fetch/solve work this solver still has in the shared pools, and
        release every device-resident buffer (pipeline state, cached
        tensors, pool replicas). The pools themselves are process-shared
        (_shared_fetch_pool / _shared_solve_pool) and stay up for other
        solvers — their workers are a bounded set of daemon threads — but
        without the cancel+release, repeated server restarts in one
        process leak device buffers through parked closures."""
        self._closed = True
        for fut in list(self._inflight_futures):
            fut.cancel()  # no-op if already running; queued work is dropped
        self._inflight_futures.clear()
        self._pipe = None
        self._dev = None
        self._snap_res = None  # resident host buffers
        self._avail_undo.clear()
        self._prune_gather_cache.clear()  # release cached device statics
        self._release_fused()
        self._release_pool()

    def _release_pool(self) -> None:
        if self._pool is None:
            return
        self._pool.release()
        if self.telemetry is not None:
            for s in self._pool.slots:
                self.telemetry.on_device_inflight(s.label, 0)

    def discard_pipeline(self) -> None:
        """Drop the pipelined device state: the next build_tensors_pipelined
        does a full upload from the host view. Used when in-flight window
        decisions are being discarded (capacity changed under them) — the
        host view is the durable truth once every surviving window has
        applied. Pool replicas are released with it (the next build bumps
        the statics epoch, so every slot re-uploads on its next turn), and
        so are the staging buffers of any un-fetched FUSED batches — their
        decisions are being discarded with the pipeline (the caller's
        epoch bump re-solves every in-flight window from host truth), so
        keeping the [K, ...] device blobs alive through parked view
        handles would be a restart-shaped leak."""
        self._pipe = None
        self._prune_gather_cache.clear()  # release cached device statics
        self._release_fused()
        self._release_pool()
        if self.telemetry is not None:
            self.telemetry.on_pipeline_event("discard")

    def _release_fused(self) -> None:
        for h in list(self._fused_owners):
            h.release_buffers()
        # WeakSet: survivors were only kept alive by external view refs;
        # they are released now and need no second pass.
        self._fused_owners.clear()

    def build_tensors_pipelined(
        self,
        nodes: Sequence[Node],
        usage,
        overhead,
        topo_version: Optional[int] = None,
        statics_version: Optional[int] = None,
        roster_rows=None,
        dirty_hint=None,
        avail_epoch=None,
        avail_journal=None,
    ) -> ClusterTensors:
        """Timing/telemetry shell around the pipelined build — the
        O(K + changed) claim lands as `build_stats` counters and the
        foundry.spark.scheduler.solver.build.* gauges."""
        bs = self.build_stats
        compared0 = bs["mirror_rows_compared"]
        dirty0 = bs["dirty_rows"]
        t0 = _time.perf_counter()
        try:
            return self._build_tensors_pipelined(
                nodes, usage, overhead,
                topo_version=topo_version,
                statics_version=statics_version,
                roster_rows=roster_rows,
                dirty_hint=dirty_hint,
                avail_epoch=avail_epoch,
                avail_journal=avail_journal,
            )
        finally:
            ms = (_time.perf_counter() - t0) * 1e3
            bs["builds"] += 1
            bs["build_ms"] += ms
            if self.telemetry is not None:
                self.telemetry.on_build(
                    ms,
                    bs["mirror_rows_compared"] - compared0,
                    bs["dirty_rows"] - dirty0,
                )

    def _build_tensors_pipelined(
        self,
        nodes: Sequence[Node],
        usage,
        overhead,
        topo_version: Optional[int] = None,
        statics_version: Optional[int] = None,
        roster_rows=None,
        dirty_hint=None,
        avail_epoch=None,
        avail_journal=None,
    ) -> ClusterTensors:
        """Device-resident availability threaded ACROSS serving windows.

        Unlike build_tensors_cached (which re-uploads the host availability
        rows verbatim), this keeps the device availability equal to
        `last window's committed base` + `external deltas`: the kernel's
        `available_after` from the previous dispatch is extended with the
        ADDITIVE difference between the current host view and an int64
        mirror of what the device already embodies. Gang placements of a
        window are debited from the mirror when the window is fetched
        (pack_window_fetch), so the host's own reservation bookkeeping for
        those gangs does not get shipped a second time — and a gang whose
        reservation the host then failed to create is automatically
        restored by the next delta. This is what makes it safe to DISPATCH
        window k+1 before FETCHING window k (the pipelined serving loop):
        k's admissions ride the device-side thread, not the host view.

        Raises PipelineDrainRequired when a non-availability field changed
        while a window is still in flight — fetch it first, then retry.
        Single-threaded by contract (the predicate batcher thread).

        `statics_version` is the HostFeatureStore's statics epoch: when the
        caller passes one and it matches the epoch of the resident pipeline
        state, the eight per-window O(nodes) static-field array compares
        are skipped outright (the epoch bumps on every node event, so an
        unchanged epoch proves the fields unchanged). Without it (or on a
        mismatch) the array compares run as before."""
        host = self.build_tensors(
            nodes, usage, overhead,
            full_node_list=True, topo_version=topo_version,
            roster_rows=roster_rows, dirty_hint=dirty_hint,
            avail_epoch=avail_epoch, avail_journal=avail_journal,
        )
        stats = self.device_state_stats
        p = self._pipe
        if p is not None and not self._resolve_base(p):
            p = None  # pooled combine failed: pipeline dead, full re-upload
        static_plan = None
        if p is not None and p["host"].available.shape == host.available.shape:
            statics_same = (
                statics_version is not None
                and statics_version == p.get("statics_version")
            ) or all(
                # Identity first: the resident build shares unchanged
                # static arrays across builds, so `is` settles most
                # fields without an O(N) value compare.
                getattr(p["host"], f) is getattr(host, f)
                or np.array_equal(getattr(p["host"], f), getattr(host, f))
                for f in _STATIC_FIELDS
            )
            if not statics_same and self._delta_statics:
                # Node event touching few rows: ship a static row-scatter
                # delta instead of the full blob (and instead of draining
                # the pipeline). In-flight windows are unaffected — their
                # decisions were computed from (and reconstruct against)
                # their own dispatch-time host view, exactly as with
                # availability deltas.
                static_plan = self._plan_static_delta(p["host"], host)
        else:
            statics_same = False
        if statics_same or static_plan is not None:
            mirror = p["mirror"]
            dirty = self._mirror_dirty(p, host, mirror)
            avail = p["avail"]
            k = len(dirty)
            if k:
                delta_rows = (
                    host.available[dirty].astype(np.int64) - mirror[dirty]
                )
            # An external availability swing too large for the int32 delta
            # rows falls through to a FULL re-upload instead of wrapping
            # silently and corrupting the device base (with windows in
            # flight that raises PipelineDrainRequired below — the standard
            # retry contract of this method).
            fits_i32 = k == 0 or (
                delta_rows.min() >= np.iinfo(np.int32).min
                and delta_rows.max() <= np.iinfo(np.int32).max
            )
            if not fits_i32 and p["unfetched"]:
                if self.telemetry is not None:
                    self.telemetry.on_pipeline_event("drain")
                raise PipelineDrainRequired(
                    "availability delta exceeds int32 with a window in flight"
                )
            if fits_i32:
                static_fields = {}
                if static_plan is not None:
                    static_fields = self._apply_static_delta(
                        p, host, static_plan
                    )
                if k:
                    # The prune planner's O(changed) sync rides exactly
                    # this dirty set (plus fetched placement rows).
                    self._prune_note_rows(dirty)
                    # ... and so do the pool slots' availability mirrors:
                    # the canonical device base changes at these rows.
                    self._avail_journal_note(p, dirty)
                    # Pad with a repeated index but ZERO delta rows: .add
                    # is cumulative, so padding must contribute nothing.
                    # The base is DONATED into the add — committed-base
                    # updates are in place, and the consumed buffer (the
                    # previous build's availability) is dead by contract.
                    kb = _bucket(k, 16)
                    idx = np.full(kb, dirty[0], dtype=np.int32)
                    idx[:k] = dirty
                    rows = np.zeros((kb, host.available.shape[1]), np.int32)
                    rows[:k] = delta_rows
                    avail = _add_rows_donated(
                        avail, jnp.asarray(idx), jnp.asarray(rows)
                    )
                    # The mirror is pipeline-private: patch the dirty rows
                    # in place instead of re-materializing the full int64
                    # view per window.
                    mirror[dirty] = host.available[dirty]
                    stats["delta_uploads"] += 1
                    stats["delta_rows"] += k
                    stats["upload_bytes"] += rows.nbytes + idx.nbytes
                    self.last_state_upload = "delta"
                    if self.telemetry is not None:
                        self.telemetry.on_transfer(
                            "h2d", rows.nbytes + idx.nbytes
                        )
                elif static_plan is not None:
                    self.last_state_upload = "delta"
                else:
                    stats["reuse_hits"] += 1
                    self.last_state_upload = "reuse"
                tensors = dataclasses.replace(
                    p["tensors"], available=avail, **static_fields
                )
                tensors.host = host
                p.update(
                    host=host, tensors=tensors, avail=avail,
                    statics_version=statics_version,
                    # Mirror synced: the pending ledger drains (a dense
                    # sync equally re-established mirror == host).
                    pending=[],
                )
                # Statics synced to `host`: restart the delta-diff
                # candidate accumulator.
                self._static_acc = []
                return tensors
        if p is not None and p["unfetched"]:
            if self.telemetry is not None:
                self.telemetry.on_pipeline_event("drain")
            raise PipelineDrainRequired(
                "cluster topology changed with a window in flight"
            )
        # Upload a COPY of the availability: jax's CPU device_put
        # ZERO-COPIES a suitably-aligned numpy buffer, so device_put of
        # the resident host buffer can leave the device base ALIASING
        # memory the resident build then patches in place — the base
        # absorbs the change by aliasing AND again via the next delta
        # upload (double debit; reproduced on the pooled path whenever
        # the allocator happened to align the buffer). Statics buffers
        # are safe as-is: changed static rows always COW before the
        # write, and same-value writes cannot skew an alias. One [N,3]
        # int32 copy per FULL upload, never on the delta path.
        tensors = jax.device_put(
            dataclasses.replace(host, available=host.available.copy())
        )
        tensors.host = host
        stats["full_uploads"] += 1
        stats["upload_bytes"] += _tensors_nbytes(host)
        self.last_state_upload = "full"
        # Statics may have changed with this full upload: pool replicas
        # re-upload on their next turn. The delta journal cannot bridge a
        # full upload — clearing it forces every lagging replica onto the
        # full path (the torn-update contract). The prune PLANNER keys on
        # HOST state, not device state: when this build named its changed
        # rows exactly, it persists (lazy warm start) instead of re-paying
        # the O(N log N) cold replan.
        self._static_epoch += 1
        self._static_journal.clear()
        self._static_acc = []  # fresh statics baseline on device
        self._prune_full_upload()
        if self.telemetry is not None:
            self.telemetry.on_transfer("h2d", _tensors_nbytes(host))
        self._pipe = {
            "host": host,
            "tensors": tensors,
            "avail": tensors.available,
            "mirror": host.available.astype(np.int64),
            "unfetched": [],
            "statics_version": statics_version,
            # Dirty-row ledger for the event-fed mirror sync: rows the
            # resident build patches + rows fetched placements debit;
            # None = unknown (dense compare next build). Starts empty —
            # the mirror IS the host view at this instant.
            "pending": [],
            # Availability epoch + journal for the per-slot device
            # mirrors (ISSUE 15): each canonical-base mutation bumps the
            # epoch and journals the rows it touched (None = unknowable,
            # forcing a full re-ship across that epoch). Fresh pipeline
            # generation: every slot replica from before is stale.
            "avail_epoch": 0,
            "avail_journal": {},
            "token": next(self._pipe_tokens),
        }
        return tensors

    def _avail_journal_note(self, p, rows) -> None:
        """Bump the pipeline's availability epoch with the rows the
        canonical device base just changed on — a window's kept/partition
        rows at dispatch, a delta upload's dirty rows — or None when the
        rows are unknowable (an unpruned whole-window commit). Pool-slot
        mirrors catch up by scattering the journaled union; any gap or
        None epoch in a slot's missed chain forces the full re-ship. A
        journaled row set may be a SUPERSET of what actually changed:
        catch-up scatters values gathered from the canonical base, so
        extra rows are byte-identical no-ops. Returns the epoch (None
        when no pool): an unknowable (None) entry can be PATCHED once the
        window's fetch learns its exact commit rows — later catch-ups
        then cross the epoch instead of full re-shipping."""
        if self._pool is None or p is None:
            return None
        e = p["avail_epoch"] + 1
        p["avail_epoch"] = e
        j = p["avail_journal"]
        j[e] = None if rows is None else np.asarray(rows)
        while len(j) > 64:
            j.pop(next(iter(j)))
        return e

    def _journal_rows_between(self, p, lo: int, hi: int):
        """Union of journaled rows across epochs (lo, hi], or None when
        the chain has a gap / an unknowable epoch."""
        if lo == hi:
            return np.empty(0, np.int64)
        j = p["avail_journal"]
        out = []
        for e in range(lo + 1, hi + 1):
            rows = j.get(e)
            if rows is None:
                return None
            out.append(rows)
        return np.unique(np.concatenate(out).astype(np.int64))

    def _pool_full_base(self, p, slot, base, base_device):
        """The full committed base, on `slot`, for a whole-window pooled
        solve — via the slot's delta-synced availability MIRROR (ISSUE
        15, the PR 11 statics epoch-journal pattern extended to
        availability). The canonical base lives on one device; a
        dispatch landing elsewhere used to re-ship the whole [N,3] — now
        a slot holding a replica whose missed epochs are all journaled
        catches up by scattering just the union of changed rows.

        Donation invariant: the returned array is consumed by the solve,
        so it must have no other referent. The canonical buffer is never
        returned to a non-owner slot (they get a caught-up replica or a
        fresh copy), and when the canonical migrates, the OLD buffer is
        handed to the slot hosting it as that slot's mirror — p["avail"]
        stops referencing it, so it is never donated again."""
        tel = self.telemetry
        if slot.is_mesh:
            return slot.place_avail(base)
        token, epoch = p["token"], p["avail_epoch"]
        if base_device == slot.placement:
            # Canonical already lives here; the solve donates it in
            # place. Clear any stale replica — it must never alias the
            # canonical, and after this solve the slot's state IS the
            # new canonical.
            slot.avail = None
            slot.avail_epoch = -1
            slot.mirror["reuse"] += 1
            return base
        # The canonical migrates to `slot`: hand the old buffer to the
        # slot that hosts it as ITS mirror (it will catch up by scatter
        # when the canonical comes back around).
        for o in self._pool.slots:
            if not o.is_mesh and o.placement == base_device:
                o.avail = base
                o.avail_epoch = epoch
                o.avail_token = token
                break
        rep = slot.avail
        rows = None
        if (
            rep is not None
            and slot.avail_token == token
            and 0 <= slot.avail_epoch <= epoch
            and getattr(rep, "shape", None) == getattr(base, "shape", None)
        ):
            rows = self._journal_rows_between(p, slot.avail_epoch, epoch)
        slot.avail = None
        slot.avail_epoch = -1
        if rows is not None:
            if not rows.size:
                slot.mirror["reuse"] += 1
                return rep
            idx = np.resize(rows, _bucket(len(rows), 16)).astype(np.int32)
            vals = _take_rows(base, jax.device_put(idx, base_device))
            out = _scatter_rows(
                rep,
                slot._put(idx),
                jax.device_put(vals, slot.placement),
            )
            nbytes = idx.nbytes + int(getattr(vals, "nbytes", 0))
            slot.mirror["catchup"] += 1
            slot.mirror["delta_rows"] += int(rows.size)
            if tel is not None:
                tel.on_device_mirror(
                    slot.label, "catchup", int(rows.size), nbytes
                )
            return out
        slot.mirror["dense"] += 1
        if tel is not None:
            tel.on_device_mirror(
                slot.label, "dense", int(base.shape[0]),
                int(getattr(base, "nbytes", 0)),
            )
        return slot.place_avail(base)

    def _plan_static_delta(self, prev, host):
        """(changed field names, dirty rows) when the static drift between
        two same-shape host views is small enough to ship as a row
        scatter; None sends the caller to the full-upload/drain path.
        Called only when at least one static field differs.

        When the resident build NAMED its changed rows
        (`_last_build_rows`), the diff runs over just those rows: the
        statics copy-on-write only ever rewrites the named patch rows, so
        they are a proven superset of every field difference — the
        8-field O(N) compare per node event becomes O(changed) at the
        million-node tier (ISSUE 15). A build that could not name its
        rows keeps the dense diff."""
        n = host.available.shape[0]
        acc = self._static_acc
        cand = None
        if acc is not None:
            cand = (
                np.unique(np.concatenate(acc)).astype(np.int64)
                if acc
                else np.empty(0, np.int64)
            )
            cand = cand[cand < n]
            if not cand.size:
                # A field differs but no build named a row since the
                # last sync: inconsistent — take the dense diff.
                cand = None
        changed: list[str] = []
        sel = cand if cand is not None else slice(None)
        rows_mask = np.zeros(
            cand.shape[0] if cand is not None else n, dtype=bool
        )
        for f in _STATIC_FIELDS:
            a = np.asarray(getattr(prev, f))
            b = np.asarray(getattr(host, f))
            if a is b:
                continue
            neq = a[sel] != b[sel]
            if neq.ndim == 2:
                neq = neq.any(axis=1)
            if not neq.any():
                continue
            changed.append(f)
            rows_mask |= neq
        if not changed:
            return None
        rows = (
            cand[rows_mask] if cand is not None
            else np.flatnonzero(rows_mask)
        )
        if len(rows) > max(32, n // 8):
            return None
        return changed, rows

    def _apply_static_delta(self, p, host, plan) -> dict:
        """Scatter the changed static-field rows into the resident device
        tensors; returns the replaced device fields for
        dataclasses.replace. Bumps the statics epoch with a JOURNAL entry
        so pool replicas catch up by scattering the same rows, and
        re-keys the prefilter's rank index rows in place (instead of the
        full-upload invalidate)."""
        changed, rows = plan
        k = len(rows)
        # np.resize pads by cycling the dirty rows; duplicate indices then
        # carry identical values, so .set stays deterministic.
        idx = np.resize(rows, _bucket(k, 16)).astype(np.int32)
        idx_dev = jnp.asarray(idx)
        out = {}
        nbytes = idx.nbytes
        for f in changed:
            vals = np.asarray(getattr(host, f))[idx]
            out[f] = _scatter_rows(
                getattr(p["tensors"], f), idx_dev, jnp.asarray(vals)
            )
            nbytes += vals.nbytes
        self._static_epoch += 1
        self._static_journal[self._static_epoch] = rows
        while len(self._static_journal) > 64:
            self._static_journal.pop(next(iter(self._static_journal)))
        stats = self.device_state_stats
        stats["static_delta_uploads"] += 1
        stats["static_delta_rows"] += k
        stats["upload_bytes"] += nbytes
        if self.telemetry is not None:
            self.telemetry.on_transfer("h2d", nbytes)
        if self._planner is not None:
            # Static row-deltas (validity/zone/name-rank/eligibility
            # flips) feed the planner as STATIC dirt: a kept row's static
            # flip re-scans its zone, a new row merges exactly.
            self._planner.note_static(rows)
        for ck, ent in list(self._prune_gather_cache.items()):
            # A cached statics sub-blob gathered rows that just changed:
            # drop that entry (the kept set itself usually changes too,
            # but a static flip on a kept row with an unchanged keep must
            # still force a re-gather). Entries whose kept rows the delta
            # missed keep serving.
            if np.isin(rows, ent["keep"]).any():
                self._prune_gather_cache.pop(ck, None)
        return out

    def _resolve_base(self, p) -> bool:
        """Resolve a pooled window's pending committed-base combine (the
        scatter of every partition's sub-base back into the global base).
        False when the combine failed — the pipeline is dead exactly like
        a failed decision fetch: drop it, count it, rebuild from host
        truth (in-flight handles still fetch fine on their own futures)."""
        avail = p.get("avail")
        if not hasattr(avail, "result"):
            return True
        try:
            p["avail"] = avail.result()
            return True
        except Exception:
            self._pipe = None
            if self.telemetry is not None:
                self.telemetry.on_pipeline_event("fetch-failure")
            return False

    def _label_rank(self, node: Node, prio) -> int:
        if prio is None:
            return INT32_INF
        label, values = prio
        val = node.labels.get(label)
        if val is not None and val in values:
            return values.index(val)
        return INT32_INF

    def _build_tensors_native(
        self,
        nodes: Sequence[Node],
        usage,
        overhead,
        *,
        full_node_list: bool = False,
        topo_version: Optional[int] = None,
        roster_rows: "np.ndarray | None" = None,
        dirty_hint: "tuple | None" = None,
        avail_epoch: "int | None" = None,
        avail_journal: "dict | None" = None,
    ) -> ClusterTensors:
        """Arena-backed ClusterTensors. Deviation from the Python builder,
        deliberate: name ranks are GLOBAL over all known nodes rather than
        recomputed over the request's filtered subset — the rank values
        differ but their relative order (all the sort kernels consume) is
        identical for any subset.

        RESIDENT since ISSUE 13: the serving path (full node list + a
        verified topology chain + a gap-free availability journal) keeps
        the nine output buffers alive between builds and patches exactly
        the changed rows (journal rows + arena upserts) in one C call.
        Static fields copy-on-write when their rows change, so in-flight
        window handles keep their dispatch-time statics; `available` is
        patched in place with an undo journal for the rare dense
        reconstructions. Every other caller (filtered subsets, missing
        epochs, pad growth) takes the full materialization into FRESH
        buffers — prior handles' arrays are never touched."""
        arena = self._arena
        seen = self._node_seen
        # Topology-version fast path: when the backend exposes a node
        # version (store/backend.py nodes_version) and it hasn't moved
        # since the last build, the whole O(nodes) identity walk is
        # skipped — at 10k nodes this walk was a measured serving-window
        # hotspot despite doing no upserts.
        # Skipping is safe regardless of subset: an unchanged version means
        # no node was created/updated/deleted since the FULL-list build that
        # recorded it, so the walk would upsert nothing.
        topo = topo_version

        def _upsert(node) -> None:
            seen[node.name] = node
            # A deleted-then-re-added name is LIVE again: its parked
            # tombstone must not release the row out from under it (a
            # deferred _release_tombstones would unmap a live node and
            # hand its registry row to the free list).
            self._pending_tombstones.discard(node.name)
            idx = self.registry.intern(node.name)
            arena.upsert(
                idx,
                node.allocatable.as_array(),
                self.registry.zone_id(node.zone),
                node.unschedulable,
                node.ready,
                self._label_rank(node, self._driver_label_priority),
                self._label_rank(node, self._executor_label_priority),
            )
            # The resident buffers no longer embody this row's statics:
            # pending until a resident patch (or full rebuild) absorbs it.
            self._res_pending.append(idx)

        if not (topo is not None and topo == self._topo_seen):
            if (
                dirty_hint is not None
                and full_node_list
                and topo is not None
                and dirty_hint[0] == self._topo_seen
            ):
                # Update/ADD/DELETE node event with a verified version
                # chain (the feature store captured exactly what changed
                # since the version this arena last synced to): upsert
                # just the changed rows. New names intern and take a
                # GAPPED name rank between their lexicographic neighbours
                # (_NameRankSpace); deleted names tombstone — their rows
                # are masked out by the roster-row request mask and
                # recycled by _release_tombstones once their usage
                # drains. The existing roster is never re-walked.
                new_names = [
                    n.name for n in dirty_hint[1] if n.name not in seen
                ]
                for node in dirty_hint[1]:
                    _upsert(node)
                if new_names:
                    self._insert_name_ranks(new_names)
                for name in (
                    dirty_hint[2] if len(dirty_hint) > 2 else ()
                ):
                    if name in seen:
                        seen.pop(name, None)
                        self._rank_space.remove(name)
                        self._pending_tombstones.add(name)
                self._topo_seen = topo
            else:
                changed_names = False
                for node in nodes:
                    if seen.get(node.name) is node:
                        continue
                    if node.name not in seen:
                        changed_names = True
                    _upsert(node)
                if changed_names or self._rank_epoch < 0:
                    self._assign_all_name_ranks()
                if full_node_list and topo is not None:
                    # Only a full-list walk proves the arena is synced for
                    # this version; a filtered subset must not suppress
                    # future walks.
                    self._topo_seen = topo
        pad = _bucket(self.registry.capacity, 8)

        usage_t = self._dense_or_scatter(usage, pad)
        overhead_t = self._dense_or_scatter(overhead, pad)
        if self._pending_tombstones:
            self._release_tombstones(usage_t, overhead_t)

        # Only the serving contract (full node list + topology chain) may
        # consume the resident buffers — a filtered subset would bake its
        # request mask into them.
        serving = topo is not None and full_node_list
        res = self._snap_res
        rows_hint = None
        if (
            serving
            and res is not None
            and not self._res_full_pending
            and res["pad"] == pad
        ):
            rows_hint = self._avail_rows_between(
                res.get("avail_epoch"), avail_epoch, avail_journal
            )
        if rows_hint is not None:
            tensors = self._patch_resident(
                res, rows_hint, usage_t, overhead_t,
                nodes, topo, pad, roster_rows,
            )
            res["avail_epoch"] = avail_epoch
            return tensors
        return self._snapshot_full(
            pad, usage_t, overhead_t, nodes, topo, serving,
            roster_rows, avail_epoch,
        )

    def _request_mask(self, nodes, topo, pad, roster_rows, cacheable):
        """[pad] bool mask of this request's candidate rows. The arena
        knows every node ever seen; this request's candidate set is the
        (selector-filtered) `nodes` list. The O(nodes) index walk is
        memoized on the topology version; only a FULL node list is
        memoizable (caller-asserted) — a filtered subset of the same
        length would collide."""
        cached = self._topo_request_mask
        if (
            cacheable
            and cached is not None
            and cached[0] == (topo, pad, len(nodes))
        ):
            return cached[1]
        request_mask = np.zeros(pad, dtype=bool)
        if roster_rows is not None and len(roster_rows) == len(nodes):
            # Feature-store rows for exactly this node list: the mask
            # is one scatter, not an O(nodes) name->index walk.
            request_mask[roster_rows[roster_rows < pad]] = True
        else:
            idxs = [self.registry.index_of(n.name) for n in nodes]
            request_mask[
                [i for i in idxs if i is not None and i < pad]
            ] = True
        if cacheable:
            if (
                cached is not None
                and cached[1].shape[0] == pad
                and np.array_equal(cached[1], request_mask)
            ):
                # Topology moved but membership did not (the routine
                # node-UPDATE case): keep the OLD array object — mask
                # identity is what keeps valid_req, the domain-AND memo
                # and the planner's per-domain contexts stable across
                # events (ISSUE 15). One O(N) bool compare per node
                # event, never per window.
                request_mask = cached[1]
            self._topo_request_mask = (
                (topo, pad, len(nodes)), request_mask,
            )
        return request_mask

    def _avail_rows_between(self, prev, cur, journal):
        """(usage rows, overhead rows, node rows) changed between the
        resident build's synced availability epoch and the snapshot's,
        from the feature store's journal — None when the chain has a gap
        (journal break, eviction, or a caller that does not thread the
        journal): the build then runs one full materialization. The
        3-way split drives COW granularity: usage rows touch only
        `available`, overhead rows additionally `schedulable`, node rows
        any static field."""
        if prev is None or cur is None or journal is None:
            return None
        if cur < prev or cur - prev > 64:
            return None
        empty = np.empty(0, np.int64)
        if cur == prev:
            return empty, empty, empty
        arows: list = []
        orows: list = []
        nrows: list = []
        for e in range(prev + 1, cur + 1):
            ent = journal.get(e)
            if ent is None:
                return None
            arows.append(ent[0])
            orows.append(ent[1])
            nrows.append(ent[2])
        return (
            np.unique(np.concatenate(arows)),
            np.unique(np.concatenate(orows)),
            np.unique(np.concatenate(nrows)),
        )

    def _acc_build_rows(self) -> None:
        """Fold the build's named rows into the statics-delta candidate
        accumulator (None = a build could not name rows: the next
        _plan_static_delta falls back to the dense field diff)."""
        rows = self._last_build_rows
        if rows is None:
            self._static_acc = None
            return
        if self._static_acc is None:
            return
        if rows[0].size:
            self._static_acc.append(rows[0])
        if rows[1].size:
            self._static_acc.append(rows[1])

    def _note_consumer_rows(self, rows) -> None:
        """Rows the resident build just patched, appended to the device
        mirrors' pending ledgers (the pipelined mirror sync and the cached
        solo path scatter exactly these instead of dense-comparing)."""
        p = self._pipe
        if p is not None and p.get("pending") is not None:
            p["pending"].append(rows)
        d = self._dev
        if d is not None and d.get("pending") is not None:
            d["pending"].append(rows)

    def _note_consumers_unknown(self) -> None:
        """This build could not name its changed rows: the device mirrors
        fall back to one dense compare each."""
        p = self._pipe
        if p is not None:
            p["pending"] = None
        d = self._dev
        if d is not None:
            d["pending"] = None

    def _mirror_dirty(self, p, host, mirror) -> np.ndarray:
        """Rows whose availability the next delta upload must ship.

        Event-fed dirty set (ISSUE 13): the pipeline's pending ledger —
        rows the resident build patched plus rows fetched placements
        debited from the mirror — is a proven superset of every
        mirror-vs-host difference, so the sync compares just those rows.
        A build that could not name its rows leaves the ledger None and
        this runs the dense [N]-wide compare once (counted in
        mirror_rows_compared — the counter CI pins at 0 in steady state).
        `build_oracle` re-runs the dense compare after the dirty-set sync
        and fails loudly on a missed row (the equivalence suites' guard).
        """
        pend = p.get("pending")
        bs = self.build_stats
        if pend is None:
            dirty = np.flatnonzero((mirror != host.available).any(axis=1))
            bs["mirror_rows_compared"] += int(mirror.shape[0])
            bs["mirror_dense_syncs"] += 1
            return dirty
        if pend:
            cand = np.unique(
                np.concatenate([np.asarray(c) for c in pend])
            ).astype(np.int64)
            cand = cand[cand < mirror.shape[0]]
        else:
            cand = np.empty(0, np.int64)
        if cand.size:
            neq = (mirror[cand] != host.available[cand]).any(axis=1)
            dirty = cand[neq]
        else:
            dirty = cand
        bs["dirty_rows"] += int(cand.size)
        if self.build_oracle:
            bs["oracle_checks"] += 1
            oracle = np.flatnonzero((mirror != host.available).any(axis=1))
            missed = np.setdiff1d(oracle, dirty)
            if missed.size:
                raise AssertionError(
                    "dirty-set mirror sync missed changed rows "
                    f"{missed[:8].tolist()} (of {missed.size})"
                )
        return dirty

    _RES_FIELDS = (
        "available", "schedulable", "zone_id", "name_rank",
        "label_rank_driver", "label_rank_executor",
        "unschedulable", "ready", "valid",
    )

    def _res_tensors(self, res) -> ClusterTensors:
        f = res["fields"]
        # Memoized bool views of the uint8 backings: view IDENTITY is
        # stable while the backing is (the pipelined statics compare
        # settles unchanged fields with `is`, not an O(N) compare).
        views = res.setdefault("views", {})
        for name in ("unschedulable", "ready"):
            v = views.get(name)
            if v is None or v.base is not f[name]:
                views[name] = v = f[name].view(np.bool_)
        return ClusterTensors(
            f["available"],
            f["schedulable"],
            f["zone_id"],
            f["name_rank"],
            f["label_rank_driver"],
            f["label_rank_executor"],
            views["unschedulable"],
            views["ready"],
            res["valid_req"],
        )

    def _snapshot_full(
        self, pad, usage_t, overhead_t, nodes, topo, serving,
        roster_rows, avail_epoch,
    ) -> ClusterTensors:
        """Full arena materialization into FRESH buffers (cold build, pad
        growth, journal gap, filtered subset). Prior handles keep the old
        arrays; a serving build replaces the resident state with the new
        buffers."""
        raw = self._arena.snapshot_raw(pad, usage_t, overhead_t)
        fields = dict(zip(self._RES_FIELDS, raw))
        request_mask = self._request_mask(
            nodes, topo, pad, roster_rows, serving
        )
        valid_req = fields["valid"].view(np.bool_) & request_mask
        self._last_build_rows = None
        self._acc_build_rows()
        self._note_consumers_unknown()
        if serving:
            self._snap_res = res = {
                "pad": pad,
                "avail_epoch": avail_epoch,
                "mask": request_mask,
                "fields": fields,
                "valid_req": valid_req,
            }
            self._res_pending = []
            self._res_full_pending = False
            self.build_stats["full_snapshots"] += 1
            return self._res_tensors(res)
        return ClusterTensors(
            *raw[:6],
            raw[6].view(np.bool_),
            raw[7].view(np.bool_),
            valid_req,
        )

    def _patch_resident(
        self, res, rows_hint, usage_t, overhead_t, nodes, topo, pad,
        roster_rows,
    ) -> ClusterTensors:
        """O(K + changed) build: recompute exactly the changed rows into
        the resident buffers. Statics copy-on-write at the granularity
        their change class requires — node rows COW every static field,
        overhead rows only `schedulable` (in-flight handles keep
        dispatch-time arrays either way); `available` patches in place
        with an undo journal while pruned handles are in flight."""
        arows, orows, nrows = rows_hint
        if self._res_pending:
            prows = np.unique(np.asarray(self._res_pending, np.int64))
            self._res_pending = []
            nrows = np.union1d(nrows, prows) if nrows.size else prows
        patch = arows
        for extra in (orows, nrows):
            if extra.size:
                patch = np.union1d(patch, extra) if patch.size else extra
        f = res["fields"]
        mask = self._request_mask(nodes, topo, pad, roster_rows, True)
        mask_changed = mask is not res["mask"]
        if patch.size:
            if nrows.size:
                # Node rows: any static field may move — COW them all so
                # stale handles' certify/fallback/escalation inputs stay
                # dispatch-time exact. The COW is also LOAD-BEARING for
                # the device protocol: _plan_static_delta detects which
                # static rows must ship by diffing the previous build's
                # arrays against these — an in-place statics patch would
                # make every node event invisible to the delta upload.
                # (O(N) memcpy per node event is the accepted cost; the
                # steady serving path never enters this branch.)
                for name in self._RES_FIELDS[1:]:
                    f[name] = f[name].copy()
            elif orows.size:
                # Overhead rows touch available + schedulable only: one
                # COW instead of eight (the routine pod-churn case).
                f["schedulable"] = f["schedulable"].copy()
            avail = f["available"]
            if self._avail_handles:
                # GC the undo journal to the oldest live handle's
                # generation before appending — sustained pipelined
                # serving always has a handle in flight, so an
                # only-clear-when-empty policy would grow it forever.
                gens = [
                    h.avail_gen
                    for h in self._avail_handles
                    if h.avail_gen is not None
                ]
                if gens:
                    min_gen = min(gens)
                    if self._avail_undo and self._avail_undo[0][0] < min_gen:
                        self._avail_undo = [
                            e for e in self._avail_undo if e[0] >= min_gen
                        ]
                self._avail_undo.append(
                    (self._avail_gen, avail, patch, avail[patch].copy())
                )
            elif self._avail_undo:
                self._avail_undo.clear()
            self._avail_gen += 1
            self._arena.snapshot_rows(
                patch, usage_t, overhead_t,
                f["available"], f["schedulable"], f["zone_id"],
                f["name_rank"], f["label_rank_driver"],
                f["label_rank_executor"], f["unschedulable"], f["ready"],
                f["valid"],
            )
            self._note_consumer_rows(patch)
        if mask_changed:
            res["mask"] = mask
            res["valid_req"] = f["valid"].view(np.bool_) & mask
        elif nrows.size:
            vals = f["valid"].view(np.bool_)[nrows] & mask[nrows]
            if not np.array_equal(vals, res["valid_req"][nrows]):
                # COW only when the valid mask actually moved: a static
                # flip that leaves validity intact (unschedulable,
                # labels) keeps the valid_req OBJECT stable — identity
                # the domain-AND memo and the planner's per-domain
                # contexts key on (ISSUE 15).
                vr = res["valid_req"].copy()
                vr[nrows] = vals
                res["valid_req"] = vr
        # Planner feed classes: overhead rows change AVAILABILITY keys
        # (avail = alloc - usage - overhead), node rows are static dirt.
        self._last_build_rows = (
            np.union1d(arows, orows) if orows.size else arows,
            nrows,
        )
        self._acc_build_rows()
        self.build_stats["incremental_builds"] += 1
        return self._res_tensors(res)

    def _release_tombstones(self, usage_t, overhead_t) -> None:
        """Recycle deleted nodes' registry rows (the delete-patch
        satellite's second half): a tombstoned row re-enters the
        registry's free list — a future node ADD reuses the index, whose
        fresh statics then ship as an ordinary delta-statics journal row.
        A row with residual reservation usage or schedulable overhead
        stays parked (recycling it would graft the leftovers onto the
        next node) and is retried every build; so does everything while
        a dispatched window is in flight (its fetch may still resolve
        the row's name)."""
        p = self._pipe
        if p is not None and p["unfetched"]:
            return
        still = set()
        for name in self._pending_tombstones:
            row = self.registry.index_of(name)
            if row is None:
                continue
            if (
                row < usage_t.shape[0]
                and row < overhead_t.shape[0]
                and not usage_t[row].any()
                and not overhead_t[row].any()
            ):
                self.registry.remove(name)
                self.tombstones_recycled += 1
            else:
                still.add(name)
        self._pending_tombstones = still

    def _assign_all_name_ranks(self) -> None:
        """Full (re)assignment of the arena's name ranks from the sorted
        known-name set — the cold path, and the gap-exhaustion fallback."""
        self._res_full_pending = True  # every slot's rank value moved
        space = self._rank_space
        space.assign_all(sorted(self._node_seen))
        index_of = self.registry.index_of
        idx = np.fromiter(
            (index_of(name) for name in space.names),
            np.int64,
            count=len(space.names),
        )
        self._arena.set_name_ranks(np.empty(0, np.int64))  # reset to INF
        self._arena.set_name_rank_values(
            idx, np.asarray(space.ranks, np.int32)
        )
        self._rank_epoch += 1

    def _insert_name_ranks(self, names: list[str]) -> None:
        """O(changed) rank insertion for newly-added names. A crowded gap
        triggers a LOCAL order-maintenance relabel (the rebalanced
        neighborhood re-scatters and rides the resident build's static
        dirt); only genuine space exhaustion falls back to the full
        renumber (counted on the space)."""
        space = self._rank_space
        changed: list[str] = []
        renumbered = False
        for name in names:
            out = space.insert(name)
            if out is None:
                renumbered = True
            elif not renumbered:
                changed.extend(out)
        index_of = self.registry.index_of
        if renumbered:
            idx = np.fromiter(
                (index_of(name) for name in space.names),
                np.int64,
                count=len(space.names),
            )
            self._arena.set_name_ranks(np.empty(0, np.int64))
            self._arena.set_name_rank_values(
                idx, np.asarray(space.ranks, np.int32)
            )
            # Every row's rank value moved: resident order keys are stale,
            # and so are the resident build's name-rank rows.
            self._res_full_pending = True
            self._prune_invalidate()
        elif changed:
            # Every rank-space name has a registry row by construction
            # (tombstones leave the space before their row recycles); the
            # filter is belt+braces against a future ordering change.
            pairs = [
                (r, n)
                for r, n in ((index_of(n), n) for n in changed)
                if r is not None
            ]
            if pairs:
                self._arena.set_name_rank_values(
                    np.asarray([r for r, _ in pairs], np.int64),
                    # rank_of at scatter time: duplicates across
                    # rebalances resolve to the FINAL value regardless of
                    # visit order.
                    np.asarray(
                        [space.rank_of(n) for _, n in pairs], np.int32
                    ),
                )
                # Rebalanced rows' name ranks moved: resident static dirt
                # (the build patches them; the planner re-keys via the
                # static row-delta it detects).
                self._res_pending.extend(int(r) for r, _ in pairs)
        self._rank_epoch += 1

    def _dense_or_scatter(self, mapping, pad: int) -> np.ndarray:
        """[pad, 3] int64: a dense array is padded/truncated in one vectorized
        op (rows past `pad` can only be registry-unused zeros); a map is
        scattered entry-by-entry (the fallback path)."""
        if isinstance(mapping, np.ndarray):
            if (
                mapping.shape[0] == pad
                and mapping.dtype == np.int64
                and mapping.flags.c_contiguous
            ):
                # Zero-copy fast path: the feature store's resident dense
                # aggregates already match the pad bucket in steady state,
                # and every consumer reads without mutating — copying
                # [N,3] int64 per window was a measured 1M-tier cost.
                return mapping
            out = np.zeros((pad, NUM_DIMS), dtype=np.int64)
            rows = min(pad, mapping.shape[0])
            out[:rows] = mapping[:rows]
            return out
        out = np.zeros((pad, NUM_DIMS), dtype=np.int64)
        for name, res in mapping.items():
            idx = self.registry.index_of(name)
            if idx is not None and idx < pad:
                out[idx] += res.as_array()
        return out

    def candidate_mask(self, tensors, node_names: Sequence[str]) -> np.ndarray:
        n = tensors.available.shape[0]
        # Native-ingest tickets (server/ingest.NativeNodeNames) are hashable
        # by content digest with memcmp equality — key the cache on the
        # ticket itself so a steady-state request (kube-scheduler resends
        # the same candidate list every call) hits WITHOUT materializing
        # its 10k names or hashing a 10k-string tuple; only a cold miss
        # iterates. Plain lists keep the tuple key.
        names = (
            node_names
            if getattr(node_names, "names_digest", None) is not None
            else tuple(node_names)
        )

        def _build():
            mask = np.zeros(n, dtype=bool)
            unresolved: set = set()
            index_of = self.registry.index_of
            for name in names:
                idx = index_of(name)
                if idx is not None and idx < n:
                    mask[idx] = True
                elif idx is None:
                    # A candidate name with no registry row yet: if it
                    # ever interns, the mask must flip — remembered so
                    # the epoch-journal patch stays exact.
                    unresolved.add(name)
            # Shared across callers — must be treated read-only (every
            # consumer either copies via `&`/stack or hands it straight to
            # the device).
            mask.flags.writeable = False
            return mask, unresolved

        for _ in range(4):
            epoch = self.registry.epoch
            if epoch & 1:  # mutation in flight: the walk would be torn
                continue
            key = (n, epoch, names)
            mask = self._cand_cache.get(key)
            if mask is not None:
                return mask
            patched = self._cand_try_patch(names, n, epoch)
            shared = self._sweep_shared
            if patched is not None:
                mask, unresolved, removed = patched
            elif shared is not None and key in shared:
                # Replay sweep (ISSUE 18): the registry state is
                # arm-invariant (node events are inputs, not decisions), so
                # a sibling lane's mask for the same (n, epoch, ticket) is
                # THIS lane's mask — reuse it instead of re-walking the
                # name->row map. Validated by the same seqlock below.
                mask, unresolved = shared[key]
                removed = set()
                shared["__hits__"] = shared.get("__hits__", 0) + 1
            else:
                mask, unresolved = _build()
                removed = set()
            # Seqlock read: the walk is valid only if the epoch is unchanged
            # after it — otherwise the mask may mix old and new name->index
            # mappings; rebuild.
            if self.registry.epoch == epoch:
                if shared is not None and key not in shared:
                    shared[key] = (mask, unresolved)
                self._cand_cache.put(key, mask)
                self._cand_patch.put(
                    names, (epoch, n, mask, unresolved, removed)
                )
                if getattr(names, "patch_base", None) is not None:
                    # Re-based: drop the lineage back-reference so old
                    # ticket generations can be collected.
                    try:
                        names.patch_base = None
                    except AttributeError:
                        pass
                return mask
        # Registry churning continuously: one consistent build under the
        # registry's lock (uncached — the epoch is stale by construction).
        return self.registry.read_consistent(lambda: _build()[0])

    def _cand_try_patch(self, names, n: int, epoch: int):
        """Patch a previously built candidate mask across registry epochs
        via the mapping-change journal (ISSUE 13): a node ADD used to
        rebuild every cached mask with an O(N) name->row walk — at the
        million-node tier that walk dominated the ADD budget. The patch
        is EXACT: a newly interned name is a member iff it was previously
        unresolved (named by the candidate list before it had a row) or
        previously removed (delete -> re-add); a removed name clears its
        row and parks in `removed` so its re-add re-members. Returns
        (mask, unresolved, removed) or None (no base / journal gap / too
        many ops / pad moved).

        Domain tickets additionally carry LINEAGE (extender._DomainNames
        patch_base/added/removed): a node event that changed an affinity
        domain's membership creates a NEW ticket naming its exact deltas
        — the patch follows the chain to the last ticket it has a base
        for, applies the registry ops, then replays the membership deltas
        oldest-first."""
        prev = self._cand_patch.get(names)
        lineage: list = []
        base_key = names
        while prev is None and len(lineage) < 8:
            base = getattr(base_key, "patch_base", None)
            if base is None:
                return None
            lineage.append(base_key)
            base_key = base
            prev = self._cand_patch.get(base_key)
        if prev is None:
            return None
        e0, n0, mask0, unresolved0, removed0 = prev
        # epoch == e0 is patchable: update/delete-driven domain membership
        # changes arrive as lineage deltas WITHOUT interning a name, so
        # the registry epoch does not move (journal replay is then empty
        # and the lineage alone is exact). Without lineage an equal epoch
        # means nothing changed — the LRU hit would have served.
        if n0 != n or epoch < e0 or (epoch == e0 and not lineage):
            return None
        ops = self.registry.journal_between(e0, epoch)
        if ops is None or len(ops) > 4096:
            return None
        # Copy-on-WRITE, not copy-on-patch: when no op actually flips a
        # bit (the overwhelmingly common case — a node event elsewhere in
        # the roster bumped the epoch, this domain's membership is
        # untouched), the ORIGINAL mask object re-caches under the new
        # epoch. Mask identity is load-bearing (ISSUE 15): the domain-AND
        # memo and the planner's per-domain plan contexts key on it, so
        # an unrelated node ADD must not cold-restart every partition's
        # planning context.
        mask = mask0
        writable = False

        def _w():
            nonlocal mask, writable
            if not writable:
                mask = mask0.copy()
                writable = True

        unresolved = set(unresolved0)
        removed = set(removed0)
        for op, nm, row in ops:
            if op == "add":
                member = nm in removed or nm in unresolved
                removed.discard(nm)
                unresolved.discard(nm)
                if row < n:
                    if bool(mask[row]) != member:
                        _w()
                        mask[row] = member
                elif member:
                    return None  # member beyond the pad: rebuild
            else:  # remove
                if row < n and mask[row]:
                    removed.add(nm)
                    _w()
                    mask[row] = False
        # Membership deltas, oldest ticket first (each delta is relative
        # to its immediate base's content).
        index_of = self.registry.index_of
        for tk in reversed(lineage):
            for nm in tk.patch_removed:
                row = index_of(nm)
                if row is not None and row < n and mask[row]:
                    _w()
                    mask[row] = False
                unresolved.discard(nm)
                removed.discard(nm)
            for nm in tk.patch_added:
                removed.discard(nm)
                row = index_of(nm)
                if row is None:
                    unresolved.add(nm)
                elif row < n:
                    if not mask[row]:
                        _w()
                        mask[row] = True
                else:
                    return None
        if writable:
            mask.flags.writeable = False
        return mask, unresolved, removed

    def _and_valid(self, mask: np.ndarray, valid_np: np.ndarray) -> np.ndarray:
        """Memoized `mask & valid` for window domains. Identity-keyed with
        both operands pinned alive by the entry (id-recycle-safe): while
        neither the candidate mask nor the valid mask changed object, the
        SAME result object returns — which both skips the O(N) AND per
        window and keys the planner's per-domain context reuse."""
        key = (id(mask), id(valid_np))
        hit = self._dom_and_memo.get(key)
        if hit is not None and hit[0] is mask and hit[1] is valid_np:
            return hit[2]
        out = mask & valid_np
        out.flags.writeable = False
        self._dom_and_memo.put(key, (mask, valid_np, out))
        return out

    def _pallas_window_program(self, cluster, win, *, fill, emax):
        """The compiled `_window_blob_pallas` for these arguments, compiled
        on first use. pack_window_dispatch calls this BEFORE entering its
        slot-failure handler: jax raises a failed Mosaic compile as a
        JaxRuntimeError, the same class as a failed device, and a kernel
        the chip's compiler refuses must propagate — never be served by
        the degraded host fallback. (jax's own caches make a compile a
        program already has in this process cheap; the dict spares the
        per-dispatch re-lowering.)"""
        from spark_scheduler_tpu.ops import pallas_window as pw

        kernel = pw.window_pack_pallas
        num_zones = self._num_zones_bucket()
        key = (
            kernel, fill, emax, num_zones,
            tuple(
                (x.shape, x.dtype)
                for x in jax.tree_util.tree_leaves((cluster, win))
            ),
        )
        prog = self._pallas_programs.get(key)
        if prog is None:
            prog = _window_blob_pallas.lower(
                cluster, win, kernel=kernel, fill=fill, emax=emax,
                num_zones=num_zones,
            ).compile()
            self._pallas_programs[key] = prog
        return prog

    def _num_zones_bucket(self) -> int:
        return _bucket(max(len(self.registry._zone_names), 1), 2)

    def pack(
        self,
        strategy: str,
        tensors,
        driver_resources: Resources,
        executor_resources: Resources,
        executor_count: int,
        driver_candidate_names: Sequence[str],
        domain_mask: np.ndarray | None = None,
    ) -> HostPacking:
        from spark_scheduler_tpu.tracing import tracer

        n = tensors.available.shape[0]
        host = _host_view(tensors)
        driver_mask = self.candidate_mask(tensors, driver_candidate_names)
        if domain_mask is None:
            domain_mask = np.asarray(host.valid)
        emax = _bucket(max(executor_count, 1), 8)
        tel = self.telemetry
        compiles_before = tel.compile_count() if tel is not None else None
        # The span covers dispatch AND the device->host transfer — the
        # transfer is where the device work is actually awaited.
        try:
            with tracer().span(
                "solve", strategy=strategy, nodes=n, executors=executor_count
            ):
                # ONE device->host transfer (one flat int32 blob) for the whole
                # decision: every fetched array is a full device round trip
                # (SURVEY.md §7 latency budget). Efficiency reporting
                # runs as pure numpy on the host-resident cluster arrays — zero
                # extra pulls.
                _shim("h2d")
                blob = _shimmed_device_get(
                    _pack_blob(
                        tensors,
                        jnp.asarray(driver_resources.as_array()),
                        jnp.asarray(executor_resources.as_array()),
                        jnp.int32(executor_count),
                        jnp.asarray(driver_mask),
                        jnp.asarray(domain_mask),
                        fill=strategy,
                        emax=emax,
                        num_zones=self._num_zones_bucket(),
                    )
                )
        except Exception as exc:
            if not (
                classify_slot_failure(exc) and self.degraded is not None
            ):
                raise
            # Solo pack does not thread the pipelined base, so the
            # pipeline survives; just this decision serves degraded.
            self._degraded_or_raise(exc)
            self.last_solve_info = {
                "path": "greedy-fallback",
                "nodes": n,
                "emax": emax,
                "compile_cache_hit": None,
                "degraded": True,
            }
            packing = self.fallback.pack(
                strategy, host, driver_resources, executor_resources,
                executor_count, driver_mask, domain_mask,
            )
            self.degraded.on_fallback_decision()
            return packing
        self.last_solve_info = {
            "path": "xla",
            "nodes": n,
            "emax": emax,
            "compile_cache_hit": (
                tel.compile_count() == compiles_before
                if tel is not None
                else None
            ),
        }
        if tel is not None:
            tel.on_pack(nodes=n, emax=emax)
            tel.on_transfer("d2h", getattr(blob, "nbytes", 0))
        driver_idx = int(blob[0])
        has_cap = bool(blob[1])
        executor_nodes = blob[2:]
        eff = avg_packing_efficiency_np(
            np.asarray(host.schedulable),
            np.asarray(host.available),
            driver_idx,
            executor_nodes,
            driver_resources.as_array(),
            executor_resources.as_array(),
        )
        exec_idx = [int(x) for x in executor_nodes if int(x) >= 0]
        self._device_recovered()
        return HostPacking(
            driver_node=self.registry.name_of(driver_idx) if driver_idx >= 0 else None,
            executor_nodes=[self.registry.name_of(i) for i in exec_idx],
            has_capacity=has_cap,
            efficiency_max=float(eff.max),
            efficiency_cpu=float(eff.cpu),
            efficiency_memory=float(eff.memory),
            efficiency_gpu=float(eff.gpu),
        )

    def can_batch(self, strategy: str) -> bool:
        return strategy in BATCHABLE_STRATEGIES

    def preemption_search(
        self,
        strategy: str,
        tensors,
        driver_resources: Resources,
        executor_resources: Resources,
        executor_count: int,
        driver_candidate_names: Sequence[str],
        freed_cum: np.ndarray,  # [C, rows, 3] int — per-candidate freed capacity
        domain_mask: np.ndarray | None = None,
    ) -> tuple[int, dict]:
        """Batched masked-fit probe over candidate eviction sets (policy
        subsystem): candidate c's availability is the cluster plus
        `freed_cum[c]` (in registry index space). ONE vmapped device program
        solves all candidates (ops/packing.py preemption_batched_fit); with
        nested prefixes the first feasible index is the minimal eviction
        set. Returns (first feasible candidate index or -1, solve info)."""
        from spark_scheduler_tpu.ops.packing import (
            PREEMPTION_FILL,
            preemption_batched_fit,
        )

        n = tensors.available.shape[0]
        host = _host_view(tensors)
        driver_mask = self.candidate_mask(tensors, driver_candidate_names)
        if domain_mask is None:
            domain_mask = np.asarray(host.valid)
        emax = _bucket(max(executor_count, 1), 8)
        c = freed_cum.shape[0]
        freed = np.zeros((c, n, freed_cum.shape[2]), dtype=np.int32)
        rows = min(freed_cum.shape[1], n)
        freed[:, :rows, :] = freed_cum[:, :rows, :]
        fill = PREEMPTION_FILL.get(strategy, "tightly-pack")
        ok, _drv, _execs = preemption_batched_fit(
            tensors,
            jnp.asarray(freed),
            jnp.asarray(driver_resources.as_array()),
            jnp.asarray(executor_resources.as_array()),
            jnp.int32(executor_count),
            jnp.asarray(driver_mask),
            jnp.asarray(domain_mask),
            fill=fill,
            emax=emax,
            num_zones=self._num_zones_bucket(),
        )
        ok_host = np.asarray(ok)
        idx = int(np.argmax(ok_host)) if bool(ok_host.any()) else -1
        return idx, {
            "path": "xla-batched-preemption",
            "candidates": c,
            "nodes": n,
            "emax": emax,
            "fill": fill,
        }

    def pack_window(
        self,
        strategy: str,
        tensors,
        requests: Sequence[WindowRequest],
    ) -> list[WindowDecision]:
        """Serve a WINDOW of coalesced /predicates driver requests in ONE
        device program (VERDICT r2 #1).

        Each request becomes a SEGMENT of the scan: its pending earlier
        drivers (hypothetical rows) followed by its own application (the
        committing row). Availability rewinds to a threaded base between
        segments, so each segment sees exactly what that request's solo
        solve would have seen — decisions are identical to serving the
        requests one at a time in window order, including the FIFO
        earlier-driver semantics (resource.go:221-258). Within a segment
        the priority orders are computed ONCE from the segment-start
        availability, exactly as the reference sorts once per request
        (resource.go:299) and reuses the orders while only availability
        mutates.

        Replaces the reference's one-pod-per-call extender protocol
        limitation (cmd/endpoints.go:28-42, SURVEY.md §2d row 1): the
        device cost is one scan over sum(rows) steps instead of one full
        RPC + solve round-trip per request.

        Synchronous form: dispatch + fetch back to back. The PIPELINED
        serving path splits the two (pack_window_dispatch /
        pack_window_fetch) so the next window's host build and device
        dispatch overlap the previous window's blocking decision pull.
        """
        return self.pack_window_fetch(
            self.pack_window_dispatch(strategy, tensors, requests)
        )

    def pack_window_dispatch(
        self,
        strategy: str,
        tensors,
        requests: Sequence[WindowRequest],
    ) -> "WindowHandle":
        """Build the segmented batch and DISPATCH the device solve without
        blocking on the result. Returns a handle for pack_window_fetch.

        When `tensors` came from build_tensors_pipelined, the threaded
        committed-base availability (still on device, never fetched) is
        recorded as the base for the NEXT pipelined build, and the handle
        notes which earlier windows were still un-fetched — their placements
        are subtracted from this window's host-side base snapshot at fetch
        time, so the host reconstruction sees exactly the availability the
        device saw."""
        if strategy not in BATCHABLE_STRATEGIES:
            raise ValueError(f"strategy {strategy!r} is not batchable")
        if self._closed:
            # Fail fast like ThreadPoolExecutor after shutdown — and BEFORE
            # any device work or pipeline mutation, so a raised dispatch
            # leaves no committed-but-orphaned window behind for a retry to
            # double-commit.
            raise RuntimeError("cannot schedule new futures after shutdown")
        if not requests:
            return WindowHandle(
                strategy=strategy, blob=None, requests=(), flat_rows=[],
                host_avail=None, host_schedulable=None, priors=(), n=0,
            )
        n = tensors.available.shape[0]
        host = _host_view(tensors)
        valid_np = np.asarray(host.valid)

        flat_rows: list[tuple] = []
        commit: list[bool] = []
        reset: list[bool] = []
        cand_rows: list[np.ndarray] = []
        dom_rows: list[np.ndarray] = []
        cand_per_req: list[np.ndarray] = []
        dom_per_req: list[np.ndarray] = []
        # Affinity-domain identity per request, for the multi-device
        # engine's partition plan: requests sharing a domain_node_names
        # tuple share ONE mask build and one partition key; None marks a
        # request whose domain cannot key a partition (precomputed mask
        # override, or the all-valid default that overlaps everything).
        dom_memo: dict[tuple, np.ndarray] = {}
        dom_keys: list[tuple | None] = []
        req_row_ranges: list[tuple[int, int]] = []
        for req in requests:
            cand = self.candidate_mask(tensors, req.driver_candidate_names)
            key: tuple | None = None
            if req.domain_mask is not None:
                dom = np.asarray(req.domain_mask) & valid_np
            elif req.domain_node_names is not None:
                dom_names = req.domain_node_names
                # Domain identity key: a digest ticket (extender
                # _DomainNames / native ingest) keys O(1); small lists
                # keep the content tuple (cross-object partition dedup);
                # a huge plain list keys by object identity — building
                # and hashing a million-name tuple per request was a
                # measured per-window host cost, and identity keying only
                # costs the partition plan on equal-content DISTINCT
                # objects (decisions unaffected — unkeyed windows solve
                # whole).
                digest = getattr(dom_names, "names_digest", None)
                if digest is not None:
                    key = ("digest", digest)
                elif len(dom_names) <= 4096:
                    key = tuple(dom_names)
                else:
                    key = ("id", id(dom_names))
                dom = dom_memo.get(key)
                if dom is None:
                    dom = self._and_valid(
                        self.candidate_mask(tensors, dom_names), valid_np
                    )
                    dom_memo[key] = dom
            else:
                dom = valid_np
            dom_keys.append(key)
            cand_per_req.append(cand)
            dom_per_req.append(dom)
            lo = len(flat_rows)
            for j, row in enumerate(req.rows):
                flat_rows.append(row)
                commit.append(j == len(req.rows) - 1)
                reset.append(j == 0)
                cand_rows.append(cand)
                dom_rows.append(dom)
            req_row_ranges.append((lo, len(flat_rows)))

        b = len(flat_rows)
        # FIFO windows repeat the SAME row objects across requests (request
        # i's hypothetical prefix shares the pending-driver parse of request
        # i+1), so materialize each distinct Resources once.
        arr_memo: dict[int, np.ndarray] = {}

        def as_arr(res) -> np.ndarray:
            a = arr_memo.get(id(res))
            if a is None:
                a = res.as_array()
                arr_memo[id(res)] = a
            return a

        drv_arr = np.stack([as_arr(r[0]) for r in flat_rows])
        exc_arr = np.stack([as_arr(r[1]) for r in flat_rows])
        counts = np.asarray([r[2] for r in flat_rows], np.int32)
        skip_arr = np.asarray([bool(r[3]) for r in flat_rows])
        emax = _bucket(max(int(counts.max()), 1), 8)
        p = self._pipe
        pipelined = p is not None and tensors is p["tensors"]
        if self._pool is not None and pipelined:
            # Multi-device engine: round-robin the window (partitioned by
            # disjoint affinity domains when possible) across the pool.
            return self._dispatch_pooled(
                strategy, tensors, requests,
                host=host,
                drv_arr=drv_arr, exc_arr=exc_arr, counts=counts,
                skip_arr=skip_arr, emax=emax,
                cand_per_req=cand_per_req, dom_per_req=dom_per_req,
                dom_keys=dom_keys, req_row_ranges=req_row_ranges,
            )
        if pipelined and self._prune_eligible(strategy):
            # Two-tier solve (core/prune.py): gather the prefilter's top-K
            # candidate rows out of the resident carry and solve a [K,3]
            # sub-cluster instead of [N,3]; decisions are certified at
            # fetch and escalate to the exact host re-solve on failure.
            dom_shared, dom_key = self._shared_prune_domain(
                requests, dom_keys, dom_per_req
            )
            if dom_shared is not None:
                handle = self._dispatch_pruned(
                    strategy, requests, host=host, p=p, n=n,
                    drv_arr=drv_arr, exc_arr=exc_arr, counts=counts,
                    skip_arr=skip_arr, emax=emax, cand_rows=cand_rows,
                    commit=commit, reset=reset, dom_shared=dom_shared,
                    cand_per_req=cand_per_req, dom_key=dom_key,
                )
                if handle is not None:
                    return handle
        from spark_scheduler_tpu.tracing import tracer

        # Route the segmented window to the Pallas path when the backend
        # compiles Mosaic and the strategy is a plain fill (ops/
        # pallas_window): XLA sorts per segment, Mosaic walks the rows with
        # availability in VMEM. Decisions identical (parity-suite pinned).
        seg_map = None
        from spark_scheduler_tpu.ops.pallas_window import (
            window_pallas_eligible,
        )

        use_pallas = window_pallas_eligible(strategy, n)
        path = "pallas" if use_pallas else "xla"
        self.window_path_counts[path] = (
            self.window_path_counts.get(path, 0) + 1
        )
        tel = self.telemetry
        compiles_before = tel.compile_count() if tel is not None else None
        seg_bucket = 1
        # Deferred-dispatch lane (sweep arms / fleet stacking): decided up
        # front because a deferring dispatch must NOT pay the h2d shim —
        # the coordinator pays ONE h2d per stacked flush, which is the
        # whole point of fusing the launches.
        lane = self._dispatch_lane
        defer = (
            pipelined
            and not use_pallas
            and lane is not None
            and lane.accepts(self)
        )
        if use_pallas:
            win, seg_idx, row_idx, s_pad, r_pad = _build_segmented_window(
                requests, drv_arr, exc_arr, counts, skip_arr,
                cand_per_req, dom_per_req,
            )
            seg_map = (seg_idx, row_idx)
            row_bucket, seg_bucket = r_pad, s_pad
            program = self._pallas_window_program(
                tensors, win, fill=strategy, emax=emax
            )
        try:
            with tracer().span(
                "solve-dispatch", strategy=strategy, nodes=n,
                window_requests=len(requests), window_rows=b, batched=True,
                path=path,
            ) as dispatch_span:
                # One simulated h2d/dispatch boundary per DISPATCH, on the
                # dispatcher thread — a fused K-window batch pays this once
                # where K sequential dispatches pay it K times.
                if not defer:
                    _shim("h2d")
                if use_pallas:
                    blob, avail_after = program(tensors, win)
                else:
                    quantum = self._row_bucket_quantum
                    if defer:
                        # The lane may carry its own row-bucket policy
                        # (fleet lane: 8, like the sweep) — it applies ONLY
                        # to deferred windows, so the serving hot path's
                        # compile-cache coarseness (32) is untouched when
                        # stacking cannot trigger.
                        quantum = (
                            getattr(lane, "row_bucket_quantum", None)
                            or quantum
                        )
                    row_bucket = _bucket(b, quantum)
                    apps = make_app_batch(
                        drv_arr,
                        exc_arr,
                        counts,
                        skippable=skip_arr,
                        # Coarse row bucket (32 on serving paths): window row
                        # counts jitter with load and FIFO depth; each
                        # distinct bucket is a fresh XLA compile, which
                        # stalls live serving for seconds.
                        pad_to=row_bucket,
                        driver_cand=np.stack(cand_rows),
                        domain=np.stack(dom_rows),
                        commit=commit,
                        reset=reset,
                    )
                    if defer:
                        # Deferred lane (ISSUE 18 sweep / ISSUE 20 fleet):
                        # don't solve yet — park the window with the
                        # coordinator, which stacks it with its peers'
                        # payloads into ONE vmapped dispatch (at the sweep's
                        # lockstep barrier, or the fleet's gather-window
                        # flush). The returned blob/avail are lazy stand-ins
                        # resolved at flush (or singly, on a forced early
                        # fetch / straggler timeout).
                        blob, avail_after = lane.defer_window(
                            self, apps,
                            avail=tensors.available,
                            statics=cluster_statics(tensors),
                            host=host,
                            fill=strategy, emax=emax,
                            num_zones=self._num_zones_bucket(),
                        )
                    elif pipelined:
                        # Double-buffered committed base: the pipeline owns the
                        # availability buffer exclusively (nothing reads it
                        # after this dispatch), so DONATE it — available_after
                        # updates it in place instead of copy-on-write.
                        blob, avail_after = _window_blob_donated(
                            tensors.available, cluster_statics(tensors), apps,
                            fill=strategy, emax=emax,
                            num_zones=self._num_zones_bucket(),
                        )
                    else:
                        blob, avail_after = _window_blob(
                            tensors, apps, fill=strategy, emax=emax,
                            num_zones=self._num_zones_bucket(),
                        )
        except Exception as exc:
            if not classify_slot_failure(exc):
                raise
            # The single device failed AT DISPATCH. The
            # pipelined base may be half-mutated (donation): drop it —
            # the next build full-uploads host truth. Per the degraded
            # policy: serve this window via the host greedy fallback, or
            # shed (DegradedUnavailableError), or propagate (no
            # controller wired).
            priors = tuple(p["unfetched"]) if pipelined else ()
            self._pipe = None
            if tel is not None:
                tel.on_pipeline_event("device-failure")
            self._degraded_or_raise(exc)
            return self._make_fallback_handle(
                strategy, requests, host, n, priors
            )

        info = {
            "path": path,
            "nodes": n,
            "rows": b,
            "row_bucket": row_bucket * seg_bucket,
            "emax": emax,
            "state_upload": self.last_state_upload if pipelined else None,
            "compile_cache_hit": (
                tel.compile_count() == compiles_before
                if tel is not None
                else None
            ),
            "dispatch_id": next(self._dispatch_seq),
            # Overwritten by pack_windows_dispatch when this dispatch
            # carries a fused K-window batch.
            "fused_k": 1,
        }
        # The solo batched-admission path (a single-segment pack_window)
        # reads this right after its solve, like pack()'s callers do.
        self.last_solve_info = info
        if tel is not None:
            tel.on_window_dispatch(
                path, nodes=n, rows=b, row_bucket=row_bucket,
                segment_bucket=seg_bucket,
            )
            if use_pallas:
                nbytes = (
                    drv_arr.nbytes + exc_arr.nbytes + counts.nbytes
                    + skip_arr.nbytes
                )
            else:
                # What the XLA window dispatch actually ships: the app
                # batch INCLUDING its [B, N] candidate/domain masks — at
                # 100k nodes the masks dominate the per-window h2d (the
                # O(N) blob the pruned path shrinks to [B, K]).
                nbytes = sum(
                    getattr(f, "nbytes", 0) for f in apps
                )
            tel.on_transfer("h2d", nbytes)
        priors: tuple = ()
        if pipelined:
            priors = tuple(p["unfetched"])
            p["avail"] = avail_after  # the next pipelined build extends this
        handle = WindowHandle(
            strategy=strategy,
            blob=blob,
            requests=tuple(requests),
            flat_rows=flat_rows,
            host_avail=np.array(np.asarray(host.available), dtype=np.int64),
            host_schedulable=np.asarray(host.schedulable),
            priors=priors,
            n=n,
        )
        # Stacked per-row requests for the fetch-side reconstruction: int64
        # so the vectorized subtractions against the int64 base never wrap.
        handle.row_driver_req = drv_arr.astype(np.int64)
        handle.row_exec_req = exc_arr.astype(np.int64)
        handle.row_skippable = skip_arr
        handle.seg_map = seg_map  # pallas path: [S,R] blob -> flat rows
        handle.host_tensors = host  # degraded-fallback re-solve inputs
        handle.info = info
        handle.dispatch_ms = dispatch_span.span.duration_ms
        handle.dispatch_id = info["dispatch_id"]
        handle.dispatched_at = self._clock()
        if pipelined:
            p["unfetched"].append(handle)
            sweep_future = getattr(blob, "sweep_future", None)
            if sweep_future is not None:
                # Deferred sweep window: the coordinator fulfils the blob at
                # its stacked flush (one grouped d2h for all arms) — no fetch
                # thread, no per-arm device_get.
                handle.blob_future = sweep_future
            else:
                # Start the device->host pull NOW on the fetch thread: the
                # transfer's round trip then elapses under the next
                # window's host build.
                handle.blob_future = _shared_fetch_pool().submit(
                    _shimmed_device_get, blob
                )
                self._track(handle.blob_future)
        return handle

    def _make_fallback_handle(
        self, strategy, requests, host, n, priors
    ) -> "WindowHandle":
        """A dispatch-less window handle: no device touched it (degraded
        'greedy' policy with no serving device); pack_window_fetch routes
        it through the host greedy fallback. Keeps the two-phase
        dispatch/fetch API intact so the serving loop and extender need
        no special case."""
        handle = WindowHandle(
            strategy=strategy,
            blob=None,
            requests=tuple(requests),
            flat_rows=[],
            host_avail=np.array(np.asarray(host.available), dtype=np.int64),
            host_schedulable=np.asarray(host.schedulable),
            priors=priors,
            n=n,
        )
        handle.use_fallback = True
        handle.host_tensors = host
        handle.info = {
            "path": "greedy-fallback",
            "nodes": n,
            "rows": sum(len(r.rows) for r in requests),
            "row_bucket": 0,
            "emax": 0,
            "state_upload": None,
            "compile_cache_hit": None,
            "dispatch_id": next(self._dispatch_seq),
            "fused_k": 1,
            "degraded": True,
        }
        handle.dispatch_id = handle.info["dispatch_id"]
        handle.dispatched_at = self._clock()
        self.last_solve_info = handle.info
        self.window_path_counts["greedy-fallback"] = (
            self.window_path_counts.get("greedy-fallback", 0) + 1
        )
        return handle

    def _fetch_fallback(self, handle: "WindowHandle") -> "list[WindowDecision]":
        """Serve a window on the host greedy fallback: the base is the
        same reconstruction every fetch path uses (host view at dispatch
        minus the placements of windows that were still in flight then),
        so degraded decisions see exactly the availability a device solve
        would have."""
        base = self._dense_base(handle)
        if handle.fallback_reason == "prune-escalation":
            # Correctness machinery with a healthy device: the sibling
            # re-solve may ride the scale-tier sharded path. Degraded-mode
            # serving (fallback_reason None) must stay host-side — the
            # device is exactly what failed.
            decisions, placements = self._escalation_decisions(
                handle.strategy, handle.host_tensors, base, handle.requests
            )
        else:
            decisions, placements = self.fallback.window_decisions(
                handle.strategy, handle.host_tensors, base, handle.requests
            )
        handle.placements = placements
        d = self.degraded
        if d is not None and handle.fallback_reason is None:
            # Prune-escalation re-solves are correctness machinery, not
            # degraded-mode serving — they must not flip the degraded
            # controller's decision gauges.
            d.on_fallback_decision(len(decisions))
        p = self._pipe
        if p is not None and handle in p["unfetched"]:
            p["unfetched"].remove(handle)
            p["mirror"] -= placements
            p["pending"] = None  # dense debit: rows unknown to the ledger
        self._prune_mark_unknown()
        self._note_dispatch_complete(handle)
        return decisions

    def _track(self, fut) -> None:
        """Register an in-flight pool future for cancel-on-close()."""
        self._inflight_futures.add(fut)
        fut.add_done_callback(self._inflight_futures.discard)

    def dispatch_occupancy(self) -> float:
        """Busy fraction of the dispatch surface at this instant: pooled =
        fraction of slots with an in-flight solve; single device = 1.0
        when a dispatched window is still un-fetched (a new dispatch
        overlaps it). The overlap-occupancy telemetry sample."""
        if self._pool is not None:
            return self._pool.occupancy()
        p = self._pipe
        return 1.0 if p is not None and p["unfetched"] else 0.0

    def pack_windows_dispatch(
        self,
        strategy: str,
        tensors,
        request_windows: Sequence[Sequence[WindowRequest]],
    ) -> "list[FusedWindowView]":
        """FUSED K-window dispatch on the resident carry state (ROADMAP
        Open item 2): the K serving windows concatenate into ONE segmented
        batch — a window boundary is an ordinary segment boundary, so the
        committed base carries ON DEVICE across the windows exactly as
        `available_after` is threaded between K sequential dispatches
        (ops/batched.py AppBatch window mode; fuse_app_batches pins the
        identity at the ops layer) — and ship as one h2d of K window
        blobs, one jitted dispatch, and one d2h of K placements instead
        of K full device round trips.

        Decisions are byte-identical to dispatching the K windows
        sequentially back-to-back (the fused-vs-sequential equivalence
        suite pins this across churn, K, and domain partitioning); the
        caller's contract is that all K windows were claimed from the
        queue at one instant, before any of them completed — exactly the
        PredicateBatcher's fused claim. On a device pool the concatenated
        batch rides the same partition/overlap machinery as a single
        window (disjoint-domain partitions still solve concurrently).

        Returns one FusedWindowView per window; fetch each IN DISPATCH
        ORDER via pack_window_fetch — the first fetch pays the single
        blocking pull, later views are free."""
        windows = [list(w) for w in request_windows]
        occupancy = self.dispatch_occupancy()
        flat: list[WindowRequest] = [r for w in windows for r in w]
        owner = self.pack_window_dispatch(strategy, tensors, flat)
        k = len(windows)
        if owner.info is not None:
            owner.info["fused_k"] = k
        self._fused_owners.add(owner)
        if self.telemetry is not None:
            self.telemetry.on_fused_dispatch(k, occupancy)
        views: list[FusedWindowView] = []
        lo = 0
        for i, w in enumerate(windows):
            hi = lo + len(w)
            views.append(FusedWindowView(owner, lo, hi, i, k))
            lo = hi
        return views

    def _dispatch_pruned(
        self, strategy, requests, *, host, p, n, drv_arr, exc_arr, counts,
        skip_arr, emax, cand_rows, commit, reset, dom_shared, cand_per_req,
        dom_key=None,
    ) -> "WindowHandle | None":
        """Tier-1 dispatch of the two-tier solve (single-device pipelined
        path): the prefilter's kept rows gather out of the resident device
        carry (a device-side [K] gather — the [N,3] base never moves), the
        statics gather host-side into a small fresh upload, the app batch
        ships [B,K] masks instead of [B,N], and the solve's availability
        DELTA scatters back into the carry additively (padding rows add
        zero). Returns None when the planner declines — the caller falls
        through to the full-tensor paths."""
        from spark_scheduler_tpu.tracing import tracer

        plan = self._plan_prune(
            host, dom_shared, cand_per_req, drv_arr, exc_arr, counts,
            dom_key=dom_key, dom_ref=requests[0].domain_node_names,
        )
        if plan is None:
            return None
        b = len(drv_arr)
        tel = self.telemetry
        compiles_before = tel.compile_count() if tel is not None else None
        keep = plan.keep
        t_gather = self._clock()
        # Statics-gather reuse (ISSUE 12 tentpole (c) + the repeat-window
        # bugfix): an unchanged kept row set (the planner re-served the
        # SAME keep array) whose gathered rows saw no static row-delta
        # re-serves the host gather AND the resident device sub-blob —
        # zero host-array touches, zero re-upload. Entries drop via
        # _apply_static_delta (rows ∩ keep), full uploads, and close().
        ent = self._prune_gather_entry(host, plan)
        statics_np = ent["statics_np"]
        gather_reused = "statics_dev" in ent
        if gather_reused:
            self.prune_stats["gather_reuse"] += 1
        # Per-request candidate gathers deduped by mask identity: serving
        # requests overwhelmingly share ONE candidate ticket, so a
        # 16-wide window pays one [K] gather from the [N] mask instead of
        # B_rows of them (ISSUE 15 tentpole (d)).
        cand_memo: dict[int, np.ndarray] = {}
        cand_subs = []
        for c in cand_rows:
            s = cand_memo.get(id(c))
            if s is None:
                s = c[keep]
                cand_memo[id(c)] = s
            cand_subs.append(s)
        cand_sub = np.stack(cand_subs)
        dom_sub = np.broadcast_to(
            np.asarray(dom_shared)[keep], (b, len(keep))
        )
        try:
            with tracer().span(
                "solve-dispatch", strategy=strategy, nodes=n,
                window_requests=len(requests), window_rows=b, batched=True,
                path="xla-pruned",
            ) as dispatch_span:
                _shim("h2d")
                if gather_reused:
                    idx_dev = ent["idx_dev"]
                    statics_dev = ent["statics_dev"]
                else:
                    idx_dev = jnp.asarray(keep)
                    statics_dev = tuple(
                        jax.device_put(f) for f in statics_np
                    )
                    ent["idx_dev"] = idx_dev
                    ent["statics_dev"] = statics_dev
                sub_avail = _take_rows(p["avail"], idx_dev)
                zone_base_dev = tuple(
                    jnp.asarray(a) for a in plan.zone_base
                )
                apps = make_app_batch(
                    drv_arr, exc_arr, counts, skippable=skip_arr,
                    pad_to=_bucket(b, self._row_bucket_quantum),
                    driver_cand=cand_sub,
                    domain=dom_sub,
                    commit=commit, reset=reset,
                )
                blob, delta = _window_blob_pruned(
                    sub_avail, statics_dev, apps,
                    zone_base_dev, fill=strategy, emax=emax,
                    num_zones=self._num_zones_bucket(),
                )
                p["avail"] = _add_rows_donated(p["avail"], idx_dev, delta)
        except Exception as exc:
            if not classify_slot_failure(exc):
                raise
            # Same contract as the full-tensor dispatch: the carry may be
            # half-mutated — drop the pipeline and serve per the degraded
            # policy.
            priors = tuple(p["unfetched"])
            self._pipe = None
            if tel is not None:
                tel.on_pipeline_event("device-failure")
            self._degraded_or_raise(exc)
            return self._make_fallback_handle(
                strategy, requests, host, n, priors
            )

        gather_ms = (self._clock() - t_gather) * 1e3
        self.prune_stats["gather_ms"] += gather_ms
        self.window_path_counts["xla-pruned"] = (
            self.window_path_counts.get("xla-pruned", 0) + 1
        )
        row_bucket = _bucket(b, self._row_bucket_quantum)
        info = {
            "path": "xla-pruned",
            "nodes": n,
            "rows": b,
            "row_bucket": row_bucket,
            "emax": emax,
            "state_upload": self.last_state_upload,
            "compile_cache_hit": (
                tel.compile_count() == compiles_before
                if tel is not None
                else None
            ),
            "dispatch_id": next(self._dispatch_seq),
            "fused_k": 1,
            "pruned": True,
            "kept_rows": plan.k_real,
            "candidate_rows": plan.dom_rows,
            "gather_reused": gather_reused,
        }
        self.last_solve_info = info
        self._note_prune_dispatch(plan, b)
        if tel is not None:
            tel.on_window_dispatch(
                "xla-pruned", nodes=n, rows=b, row_bucket=row_bucket,
            )
            tel.on_prune_phases(plan.plan_ms, gather_ms, plan.offset_ms)
            if gather_reused:
                tel.on_prune_gather_reuse()
            # What the pruned dispatch actually ships: gathered statics +
            # app arrays + [B,K] masks + the zone offsets — the O(N) blob
            # (and the [B,N] masks) never leave the host, and a reused
            # gather re-serves the resident statics sub-blob without
            # re-uploading it.
            tel.on_transfer(
                "h2d",
                (
                    0
                    if gather_reused
                    else sum(f.nbytes for f in statics_np) + keep.nbytes
                )
                + drv_arr.nbytes + exc_arr.nbytes + counts.nbytes
                + skip_arr.nbytes + cand_sub.nbytes + dom_sub.nbytes
                + sum(a.nbytes for a in plan.zone_base),
            )
        handle = WindowHandle(
            strategy=strategy,
            blob=blob,
            requests=tuple(requests),
            flat_rows=[],
            host_avail=None,
            host_schedulable=np.asarray(host.schedulable),
            priors=tuple(p["unfetched"]),
            n=n,
        )
        handle.host_avail32 = np.asarray(host.available)
        # Dispatch-time kept-row base, gathered NOW (ISSUE 13): the
        # resident host buffer mutates in place under later builds, so
        # the certificate's base must be captured in [K,3] space here —
        # the fetch path never touches an [N]-wide array. avail_gen +
        # the undo journal cover the rare dense reconstructions
        # (escalation / fallback re-solves).
        handle.base_kept = handle.host_avail32[
            plan.keep[: plan.k_real]
        ].astype(np.int64)
        handle.avail_gen = self._avail_gen
        self._avail_handles.add(handle)
        handle.row_driver_req = drv_arr.astype(np.int64)
        handle.row_exec_req = exc_arr.astype(np.int64)
        handle.row_skippable = skip_arr
        handle.host_tensors = host
        handle.prune = plan
        handle.info = info
        handle.dispatch_ms = dispatch_span.span.duration_ms
        handle.dispatch_id = info["dispatch_id"]
        handle.dispatched_at = self._clock()
        p["unfetched"].append(handle)
        handle.blob_future = _shared_fetch_pool().submit(
            _shimmed_device_get, blob
        )
        self._track(handle.blob_future)
        return handle

    def _fetch_pruned(self, handle: "WindowHandle", blob) -> "list[WindowDecision]":
        """Tier 2 of the two-tier solve: run the soundness certificate
        against the exact host reconstruction and either apply the
        decisions (the normal path) or escalate the window to the exact
        host re-solve.

        O(K + rows) host work since ISSUE 12: the certificate and the
        decision reconstruction both operate on the KEPT rows (base and
        placements gathered to [K,3]); nothing on this path copies,
        compares, or subtracts an [N,3] array — the dense `placements`
        tensor is a lazily-zeroed scatter target for downstream priors."""
        from spark_scheduler_tpu.core.prune import certify_window

        plan = handle.prune
        blob = np.asarray(blob)
        gmap = plan.keep.astype(np.int64)
        keep_real = plan.keep[: plan.k_real]
        drivers_l = blob[:, 0].astype(np.int64)
        admitted = blob[:, 1].astype(bool)
        packed = blob[:, 2].astype(bool)
        execs_l = blob[:, 3:].astype(np.int64)
        drivers = np.where(
            drivers_l >= 0, gmap[np.clip(drivers_l, 0, None)], -1
        )
        execs = np.where(execs_l >= 0, gmap[np.clip(execs_l, 0, None)], -1)
        host_avail32 = handle.host_avail32
        ps = self._prior_sparse(handle)
        if ps is None:
            ok, reason = False, "prior-unknown"
        else:
            prior_rows, prior_deltas = ps
            # Dispatch-time [K,3] base captured at dispatch — the live
            # host buffer has moved on under later resident builds.
            base_kept = handle.base_kept.copy()
            if prior_rows.size:
                loc = np.searchsorted(keep_real, prior_rows)
                locc = np.clip(loc, 0, keep_real.size - 1)
                on_kept = keep_real[locc] == prior_rows
                if on_kept.any():
                    base_kept[locc[on_kept]] -= prior_deltas[on_kept]
            ok, reason = certify_window(
                plan,
                strategy=handle.strategy,
                requests=handle.requests,
                drivers=drivers,
                admitted=admitted,
                packed=packed,
                execs=execs,
                drv64=handle.row_driver_req,
                exc64=handle.row_exec_req,
                base_kept=base_kept.copy(),  # certify threads commits
                host=handle.host_tensors,
                prior_rows=prior_rows,
                prior_deltas=prior_deltas,
            )
        if not ok:
            return self._escalate_pruned(
                handle, self._dense_base(handle), reason
            )
        # Compact reconstruction over the kept rows: base/placements are
        # [Kp,3], decision indices stay LOCAL, and gmap resolves names.
        kp = plan.keep.shape[0]
        base_loc = np.zeros((kp, host_avail32.shape[1]), np.int64)
        base_loc[: plan.k_real] = base_kept
        placements_loc = np.zeros_like(base_loc)
        sched_kept = np.asarray(handle.host_schedulable)[plan.keep]
        decisions = self._reconstruct_requests(
            handle.requests, drivers_l, admitted, packed, execs_l,
            handle.row_driver_req, handle.row_exec_req,
            handle.row_skippable, base_loc, placements_loc,
            sched_kept, row_map=gmap,
        )
        # Sparse committed placements: the dense [N,3] tensor (a 24 MB
        # calloc per window at 1M) is never materialized — later windows
        # subtract priors through (placement_rows, placement_vals), and
        # the rare dense consumers reconstruct on demand (ISSUE 15).
        loc_rows = np.flatnonzero(placements_loc.any(axis=1))
        prows = gmap[loc_rows]  # keep's real part is sorted: prows too
        pvals = placements_loc[loc_rows]
        handle.placement_rows = prows
        handle.placement_vals = pvals
        p = self._pipe
        if p is not None and handle in p["unfetched"]:
            p["unfetched"].remove(handle)
            if prows.size:
                p["mirror"][prows] -= pvals
                if p.get("pending") is not None:
                    # Debited rows differ from the host view until the
                    # reservations write back: the mirror sync must keep
                    # comparing them (the event-fed dirty set's second
                    # feed, next to the resident build's patch rows).
                    p["pending"].append(prows)
        # The placed rows are availability churn the planner can absorb
        # exactly (they are kept rows by construction).
        self._prune_note_rows(prows)
        self._note_dispatch_complete(handle)
        self._device_recovered()
        return decisions

    def _escalation_decisions(self, strategy, host, base, requests):
        """Exact re-solve of a window from host truth — the escalation
        path's solver. With `solver.scale-tier` on, the re-solve runs as
        a NODE-SHARDED device solve over the local mesh (parallel/solve
        node_sharding): the [N] tensors stream across device slots
        instead of a host-Python O(N x rows) walk, which is what keeps
        certificate escalations affordable at the million-node tier.
        Decisions are byte-identical either way — the device kernels ARE
        the greedy oracle's semantics (golden-parity pinned), and the
        escalation-parity test pins this seam. Any device failure falls
        back to the host greedy oracle. Returns (decisions,
        placements[N,3] int64); `base` is never mutated."""
        if self._scale_tier and strategy in BATCHABLE_STRATEGIES:
            try:
                out = self._scale_tier_decisions(
                    strategy, host, base, requests
                )
                self.scale_tier_stats["resolves"] += 1
                return out
            except Exception:
                self.scale_tier_stats["fallbacks"] += 1
        return self.fallback.window_decisions(strategy, host, base, requests)

    def _scale_mesh_for(self, n: int):
        """The ("nodes",) mesh for scale-tier re-solves, over the largest
        power-of-two local device count dividing `n` (row counts are
        power-of-two bucketed, so this is all of them in practice).
        None = one device (unsharded fast path)."""
        devs = jax.devices()
        shards = 1
        while shards * 2 <= len(devs) and n % (shards * 2) == 0:
            shards *= 2
        if shards <= 1:
            return None
        cached = self._scale_mesh
        if cached is not None and cached.devices.size == shards:
            return cached
        from jax.sharding import Mesh

        self._scale_mesh = Mesh(np.asarray(devs[:shards]), ("nodes",))
        return self._scale_mesh

    def _scale_tier_decisions(self, strategy, host, base, requests):
        """One synchronous node-sharded window solve from the exact host
        reconstruction (`base` = host view at dispatch minus in-flight
        priors' placements — precisely what the escalated decisions must
        be computed against)."""
        n = host.available.shape[0]
        valid_np = np.asarray(host.valid)
        flat_rows: list[tuple] = []
        commit: list[bool] = []
        reset: list[bool] = []
        cand_rows: list[np.ndarray] = []
        dom_rows: list[np.ndarray] = []
        for req in requests:
            cand = self.candidate_mask(host, req.driver_candidate_names)
            if req.domain_mask is not None:
                dom = np.asarray(req.domain_mask) & valid_np
            elif req.domain_node_names is not None:
                dom = (
                    self.candidate_mask(host, req.domain_node_names)
                    & valid_np
                )
            else:
                dom = valid_np
            for j, row in enumerate(req.rows):
                flat_rows.append(row)
                commit.append(j == len(req.rows) - 1)
                reset.append(j == 0)
                cand_rows.append(cand)
                dom_rows.append(dom)
        b = len(flat_rows)
        drv_arr = np.stack([r[0].as_array() for r in flat_rows])
        exc_arr = np.stack([r[1].as_array() for r in flat_rows])
        counts = np.asarray([r[2] for r in flat_rows], np.int32)
        skip_arr = np.asarray([bool(r[3]) for r in flat_rows])
        emax = _bucket(max(int(counts.max()), 1), 8)
        apps = make_app_batch(
            drv_arr, exc_arr, counts, skippable=skip_arr,
            pad_to=_bucket(b, 32),
            driver_cand=np.stack(cand_rows), domain=np.stack(dom_rows),
            commit=commit, reset=reset,
        )
        avail32 = np.clip(base, -INT32_INF, INT32_INF).astype(np.int32)
        statics_np = cluster_statics(host)
        mesh = self._scale_mesh_for(n)
        if mesh is not None:
            from spark_scheduler_tpu.parallel.solve import (
                node_sharding,
                shard_apps,
            )

            avail_dev = jax.device_put(
                jnp.asarray(avail32), node_sharding(mesh, 2)
            )
            statics_dev = tuple(
                jax.device_put(
                    jnp.asarray(np.asarray(f)),
                    node_sharding(mesh, np.asarray(f).ndim),
                )
                for f in statics_np
            )
            apps_dev = shard_apps(apps, mesh)
            self.scale_tier_stats["sharded"] += 1
        else:
            avail_dev = jnp.asarray(avail32)
            statics_dev = tuple(jnp.asarray(np.asarray(f)) for f in statics_np)
            apps_dev = apps
        blob, _after = _window_blob_statics(
            avail_dev, statics_dev, apps_dev,
            fill=strategy, emax=emax,
            num_zones=self._num_zones_bucket(),
        )
        blob = np.asarray(jax.device_get(blob))
        drivers = blob[:, 0].astype(np.int64)
        admitted = blob[:, 1].astype(bool)
        packed = blob[:, 2].astype(bool)
        execs = blob[:, 3:].astype(np.int64)
        base_thread = np.asarray(base).astype(np.int64).copy()
        placements = np.zeros_like(base_thread)
        decisions = self._reconstruct_requests(
            requests, drivers, admitted, packed, execs,
            drv_arr.astype(np.int64), exc_arr.astype(np.int64), skip_arr,
            base_thread, placements, np.asarray(host.schedulable),
        )
        return decisions, placements

    def _escalate_pruned(self, handle, base, reason) -> "list[WindowDecision]":
        """Failed certificate: re-solve the whole window from host truth —
        host-side via the greedy oracle (slot-for-slot the kernels'
        semantics — pinned by the golden parity suite), or, under
        `solver.scale-tier`, as the node-sharded device re-solve — so the
        escalated decisions equal the full-tensor device solve's byte for
        byte. The poisoned carry and every window dispatched on it are
        invalidated by _note_prune_escalation."""
        decisions, placements = self._escalation_decisions(
            handle.strategy, handle.host_tensors, base, handle.requests
        )
        handle.placements = placements
        self._note_prune_escalation(handle, reason)
        self._note_dispatch_complete(handle)
        return decisions

    def _dispatch_pooled(
        self, strategy, tensors, requests, *, host, drv_arr, exc_arr,
        counts, skip_arr, emax, cand_per_req, dom_per_req, dom_keys,
        req_row_ranges,
    ) -> "WindowHandle":
        """Multi-device window dispatch (the engine behind `solver.mesh` /
        `solver.device-pool`).

        The window is split into PARTITIONS of requests whose affinity
        domains are provably pairwise-disjoint (instance groups in
        practice: failover.go:276-313 groups nodes by the instance-group
        label, and every request's node selector pins it to one group).
        Requests inside a partition interact only through availability
        rows of their own domain, and zone ranks / priority orders /
        packing efficiencies all derive from domain-masked aggregates
        (ops/sorting.py, ops/efficiency.py), so partitions COMMUTE:
        solving them concurrently — each over a GATHERED sub-cluster of
        just its domain's rows, on its own pool slot — produces decisions
        byte-identical to the serialized window (pinned by
        tests/test_window_serving.py). Windows that do not partition
        (shared or unkeyed domains) run whole on the next slot, which
        still overlaps their d2h decision pull with the next window's
        h2d upload on another device.

        The committed base stays a single logical thread: each
        partition's `available_after` rows scatter back into the
        (donated) global base, and the next pipelined build resolves that
        combine before applying external deltas."""
        from spark_scheduler_tpu.tracing import tracer

        p = self._pipe
        n = tensors.available.shape[0]
        pool = self._pool
        tel = self.telemetry
        compiles_before = tel.compile_count() if tel is not None else None
        num_zones = self._num_zones_bucket()
        # Sized to the device pool (ISSUE 15 satellite): two workers per
        # slot keeps the upload/solve double-buffer engaged at pipeline
        # depth 2; a 1-slot mesh solver gets 2 workers, not 8.
        solve_pool = _shared_solve_pool(min(8, 2 * len(pool.slots)))
        now = self._clock()

        # Quarantine gate: probe any quarantined slot whose interval
        # elapsed; with NO healthy slot left, serve per the degraded
        # policy instead of dispatching into a dead pool.
        if pool.quarantined_slots():
            self.probe_quarantined()
        if not pool.healthy_slots():
            exc = AllSlotsQuarantinedError(
                f"all {len(pool.slots)} device slot(s) quarantined"
            )
            priors = tuple(p["unfetched"])
            self._pipe = None
            if tel is not None:
                tel.on_pipeline_event("device-failure")
            self._degraded_or_raise(exc)
            return self._make_fallback_handle(
                strategy, requests, host, n, priors
            )

        # ---- partition plan: ≥2 distinct domain keys, all keyed, masks
        # pairwise disjoint and non-empty. Plain-device slots only — a
        # sharded (mesh) slot solves the whole window over the node axis.
        plan = None
        if (
            len(pool.slots) > 1
            and not any(s.is_mesh for s in pool.slots)
            and all(k is not None for k in dom_keys)
        ):
            groups: dict[tuple, list[int]] = {}
            for r, key in enumerate(dom_keys):
                groups.setdefault(key, []).append(r)
            if len(groups) > 1:
                masks = [dom_per_req[ids[0]] for ids in groups.values()]
                overlap = np.zeros(n, np.int32)
                for m in masks:
                    overlap += m
                if int(overlap.max()) <= 1 and all(m.any() for m in masks):
                    plan = list(groups.items())

        base = p["avail"]
        base_device = next(iter(base.devices()))
        request_device: list = [None] * len(requests)
        parts: list[_WindowPart] = []
        # Dispatch-time host availability reference (int32, NOT a copy):
        # gathered parts capture their [k,3] base from it below, and the
        # rare dense paths reconstruct via the undo journal — the per
        # -window [N,3] int64 host_avail copy is gone (ISSUE 15).
        havail32 = np.asarray(host.available)

        # Candidate pruning on the pooled engine: each partition (or the
        # whole window when it does not partition, provided its requests
        # share one domain) prunes its own gather to the prefilter's top-K
        # rows — the sub-cluster solve machinery is identical, only the
        # index set shrinks and the committed rows scatter back as deltas.
        try_prune = self._prune_eligible(strategy)
        shared_dom, shared_key = (
            self._shared_prune_domain(requests, dom_keys, dom_per_req)
            if try_prune
            else (None, None)
        )

        def submit_part(slot, req_ids, idx_key, idx):
            row_sel = np.concatenate(
                [np.arange(*req_row_ranges[r]) for r in req_ids]
            )
            drv_g, exc_g = drv_arr[row_sel], exc_arr[row_sel]
            cnt_g, skip_g = counts[row_sel], skip_arr[row_sel]
            prune_plan = None
            if try_prune:
                part_dom = (
                    dom_per_req[req_ids[0]] if idx is not None
                    else shared_dom
                )
                part_key = (
                    dom_keys[req_ids[0]] if idx is not None
                    else shared_key
                )
                if part_dom is not None:
                    prune_plan = self._plan_prune(
                        host, part_dom,
                        [cand_per_req[r] for r in req_ids],
                        drv_g, exc_g, cnt_g,
                        dom_key=part_key,
                        dom_ref=requests[req_ids[0]].domain_node_names,
                    )
                if prune_plan is not None:
                    # The pruned gather REPLACES the domain gather: padded
                    # keep rows, no sub-replica caching (the keep set
                    # changes with every window's availability).
                    idx = prune_plan.keep
                    idx_key = None
                    self._note_prune_dispatch(prune_plan, len(row_sel))
            commit_g: list[bool] = []
            reset_g: list[bool] = []
            cand_g: list[np.ndarray] = []
            dom_g: list[np.ndarray] = []
            for r in req_ids:
                lo, hi = req_row_ranges[r]
                span = hi - lo
                commit_g += [False] * (span - 1) + [True]
                reset_g += [True] + [False] * (span - 1)
                c, d = cand_per_req[r], dom_per_req[r]
                if idx is not None:
                    c, d = c[idx], d[idx]
                cand_g += [c] * span
                dom_g += [d] * span
            b_g = len(row_sel)
            apps = make_app_batch(
                drv_g, exc_g, cnt_g, skippable=skip_g,
                pad_to=_bucket(b_g, 8),
                driver_cand=np.stack(cand_g), domain=np.stack(dom_g),
                commit=commit_g, reset=reset_g,
            )
            # Host-side copy kept on the part for slot-failure re-dispatch
            # (place_apps may shard `apps` onto the dying slot's mesh).
            apps_host = apps
            epoch = self._static_epoch
            # Simulated h2d boundary on the DISPATCHER thread: the pooled
            # engine still ships one window-batch upload per partition
            # submit over the one host-device link.
            _shim("h2d")
            if idx is None:
                statics = slot.resident_statics(
                    host, epoch, self._clock, tel,
                    journal=self._static_journal,
                )
                # Whole-window base via the slot's delta-synced
                # availability mirror (ISSUE 15): a lagging slot catches
                # up by row-scatter when its missed epochs are journaled.
                sub_avail = self._pool_full_base(p, slot, base, base_device)
            elif prune_plan is not None:
                # Per-partition statics-gather reuse (ISSUE 15 tentpole
                # (b)): the planner's per-domain contexts re-serve the
                # SAME keep array across windows, so the gathered
                # sub-blob caches host-side per keep identity and
                # device-side per (keep, generation) on the slot — a
                # reused plan pays zero host gather and zero re-upload.
                t_gather = self._clock()
                ent = self._prune_gather_entry(host, prune_plan)
                skey = ("prune", id(prune_plan.keep))
                cached = slot.sub_statics.get(skey)
                if cached is not None and cached[0] == ent["gen"]:
                    statics = cached[1]
                    slot.uploads["reuse"] += 1
                    self.prune_stats["gather_reuse"] += 1
                    if tel is not None:
                        tel.on_device_upload(slot.label, "reuse", 0)
                        tel.on_prune_gather_reuse()
                else:
                    statics = tuple(slot._put(f) for f in ent["statics_np"])
                    if len(slot.sub_statics) >= 64:
                        slot.sub_statics.clear()
                    slot.sub_statics[skey] = (ent["gen"], statics)
                    slot.uploads["full"] += 1
                    if tel is not None:
                        tel.on_device_upload(
                            slot.label, "full",
                            sum(f.nbytes for f in ent["statics_np"]),
                        )
                sub_avail = slot.place_avail(_take_rows(base, jnp.asarray(idx)))
                self.prune_stats["gather_ms"] += (
                    self._clock() - t_gather
                ) * 1e3
            else:
                statics = slot.sub_replica(
                    host, idx_key, idx, epoch, self._clock, tel
                )
                sub_avail = slot.place_avail(_take_rows(base, jnp.asarray(idx)))
            apps = slot.place_apps(apps)
            # Donate the sub-base on plain devices: a gathered copy (or a
            # base the combine will replace) that nothing else reads.
            if prune_plan is not None:
                zone_base_dev = tuple(
                    slot._put(a) for a in prune_plan.zone_base
                )

                def fn(avail_, statics_, apps_, *, fill, emax, num_zones,
                       _zb=zone_base_dev):
                    return _window_blob_pruned(
                        avail_, statics_, apps_, _zb,
                        fill=fill, emax=emax, num_zones=num_zones,
                    )
            else:
                fn = (
                    _window_blob_statics if slot.is_mesh
                    else _window_blob_donated
                )
            slot.inflight += 1
            if tel is not None:
                tel.on_device_inflight(slot.label, slot.inflight)
                if slot.last_full_upload:
                    tel.on_device_age(
                        slot.label, max(0.0, now - slot.last_full_upload)
                    )

            from concurrent.futures import Future as _Future

            # The committed sub-base publishes on its OWN future the
            # moment the solve lands — the next window's base combine
            # must never wait out this part's decision-blob d2h (that
            # transfer overlaps the next window's work, exactly like the
            # single-device eager fetch).
            after_fut: _Future = _Future()

            def run():
                t0 = self._clock()
                try:
                    _shim("dispatch")
                    blob, after = fn(
                        sub_avail, statics, apps,
                        fill=strategy, emax=emax, num_zones=num_zones,
                    )
                    after = jax.block_until_ready(after)
                except BaseException as exc:
                    after_fut.set_exception(exc)
                    raise
                after_fut.set_result(after)
                t1 = self._clock()
                _shim("d2h")
                blob_np = np.asarray(jax.device_get(blob))
                t2 = self._clock()
                return {
                    "blob": blob_np,
                    "solve_ms": (t1 - t0) * 1e3,
                    "fetch_ms": (t2 - t1) * 1e3,
                }

            fut = solve_pool.submit(run)
            self._track(fut)

            def _propagate_cancel(f, af=after_fut):
                # A close()-cancelled part never runs; the base future
                # must fail too, not hang a later resolve.
                if f.cancelled() and not af.done():
                    af.cancel()

            fut.add_done_callback(_propagate_cancel)
            for r in req_ids:
                request_device[r] = slot.label
            return _WindowPart(
                future=fut, after_future=after_fut, req_ids=list(req_ids),
                requests=[requests[r] for r in req_ids],
                row_drv=drv_g.astype(np.int64),
                row_exc=exc_g.astype(np.int64),
                row_skip=skip_g, idx=idx, slot=slot, rows=b_g,
                idx_key=idx_key, apps=apps_host, prune=prune_plan,
                # Compact-fetch base: the part's rows' availability at
                # dispatch (the resident buffer mutates afterwards).
                base_kept=(
                    havail32[idx].astype(np.int64)
                    if idx is not None
                    else None
                ),
            )

        note_epoch = None
        try:
            with tracer().span(
                "solve-dispatch", strategy=strategy, nodes=n,
                window_requests=len(requests), window_rows=len(drv_arr),
                batched=True, path="pool",
                partitions=len(plan) if plan else 1,
            ) as dispatch_span:
                if plan is None:
                    parts.append(
                        submit_part(
                            pool.next_slot(), list(range(len(requests))),
                            None, None,
                        )
                    )
                    head = parts[0]
                    if head.prune is not None:
                        # Pruned whole-window solve: the part returns the
                        # kept rows' availability DELTA — fold it into the
                        # (donated) global base instead of replacing it.
                        p["avail"] = _PendingBase(
                            lambda: _add_rows_donated(
                                base,
                                jnp.asarray(head.idx),
                                jax.device_put(
                                    head.after_future.result(), base_device
                                ),
                            )
                        )
                        # Commits land on kept rows only: journal them so
                        # lagging slot mirrors catch up by scatter.
                        self._avail_journal_note(p, head.idx)
                    else:
                        p["avail"] = _PendingBase(
                            lambda: head.after_future.result()
                        )
                        # Unpruned whole window: the commit rows are
                        # unknowable at dispatch — mirrors crossing this
                        # epoch must full re-ship until the fetch patches
                        # the entry with the exact rows.
                        note_epoch = self._avail_journal_note(p, None)
                else:
                    for key, req_ids in plan:
                        idx = np.flatnonzero(
                            dom_per_req[req_ids[0]]
                        ).astype(np.int32)
                        parts.append(
                            submit_part(pool.next_slot(), req_ids, key, idx)
                        )

                    def combine(parts=parts, base=base):
                        # Scatter every partition's committed sub-base back
                        # into the global base (disjoint rows; the base is
                        # DONATED through the chain — in-place double-buffer).
                        # Waits only on the solves (after_future), never on
                        # the decision-blob transfers. Pruned partitions
                        # return DELTAS over padded keep rows — those fold
                        # in additively (padding adds zero).
                        out = base
                        for part in parts:
                            rows = jax.device_put(
                                part.after_future.result(), base_device
                            )
                            if part.prune is not None:
                                out = _add_rows_donated(
                                    out, jnp.asarray(part.idx), rows
                                )
                            else:
                                out = _scatter_rows_exact_donated(
                                    out, jnp.asarray(part.idx), rows
                                )
                        return out

                    p["avail"] = _PendingBase(combine)
                    # Partition scatters touch exactly the partitions'
                    # gathered rows (pruned parts: their kept rows).
                    self._avail_journal_note(
                        p, np.concatenate([pt.idx for pt in parts])
                    )
        except Exception as exc:
            if not classify_slot_failure(exc):
                raise
            # A device boundary failed ON THE DISPATCHER THREAD (window
            # upload): already-submitted partitions are cancelled, the
            # threaded base is suspect, and the window serves per the
            # degraded policy.
            for part in parts:
                part.future.cancel()
                part.slot.inflight = max(0, part.slot.inflight - 1)
                if tel is not None:
                    tel.on_device_inflight(part.slot.label, part.slot.inflight)
            priors = tuple(p["unfetched"])
            self._pipe = None
            if tel is not None:
                tel.on_pipeline_event("device-failure")
            self._degraded_or_raise(exc)
            return self._make_fallback_handle(
                strategy, requests, host, n, priors
            )

        self.window_path_counts["pool"] = (
            self.window_path_counts.get("pool", 0) + 1
        )
        b = len(drv_arr)
        info = {
            "path": "pool",
            "nodes": n,
            "rows": b,
            "row_bucket": _bucket(b, 8),
            "emax": emax,
            "partitions": len(parts),
            "devices": sorted({pt.slot.label for pt in parts}),
            "state_upload": self.last_state_upload,
            "compile_cache_hit": (
                tel.compile_count() == compiles_before
                if tel is not None
                else None
            ),
            "dispatch_id": next(self._dispatch_seq),
            "fused_k": 1,
        }
        self.last_solve_info = info
        if tel is not None:
            tel.on_window_dispatch(
                "pool", nodes=n, rows=b, row_bucket=_bucket(b, 8),
            )
            tel.on_transfer(
                "h2d",
                drv_arr.nbytes + exc_arr.nbytes + counts.nbytes
                + skip_arr.nbytes,
            )
        handle = WindowHandle(
            strategy=strategy,
            blob=None,
            requests=tuple(requests),
            flat_rows=[],
            # No dense dispatch-time copy (ISSUE 15): gathered parts
            # carry their [k,3] base; the rare dense paths reconstruct
            # via host_avail32 + the availability undo journal.
            host_avail=None,
            host_schedulable=np.asarray(host.schedulable),
            priors=tuple(p["unfetched"]),
            n=n,
        )
        handle.host_avail32 = havail32
        handle.avail_gen = self._avail_gen
        handle.avail_note_epoch = note_epoch
        self._avail_handles.add(handle)
        handle.parts = parts
        handle.request_device = request_device
        handle.host_tensors = host  # slot-failure re-dispatch inputs
        handle.info = info
        handle.dispatch_ms = dispatch_span.span.duration_ms
        handle.dispatch_id = info["dispatch_id"]
        handle.dispatched_at = self._clock()
        p["unfetched"].append(handle)
        return handle

    def pack_window_fetch(self, handle) -> list[WindowDecision]:
        """Block on a dispatched window's decisions and reconstruct the
        per-request outcomes (the second half of pack_window). A
        FusedWindowView fetches its umbrella ONCE (memoized — including a
        failure, which every sub-window of the batch must surface
        identically) and slices its own requests' decisions out."""
        if isinstance(handle, FusedWindowView):
            owner = handle.owner
            res = owner.fused_decisions
            if res is None:
                try:
                    res = ("ok", self.pack_window_fetch(owner))
                except Exception as exc:
                    res = ("err", exc)
                owner.fused_decisions = res
            kind, val = res
            if kind == "err":
                raise val
            return val[handle.lo:handle.hi]
        if handle.released:
            # close()/discard_pipeline() dropped this dispatch's staging
            # buffers; its decisions are gone by design (the caller's
            # epoch machinery re-solves from host truth).
            raise RuntimeError("window dispatch was discarded")
        if not handle.requests:
            return []
        if handle.use_fallback:
            return self._fetch_fallback(handle)
        if handle.parts is not None:
            return self._fetch_pooled(handle)
        from spark_scheduler_tpu.tracing import tracer

        requests, n = handle.requests, handle.n
        trace = tracer()
        with trace.span(
            "solve", strategy=handle.strategy, nodes=n,
            window_requests=len(requests), batched=True,
        ):
            try:
                with trace.span("fetch-wait") as wait:
                    if handle.blob_future is not None:
                        blob = handle.blob_future.result()
                    else:
                        blob = _shimmed_device_get(handle.blob)
                handle.fetch_wait_ms = wait.span.duration_ms
            except Exception as exc:
                # The device base embodies this window's (now unknowable)
                # placements while no reservation was created for them.
                # Drop the whole pipeline: the next build does a full upload
                # from the host view — the durable truth — restoring the
                # lost gangs' capacity. Later in-flight handles still fetch
                # fine (their blobs are independent); they just skip the
                # mirror debit of a dead pipeline.
                self._pipe = None
                if self.telemetry is not None:
                    self.telemetry.on_pipeline_event("fetch-failure")
                if (
                    classify_slot_failure(exc)
                    and handle.host_tensors is not None
                    and self.degraded is not None
                ):
                    # Single device, no survivor: the degraded policy
                    # answers — host greedy re-solve of THIS window (its
                    # decisions are not yet applied anywhere, so the
                    # re-solve is exact), or shed.
                    self._degraded_or_raise(exc)
                    return self._fetch_fallback(handle)
                raise
        if self.telemetry is not None:
            self.telemetry.on_transfer("d2h", getattr(blob, "nbytes", 0))
        if handle.prune is not None:
            return self._fetch_pruned(handle, blob)
        if handle.seg_map is not None:
            # Pallas window path: the device blob is [S, R, 3+emax];
            # flatten the real rows back into flat-row order host-side.
            blob = np.asarray(blob)[handle.seg_map[0], handle.seg_map[1]]
        drivers = blob[:, 0]
        admitted = blob[:, 1].astype(bool)
        packed = blob[:, 2].astype(bool)
        execs = blob[:, 3:]

        base = self._dense_base(handle)
        placements = np.zeros_like(base)
        decisions = self._reconstruct_requests(
            requests, drivers, admitted, packed, execs,
            handle.row_driver_req, handle.row_exec_req,
            handle.row_skippable, base, placements,
            handle.host_schedulable,
        )
        handle.placements = placements
        # Pipeline accounting: the device base now permanently embodies this
        # window's committed gangs; debit them from the mirror so the next
        # build's host-vs-mirror delta ships only EXTERNAL changes. When the
        # host later fails to create one of these reservations, its usage
        # never reaches the host view and the next delta restores the gang's
        # capacity on device automatically (self-correcting drift).
        # The debit is SPARSE (ISSUE 15): the committed rows are read
        # straight off the decision blob — exactly the support of
        # `placements` — so the mirror subtracts O(placed) rows, the
        # pending ledger stays exact (no dense compare next build), and
        # the planner absorbs the rows instead of a snapshot diff.
        prows = self._commit_rows(handle.requests, drivers, admitted, execs)
        handle.placement_rows = prows
        handle.placement_vals = placements[prows]
        p = self._pipe
        if p is not None and handle in p["unfetched"]:
            p["unfetched"].remove(handle)
            if prows.size:
                p["mirror"][prows] -= placements[prows]
                if p.get("pending") is not None:
                    p["pending"].append(prows)
        self._prune_note_rows(prows)
        self._note_dispatch_complete(handle)
        self._device_recovered()
        return decisions

    def _note_dispatch_complete(self, handle) -> None:
        """Amortized round-trip telemetry: dispatch -> decisions-on-host
        wall time divided by the dispatch's fused window count — the
        per-window share of the device round trip a fused batch pays."""
        tel = self.telemetry
        if tel is None or not handle.dispatched_at:
            return
        k = max(1, (handle.info or {}).get("fused_k", 1))
        tel.on_dispatch_complete(
            (self._clock() - handle.dispatched_at) * 1e3 / k, k
        )

    def _fetch_pooled(self, handle: "WindowHandle") -> list[WindowDecision]:
        """Fetch + reconstruct a pooled (possibly partitioned) window.

        Partitions are row-disjoint, so any completion order yields the
        serialized window's exact base. GATHERED parts (domain partitions
        and pruned top-K parts) reconstruct in COMPACT part-local space
        against the [k,3] base captured at dispatch and accumulate their
        committed placements SPARSELY: the mirror debit then scatters
        exactly the union of partition debit rows, the pending ledger
        stays exact, and `mirror_dense_syncs` pins to 0 on the pooled
        path (ISSUE 15 — nothing here touches an [N]-wide array). Only
        an unpartitioned UNPRUNED window (idx None, a single part by
        construction) pays the dense reconstruction; escalations and
        greedy fallbacks materialize the dense base lazily.
        """
        from spark_scheduler_tpu.tracing import tracer

        requests, n = handle.requests, handle.n
        tel = self.telemetry
        results: list = [None] * len(requests)
        # Lenient prior union for the compact reconstructions (the
        # dense-base semantics: an unknown prior contributes nothing).
        lp_rows, lp_vals = self._collect_priors(handle, strict=False)
        sp_rows: list[np.ndarray] = []
        sp_vals: list[np.ndarray] = []
        dense: dict = {"base": None, "placements": None}
        # False once an escalation / greedy-fallback part contributed
        # placements the sparse lists do not cover (those flows kill the
        # pipeline, so the debit never runs — but later windows must then
        # read the DENSE placements, not an incomplete sparse support).
        support_complete = True

        def dense_base() -> np.ndarray:
            # Lazy dense view for the idx-None part / escalations /
            # greedy fallbacks: dispatch-time reconstruction minus the
            # placements already committed by earlier compact parts —
            # which must ALSO back-fill the dense placements tensor
            # (support_complete=False publishes it as the handle's
            # placements; later in-flight windows subtract it as a
            # prior, and missing the earlier partitions' commits would
            # let their re-solves double-book those rows).
            if dense["base"] is None:
                b = self._dense_base(handle)
                pl = np.zeros_like(b)
                for r_, v_ in zip(sp_rows, sp_vals):
                    if r_.size:
                        b[r_] -= v_
                        pl[r_] += v_
                dense["base"] = b
                dense["placements"] = pl
            return dense["base"]

        def commit_sparse(rows, vals) -> None:
            sp_rows.append(rows)
            sp_vals.append(vals)
            if dense["base"] is not None and rows.size:
                dense["base"][rows] -= vals
                dense["placements"][rows] += vals

        strict_ps = None
        strict_known = False
        trace = tracer()
        handle.fetch_wait_ms = 0.0
        with trace.span(
            "solve", strategy=handle.strategy, nodes=n,
            window_requests=len(requests), batched=True,
            path="pool", partitions=len(handle.parts),
        ):
            for part_i, part in enumerate(handle.parts):
                redispatched = False
                try:
                    with trace.span("fetch-wait") as wait:
                        out = part.future.result()
                    handle.fetch_wait_ms += wait.span.duration_ms
                except Exception as exc:
                    part.slot.inflight = max(0, part.slot.inflight - 1)
                    if tel is not None:
                        tel.on_device_inflight(
                            part.slot.label, part.slot.inflight
                        )
                    recoverable = (
                        classify_slot_failure(exc)
                        and part.apps is not None
                        and handle.host_tensors is not None
                    )
                    if not recoverable:
                        # Same contract as a single-device fetch failure:
                        # the device base embodies unknowable placements,
                        # so the whole pipeline drops and the next build
                        # re-uploads host truth (the dead combine is
                        # skipped by _resolve_base the same way). Only
                        # the parts not yet processed release their
                        # in-flight slots here — earlier parts already
                        # did.
                        self._pipe = None
                        for pt in handle.parts[part_i + 1:]:
                            pt.slot.inflight = max(0, pt.slot.inflight - 1)
                            if tel is not None:
                                tel.on_device_inflight(
                                    pt.slot.label, pt.slot.inflight
                                )
                        if tel is not None:
                            tel.on_pipeline_event("fetch-failure")
                        raise
                    # SLOT FAILURE RECOVERY: quarantine the slot (its
                    # resident state is unreachable; the threaded base it
                    # fed is poisoned — pipeline rebuilds from host
                    # truth), then re-dispatch this partition on a
                    # surviving slot with byte-identical inputs. With no
                    # survivor, the degraded policy answers (greedy
                    # fallback decisions, or shed).
                    self._pipe = None
                    if tel is not None:
                        tel.on_pipeline_event("fetch-failure")
                    self._quarantine_slot(part.slot, exc)
                    try:
                        recovered = self._redispatch_part(
                            handle, part, dense_base()
                        )
                    except Exception:
                        for pt in handle.parts[part_i + 1:]:
                            pt.slot.inflight = max(0, pt.slot.inflight - 1)
                            if tel is not None:
                                tel.on_device_inflight(
                                    pt.slot.label, pt.slot.inflight
                                )
                        raise
                    if isinstance(recovered, tuple):
                        # Greedy-fallback decisions for this part: apply
                        # its placements to the dense base and move on.
                        decs, ppl = recovered
                        dense_base()
                        dense["base"] -= ppl
                        dense["placements"] += ppl
                        support_complete = False
                        for rid, d in zip(part.req_ids, decs):
                            results[rid] = d
                        continue
                    out = recovered
                    redispatched = True
                blob = out["blob"]
                if not redispatched:
                    part.slot.inflight = max(0, part.slot.inflight - 1)
                if tel is not None:
                    tel.on_transfer("d2h", blob.nbytes)
                    tel.on_device_window(
                        out.get("device", part.slot.label),
                        out["solve_ms"], out["fetch_ms"],
                        inflight=part.slot.inflight,
                    )
                drivers_l = blob[:, 0].astype(np.int64)
                admitted = blob[:, 1].astype(bool)
                packed = blob[:, 2].astype(bool)
                execs_l = blob[:, 3:].astype(np.int64)
                if part.idx is None:
                    # Dense whole-window path: indices are global, the
                    # reconstruction threads the dense base — exactly the
                    # single-device unpruned fetch, with the debit rows
                    # still read sparsely off the blob. (A whole window
                    # has exactly ONE part, so the pre-recon placements
                    # tensor holds no other part's commits and the prows
                    # capture below is this part's alone.)
                    base_d = dense_base()
                    decisions = self._reconstruct_requests(
                        part.requests, drivers_l, admitted, packed,
                        execs_l, part.row_drv, part.row_exc,
                        part.row_skip, base_d, dense["placements"],
                        handle.host_schedulable,
                    )
                    prows = self._commit_rows(
                        part.requests, drivers_l, admitted, execs_l
                    )
                    sp_rows.append(prows)
                    sp_vals.append(dense["placements"][prows].copy())
                    for rid, d in zip(part.req_ids, decisions):
                        results[rid] = d
                    continue
                gmap = part.idx.astype(np.int64)
                if part.prune is not None:
                    # Two-tier certificate, per partition, in compact
                    # space: the [k,3] base captured at dispatch minus
                    # the (strict) prior deltas on this part's kept rows.
                    from spark_scheduler_tpu.core.prune import (
                        certify_window,
                    )

                    if not strict_known:
                        strict_ps = self._prior_sparse(handle)
                        strict_known = True
                    k_real = part.prune.k_real
                    keep_real = part.prune.keep[:k_real]
                    if strict_ps is None:
                        cert_ok, reason, bk = False, "prior-unknown", None
                    else:
                        prior_rows, prior_deltas = strict_ps
                        bk = part.base_kept[:k_real].copy()
                        if prior_rows.size:
                            loc = np.searchsorted(keep_real, prior_rows)
                            locc = np.clip(loc, 0, keep_real.size - 1)
                            on_kept = keep_real[locc] == prior_rows
                            if on_kept.any():
                                bk[locc[on_kept]] -= prior_deltas[on_kept]
                        drv_g = np.where(
                            drivers_l >= 0,
                            gmap[np.clip(drivers_l, 0, None)], -1,
                        )
                        exc_g = np.where(
                            execs_l >= 0,
                            gmap[np.clip(execs_l, 0, None)], -1,
                        )
                        cert_ok, reason = certify_window(
                            part.prune,
                            strategy=handle.strategy,
                            requests=part.requests,
                            drivers=drv_g,
                            admitted=admitted,
                            packed=packed,
                            execs=exc_g,
                            drv64=part.row_drv,
                            exc64=part.row_exc,
                            base_kept=bk.copy(),  # certify threads commits
                            host=handle.host_tensors,
                            prior_rows=prior_rows,
                            prior_deltas=prior_deltas,
                        )
                    if not cert_ok:
                        # Escalate just this partition: re-solve it on the
                        # exact host reconstruction (other partitions are
                        # row-disjoint and stand), then invalidate the
                        # poisoned carry and the windows dispatched on it.
                        decs, ppl = self._escalation_decisions(
                            handle.strategy, handle.host_tensors,
                            dense_base(), part.requests,
                        )
                        dense["base"] -= ppl
                        dense["placements"] += ppl
                        support_complete = False
                        for rid, d in zip(part.req_ids, decs):
                            results[rid] = d
                        self._note_prune_escalation(handle, reason)
                        continue
                    kp = gmap.shape[0]
                    base_loc = np.zeros(
                        (kp, part.base_kept.shape[1]), np.int64
                    )
                    base_loc[:k_real] = bk
                    placements_loc = np.zeros_like(base_loc)
                    sched_loc = np.asarray(handle.host_schedulable)[
                        part.idx
                    ]
                    decisions = self._reconstruct_requests(
                        part.requests, drivers_l, admitted, packed,
                        execs_l, part.row_drv, part.row_exc,
                        part.row_skip, base_loc, placements_loc,
                        sched_loc, row_map=gmap,
                    )
                    loc = np.flatnonzero(placements_loc.any(axis=1))
                    commit_sparse(gmap[loc], placements_loc[loc])
                    for rid, d in zip(part.req_ids, decisions):
                        results[rid] = d
                    continue
                # Unpruned gathered partition: compact reconstruction in
                # the part's local row space (lenient priors — the
                # dense-base semantics).
                bk = part.base_kept.copy()
                if lp_rows.size:
                    loc = np.searchsorted(gmap, lp_rows)
                    locc = np.clip(loc, 0, gmap.size - 1)
                    on = gmap[locc] == lp_rows
                    if on.any():
                        bk[locc[on]] -= lp_vals[on]
                placements_loc = np.zeros_like(bk)
                sched_loc = np.asarray(handle.host_schedulable)[part.idx]
                decisions = self._reconstruct_requests(
                    part.requests, drivers_l, admitted, packed, execs_l,
                    part.row_drv, part.row_exc, part.row_skip,
                    bk, placements_loc, sched_loc, row_map=gmap,
                )
                loc = np.flatnonzero(placements_loc.any(axis=1))
                commit_sparse(gmap[loc], placements_loc[loc])
                for rid, d in zip(part.req_ids, decisions):
                    results[rid] = d
        # Combined sparse support of this window's committed placements.
        if sp_rows:
            allr = np.concatenate(sp_rows)
        else:
            allr = np.empty(0, np.int64)
        if allr.size:
            allv = np.concatenate(sp_vals)
            uniq, inv = np.unique(allr, return_inverse=True)
            vals = np.zeros((uniq.size, allv.shape[1]), np.int64)
            np.add.at(vals, inv, allv)
        else:
            uniq = np.empty(0, np.int64)
            vals = np.empty((0, NUM_DIMS), np.int64)
        if dense["placements"] is not None:
            handle.placements = dense["placements"]
        if support_complete:
            handle.placement_rows = uniq
            handle.placement_vals = vals
        p = self._pipe
        if p is not None and handle in p["unfetched"]:
            p["unfetched"].remove(handle)
            if uniq.size:
                # Sparse pooled debit (ISSUE 15 tentpole (a)): scatter
                # exactly the union of partition debit rows into the
                # mirror and the pending ledger — the next build compares
                # just these instead of a dense [N] sweep.
                p["mirror"][uniq] -= vals
                if p.get("pending") is not None:
                    p["pending"].append(uniq)
                self.build_stats["pooled_debit_rows"] += int(uniq.size)
            self._prune_note_rows(uniq)
            ne = handle.avail_note_epoch
            if (
                ne is not None
                and p.get("avail_journal", {}).get(ne, 0) is None
            ):
                # The dispatch journaled this epoch as unknowable; the
                # fetch just learned the exact commit rows — patch the
                # entry so slot mirrors can catch up across it.
                p["avail_journal"][ne] = uniq
        else:
            # Pipeline died mid-fetch (escalation / slot failure): the
            # next build full-uploads host truth; the planner resyncs.
            self._prune_mark_unknown()
        self._note_dispatch_complete(handle)
        self._device_recovered()
        return results

    def _redispatch_part(self, handle: "WindowHandle", part: "_WindowPart", base):
        """Re-run a failed partition's solve on a SURVIVING slot with
        byte-identical inputs: the availability rows come from the host
        reconstruction (`base` — host view at dispatch minus in-flight
        priors' placements, which is exactly what the dead slot's device
        base embodied; partitions are row-disjoint, so earlier parts'
        commits cannot touch this part's rows), the statics re-upload to
        the survivor, and the app batch is the part's stashed host copy.
        Slot choice never affects decisions (pool invariant), so the
        retried decisions equal what the dead slot would have returned —
        pinned by tests/test_slot_recovery.py.

        Returns a worker-style {"blob", "solve_ms", "fetch_ms", "device"}
        dict, or (decisions, placements) when NO slot survives and the
        degraded policy is greedy. Raises DegradedUnavailableError (shed)
        or AllSlotsQuarantinedError (no controller) otherwise."""
        pool = self._pool
        host = handle.host_tensors
        strategy = handle.strategy
        emax = (handle.info or {}).get("emax")
        # Safe to recompute: a zone-set change implies a node event, which
        # forces a pipeline drain BEFORE any new dispatch — no window can
        # be in flight across it.
        num_zones = self._num_zones_bucket()
        while True:
            self.probe_quarantined()
            healthy = pool.healthy_slots()
            if not healthy:
                exc = AllSlotsQuarantinedError(
                    "no surviving slot for re-dispatch"
                )
                self._degraded_or_raise(exc)
                decs, ppl = self.fallback.window_decisions(
                    strategy, host, base, part.requests
                )
                if self.degraded is not None:
                    self.degraded.on_fallback_decision(len(decs))
                return decs, ppl
            slot = min(healthy, key=lambda s: s.inflight)
            t0 = self._clock()
            try:
                _shim("h2d")
                epoch = self._static_epoch
                if part.idx is None:
                    statics = slot.resident_statics(
                        host, epoch, self._clock, self.telemetry,
                        journal=self._static_journal,
                    )
                    avail_rows = base
                elif part.prune is not None:
                    # Pruned partition: fresh gathered statics on the
                    # survivor (the keep set is per-window, never cached);
                    # the gathered base rows equal what the dead slot's
                    # device gather embodied.
                    statics = tuple(
                        slot._put(f)
                        for f in _gather_statics_host(
                            host, part.idx, part.prune.k_real
                        )
                    )
                    avail_rows = base[part.idx]
                else:
                    statics = slot.sub_replica(
                        host, part.idx_key, part.idx, epoch, self._clock,
                        self.telemetry,
                    )
                    avail_rows = base[part.idx]
                sub_avail = slot._put(
                    np.asarray(avail_rows, dtype=np.int32)
                )
                apps = slot.place_apps(part.apps)
                if part.prune is not None:
                    zone_base_dev = tuple(
                        slot._put(a) for a in part.prune.zone_base
                    )

                    def fn(avail_, statics_, apps_, *, fill, emax,
                           num_zones, _zb=zone_base_dev):
                        return _window_blob_pruned(
                            avail_, statics_, apps_, _zb,
                            fill=fill, emax=emax, num_zones=num_zones,
                        )
                else:
                    fn = (
                        _window_blob_statics if slot.is_mesh
                        else _window_blob_donated
                    )
                _shim("dispatch")
                blob, _after = fn(
                    sub_avail, statics, apps,
                    fill=strategy, emax=emax, num_zones=num_zones,
                )
                t1 = self._clock()
                _shim("d2h")
                blob_np = np.asarray(jax.device_get(blob))
                t2 = self._clock()
            except Exception as exc:
                if classify_slot_failure(exc):
                    # The survivor died too (e.g. the fault is the shared
                    # host link, not one device): quarantine it and keep
                    # walking the pool.
                    self._quarantine_slot(slot, exc)
                    continue
                raise
            self.redispatch_count += 1
            self._on_slot_event("redispatch", slot.label)
            if handle.info is not None:
                handle.info["redispatches"] = (
                    handle.info.get("redispatches", 0) + 1
                )
            if handle.request_device is not None:
                for r in part.req_ids:
                    handle.request_device[r] = slot.label
            return {
                "blob": blob_np,
                "solve_ms": (t1 - t0) * 1e3,
                "fetch_ms": (t2 - t1) * 1e3,
                "device": slot.label,
            }

    def _reconstruct_requests(
        self, requests, drivers, admitted, packed, execs,
        drv64, exc64, skip, base, placements, host_schedulable,
        row_map=None,
    ) -> list[WindowDecision]:
        """Host-side reconstruction for per-request packing efficiency: the
        availability each admitted request's final pack saw = the
        host view at dispatch, minus the committed placements of windows
        that were still in flight then (the device had them threaded),
        minus committed placements of earlier segments, minus in-segment
        admitted hypothetical placements. Vectorized over each segment's
        rows (a FIFO window carries O(requests x pending) hypothetical
        rows — per-row Python was the serving loop's hot spot). Mutates
        `base` and `placements` in place (the pooled fetch threads ONE
        base through every partition).

        `row_map` (pruned fetches): decision indices, `base` and
        `placements` live in a COMPACT kept-row space; row_map maps a
        local index to its global registry row for name resolution — the
        whole reconstruction then costs O(K) instead of O(N) (the
        per-request `base.copy()` below was a measured [N,3] cost per
        admitted request at the million-node tier)."""
        if row_map is not None:
            name_of = lambda i: self.registry.name_of(int(row_map[i]))  # noqa: E731
        else:
            name_of = lambda i: self.registry.name_of(int(i))  # noqa: E731
        decisions: list[WindowDecision] = []
        row = 0
        for r, req in enumerate(requests):
            nrows = len(req.rows)
            hyp = np.arange(row, row + nrows - 1)
            real = row + nrows - 1
            row += nrows
            req_admitted = bool(admitted[real])
            earlier_blocked = False
            eff = None
            if nrows > 1:
                adm_h = admitted[hyp]
                earlier_blocked = bool(
                    np.any(~adm_h & ~packed[hyp] & ~skip[hyp])
                )
            if req_admitted:
                seg_avail = base.copy()
                if nrows > 1:
                    dsel = adm_h & (drivers[hyp] >= 0)
                    if dsel.any():
                        np.subtract.at(
                            seg_avail, drivers[hyp][dsel], drv64[hyp][dsel]
                        )
                    e = execs[hyp]
                    esel = adm_h[:, None] & (e >= 0)
                    if esel.any():
                        ri, _si = np.nonzero(esel)
                        np.subtract.at(seg_avail, e[esel], exc64[hyp][ri])
                eff = avg_packing_efficiency_np(
                    host_schedulable,
                    seg_avail,
                    int(drivers[real]),
                    execs[real],
                    drv64[real],
                    exc64[real],
                )
                # Commit this request's placement into the base for the
                # segments after it (mirrors the device-side base thread).
                if drivers[real] >= 0:
                    base[drivers[real]] -= drv64[real]
                    placements[drivers[real]] += drv64[real]
                ev = execs[real]
                ev = ev[ev >= 0]
                if ev.size:
                    np.subtract.at(base, ev, exc64[real])
                    np.add.at(placements, ev, exc64[real])
            exec_idx = [int(x) for x in execs[real] if int(x) >= 0]
            decisions.append(
                WindowDecision(
                    packing=HostPacking(
                        driver_node=(
                            name_of(drivers[real])
                            if drivers[real] >= 0
                            else None
                        ),
                        executor_nodes=[
                            name_of(x) for x in exec_idx
                        ],
                        has_capacity=bool(packed[real]),
                        efficiency_max=float(eff.max) if eff else 0.0,
                        efficiency_cpu=float(eff.cpu) if eff else 0.0,
                        efficiency_memory=float(eff.memory) if eff else 0.0,
                        efficiency_gpu=float(eff.gpu) if eff else 0.0,
                    ),
                    admitted=req_admitted,
                    earlier_blocked=earlier_blocked,
                )
            )
        return decisions

    def subtract_usage(self, tensors, usage: dict[str, Resources]):
        """Subtract per-node usage from availability in-place-equivalent
        (NodeGroupSchedulingMetadata.SubtractUsageIfExists,
        resources.go:128-135); returns new tensors."""
        avail = np.array(tensors.available)
        for name, res in usage.items():
            idx = self.registry.index_of(name)
            if idx is not None and idx < avail.shape[0]:
                avail[idx] = avail[idx] - res.as_array()
        import dataclasses as _dc

        return _dc.replace(tensors, available=avail)
