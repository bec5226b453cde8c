"""Host-scaling sweep (ISSUE 11, the million-node tier).

One JSON line per (tier, pool) arm (default tiers 10k / 100k / 1M via
BENCH_SCALE_TIERS, pools {1, 2} via BENCH_SCALE_POOLS — ISSUE 15),
measuring the numbers the tier is judged on:

  window_p50_ms        steady-state serving-window service time (extender
                       dispatch -> decisions, pruned two-tier solve;
                       pool arms serve 2-group PARTITIONED windows);
  node_update_ms /     cost of one node event: the event applied through
  node_add_ms          the backend, then ONE single-request window served
                       (snapshot patch + O(changed) build + delta upload +
                       solve) — the end-to-end node-event path;
  upload_bytes_per_event
                       h2d bytes per device-state upload during the event
                       phase (the O(changed) claim as a number);
  warm_restart_ms      discard the pipeline and re-serve from warm host
                       caches — the warm-standby promotion analog (caches
                       hot, device state cold; the HA promotion itself is
                       measured in PR 8's ha_failover section);
  wide (16-req) arm    plan/gather phase means recorded separately for
                       the wide windows (ISSUE 15 residual (d): the
                       reused-plan 16-wide host cost must track window
                       size, not cluster size).

Everything runs in process against the local jax backend: no HTTP hop —
this is the HOST scaling story. Candidate names ride an
identity-keyed ticket (the in-process analog of the native ingest lane's
digest ticket) so the 1M-name candidate list is not re-hashed per request.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_POOLS = [
    int(x) for x in os.environ.get("BENCH_SCALE_POOLS", "1,2").split(",")
]
if max(_POOLS) > 1 and "xla_force_host_platform_device_count" not in (
    os.environ.get("XLA_FLAGS", "")
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={max(_POOLS)}"
    )

import numpy as np  # noqa: E402


class NameTicket(list):
    """Candidate-name list with O(1) identity hash/eq — the in-process
    stand-in for server/ingest.NativeNodeNames, so the solver's
    candidate-mask LRU hits without hashing N strings per request."""

    __hash__ = object.__hash__

    def __eq__(self, other):
        return self is other

    @property
    def names_digest(self):
        return id(self)


def _pct(vals, q):
    return round(float(np.percentile(vals, q)), 3)


def run_tier(n_nodes: int, windows: int, pool: int = 1) -> dict:
    import dataclasses

    from spark_scheduler_tpu.core.extender import ExtenderArgs
    from spark_scheduler_tpu.server.app import build_scheduler_app
    from spark_scheduler_tpu.server.config import InstallConfig
    from spark_scheduler_tpu.store.backend import InMemoryBackend
    from spark_scheduler_tpu.testing.harness import (
        INSTANCE_GROUP_LABEL,
        new_node,
        static_allocation_spark_pods,
    )

    backend = InMemoryBackend()
    t0 = time.perf_counter()
    for i in range(n_nodes):
        if pool > 1:
            # Two instance groups: serving windows PARTITION across the
            # device pool (ISSUE 15 — the pooled million-node arm).
            backend.add_node(
                new_node(
                    f"s{i:07d}", zone=f"zone{i % 4}",
                    instance_group=f"ig{i % 2}",
                )
            )
        else:
            backend.add_node(new_node(f"s{i:07d}", zone=f"zone{i % 4}"))
    roster_ingest_s = time.perf_counter() - t0
    names = NameTicket(f"s{i:07d}" for i in range(n_nodes))

    app = build_scheduler_app(
        backend,
        InstallConfig(
            fifo=False,
            sync_writes=True,
            instance_group_label=INSTANCE_GROUP_LABEL,
            solver_prune_top_k=64,
            solver_device_pool=pool,
            flight_recorder=False,
        ),
    )
    ext = app.extender
    ext._last_request = float("inf")
    seq = iter(range(10_000_000))

    def serve_window(n_req=4, execs=2):
        args = []
        for r in range(n_req):
            kw = {"instance_group": f"ig{r % 2}"} if pool > 1 else {}
            d = static_allocation_spark_pods(
                f"hs-{next(seq)}", execs, **kw
            )[0]
            backend.add_pod(d)
            args.append(ExtenderArgs(pod=d, node_names=names))
        t0 = time.perf_counter()
        tok = ext.predicate_window_dispatch(args)
        res = ext.predicate_window_complete(tok)
        return (time.perf_counter() - t0) * 1e3, res

    # Boot: cold featurize + first full upload + first (compiling) window.
    t0 = time.perf_counter()
    boot_ms_raw, res = serve_window(1)
    boot_ms = (time.perf_counter() - t0) * 1e3
    assert res[0].node_names, "boot window failed to place"

    # Pin the boot-time roster out of GC traversal: at 1M nodes the heap
    # holds ~10M long-lived objects, and CPython gen-2 collections were
    # the dominant per-event p99 noise (multi-hundred-ms pauses with no
    # scheduler counter moving). Standard long-lived-heap serving
    # practice; steady-state garbage still collects normally. Unfrozen
    # (and collected) before this arm returns — a sweep runs 6 arms in
    # one process, and permanently freezing each arm's heap would leak
    # every dead roster into the next arm's measurements.
    import gc

    gc.collect()
    gc.freeze()

    # Steady-state window service (4-request windows), plus a WIDE arm
    # (16-request windows — the natural fill at fleet-scale traffic):
    # per-decision cost is the tier's acceptance number, and the wide
    # windows amortize the per-window host passes exactly as real load
    # does.
    lat = [serve_window()[0] for _ in range(windows)]

    # Pipelined arm (depth 2): dispatch window N+1 BEFORE completing N —
    # the serving loop's actual operating mode, where a pool overlaps
    # window N+1's host build + upload with window N's solve across
    # slots. Sequential dispatch→complete (the p50 above) cannot show
    # that overlap.
    def dispatch_only(n_req=4, execs=2):
        args = []
        for r in range(n_req):
            kw = {"instance_group": f"ig{r % 2}"} if pool > 1 else {}
            d = static_allocation_spark_pods(
                f"hs-{next(seq)}", execs, **kw
            )[0]
            backend.add_pod(d)
            args.append(ExtenderArgs(pod=d, node_names=names))
        return ext.predicate_window_dispatch(args)

    t0 = time.perf_counter()
    prev = dispatch_only()
    for _ in range(windows - 1):
        cur = dispatch_only()
        ext.predicate_window_complete(prev)
        prev = cur
    ext.predicate_window_complete(prev)
    window_pipelined_ms = (time.perf_counter() - t0) * 1e3 / windows

    serve_window(16)  # untimed: compiles the wide-bucket kernels
    prune_stats = app.solver.prune_stats
    pr0 = {
        k: prune_stats[k]
        for k in ("windows", "plan_ms", "gather_ms", "offset_ms")
    }
    lat_wide = [
        serve_window(16)[0] for _ in range(max(4, windows // 2))
    ]
    # Per-phase host cost of the WIDE (16-request) arm alone — the
    # reused-plan gather/plan residual ISSUE 15 (d) pins to ≤1.5x the
    # 100k cost.
    wide_n = max(int(prune_stats["windows"] - pr0["windows"]), 1)
    wide_phases = {
        f"wide_{k}_mean": round(
            (prune_stats[k] - pr0[k]) / wide_n, 4
        )
        for k in ("plan_ms", "gather_ms", "offset_ms")
    }

    stats = app.solver.device_state_stats

    def upload_bytes_per_event(before, after):
        events = sum(
            after[k] - before[k]
            for k in ("full_uploads", "delta_uploads", "static_delta_uploads")
        )
        if not events:
            return 0.0
        return round((after["upload_bytes"] - before["upload_bytes"]) / events, 1)

    # Node events: updates (unschedulable flip on high-index idle nodes)
    # and adds, each followed by ONE single-request window.
    upd_lat, add_lat = [], []
    before_events = dict(stats)
    for j in range(6):
        name = f"s{n_nodes - 1 - j:07d}"
        cur = backend.get_node(name)
        t0 = time.perf_counter()
        backend.update(
            "nodes", dataclasses.replace(cur, unschedulable=not cur.unschedulable)
        )
        w_ms, _ = serve_window(1)
        upd_lat.append((time.perf_counter() - t0) * 1e3)
    for j in range(6):
        t0 = time.perf_counter()
        backend.add_node(new_node(f"late{j:03d}", zone=f"zone{j % 4}"))
        w_ms, _ = serve_window(1)
        add_lat.append((time.perf_counter() - t0) * 1e3)
    after_events = dict(stats)

    # Node-ADD burst arm (ISSUE 13): BENCH_ADD_BURST sequential adds, one
    # served window each — p50/p99 per add measures the AMORTIZED growth
    # claim (preallocated roster/master buffers, O(changed) patches), not
    # a single lucky event.
    burst_n = int(os.environ.get("BENCH_ADD_BURST", "100"))
    grows_before = ext.features.stats()["array_grows"]
    burst_lat = []
    for j in range(burst_n):
        t0 = time.perf_counter()
        backend.add_node(new_node(f"burst{j:04d}", zone=f"zone{j % 4}"))
        serve_window(1)
        burst_lat.append((time.perf_counter() - t0) * 1e3)
    burst_grows = ext.features.stats()["array_grows"] - grows_before

    fs = ext.features.stats()

    # Warm restart (promotion analog): device state dropped, host caches hot.
    app.solver.discard_pipeline()
    t0 = time.perf_counter()
    serve_window(1)
    warm_restart_ms = (time.perf_counter() - t0) * 1e3

    out = {
        "n_nodes": n_nodes,
        "pool": pool,
        "roster_ingest_s": round(roster_ingest_s, 2),
        "boot_ms": round(boot_ms, 1),
        **wide_phases,
        "window_p50_ms": _pct(lat, 50),
        "window_p95_ms": _pct(lat, 95),
        "window_pipelined_ms": round(window_pipelined_ms, 3),
        "decisions_per_s": round(4 / (_pct(lat, 50) / 1e3), 1),
        "window16_p50_ms": _pct(lat_wide, 50),
        "per_decision_ms": round(_pct(lat_wide, 50) / 16, 3),
        "node_update_ms_p50": _pct(upd_lat, 50),
        "node_add_ms_p50": _pct(add_lat, 50),
        "add_burst_n": burst_n,
        "add_burst_p50_ms": _pct(burst_lat, 50),
        "add_burst_p99_ms": _pct(burst_lat, 99),
        "add_burst_array_grows": burst_grows,
        "upload_bytes_per_event": upload_bytes_per_event(
            before_events, after_events
        ),
        "warm_restart_ms": round(warm_restart_ms, 1),
        "roster_rebuilds_after_boot": fs["roster_rebuilds"] - 1,
        "roster_add_patches": fs["roster_add_patches"],
        "build": dict(app.solver.build_stats),
        "array_grows": fs["array_grows"],
        "device_state": dict(stats),
        "prune": dict(app.solver.prune_stats, reasons=dict(
            app.solver.prune_stats["reasons"])),
        "native_arena": app.solver.uses_native_arena,
    }
    app.stop()
    gc.unfreeze()
    gc.collect()
    return out


def main():
    tiers = [
        int(x)
        for x in os.environ.get(
            "BENCH_SCALE_TIERS", "10000,100000,1000000"
        ).split(",")
    ]
    windows = int(os.environ.get("BENCH_SCALE_WINDOWS", "12"))
    for pool in _POOLS:
        for n in tiers:
            out = run_tier(n, windows, pool=pool)
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
