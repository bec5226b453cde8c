"""Multi-device window-solve engine: decisions/s vs device count at 10k nodes.

Runs IN-PROCESS pipelined serving windows (predicate_window_dispatch /
predicate_window_complete) against a 10,240-node cluster split into 8
instance groups — the reference's real topology (failover.go:276-313) and
the shape that lets the engine partition each window into disjoint-domain
sub-solves. One arm per device-pool size:

  pool 1   = the single-device serving path (the engine disabled — today's
             baseline, whole 10k-node windows on the default device);
  pool 2/4/8 = the engine: each window partitions by instance group into
             gathered sub-cluster solves running CONCURRENTLY across the
             pool, the committed base scatter-combined between windows.

Forces an 8-device virtual CPU mesh BEFORE jax initializes, so it must run
in a process of its own, not one whose jax is already bound to its
backend. One JSON line per arm on stdout:
    python hack/multidevice_bench.py
"""

from __future__ import annotations

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")  # before any jax op

import json
import sys
import time

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
)

import numpy as np

N_GROUPS = 8
NODES_PER_GROUP = 1280  # 8 x 1280 = 10,240 nodes
WINDOW = 32  # 4 drivers per group per window
N_WINDOWS = 6
POOLS = (1, 2, 4, 8)


def _build(pool: int):
    from spark_scheduler_tpu.server.app import build_scheduler_app
    from spark_scheduler_tpu.server.config import InstallConfig
    from spark_scheduler_tpu.store.backend import InMemoryBackend
    from spark_scheduler_tpu.testing.harness import (
        INSTANCE_GROUP_LABEL,
        new_node,
    )

    backend = InMemoryBackend()
    group_names: dict[int, list[str]] = {}
    for g in range(N_GROUPS):
        group_names[g] = []
        for i in range(NODES_PER_GROUP):
            node = new_node(
                f"g{g}-n{i}", zone=f"zone{i % 4}",
                instance_group=f"group-{g}",
            )
            backend.add_node(node)
            group_names[g].append(node.name)
    app = build_scheduler_app(
        backend,
        InstallConfig(
            fifo=True, sync_writes=True,
            instance_group_label=INSTANCE_GROUP_LABEL,
            solver_device_pool=pool,
        ),
    )
    return backend, app, group_names


def _run_arm(pool: int) -> dict:
    from spark_scheduler_tpu.core.extender import ExtenderArgs
    from spark_scheduler_tpu.testing.harness import (
        static_allocation_spark_pods,
    )

    backend, app, group_names = _build(pool)
    ext = app.extender

    def dispatch_window(tag, k):
        drivers = []
        args = []
        for j in range(WINDOW):
            g = j % N_GROUPS
            pod = static_allocation_spark_pods(
                f"mdb-{tag}-{k}-{j}", 4, instance_group=f"group-{g}"
            )[0]
            backend.add_pod(pod)
            drivers.append(pod)
            args.append(
                ExtenderArgs(pod=pod, node_names=list(group_names[g]))
            )
        return drivers, ext.predicate_window_dispatch(args)

    def complete_window(drivers, t):
        results = ext.predicate_window_complete(t)
        for d, r in zip(drivers, results):
            if not r.node_names:
                raise RuntimeError(f"{d.name}: {r.outcome}")
            backend.bind_pod(d, r.node_names[0])

    # Warm: compiles for every window shape this arm hits.
    for w in range(2):
        complete_window(*dispatch_window("warm", w))
    t0 = time.perf_counter()
    prev = dispatch_window("run", 0)
    for k in range(1, N_WINDOWS):
        nxt = dispatch_window("run", k)
        complete_window(*prev)
        prev = nxt
    complete_window(*prev)
    wall = time.perf_counter() - t0
    solver = app.solver
    out = {
        "devices": pool,
        "decisions_per_s": round(WINDOW * N_WINDOWS / wall, 1),
        "windows_of": WINDOW,
        "windows": N_WINDOWS,
        "nodes": N_GROUPS * NODES_PER_GROUP,
        "instance_groups": N_GROUPS,
        "window_path_counts": dict(solver.window_path_counts),
        "device_pool_stats": solver.device_pool_stats(),
        "partitions_last_window": (
            (solver.last_solve_info or {}).get("partitions")
        ),
        "pipelined": True,
        "fused_k": 1,
        "path": (
            "single-device serving path (engine off)"
            if pool == 1
            else "device pool: disjoint-domain partitions solved "
            "concurrently, committed base scatter-combined"
        ),
    }
    app.stop()
    return out


def main() -> int:
    from spark_scheduler_tpu.tracing import Svc1Logger, set_svc1log

    set_svc1log(Svc1Logger(stream=open(os.devnull, "w")))
    baseline = None
    for pool in POOLS:
        arm = _run_arm(pool)
        if pool == 1:
            baseline = arm["decisions_per_s"]
        arm["speedup_vs_single_device"] = (
            round(arm["decisions_per_s"] / baseline, 2) if baseline else None
        )
        print(json.dumps(arm), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
