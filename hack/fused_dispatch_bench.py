"""Fused multi-window dispatch: decisions/s vs simulated device RTT.

The fused engine's win is invisible on a local CPU backend (device
boundaries are microseconds), so this bench injects a device round trip
with the simulated-RTT device shim (testing/rtt_shim.py): every window
DISPATCH pays rtt/2 on the dispatcher thread and every decision pull pays
rtt/2 on a fetch thread — the structure bench.py reports as
`device_rtt_floor_ms` (not measured on the v5e chip yet; PERF.md).

Arms: fused_k in {1, 4} (1 = today's one-window-per-dispatch serving
loop, pipelined dispatch-before-fetch; 4 = the fused claim — 4 windows
per device round trip) x simulated RTT in {10, 50, 100} ms on a single
device, plus an RTT-50 pair on a 2-slot device pool (fused batches ride
the same partition/overlap machinery). In-process windows through the
REAL extender dispatch/complete path (reservations, write-back, epoch
machinery) — the HTTP layer is out of frame, as in the in-process
controls of every serving section.

Runs as a subprocess of bench.py's `fused_dispatch` section (the pool
arms need the 8-device virtual CPU mesh forced before jax initializes).
One JSON line per arm on stdout; standalone:
    python hack/fused_dispatch_bench.py
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

N_GROUPS = 2
NODES_PER_GROUP = 128
WINDOW = 8  # requests per serving window
N_WINDOWS = 8  # measured windows per arm
EXECS = 2
# (pool, fused_k, rtt_ms): the single-device RTT sweep is the
# PERFORMANCE.md table; the pool pair shows fusion composing with the
# multi-device engine.
ARMS = (
    (1, 1, 10), (1, 4, 10),
    (1, 1, 50), (1, 4, 50), (1, 8, 50),
    (1, 1, 100), (1, 4, 100), (1, 8, 100),
    (2, 1, 50), (2, 4, 50),
)


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
                f"g{g}-n{i}", zone=f"zone{i % 2}",
                instance_group=f"group-{g}",
            )
            backend.add_node(node)
            group_names[g].append(node.name)
    app = build_scheduler_app(
        backend,
        InstallConfig(
            fifo=False, sync_writes=True,
            instance_group_label=INSTANCE_GROUP_LABEL,
            solver_device_pool=pool,
        ),
    )
    return backend, app, group_names


def _run_arm(pool: int, fused_k: int, rtt_ms: float) -> dict:
    from spark_scheduler_tpu.core.extender import ExtenderArgs
    from spark_scheduler_tpu.testing.harness import (
        static_allocation_spark_pods,
    )
    from spark_scheduler_tpu.testing.rtt_shim import SimulatedRTT

    backend, app, group_names = _build(pool)
    ext = app.extender

    def make_window(tag, k):
        drivers, args = [], []
        for j in range(WINDOW):
            g = j % N_GROUPS
            pod = static_allocation_spark_pods(
                f"fd-{tag}-{k}-{j}", EXECS, instance_group=f"group-{g}"
            )[0]
            backend.add_pod(pod)
            drivers.append(pod)
            args.append(
                ExtenderArgs(pod=pod, node_names=list(group_names[g]))
            )
        return drivers, args

    def complete(drivers, ticket):
        for d, r in zip(drivers, ext.predicate_window_complete(ticket)):
            if not r.node_names:
                raise RuntimeError(f"{d.name}: {r.outcome}")
            backend.bind_pod(d, r.node_names[0])

    def dispatch_group(tag, k, n_windows):
        """One dispatch unit: a single window (fused_k=1) or a fused
        group of n_windows sub-windows in ONE device program."""
        members = [make_window(tag, k * fused_k + i) for i in range(n_windows)]
        if n_windows == 1:
            tickets = [ext.predicate_window_dispatch(members[0][1])]
        else:
            tickets = ext.predicate_windows_dispatch(
                [args for _, args in members]
            )
        return [(drivers, t) for (drivers, _), t in zip(members, tickets)]

    def complete_group(group):
        for drivers, t in group:
            complete(drivers, t)

    # Warm (shim off): compiles for every window shape this arm hits.
    n_groups_run = N_WINDOWS // fused_k
    complete_group(dispatch_group("warm", 0, fused_k))
    complete_group(dispatch_group("warm2", 1, 1))

    shim = SimulatedRTT(rtt_ms=rtt_ms)
    with shim:
        t0 = time.perf_counter()
        # Pipelined one dispatch-unit ahead, like the serving batcher.
        prev = dispatch_group("run", 0, fused_k)
        for k in range(1, n_groups_run):
            nxt = dispatch_group("run", k, fused_k)
            complete_group(prev)
            prev = nxt
        complete_group(prev)
        wall = time.perf_counter() - t0
    decisions = WINDOW * N_WINDOWS
    out = {
        "pool": pool,
        "fused_k": fused_k,
        "rtt_ms": rtt_ms,
        "decisions_per_s": round(decisions / wall, 1),
        "amortized_rtt_floor_ms_per_window": round(
            wall * 1e3 / N_WINDOWS, 2
        ),
        "windows": N_WINDOWS,
        "window_requests": WINDOW,
        "nodes": N_GROUPS * NODES_PER_GROUP,
        "shim_events": dict(shim.counts),
        "window_path_counts": dict(app.solver.window_path_counts),
        "path": (
            "one-window-per-dispatch (pipelined)"
            if fused_k == 1
            else f"fused {fused_k}-window dispatch on resident carry state"
        ),
    }
    app.stop()
    return out


def main() -> int:
    from spark_scheduler_tpu.tracing import Svc1Logger, set_svc1log

    set_svc1log(Svc1Logger(stream=open(os.devnull, "w")))
    baselines: dict[tuple, float] = {}
    for pool, fused_k, rtt in ARMS:
        arm = _run_arm(pool, fused_k, rtt)
        key = (pool, rtt)
        if fused_k == 1:
            baselines[key] = arm["decisions_per_s"]
        base = baselines.get(key)
        arm["speedup_vs_unfused"] = (
            round(arm["decisions_per_s"] / base, 2)
            if base and fused_k > 1
            else None
        )
        print(json.dumps(arm), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
