"""Fault-recovery bench (ISSUE 9): slot-kill mid-burst on a 2-slot pool.

Three arms over the SAME seeded workload (1,280 nodes in 2 instance
groups, pipelined serving windows through the extender):

  steady      2-slot pool, no faults — the throughput baseline;
  slot_kill   one device slot dies mid-burst (FaultInjector device
              surface): the dead partition is quarantined and
              re-dispatched on the survivor; reports decisions/s dip vs
              steady, the faulted window's wall latency (time-to-recover
              proxy) vs the steady per-window median, and ASSERTS the
              decisions are byte-identical to the steady arm's;
  all_killed  every slot dies and stays dead: the degraded greedy
              fallback serves the rest of the burst — the throughput
              floor when no device can serve (also asserted
              byte-identical).

Forces an 8-device virtual CPU mesh BEFORE jax initializes, so it runs
in a process of its own. One JSON line per arm on stdout:
    python hack/fault_recovery_bench.py
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
NODES_PER_GROUP = 640
WINDOW = 8  # 4 drivers per group per window
N_WINDOWS = 8
KILL_AT_DISPATCH = 5  # device.dispatch event index that dies mid-burst


def _build():
    from spark_scheduler_tpu.faults.degraded import DegradedModeController
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
            solver_device_pool=2,
            degraded_mode="greedy",
        ),
    )
    assert isinstance(app.solver.degraded, DegradedModeController)
    return backend, app, group_names


def _run_arm(arm: str) -> dict:
    from spark_scheduler_tpu.core.extender import ExtenderArgs
    from spark_scheduler_tpu.faults import FaultInjector, FaultPlan, FaultSpec
    from spark_scheduler_tpu.testing.harness import (
        static_allocation_spark_pods,
    )

    backend, app, group_names = _build()
    ext = app.extender

    def dispatch_window(tag, k):
        drivers = []
        args = []
        for j in range(WINDOW):
            g = j % N_GROUPS
            pod = static_allocation_spark_pods(
                f"frb-{tag}-{k}-{j}", 4, instance_group=f"group-{g}"
            )[0]
            backend.add_pod(pod)
            drivers.append(pod)
            args.append(
                ExtenderArgs(pod=pod, node_names=list(group_names[g]))
            )
        return drivers, ext.predicate_window_dispatch(args)

    def complete_window(drivers, t):
        placements = []
        results = ext.predicate_window_complete(t)
        for d, r in zip(drivers, results):
            if not r.node_names:
                raise RuntimeError(f"{d.name}: {r.outcome}")
            backend.bind_pod(d, r.node_names[0])
            placements.append((d.name, r.node_names[0]))
        return placements

    # Warm compiles for every shape this arm hits (device AND fallback).
    for w in range(2):
        complete_window(*dispatch_window("warm", w))

    plan = None
    if arm == "slot_kill":
        plan = FaultPlan(
            seed=0, name="bench-slot-kill",
            specs=[FaultSpec(surface="device.dispatch", mode="error",
                             at=[KILL_AT_DISPATCH], limit=1)],
        )
    elif arm == "all_killed":
        plan = FaultPlan(
            seed=0, name="bench-pool-down",
            specs=[FaultSpec(surface="device.dispatch", mode="partition",
                             start=KILL_AT_DISPATCH)],
        )
    injector = FaultInjector(plan) if plan is not None else None

    window_ms: list[float] = []
    placements: list = []
    try:
        if injector is not None:
            injector.__enter__()
            injector.install_device()
        t0 = time.perf_counter()
        for k in range(N_WINDOWS):
            w0 = time.perf_counter()
            placements.extend(complete_window(*dispatch_window("run", k)))
            window_ms.append((time.perf_counter() - w0) * 1e3)
        wall = time.perf_counter() - t0
    finally:
        if injector is not None:
            injector.__exit__(None, None, None)

    solver = app.solver
    ordered = sorted(window_ms)
    out = {
        "arm": arm,
        "decisions_per_s": round(WINDOW * N_WINDOWS / wall, 1),
        "windows_of": WINDOW,
        "windows": N_WINDOWS,
        "nodes": N_GROUPS * NODES_PER_GROUP,
        "window_p50_ms": round(ordered[len(ordered) // 2], 2),
        "window_max_ms": round(ordered[-1], 2),
        "device_health": solver.device_health(),
        "redispatches": solver.redispatch_count,
        "placements": placements,
    }
    if solver.degraded is not None:
        snap = solver.degraded.snapshot()
        out["degraded"] = {
            "active": snap["active"],
            "engagements": snap["engagements"],
            "fallback_decisions": snap["fallback_decisions"],
        }
    app.stop()
    return out


def main() -> int:
    from spark_scheduler_tpu.tracing import Svc1Logger, set_svc1log

    set_svc1log(Svc1Logger(stream=open(os.devnull, "w")))
    steady = _run_arm("steady")
    arms = [steady]
    for arm in ("slot_kill", "all_killed"):
        res = _run_arm(arm)
        # Byte-identical recovery: the same workload must land the same
        # placements with a dead slot (survivor re-dispatch) and with a
        # dead POOL (greedy fallback) as it does fault-free.
        assert res.pop("placements") == steady["placements"], (
            f"{arm} placements diverged from steady"
        )
        res["byte_identical_to_steady"] = True
        res["dip_vs_steady"] = round(
            res["decisions_per_s"] / steady["decisions_per_s"], 3
        )
        # Time-to-recover proxy: the faulted window's wall latency over
        # the steady per-window median — what the burst actually paid for
        # quarantine + re-upload + re-dispatch (or fallback engagement).
        res["recovery_spike_ms"] = round(
            res["window_max_ms"] - steady["window_p50_ms"], 2
        )
        arms.append(res)
    steady_out = dict(steady)
    steady_out.pop("placements", None)
    print(json.dumps(steady_out), flush=True)
    for res in arms[1:]:
        print(json.dumps(res), flush=True)
    # Sanity: the slot-kill arm actually killed a slot, the all-killed
    # arm actually degraded.
    slot_kill, all_killed = arms[1], arms[2]
    assert slot_kill["redispatches"] >= 1
    assert len(slot_kill["device_health"]["quarantined"]) == 1
    assert all_killed["degraded"]["fallback_decisions"] > 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
