"""HA sharded-serving + chaos arms of bench.py's ha_failover section.

Measures 2-active-replica instance-group sharding (ha/replica.py
ShardedServingGroup) against a single unsharded replica on the SAME
workload, twice: a pure-CPU arm (informational — a single XLA CPU solve
already saturates every host core, so two concurrent solves cannot scale
there) and a simulated-RTT arm (testing/rtt_shim.py, where the control serializes one device
round trip per window and the shards overlap theirs — the arm that
carries the >= 1.5x bar). Byte-identical per-group placements are
ASSERTED in both arms. Then runs the leader-kill chaos soak
(testing/soak.py HAChaosSoak, >= 3 cycles).

Runs as a SUBPROCESS of bench.py (like hack/multidevice_bench.py). The
persistent XLA compilation cache is ENABLED again: the historical flake
(concurrently-serving solvers intermittently produced wrong window
decisions on executables reloaded from the cache, so this arm used to
run cache-free) is closed by InstallConfig.serialize_jax_cache_io() —
the cache's executable serialize/deserialize + file I/O now runs behind
one process-wide lock, which enable_jax_compile_cache installs. The
equivalence assertions below are the regression guard: a recurrence
fails the arm loudly. One JSON line per arm on stdout; standalone:
    python hack/ha_shard_bench.py
"""

from __future__ import annotations

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import contextlib
import copy
import json
import sys
import threading
import time

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
)

N_GROUPS = 8
APPS_PER_GROUP = 16
WINDOW = 8


def sharded_arm(nodes_per_group: int, rtt_ms):
    from spark_scheduler_tpu.core.extender import ExtenderArgs
    from spark_scheduler_tpu.ha.replica import ShardedServingGroup
    from spark_scheduler_tpu.ha.shard import ShardMap
    from spark_scheduler_tpu.server.config import InstallConfig
    from spark_scheduler_tpu.store.backend import DEMAND_CRD, InMemoryBackend
    from spark_scheduler_tpu.testing.harness import (
        INSTANCE_GROUP_LABEL,
        Harness,
        new_node,
        static_allocation_spark_pods,
    )
    from spark_scheduler_tpu.testing.rtt_shim import SimulatedRTT

    shard_map = ShardMap(2)
    groups = [f"shard-group-{i}" for i in range(N_GROUPS)]
    # One compile-warmup group OWNED BY EACH replica so both solvers (and
    # the control) pay jit warmup outside the timed section.
    warm_groups = []
    for owner in (0, 1):
        warm_groups.append(
            next(
                g
                for g in (f"warmup-{i}" for i in range(64))
                if shard_map.owner(g) == owner and g not in warm_groups
            )
        )
    nodes = []
    for gi, g in enumerate(groups):
        nodes.extend(
            new_node(f"g{gi}-n{i}", zone=f"zone{i % 3}", instance_group=g)
            for i in range(nodes_per_group)
        )
    for wi, g in enumerate(warm_groups):
        nodes.extend(
            new_node(f"w{wi}-n{i}", zone="zone0", instance_group=g)
            for i in range(WINDOW * 2)
        )
    node_names = [n.name for n in nodes]
    workload = []
    for g in warm_groups:
        workload.append((g, [
            static_allocation_spark_pods(
                f"{g}-app-{a}", 1, instance_group=g)[0]
            for a in range(WINDOW)
        ], True))
    for g in groups:
        for w in range(APPS_PER_GROUP // WINDOW):
            workload.append((g, [
                static_allocation_spark_pods(
                    f"{g}-app-{w}-{a}", 1, instance_group=g)[0]
                for a in range(WINDOW)
            ], False))
    timed = [(g, pods) for g, pods, warm in workload if not warm]

    def args_of(pods):
        return [
            ExtenderArgs(pod=copy.deepcopy(p), node_names=list(node_names))
            for p in pods
        ]

    shim = SimulatedRTT(rtt_ms) if rtt_ms else contextlib.nullcontext()
    with shim:
        # Control: ONE unsharded replica serves every window sequentially.
        control = Harness(binpack_algo="tightly-pack", fifo=True)
        control.add_nodes(*(copy.deepcopy(n) for n in nodes))
        control_placed = {}
        for g, pods, warm in workload:
            if warm:
                for res in control.extender.predicate_batch(args_of(pods)):
                    assert res.ok
        t0 = time.perf_counter()
        for g, pods in timed:
            for p, res in zip(
                pods, control.extender.predicate_batch(args_of(pods))
            ):
                assert res.ok, (g, p.name, res.outcome)
                control_placed[p.name] = res.node_names[0]
        single_s = time.perf_counter() - t0

        # Sharded: 2 active replicas over one shared backend, one serving
        # thread per replica driving ITS OWN groups' windows.
        shared = InMemoryBackend()
        shared.register_crd(DEMAND_CRD)
        sharded = ShardedServingGroup(
            shared,
            2,
            config_factory=lambda i: InstallConfig(
                fifo=True,
                binpack_algo="tightly-pack",
                instance_group_label=INSTANCE_GROUP_LABEL,
                sync_writes=True,
                ha_enabled=True,
            ),
        )
        sharded.start()
        for n in nodes:
            shared.add_node(copy.deepcopy(n))
        for g, pods, warm in workload:
            if warm:
                idx = shard_map.owner(g)
                ext = sharded.replicas[idx].app.extender
                for res in ext.predicate_batch(args_of(pods)):
                    assert res.ok
        per_replica = {0: [], 1: []}
        for g, pods in timed:
            per_replica[shard_map.owner(g)].append((g, pods))
        sharded_placed = {}
        placed_lock = threading.Lock()
        errors = []

        def serve(idx):
            try:
                ext = sharded.replicas[idx].app.extender
                for g, pods in per_replica[idx]:
                    results = ext.predicate_batch(args_of(pods))
                    with placed_lock:
                        for p, res in zip(pods, results):
                            assert res.ok, (g, p.name, res.outcome)
                            sharded_placed[p.name] = res.node_names[0]
            except Exception as exc:  # surfaced after join
                errors.append(exc)

        threads = [
            threading.Thread(target=serve, args=(i,)) for i in (0, 1)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        sharded_s = time.perf_counter() - t0
        assert not errors, errors
        forwarded = sharded.forwarded
        sharded.stop()
    # Byte-identical per group: every driver landed on the same node.
    assert sharded_placed == control_placed, {
        k: (control_placed.get(k), sharded_placed.get(k))
        for k in set(control_placed) | set(sharded_placed)
        if control_placed.get(k) != sharded_placed.get(k)
    }
    decisions = len(control_placed)
    return {
        "single_replica_dps": round(decisions / single_s, 1),
        "sharded_2replica_dps": round(decisions / sharded_s, 1),
        "speedup": round(single_s / sharded_s, 2),
        "decisions": decisions,
        "groups": N_GROUPS,
        "nodes": len(nodes),
        "rtt_ms": rtt_ms,
        "byte_identical_per_group": True,
        "forwarded": forwarded,
    }


def main() -> None:
    from spark_scheduler_tpu.server.config import InstallConfig

    InstallConfig.enable_jax_compile_cache()
    # Pure-CPU arm: informational on shared-core boxes.
    pure = sharded_arm(512, None)
    print(json.dumps({"arm": "pure_cpu", **pure}), flush=True)
    # 50 ms simulated device RTT per window — the
    # control serializes round trips, the shards overlap theirs. This arm
    # carries the >= 1.5x bar.
    rtt = sharded_arm(256, 50.0)
    print(json.dumps({"arm": "rtt50", **rtt}), flush=True)

    from spark_scheduler_tpu.testing.soak import HAChaosSoak

    soak = HAChaosSoak(strategy="tightly-pack", n_nodes=24, ttl_s=1.0)
    stats = soak.run(cycles=3, burst=6)
    print(json.dumps({"arm": "chaos", **stats}), flush=True)


if __name__ == "__main__":
    main()
