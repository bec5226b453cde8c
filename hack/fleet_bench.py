"""Fleet scaling bench: F=4 concurrent per-cluster stacks vs ONE cluster
serving the same total load behind one pipeline — plus the fused-dispatch
A/B (stacked vs unstacked fleet, ISSUE 20).

The acceptance bar (ISSUE 19): at F=4 clusters on a >=4-slot pool rig,
aggregate decisions/s >= 3x the single-cluster control — concurrent
per-cluster solves, not round-robin serialization — AND per-cluster
decisions byte-identical to a standalone cluster replaying the same op
stream. Both are asserted IN-ARM: a run that fails either raises.

The device round trip is simulated (testing/rtt_shim.SimulatedRTT, the
fused-dispatch precedent): each window pays a sleeping RTT on the thread
that would pay it on a real device, and sleeps overlap across the
fleet's per-cluster worker threads exactly as the per-device RPCs would.
On this 2-core CPU rig the XLA solve itself is ~ms and partially
serializes on the shared CPU backend; the RTT is what scales, which is
honest to a deployment where the device round trip dominates.

The STACKED section (ISSUE 20 bar: >=1.5x at F=4 / 40 ms) runs both its
arms under `link_serialized=True` — one shared device link, where F
concurrent per-cluster round trips queue instead of overlapping. That is
the regime the fused fleet dispatch exists for: the unstacked fleet pays
F serialized RTTs per round of windows, the stacked fleet gathers them
into ONE `bucket_stacked_fifo_pack` launch and pays one. Arms INTERLEAVE
(off, on, off, on) over the same offered-load trace after a shared
untimed warm round per mode, so neither mode inherits the other's
compile warmup, and the reported rate is the mean of its reps. Asserted
in-arm: speedup >= --min-stack-speedup, stacked_dispatches > 0,
forced_resolves == 0, and per-cluster byte-identity
(verify_cluster_equivalence) in the same run.

Emits one JSON line per arm (fleet serving lines carry
stacked_dispatches/stack_arms) and a final summary line per section.
"""

import os

# A >=4-slot pool rig, forced before jax initializes (CPU container).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse
import json
import sys
import threading
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

CLUSTERS = 4
APPS_PER_CLUSTER = 5
EXECUTORS = 2  # gang = driver + 2 executors -> 3 decisions per app


def _emit(entry):
    print(json.dumps(entry), flush=True)


def build_apps(cluster, n_apps):
    from spark_scheduler_tpu.testing.harness import (
        static_allocation_spark_pods,
    )

    return [
        static_allocation_spark_pods(
            f"fleet-app-c{cluster}-{k}", EXECUTORS,
            instance_group=f"ig-{cluster}",
        )
        for k in range(n_apps)
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clusters", type=int, default=CLUSTERS)
    ap.add_argument("--apps-per-cluster", type=int, default=APPS_PER_CLUSTER)
    ap.add_argument("--rtt-ms", type=float, default=40.0)
    ap.add_argument("--nodes-per-cluster", type=int, default=8)
    ap.add_argument("--min-speedup", type=float, default=3.0)
    ap.add_argument(
        "--stack-window-ms",
        type=float,
        default=120.0,
        help="gather window for the stacked arms (fleet.stack-window-ms)",
    )
    ap.add_argument("--min-stack-speedup", type=float, default=1.5)
    ap.add_argument(
        "--stack-reps",
        type=int,
        default=2,
        help="interleaved reps per stacked-section mode (off/on pairs)",
    )
    ap.add_argument(
        "--skip-stacked",
        action="store_true",
        help="run only the ISSUE 19 scaling section",
    )
    args = ap.parse_args()

    import jax

    from spark_scheduler_tpu.fleet import (
        ClusterStack,
        FleetFacade,
        verify_cluster_equivalence,
    )
    from spark_scheduler_tpu.server.config import InstallConfig
    from spark_scheduler_tpu.testing.harness import (
        INSTANCE_GROUP_LABEL,
        new_node,
    )
    from spark_scheduler_tpu.testing.rtt_shim import SimulatedRTT
    from spark_scheduler_tpu.tracing import Svc1Logger, set_svc1log

    set_svc1log(Svc1Logger(stream=open(os.devnull, "w")))

    n_devices = len(jax.devices())
    F = args.clusters
    cfg = InstallConfig(
        fifo=True, sync_writes=True,
        instance_group_label=INSTANCE_GROUP_LABEL,
    )
    decisions_per_app = 1 + EXECUTORS
    total_apps = F * args.apps_per_cluster
    total_decisions = total_apps * decisions_per_app

    # --- warm the kernels OUTSIDE the timed arms, for BOTH arms' window
    # shapes (the control's consolidated cluster pads to a different
    # bucket than a fleet cluster — an unwarmed control would pay its
    # first-compiles inside the wall clock and flatter the fleet arm).
    for n_nodes, tag in (
        (F * args.nodes_per_cluster, "warm-big"),
        (args.nodes_per_cluster, "warm-small"),
    ):
        warm = ClusterStack(0, cfg, threaded=False)
        for i in range(n_nodes):
            warm.add_node(
                new_node(f"{tag}-n{i}", instance_group=f"ig-{i % F}")
            )
        for c in range(F):
            for pods in build_apps(c, 1):
                for p in pods:
                    warm.schedule(p)
        warm.stop()

    # --- control arm: ONE cluster, all nodes, the whole load through one
    # pipeline (the serialization baseline the facade removes).
    control = ClusterStack(0, cfg, threaded=False, record_ops=False)
    for c in range(F):
        for i in range(args.nodes_per_cluster):
            control.add_node(
                new_node(f"c{c}-n{i}", instance_group=f"ig-{c}")
            )
    control_apps = [
        pods
        for c in range(F)
        for pods in build_apps(c, args.apps_per_cluster)
    ]
    with SimulatedRTT(args.rtt_ms):
        t0 = time.perf_counter()
        for pods in control_apps:
            for p in pods:
                r = control.schedule(p)
                assert r.ok, f"control denial: {r.outcome}"
        control_wall = time.perf_counter() - t0
    control.stop()
    control_rate = total_decisions / control_wall
    _emit({
        "metric": "fleet_decisions_per_s_single_cluster",
        "value": round(control_rate, 1),
        "unit": "decisions/s",
        "vs_baseline": 1.0,
        "clusters": 1,
        "spillovers": 0,
        "detail": {
            "decisions": total_decisions,
            "wall_s": round(control_wall, 3),
            "rtt_ms": args.rtt_ms,
            "devices": n_devices,
        },
    })

    # --- fleet arm: F stacks, same total load, one client thread per
    # cluster (kube-scheduler fans out across cluster endpoints), every
    # cluster's op stream recorded for the in-arm equivalence check.
    facade = FleetFacade(F, cfg, record_ops=True)
    for c in range(F):
        for i in range(args.nodes_per_cluster):
            facade.add_node(
                c, new_node(f"c{c}-n{i}", instance_group=f"ig-{c}")
            )
    fleet_apps = {
        c: build_apps(c, args.apps_per_cluster) for c in range(F)
    }
    errors = []

    def pump(c):
        try:
            for pods in fleet_apps[c]:
                for p in pods:
                    d = facade.schedule(p, via=c)
                    assert d.ok, (
                        f"fleet denial c{c}: {d.result.outcome}"
                    )
        except Exception as exc:  # surfaced after join
            errors.append(exc)

    with SimulatedRTT(args.rtt_ms):
        threads = [
            threading.Thread(target=pump, args=(c,)) for c in range(F)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        fleet_wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    fleet_rate = total_decisions / fleet_wall
    speedup = fleet_rate / control_rate

    # In-arm assertion #1: concurrency actually scaled throughput.
    assert speedup >= args.min_speedup, (
        f"fleet scaling below bar: {speedup:.2f}x < {args.min_speedup}x "
        f"(fleet {fleet_rate:.1f}/s vs single {control_rate:.1f}/s)"
    )
    # In-arm assertion #2: every cluster's decisions byte-identical to a
    # standalone cluster replaying the same op stream.
    equivalence = verify_cluster_equivalence(facade)

    st = facade.state()
    stacking = st.get("stacking", {})
    _emit({
        "metric": f"fleet_decisions_per_s_{F}_clusters",
        "value": round(fleet_rate, 1),
        "unit": "decisions/s",
        # vs_baseline = speedup / 3: >= 1.0 clears the acceptance bar.
        "vs_baseline": round(speedup / args.min_speedup, 2),
        "clusters": F,
        "spillovers": st["spillover"]["spilled"],
        "stacked_dispatches": stacking.get("stacked_dispatches", 0),
        "stack_arms": stacking.get("stack_arms", 0),
        "detail": {
            "decisions": total_decisions,
            "wall_s": round(fleet_wall, 3),
            "speedup_vs_single": round(speedup, 2),
            "rtt_ms": args.rtt_ms,
            "devices": n_devices,
            "byte_identical_clusters": len(equivalence),
            "router_picks": st["router"]["picks"],
            "forwarded": st["forwarded"],
        },
    })
    facade.stop()
    _emit({
        "metric": "fleet_scaling_summary",
        "value": round(speedup, 2),
        "unit": "x",
        "vs_baseline": round(speedup / args.min_speedup, 2),
        "clusters": F,
        "spillovers": st["spillover"]["spilled"],
        "stacked_dispatches": stacking.get("stacked_dispatches", 0),
        "stack_arms": stacking.get("stack_arms", 0),
        "detail": {
            "single_cluster_decisions_per_s": round(control_rate, 1),
            "fleet_decisions_per_s": round(fleet_rate, 1),
            "equivalence": {str(k): v for k, v in equivalence.items()},
        },
    })

    if not args.skip_stacked:
        run_stacked_section(args, cfg)


def run_stacked_section(args, cfg):
    """ISSUE 20 A/B: stacked vs unstacked fleet over ONE shared device
    link (link_serialized RTT), interleaved arms on the same offered
    load. See the module docstring for the protocol."""
    import statistics

    from spark_scheduler_tpu.fleet import (
        FleetFacade,
        verify_cluster_equivalence,
    )
    from spark_scheduler_tpu.testing.harness import new_node
    from spark_scheduler_tpu.testing.rtt_shim import SimulatedRTT

    F = args.clusters

    def run_arm(stack_ms, rep):
        """One arm: fresh facade, the SAME offered-load trace (identical
        per-cluster app streams), one pump thread per cluster. The warm
        round (rep < 0) runs WITHOUT the RTT shim so first-compiles of
        this mode's window shapes land outside every timed rep."""
        facade = FleetFacade(
            F, cfg, record_ops=True, stack_window_ms=stack_ms
        )
        for c in range(F):
            for i in range(args.nodes_per_cluster):
                facade.add_node(
                    c, new_node(f"c{c}-n{i}", instance_group=f"ig-{c}")
                )
        errors = []

        def pump(c, tag, n_apps):
            try:
                from spark_scheduler_tpu.testing.harness import (
                    static_allocation_spark_pods,
                )

                for k in range(n_apps):
                    pods = static_allocation_spark_pods(
                        f"{tag}-c{c}-{k}", EXECUTORS,
                        instance_group=f"ig-{c}",
                    )
                    for p in pods:
                        d = facade.schedule(p, via=c)
                        assert d.ok, (
                            f"stacked-section denial c{c}: "
                            f"{d.result.outcome}"
                        )
            except Exception as exc:  # surfaced after join
                errors.append(exc)

        def drive(tag, n_apps):
            threads = [
                threading.Thread(target=pump, args=(c, tag, n_apps))
                for c in range(F)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return time.perf_counter() - t0

        # Untimed warm round: compiles (incl. the stacked kernel's
        # [M, B, N] shapes when stacking is on) happen here.
        drive("warm", 1)
        with SimulatedRTT(args.rtt_ms, link_serialized=True):
            wall = drive(f"rep{rep}", args.apps_per_cluster)
        if errors:
            raise errors[0]
        decisions = F * args.apps_per_cluster * (1 + EXECUTORS)
        stacking = facade.state().get("stacking", {})
        equivalence = verify_cluster_equivalence(facade)
        facade.stop()
        return decisions / wall, stacking, equivalence

    # Interleave off/on so neither mode systematically inherits cache or
    # rig warm-up from the other.
    rates = {"off": [], "on": []}
    last = {}
    for rep in range(args.stack_reps):
        for mode, stack_ms in (
            ("off", 0.0),
            ("on", args.stack_window_ms),
        ):
            rate, stacking, equivalence = run_arm(stack_ms, rep)
            rates[mode].append(rate)
            last[mode] = (stacking, equivalence)
    off_rate = statistics.mean(rates["off"])
    on_rate = statistics.mean(rates["on"])
    speedup = on_rate / off_rate
    stacking, equivalence = last["on"]

    # In-arm assertion #1: fused launches beat per-cluster launches on
    # the shared link by the acceptance bar.
    assert speedup >= args.min_stack_speedup, (
        f"stacked fleet below bar: {speedup:.2f}x < "
        f"{args.min_stack_speedup}x (stacked {on_rate:.1f}/s vs "
        f"unstacked {off_rate:.1f}/s)"
    )
    # In-arm assertion #2: stacking actually happened, and nothing was
    # force-resolved in steady state.
    assert stacking.get("stacked_dispatches", 0) > 0, (
        f"no stacked dispatches fired: {stacking}"
    )
    assert stacking.get("forced_resolves", 0) == 0, (
        f"forced resolves in steady state: {stacking}"
    )
    # In-arm assertion #3 ran inside run_arm for EVERY stacked rep:
    # verify_cluster_equivalence (stacked == standalone unstacked replay).

    for mode, rate in (("unstacked", off_rate), ("stacked", on_rate)):
        st_line = last["on" if mode == "stacked" else "off"][0]
        _emit({
            "metric": f"fleet_{mode}_serialized_decisions_per_s",
            "value": round(rate, 1),
            "unit": "decisions/s",
            "vs_baseline": 1.0 if mode == "unstacked" else round(
                speedup / args.min_stack_speedup, 2
            ),
            "clusters": F,
            "spillovers": 0,
            "stacked_dispatches": st_line.get("stacked_dispatches", 0),
            "stack_arms": st_line.get("stack_arms", 0),
            "detail": {
                "rtt_ms": args.rtt_ms,
                "link_serialized": True,
                "stack_window_ms": (
                    0.0 if mode == "unstacked" else args.stack_window_ms
                ),
                "reps": rates["off" if mode == "unstacked" else "on"],
            },
        })
    _emit({
        "metric": "fleet_stacking_summary",
        "value": round(speedup, 2),
        "unit": "x",
        "vs_baseline": round(speedup / args.min_stack_speedup, 2),
        "clusters": F,
        "spillovers": 0,
        "stacked_dispatches": stacking.get("stacked_dispatches", 0),
        "stack_arms": stacking.get("stack_arms", 0),
        "detail": {
            "unstacked_decisions_per_s": round(off_rate, 1),
            "stacked_decisions_per_s": round(on_rate, 1),
            "rtt_ms": args.rtt_ms,
            "stack_window_ms": args.stack_window_ms,
            "fallbacks": stacking.get("fallbacks", 0),
            "forced_resolves": stacking.get("forced_resolves", 0),
            "gather_wait_ms": stacking.get("gather_wait_ms", 0.0),
            "equivalence": {str(k): v for k, v in equivalence.items()},
        },
    })


if __name__ == "__main__":
    main()
