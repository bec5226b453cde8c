"""Device-slot failure recovery + degraded-mode serving (ISSUE 9).

Acceptance criteria pinned here:

  - 2-slot pool, one slot killed mid-burst: serving continues on the
    survivor with BYTE-IDENTICAL decisions (the re-dispatched partition
    re-solves from the host reconstruction the dead slot's base
    embodied), the dead slot is quarantined, and a later probe
    reinstates it;
  - ALL slots killed: the degraded policy engages — "greedy" keeps
    serving byte-identical decisions via the host fallback and recovers
    once a probe succeeds; "shed" raises DegradedUnavailableError
    carrying Retry-After;
  - the server reflects it: readiness stays 200-but-degraded under
    greedy, flips 503 under shed; /predicates sheds 503 with a
    Retry-After header; /debug/state carries quarantine + degraded
    state.

The conftest's 8-device virtual CPU mesh provides the pool slots.
"""

from __future__ import annotations

import http.client
import json

import numpy as np
import pytest

from spark_scheduler_tpu.core.solver import PlacementSolver, WindowRequest
from spark_scheduler_tpu.faults import (
    DegradedModeController,
    DegradedUnavailableError,
    FaultInjector,
    FaultPlan,
    FaultSpec,
)
from spark_scheduler_tpu.models.kube import Node, ZONE_LABEL
from spark_scheduler_tpu.models.resources import Resources

ONE = Resources.from_quantities("1", "1Gi")
TWO = Resources.from_quantities("2", "2Gi")


def _nodes(n):
    return [
        Node(
            name=f"n{i:03d}",
            allocatable=Resources.from_quantities(
                "8", "8Gi", "1", round_up=False
            ),
            labels={ZONE_LABEL: f"z{i % 2}"},
        )
        for i in range(n)
    ]


def _windows(rng, nodes, k, per, domains):
    """K windows of `per` requests, domains cycled per request so every
    window partitions across the pool (test_fused_dispatch idiom)."""
    windows = []
    r = 0
    for _ in range(k):
        reqs = []
        for _ in range(per):
            res = TWO if rng.random() < 0.3 else ONE
            dom = domains[r % len(domains)]
            reqs.append(
                WindowRequest(
                    rows=[(res, ONE, int(rng.integers(1, 4)), False)],
                    driver_candidate_names=dom,
                    domain_node_names=dom,
                )
            )
            r += 1
        windows.append(reqs)
    return windows


def _run(solver, nodes, batches, strategy="tightly-pack"):
    out = []
    for wins in batches:
        handles = []
        for w in wins:
            t = solver.build_tensors_pipelined(nodes, {}, {})
            handles.append(solver.pack_window_dispatch(strategy, t, w))
        for h in handles:
            out.extend(solver.pack_window_fetch(h))
    return out


def _fixture(seed=11, n_batches=3):
    rng = np.random.default_rng(seed)
    nodes = _nodes(16)
    half = [n.name for n in nodes[:8]], [n.name for n in nodes[8:]]
    batches = [_windows(rng, nodes, 1, 4, half) for _ in range(n_batches)]
    return nodes, batches


# -------------------------------------------------- one slot dies mid-burst


def test_slot_kill_mid_burst_byte_identical_on_survivor():
    nodes, batches = _fixture()
    baseline = _run(PlacementSolver(use_native=False), nodes, batches)

    pooled = PlacementSolver(use_native=False, device_pool=2)
    assert pooled.pool_size == 2
    # The 3rd partition solve dies (window 2's first part): device lost
    # mid-burst, classified slot-fatal via DeviceFaultError.
    plan = FaultPlan(
        seed=0, name="slot-kill",
        specs=[FaultSpec(surface="device.dispatch", mode="error",
                         at=[2], limit=1)],
    )
    with FaultInjector(plan) as inj:
        inj.install_device()
        faulted = _run(pooled, nodes, batches)

    assert faulted == baseline, "recovered decisions diverged"
    health = pooled.device_health()
    assert health["healthy"] == 1 and len(health["quarantined"]) == 1
    assert pooled.redispatch_count >= 1

    # Probe-based reinstatement: the injector is gone, so a forced probe
    # brings the slot back; the next burst runs pooled again and still
    # matches the single-device truth.
    assert pooled.probe_quarantined(force=True) == 1
    assert pooled.device_health()["healthy"] == 2
    rng = np.random.default_rng(99)
    half = [n.name for n in nodes[:8]], [n.name for n in nodes[8:]]
    more = [_windows(rng, nodes, 1, 4, half)]
    again = _run(PlacementSolver(use_native=False), nodes, more)
    assert _run(pooled, nodes, more) == again


# ------------------------------------------------------- every slot dies


def _open_ended_dispatch_kill(start):
    """From device-event `start` on, EVERY worker-side dispatch fails —
    both slots die, and probes keep failing until the injector leaves."""
    return FaultPlan(
        seed=0, name="pool-down",
        specs=[FaultSpec(surface="device.dispatch", mode="partition",
                         start=start)],
    )


def test_all_slots_killed_greedy_fallback_byte_identical_then_recovers():
    nodes, batches = _fixture(seed=23, n_batches=4)
    baseline = _run(PlacementSolver(use_native=False), nodes, batches)

    pooled = PlacementSolver(use_native=False, device_pool=2)
    pooled.degraded = DegradedModeController(policy="greedy")
    # Window 1 (2 partition dispatch events) succeeds; everything after
    # fails: window 2 quarantines both slots and serves via the host
    # greedy fallback, windows 3-4 fall back at the dispatch gate.
    with FaultInjector(_open_ended_dispatch_kill(2)) as inj:
        inj.install_device()
        faulted = _run(pooled, nodes, batches)

    assert faulted == baseline, "degraded decisions diverged"
    health = pooled.device_health()
    assert health["healthy"] == 0 and len(health["quarantined"]) == 2
    snap = pooled.degraded.snapshot()
    assert snap["active"] and snap["fallback_decisions"] > 0

    # Probes succeed once the fault plan is gone: slots reinstate,
    # degraded clears, and the pool serves again byte-identically.
    assert pooled.probe_quarantined(force=True) == 2
    assert not pooled.degraded.active
    rng = np.random.default_rng(7)
    half = [n.name for n in nodes[:8]], [n.name for n in nodes[8:]]
    more = [_windows(rng, nodes, 1, 4, half)]
    assert _run(pooled, nodes, more) == _run(
        PlacementSolver(use_native=False), nodes, more
    )


def test_all_slots_killed_shed_policy_raises_retry_after():
    nodes, batches = _fixture(seed=31, n_batches=1)
    pooled = PlacementSolver(use_native=False, device_pool=2)
    pooled.degraded = DegradedModeController(
        policy="shed", retry_after_s=7.0
    )
    with FaultInjector(_open_ended_dispatch_kill(0)) as inj:
        inj.install_device()
        with pytest.raises(DegradedUnavailableError) as ei:
            _run(pooled, nodes, batches)
    assert ei.value.retry_after_s == 7.0
    snap = pooled.degraded.snapshot()
    assert snap["active"] and snap["shed_requests"] >= 1


# ------------------------------------------------------------ server level


def _boot_server(degraded_mode):
    from spark_scheduler_tpu.metrics import MetricRegistry, SchedulerMetrics
    from spark_scheduler_tpu.server.app import build_scheduler_app
    from spark_scheduler_tpu.server.config import InstallConfig
    from spark_scheduler_tpu.server.http import SchedulerHTTPServer
    from spark_scheduler_tpu.store.backend import InMemoryBackend
    from spark_scheduler_tpu.testing.harness import (
        INSTANCE_GROUP_LABEL,
        new_node,
    )

    backend = InMemoryBackend()
    for i in range(6):
        backend.add_node(new_node(f"srv-n{i}", zone=f"zone{i % 2}"))
    registry = MetricRegistry()
    app = build_scheduler_app(
        backend,
        InstallConfig(
            fifo=True,
            sync_writes=True,
            instance_group_label=INSTANCE_GROUP_LABEL,
            degraded_mode=degraded_mode,
            degraded_retry_after_s=9.0,
            debug_routes=True,
        ),
        metrics=SchedulerMetrics(registry, INSTANCE_GROUP_LABEL),
    )
    server = SchedulerHTTPServer(
        app, registry, host="127.0.0.1", port=0, debug_routes=True,
        request_timeout_s=60.0,
    )
    server.start()
    return backend, app, server


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", path)
    r = conn.getresponse()
    body = r.read()
    conn.close()
    return r.status, dict(r.getheaders()), body


def _predicate(port, backend, app_id):
    from spark_scheduler_tpu.server.kube_io import pod_to_k8s
    from spark_scheduler_tpu.testing.harness import (
        static_allocation_spark_pods,
    )

    pod = static_allocation_spark_pods(app_id, 1)[0]
    backend.add_pod(pod)
    payload = json.dumps(
        {
            "Pod": pod_to_k8s(pod),
            "NodeNames": [n.name for n in backend.list_nodes()],
        }
    )
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request(
        "POST", "/predicates", body=payload,
        headers={"Content-Type": "application/json"},
    )
    r = conn.getresponse()
    body = r.read()
    conn.close()
    return r.status, dict(r.getheaders()), body


def test_server_greedy_degraded_keeps_serving_and_reports():
    backend, app, server = _boot_server("greedy")
    try:
        plan = FaultPlan(
            seed=0, name="server-down",
            specs=[FaultSpec(surface="device.h2d", mode="partition",
                             start=0)],
        )
        with FaultInjector(plan) as inj:
            inj.install_device()
            status, _, body = _predicate(server.port, backend, "deg-app")
            assert status == 200
            out = json.loads(body)
            assert out.get("NodeNames"), out  # fallback still decides
            status, _, body = _get(server.port, "/status/readiness")
            assert status == 200
            ready = json.loads(body)
            assert ready["degraded"] and ready["policy"] == "greedy"
            status, _, body = _get(server.port, "/debug/state")
            assert status == 200
            faults = json.loads(body)["faults"]
            assert faults["degraded"]["active"]
        # Fault plan gone: the next served window clears degraded.
        status, _, body = _predicate(server.port, backend, "deg-app-2")
        assert status == 200
        status, _, body = _get(server.port, "/status/readiness")
        assert status == 200
        assert "degraded" not in json.loads(body)
    finally:
        server.stop()


def test_server_shed_degraded_readiness_flips_503_under_ha():
    """Degraded mode composes with HA readiness: a SERVING leader that
    sheds every predicate must answer readiness 503 too — the HA branch
    answering 200 {ready, role} first would keep the load balancer
    routing to a replica that 503s every request."""
    from spark_scheduler_tpu.metrics import MetricRegistry, SchedulerMetrics
    from spark_scheduler_tpu.server.app import build_scheduler_app
    from spark_scheduler_tpu.server.config import InstallConfig
    from spark_scheduler_tpu.server.http import SchedulerHTTPServer
    from spark_scheduler_tpu.store.backend import InMemoryBackend
    from spark_scheduler_tpu.testing.harness import (
        INSTANCE_GROUP_LABEL,
        new_node,
    )

    class _ServingHA:
        role = "leader"

        def is_serving(self):
            return True

        def state(self):
            return {"role": self.role}

        def start(self):
            pass

        def stop(self):
            pass

    backend = InMemoryBackend()
    for i in range(4):
        backend.add_node(new_node(f"ha-n{i}", zone=f"zone{i % 2}"))
    registry = MetricRegistry()
    app = build_scheduler_app(
        backend,
        InstallConfig(
            fifo=True, sync_writes=True,
            instance_group_label=INSTANCE_GROUP_LABEL,
            degraded_mode="shed", degraded_retry_after_s=9.0,
            debug_routes=True,
        ),
        metrics=SchedulerMetrics(registry, INSTANCE_GROUP_LABEL),
    )
    server = SchedulerHTTPServer(
        app, registry, host="127.0.0.1", port=0, debug_routes=True,
        request_timeout_s=60.0, ha=_ServingHA(),
    )
    server.start()
    try:
        # Healthy serving leader: 200 with the role.
        status, _, body = _get(server.port, "/status/readiness")
        assert status == 200
        out = json.loads(body)
        assert out["ready"] and out["role"] == "leader"
        plan = FaultPlan(
            seed=0, name="ha-shed",
            specs=[FaultSpec(surface="device.h2d", mode="partition",
                             start=0)],
        )
        with FaultInjector(plan) as inj:
            inj.install_device()
            status, headers, _ = _predicate(server.port, backend, "ha-shed-app")
            assert status == 503
            status, _, body = _get(server.port, "/status/readiness")
            assert status == 503
            out = json.loads(body)
            assert out["degraded"] and out["policy"] == "shed"
            assert out["role"] == "leader"  # HA fields still present
    finally:
        server.stop()


def test_server_shed_degraded_503_retry_after_and_readiness():
    backend, app, server = _boot_server("shed")
    try:
        plan = FaultPlan(
            seed=0, name="server-shed",
            specs=[FaultSpec(surface="device.h2d", mode="partition",
                             start=0)],
        )
        with FaultInjector(plan) as inj:
            inj.install_device()
            status, headers, body = _predicate(
                server.port, backend, "shed-app"
            )
            assert status == 503
            assert headers.get("Retry-After") == "9"
            assert json.loads(body)["degraded"] is True
            status, _, body = _get(server.port, "/status/readiness")
            assert status == 503
            assert json.loads(body)["degraded"] is True
    finally:
        server.stop()
