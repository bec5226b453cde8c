"""Unit suite for the fault-tolerance subsystem (ISSUE 9).

Pins the exact contracts the rest of the repo builds on: RetryPolicy's
backoff sequence / jitter bounds / deadline abort / per-attempt timeout,
the circuit breaker's closed -> open -> half-open -> closed discipline,
FaultInjector determinism (same seed => same schedule) and its adapter
seams (backend hook compat, nested install/uninstall, lease wrapper),
the degraded-mode controller, the slot-failure classifier, and the
`async_client_retry_count` back-compat alias.
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time

import pytest

from spark_scheduler_tpu.faults import (
    AttemptTimeoutError,
    BreakerOpenError,
    CircuitBreaker,
    DegradedModeController,
    DeviceFaultError,
    FaultInjector,
    FaultPlan,
    FaultSpec,
    FaultyLeaseStore,
    InjectedFault,
    RetryDeadlineExceeded,
    RetryPolicy,
    classify_slot_failure,
)
from spark_scheduler_tpu.faults.retry import CLOSED, HALF_OPEN, OPEN


# ---------------------------------------------------------------- RetryPolicy


def test_backoff_sequence_exponential_and_capped():
    p = RetryPolicy(base_delay_s=0.1, multiplier=2.0, max_delay_s=1.0)
    assert [p.backoff(i) for i in range(6)] == [
        0.1, 0.2, 0.4, 0.8, 1.0, 1.0
    ]


def test_full_jitter_bounds_and_determinism():
    p = RetryPolicy(base_delay_s=0.5, multiplier=2.0, max_delay_s=8.0)
    draws = [p.delay(i, random.Random(7)) for i in range(20) for _ in range(5)]
    for i in range(20):
        for d in draws[i * 5:(i + 1) * 5]:
            assert 0.0 <= d <= p.backoff(i)
    # Seeded rng => reproducible jitter (the chaos matrix relies on it).
    rng_a, rng_b = random.Random(11), random.Random(11)
    assert [p.delay(i, rng_a) for i in range(10)] == [
        p.delay(i, rng_b) for i in range(10)
    ]


def test_no_jitter_is_deterministic_backoff():
    p = RetryPolicy(jitter="none", base_delay_s=0.25, multiplier=3.0,
                    max_delay_s=10.0)
    assert p.delay(0) == 0.25
    assert p.delay(1) == 0.75
    assert p.delay(2) == 2.25


def test_call_retries_then_succeeds_with_recorded_pauses():
    p = RetryPolicy(max_attempts=5, base_delay_s=0.1, multiplier=2.0,
                    max_delay_s=10.0, jitter="none")
    attempts = {"n": 0}
    pauses: list[float] = []
    retries: list[tuple[int, float]] = []

    def flaky():
        attempts["n"] += 1
        if attempts["n"] < 4:
            raise ValueError(f"boom {attempts['n']}")
        return "ok"

    out = p.call(
        flaky,
        sleep=pauses.append,
        on_retry=lambda n, exc, pause: retries.append((n, pause)),
    )
    assert out == "ok"
    assert attempts["n"] == 4
    assert pauses == [0.1, 0.2, 0.4]  # exact deterministic ladder
    assert retries == [(1, 0.1), (2, 0.2), (3, 0.4)]


def test_call_exhausts_attempts_and_raises_last_error():
    p = RetryPolicy(max_attempts=3, base_delay_s=0.0, jitter="none")
    attempts = {"n": 0}

    def always():
        attempts["n"] += 1
        raise ValueError(f"boom {attempts['n']}")

    with pytest.raises(ValueError, match="boom 3"):
        p.call(always, sleep=lambda s: None)
    assert attempts["n"] == 3  # max_attempts counts TOTAL tries


def test_call_retry_on_filters_exception_types():
    p = RetryPolicy(max_attempts=5, base_delay_s=0.0, jitter="none")

    def wrong_type():
        raise KeyError("not retryable")

    with pytest.raises(KeyError):
        p.call(wrong_type, retry_on=(ValueError,), sleep=lambda s: None)


def test_deadline_aborts_between_attempts_and_chains_cause():
    # Virtual clock: each attempt "takes" 1s; deadline 2.5s => the third
    # retry pause would cross it.
    now = {"t": 0.0}

    def clock():
        return now["t"]

    def sleep(s):
        now["t"] += s

    def failing():
        now["t"] += 1.0
        raise ConnectionError("down")

    p = RetryPolicy(max_attempts=None, base_delay_s=0.5, multiplier=1.0,
                    max_delay_s=0.5, jitter="none", deadline_s=2.5)
    with pytest.raises(RetryDeadlineExceeded) as ei:
        p.call(failing, clock=clock, sleep=sleep)
    assert isinstance(ei.value.__cause__, ConnectionError)
    # Never slept past the deadline: the abort happens BEFORE the pause.
    assert now["t"] <= 2.5 + 1.0


def test_attempt_timeout_abandons_and_retries():
    p = RetryPolicy(max_attempts=2, base_delay_s=0.0, jitter="none",
                    attempt_timeout_s=0.05)
    release = threading.Event()
    calls = {"n": 0}

    def slow_then_fast():
        calls["n"] += 1
        if calls["n"] == 1:
            release.wait(5.0)  # hangs well past the per-attempt timeout
            return "late"
        return "fast"

    try:
        assert p.call(slow_then_fast, sleep=lambda s: None) == "fast"
    finally:
        release.set()
    assert calls["n"] == 2


def test_attempt_timeout_exhaustion_raises_attempt_timeout_error():
    p = RetryPolicy(max_attempts=1, attempt_timeout_s=0.02)
    release = threading.Event()
    try:
        with pytest.raises(AttemptTimeoutError):
            p.call(lambda: release.wait(5.0), sleep=lambda s: None)
    finally:
        release.set()


def test_unbounded_attempts_keep_retrying():
    p = RetryPolicy(max_attempts=None, base_delay_s=0.0, jitter="none")
    attempts = {"n": 0}

    def eventually():
        attempts["n"] += 1
        if attempts["n"] < 50:
            raise OSError("flap")
        return attempts["n"]

    assert p.call(eventually, sleep=lambda s: None) == 50


# ------------------------------------------------------------ CircuitBreaker


def _clocked_breaker(threshold=3, reset=10.0):
    now = {"t": 0.0}
    transitions: list[tuple[str, str]] = []
    b = CircuitBreaker(
        failure_threshold=threshold,
        reset_timeout_s=reset,
        clock=lambda: now["t"],
        on_transition=lambda old, new: transitions.append((old, new)),
        name="test",
    )
    return b, now, transitions


def test_breaker_opens_at_threshold_and_refuses():
    b, now, transitions = _clocked_breaker(threshold=3)
    for _ in range(2):
        assert b.allow()
        b.on_failure()
    assert b.state == CLOSED
    assert b.allow()
    b.on_failure()  # third consecutive failure
    assert b.state == OPEN
    assert not b.allow()
    assert transitions == [(CLOSED, OPEN)]
    assert b.opens == 1


def test_breaker_half_open_probe_success_closes():
    b, now, transitions = _clocked_breaker(threshold=1, reset=10.0)
    b.on_failure()
    assert b.state == OPEN and not b.allow()
    now["t"] = 10.0  # reset window elapsed
    assert b.allow()  # the half-open probe slot
    assert b.state == HALF_OPEN
    assert not b.allow()  # exactly ONE probe at a time
    b.on_success()
    assert b.state == CLOSED
    assert b.allow()
    assert transitions == [(CLOSED, OPEN), (OPEN, HALF_OPEN),
                           (HALF_OPEN, CLOSED)]


def test_breaker_half_open_probe_failure_reopens_and_rearms():
    b, now, _ = _clocked_breaker(threshold=1, reset=5.0)
    b.on_failure()
    now["t"] = 5.0
    assert b.allow()
    b.on_failure()  # the probe failed
    assert b.state == OPEN
    assert not b.allow()  # window re-armed from the re-open
    now["t"] = 10.0
    assert b.allow()  # next probe window
    assert b.opens == 2


def test_breaker_success_resets_failure_streak():
    b, _, _ = _clocked_breaker(threshold=3)
    b.on_failure()
    b.on_failure()
    b.on_success()
    b.on_failure()
    b.on_failure()
    assert b.state == CLOSED  # streak restarted after the success


def test_policy_call_with_breaker_raises_breaker_open():
    b, now, _ = _clocked_breaker(threshold=2, reset=30.0)
    p = RetryPolicy(max_attempts=5, base_delay_s=0.0, jitter="none")
    calls = {"n": 0}

    def failing():
        calls["n"] += 1
        raise ConnectionError("down")

    # The ladder feeds the breaker; once it opens mid-ladder the next
    # attempt is refused without touching fn.
    with pytest.raises(BreakerOpenError):
        p.call(failing, breaker=b, sleep=lambda s: None)
    assert calls["n"] == 2  # threshold, not the full attempt budget
    assert b.state == OPEN


# -------------------------------------------------------------- FaultInjector


def _plan(seed=1, **spec_kw):
    return FaultPlan(seed=seed, specs=[FaultSpec(**spec_kw)])


def test_injector_same_seed_same_schedule():
    plan = FaultPlan(
        seed=42,
        name="replay",
        specs=[
            FaultSpec(surface="backend.*", mode="error", p=0.3),
            FaultSpec(surface="device.*", mode="error", p=0.5),
        ],
    )
    surfaces = (
        ["backend.resourcereservations.create"] * 10
        + ["device.dispatch"] * 10
        + ["backend.demands.update"] * 10
    )

    def run():
        inj = FaultInjector(plan)
        for s in surfaces:
            try:
                inj.fire(s)
            except InjectedFault:
                pass
        return inj.schedule()

    first, second = run(), run()
    assert first == second
    assert first  # the plan actually fired something
    # A different seed moves the p-draws.
    other = FaultInjector(FaultPlan(seed=43, specs=plan.specs))
    for s in surfaces:
        try:
            other.fire(s)
        except InjectedFault:
            pass
    assert other.schedule() != first


def test_injector_at_every_limit_partition_triggers():
    at = FaultInjector(_plan(surface="a.*", at=[1, 3]))
    fired = []
    for i in range(5):
        try:
            at.fire("a.x")
        except InjectedFault:
            fired.append(i)
    assert fired == [1, 3]

    every = FaultInjector(_plan(surface="a.*", every=3, limit=2))
    fired = []
    for i in range(10):
        try:
            every.fire("a.x")
        except InjectedFault:
            fired.append(i)
    assert fired == [0, 3]  # every 3rd, capped by limit=2

    part = FaultInjector(_plan(surface="a.*", mode="partition", start=2,
                               length=3))
    fired = []
    for i in range(8):
        try:
            part.fire("a.x")
        except InjectedFault:
            fired.append(i)
    assert fired == [2, 3, 4]  # one contiguous outage window


def test_injector_latency_mode_sleeps_injected_duration():
    slept: list[float] = []
    inj = FaultInjector(
        _plan(surface="backend.*", mode="latency", latency_ms=25.0),
        sleep=slept.append,
    )
    inj.fire("backend.nodes.update")  # latency never raises
    assert slept == [0.025]
    assert inj.schedule()[0][3] == "latency"


def test_injector_device_surface_raises_slot_fatal():
    inj = FaultInjector(_plan(surface="device.*", limit=1))
    with pytest.raises(DeviceFaultError) as ei:
        inj.fire("device.d2h")
    assert classify_slot_failure(ei.value)
    # Non-device surfaces raise the plain InjectedFault.
    inj2 = FaultInjector(_plan(surface="wal.*", limit=1))
    with pytest.raises(InjectedFault) as ei2:
        inj2.fire("wal.append")
    assert not isinstance(ei2.value, DeviceFaultError)


def test_backend_hook_returns_exception_instead_of_raising():
    """The ad-hoc `backend.fault_injector` contract this subsumes: the
    hook RETURNS the exception (the backend raises it under its lock)."""
    inj = FaultInjector(_plan(surface="backend.resourcereservations.create",
                              limit=1))
    hook = inj.backend_hook()
    exc = hook("resourcereservations", "create", object())
    assert isinstance(exc, InjectedFault)
    assert hook("resourcereservations", "create", object()) is None  # limit
    assert hook("pods", "update", object()) is None  # surface mismatch


def test_install_backend_nests_and_uninstall_restores():
    class StubBackend:
        fault_injector = None

    b = StubBackend()
    prior_calls = []
    b.fault_injector = lambda *a: prior_calls.append(a) or None

    outer = FaultInjector(_plan(surface="backend.*", p=0.0))
    outer.install_backend(b)
    inner = FaultInjector(_plan(surface="backend.*", p=0.0))
    with inner:
        inner.install_backend(b)
        assert b.fault_injector is not None
        b.fault_injector("pods", "create", None)
        assert inner.counts.get("backend.pods.create") == 1
    # Inner uninstall hands the seam back to the OUTER injector.
    b.fault_injector("pods", "create", None)
    assert outer.counts.get("backend.pods.create") == 1
    outer.uninstall()
    # ... and outer hands it back to the original hook.
    b.fault_injector("pods", "create", None)
    assert len(prior_calls) == 1


def test_device_shim_composes_with_inner_and_uninstall_restores():
    from spark_scheduler_tpu.core import solver as solver_mod

    prior = solver_mod._DEVICE_SHIM
    inner_events: list[str] = []
    inj = FaultInjector(_plan(surface="device.dispatch", at=[0]))
    try:
        inj.install_device(inner=inner_events.append)
        with pytest.raises(DeviceFaultError):
            solver_mod._shim("dispatch")
        solver_mod._shim("h2d")  # surface mismatch: delegates only
        assert inner_events == ["h2d"]  # the raising fire skipped delegation
        assert inj.counts == {"device.dispatch": 1, "device.h2d": 1}
    finally:
        inj.uninstall()
    assert solver_mod._DEVICE_SHIM is prior


def test_faulty_lease_store_fires_lease_surfaces():
    class StubStore:
        def read(self):
            return "record"

        def compare_and_swap(self, expect, record):
            return True

    inj = FaultInjector(_plan(surface="lease.write", limit=1))
    store = FaultyLeaseStore(StubStore(), inj)
    assert store.read() == "record"
    with pytest.raises(InjectedFault):
        store.compare_and_swap(None, "r")
    assert store.compare_and_swap(None, "r")  # limit exhausted
    assert inj.counts == {"lease.read": 1, "lease.write": 2}


def test_plan_from_dict_round_trip():
    plan = FaultPlan.from_dict(
        {
            "seed": 9,
            "name": "matrix-backend",
            "specs": [
                {"surface": "backend.*", "mode": "latency",
                 "latency-ms": 5.0, "p": 0.2},
                {"surface": "wal.append", "at": [4]},
            ],
        }
    )
    assert plan.seed == 9 and plan.name == "matrix-backend"
    assert plan.specs[0].latency_ms == 5.0 and plan.specs[0].p == 0.2
    assert plan.specs[1].at == [4]


# ---------------------------------------------------------------- degraded


def test_degraded_controller_engage_clear_and_counts():
    now = {"t": 100.0}
    changes: list[bool] = []
    d = DegradedModeController(
        policy="greedy", clock=lambda: now["t"], on_change=changes.append
    )
    assert not d.active and not d.sheds
    d.engage("slot died")
    d.engage("slot died again")  # no double-count while active
    assert d.active and d.engagements == 1 and d.since == 100.0
    d.on_fallback_decision(3)
    d.clear()
    d.clear()
    assert not d.active
    assert changes == [True, False]
    snap = d.snapshot()
    assert snap["engagements"] == 1 and snap["fallback_decisions"] == 3
    assert snap["since"] is None


def test_degraded_controller_rejects_unknown_policy():
    with pytest.raises(ValueError, match="degraded-mode policy"):
        DegradedModeController(policy="panic")


def test_classify_slot_failure_taxonomy():
    import jax

    assert classify_slot_failure(DeviceFaultError("device.d2h"))
    assert classify_slot_failure(ConnectionError("connection reset"))
    assert classify_slot_failure(TimeoutError("rpc deadline"))
    assert classify_slot_failure(OSError("broken pipe"))
    assert classify_slot_failure(
        jax.errors.JaxRuntimeError("INTERNAL: device failed")
    )
    assert not classify_slot_failure(TypeError("programming error"))
    assert not classify_slot_failure(ValueError("bad shape"))
    assert not classify_slot_failure(InjectedFault("backend.pods.create"))


# ----------------------------------------------------- config + back-compat


def test_install_config_parses_retry_and_degraded_keys():
    from spark_scheduler_tpu.server.config import InstallConfig

    cfg = InstallConfig.from_dict(
        {
            "server": {
                "degraded-mode": "shed",
                "degraded-retry-after": "10s",
            },
            "solver": {"quarantine-probe": "2s"},
            "retry": {
                "base-delay": "50ms",
                "multiplier": 3.0,
                "max-delay": "4s",
                "breaker-failure-threshold": 4,
                "breaker-reset-timeout": "8s",
            },
            "async-client-retry-count": 7,
        }
    )
    assert cfg.degraded_mode == "shed"
    assert cfg.degraded_retry_after_s == 10.0
    assert cfg.quarantine_probe_s == 2.0
    assert cfg.retry_base_delay_s == 0.05
    assert cfg.retry_multiplier == 3.0
    assert cfg.retry_max_delay_s == 4.0
    assert cfg.breaker_failure_threshold == 4
    assert cfg.breaker_reset_timeout_s == 8.0
    assert cfg.async_client_retry_count == 7


def test_install_config_defaults_keep_greedy_policy():
    from spark_scheduler_tpu.server.config import InstallConfig

    cfg = InstallConfig.from_dict({})
    assert cfg.degraded_mode == "greedy"
    assert cfg.breaker_failure_threshold == 8


def test_async_client_retry_count_alias_still_bounds_requeues():
    """`async-client-retry-count` keeps working as the attempt budget:
    a write failing more than `count` times is dropped, exactly as
    before ISSUE 9 — the policy only supplies the DELAYS."""
    from spark_scheduler_tpu.models.reservations import (
        Reservation,
        ReservationSpec,
        ReservationStatus,
        ResourceReservation,
    )
    from spark_scheduler_tpu.models.resources import Resources
    from spark_scheduler_tpu.store.backend import InMemoryBackend
    from spark_scheduler_tpu.store.cache import ResourceReservationCache

    backend = InMemoryBackend()
    cache = ResourceReservationCache(
        backend, max_retries=2,
        retry_policy=RetryPolicy(base_delay_s=0.0, jitter="none"),
    )
    client = cache.client
    assert client._max_retries == 2
    dropped: list = []
    client._on_error = lambda req, exc: dropped.append((req, exc))
    rr = ResourceReservation(
        name="alias-app", namespace="ns", labels={}, owner_pod_uid="uid",
        spec=ReservationSpec(
            {"driver": Reservation("n0", Resources.from_quantities("1", "1Gi"))}
        ),
        status=ReservationStatus({"driver": "alias-app-driver"}),
    )
    # Every backend write fails: the request retries its bounded budget
    # then drops with the metric — never an unbounded loop.
    inj = FaultInjector(_plan(surface="backend.resourcereservations.*",
                              mode="error"))
    with inj:
        inj.install_backend(backend)
        cache.create(rr)
        client.drain_sync()
    m = client.metrics
    assert m.retries == 2  # exactly the alias budget
    assert m.dropped == 1  # then dropped — local store keeps the intent
    assert len(dropped) == 1
    assert backend.get("resourcereservations", "ns", "alias-app") is None
    # The injector gone, the same write path works again (the drop lost
    # this request only; nothing is wedged).
    rr2 = dataclasses.replace(rr, name="alias-app-2")
    cache.create(rr2)
    client.drain_sync()
    assert backend.get("resourcereservations", "ns", "alias-app-2") is not None


def _breaker_client(breaker):
    from spark_scheduler_tpu.store.backend import InMemoryBackend
    from spark_scheduler_tpu.store.cache import ResourceReservationCache

    backend = InMemoryBackend()
    cache = ResourceReservationCache(
        backend, max_retries=2,
        retry_policy=RetryPolicy(base_delay_s=0.0, jitter="none"),
        breaker=breaker,
    )
    return backend, cache, cache.client


def _reservation(name):
    from spark_scheduler_tpu.models.reservations import (
        Reservation,
        ReservationSpec,
        ReservationStatus,
        ResourceReservation,
    )
    from spark_scheduler_tpu.models.resources import Resources

    return ResourceReservation(
        name=name, namespace="ns", labels={}, owner_pod_uid="uid",
        spec=ReservationSpec(
            {"driver": Reservation("n0", Resources.from_quantities("1", "1Gi"))}
        ),
        status=ReservationStatus({"driver": f"{name}-driver"}),
    )


def _pop_one(client):
    for bucket in range(client._queue.num_buckets):
        req = client._queue.pop(bucket, timeout_s=0)
        if req is not None:
            return req
    return None


def test_breaker_refusal_requeues_without_consuming_budget():
    """A write refused by the OPEN breaker is the breaker's state, not the
    request's failure: it requeues with its retry budget INTACT (the 5-step
    ladder exhausts in well under reset_timeout, so consuming budget on
    refusals would drop every write queued while the breaker is open), and
    lands once the backend recovers."""
    b, now, _ = _clocked_breaker(threshold=1, reset=60.0)
    backend, cache, client = _breaker_client(b)
    b.on_failure()  # breaker OPEN
    assert b.state == OPEN
    cache.create(_reservation("refused-app"))
    # Background-worker path: the open breaker refuses, the request
    # requeues at the SAME retry_count, nothing drops.
    for _ in range(10):  # 10 refusals >> the 2-retry alias budget
        req = _pop_one(client)
        assert req is not None and req.retry_count == 0
        client.process(req, allow_backoff=True)
    assert client.metrics.dropped == 0
    assert backend.get("resourcereservations", "ns", "refused-app") is None
    # Reset window passes: the requeued write goes through and closes
    # the breaker — nothing was lost.
    now["t"] += 61.0
    req = _pop_one(client)
    client.process(req, allow_backoff=True)
    assert backend.get("resourcereservations", "ns", "refused-app") is not None
    assert b.state == CLOSED


def test_breaker_half_open_probe_freed_by_namespace_terminating():
    """NamespaceTerminatingError means the backend ANSWERED — a healthy
    dependency refusing one request. It must report success to the breaker:
    swallowing the outcome would leave the half-open probe slot taken
    forever, wedging every later write behind BreakerOpenError."""
    from spark_scheduler_tpu.store.backend import NamespaceTerminatingError

    b, now, _ = _clocked_breaker(threshold=1, reset=10.0)
    backend, cache, client = _breaker_client(b)
    b.on_failure()  # OPEN
    now["t"] += 11.0  # past the reset window: next allow() is the probe
    client.fault_hook = lambda req: (_ for _ in ()).throw(
        NamespaceTerminatingError("ns terminating")
    )
    cache.create(_reservation("terminating-app"))
    req = _pop_one(client)
    client.process(req, allow_backoff=True)
    # Dropped as non-retryable, AND the probe slot released: CLOSED.
    assert client.metrics.dropped == 1
    assert b.state == CLOSED
    client.fault_hook = None
    cache.create(_reservation("after-app"))
    req = _pop_one(client)
    client.process(req, allow_backoff=True)
    assert backend.get("resourcereservations", "ns", "after-app") is not None


def test_build_app_wires_retry_policy_from_config():
    from spark_scheduler_tpu.testing.harness import Harness

    h = Harness(
        binpack_algo="tightly-pack",
        fifo=False,
        async_client_retry_count=3,
        retry_base_delay_s=0.5,
        retry_multiplier=4.0,
        retry_max_delay_s=6.0,
        breaker_failure_threshold=2,
    )
    client = h.app.rr_cache.client
    p = client._retry_policy
    assert p.max_attempts == 4  # count + 1 (total tries)
    assert p.base_delay_s == 0.5 and p.multiplier == 4.0
    assert p.max_delay_s == 6.0
    assert client._breaker is not None
    assert client._breaker.failure_threshold == 2


def test_injector_on_fire_publishes_fault_telemetry():
    """FaultInjector.on_fire -> RetryTelemetry.fault_hook: every fired
    fault lands on foundry.spark.scheduler.faults.injected, tagged by
    surface and action."""
    from spark_scheduler_tpu.metrics import MetricRegistry
    from spark_scheduler_tpu.observability.telemetry import (
        FAULTS_INJECTED,
        RetryTelemetry,
    )

    registry = MetricRegistry()
    tel = RetryTelemetry(registry)
    inj = FaultInjector(
        _plan(surface="backend.*", limit=2), on_fire=tel.fault_hook()
    )
    for _ in range(3):
        try:
            inj.fire("backend.resourcereservations.create")
        except InjectedFault:
            pass
    counter = registry.counter(
        FAULTS_INJECTED,
        surface="backend.resourcereservations.create",
        action="error",
    )
    assert counter.value == 2  # limit capped the third fire


# ------------------------------------------- compile vs runtime device error


def _tiny_window_solver():
    from spark_scheduler_tpu.core.solver import PlacementSolver, WindowRequest
    from spark_scheduler_tpu.models.kube import Node
    from spark_scheduler_tpu.models.resources import Resources

    solver = PlacementSolver(use_native=False)
    solver.degraded = DegradedModeController(policy="greedy")
    nodes = [
        Node(name=f"n{i}", allocatable=Resources.from_quantities("8", "8Gi"))
        for i in range(12)
    ]
    tensors = solver.build_tensors(nodes, {}, {})
    one = Resources.from_quantities("1", "1Gi")
    requests = [
        WindowRequest(
            rows=[(one, one, 3, False)],
            driver_candidate_names=[n.name for n in nodes],
        )
    ]
    return solver, tensors, requests


def test_window_compile_failure_propagates_past_degraded_fallback(
    monkeypatch,
):
    """jax raises a refused Mosaic compile as the same JaxRuntimeError as a
    failed device. The window program compiles before the dispatch's
    slot-failure handler, so the compile error propagates and the greedy
    degraded fallback never serves the window."""
    import jax

    from spark_scheduler_tpu.ops import pallas_window as pw

    def refused_kernel(*args, **kwargs):
        raise jax.errors.JaxRuntimeError(
            "INTERNAL: Mosaic failed to compile TPU kernel"
        )

    monkeypatch.setattr(pw, "window_pallas_eligible", lambda fill, n: True)
    monkeypatch.setattr(pw, "window_pack_pallas", refused_kernel)
    solver, tensors, requests = _tiny_window_solver()
    with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
        solver.pack_window("tightly-pack", tensors, requests)
    assert "greedy-fallback" not in solver.window_path_counts
    assert solver.degraded.engagements == 0


def test_window_runtime_failure_serves_degraded(monkeypatch):
    """The same error class raised by the compiled program as it runs is a
    device failure: the window is served by the greedy fallback."""
    import jax

    from spark_scheduler_tpu.core.solver import PlacementSolver
    from spark_scheduler_tpu.ops import pallas_window as pw

    def failing_program(*args, **kwargs):
        def run(*a):
            raise jax.errors.JaxRuntimeError("INTERNAL: device halted")

        return run

    monkeypatch.setattr(pw, "window_pallas_eligible", lambda fill, n: True)
    monkeypatch.setattr(
        PlacementSolver, "_pallas_window_program", failing_program
    )
    solver, tensors, requests = _tiny_window_solver()
    (decision,) = solver.pack_window("tightly-pack", tensors, requests)
    assert decision.admitted
    assert solver.window_path_counts.get("greedy-fallback") == 1
    assert solver.degraded.engagements == 1
