"""Tracing + safe-param logging tests (SURVEY.md §5.1, VERDICT #8/#9).

Covers span structure (predicate -> solve nesting, write-back), b3
propagation from caller headers, the /debug/traces route, svc1log safe
params, the JAX profiler capture producing an artifact, and the program's
spans written into a running capture (and nothing into jax.profiler
without one).
"""

from __future__ import annotations

import glob
import http.client
import io
import json
import os

import pytest

from spark_scheduler_tpu.tracing import (
    Svc1Logger,
    Tracer,
    demand_safe_params,
    pod_safe_params,
    rr_safe_params,
    set_svc1log,
    set_tracer,
    start_jax_profile,
    stop_jax_profile,
    tracer,
)


class TestTracer:
    def test_span_nesting_and_ring_buffer(self):
        t = Tracer()
        with t.span("outer", a=1) as outer_span:
            with t.span("inner") as inner_span:
                assert t.current() is inner_span.span
            assert t.current() is outer_span.span
        spans = t.finished_spans()
        names = [s["name"] for s in spans]
        assert names == ["inner", "outer"]  # finish order
        inner, outer = spans
        assert inner["traceId"] == outer["traceId"]
        assert inner["parentId"] == outer["id"]
        assert outer["tags"] == {"a": 1}

    def test_b3_header_extraction_and_injection(self):
        t = Tracer()
        headers = {"X-B3-TraceId": "beef" * 8, "X-B3-SpanId": "cafe" * 4}
        with t.root_from_headers(headers, "srv") as root:
            assert root.span.trace_id == "beef" * 8
            assert root.span.parent_id == "cafe" * 4
            out = t.inject_headers()
            assert out["X-B3-TraceId"] == "beef" * 8
            assert out["X-B3-SpanId"] == root.span.span_id
        # single-header form
        with t.root_from_headers({"b3": "aa-bb-1"}, "srv") as root:
            assert root.span.trace_id == "aa"
            assert root.span.parent_id == "bb"
        # unsampled traces are not recorded
        t.clear()
        with t.root_from_headers({"b3": "aa-bb-0"}, "srv"):
            pass
        assert t.finished_spans() == []
        # lone deny form "b3: 0" also suppresses recording
        with t.root_from_headers({"b3": "0"}, "srv"):
            pass
        assert t.finished_spans() == []

    def test_error_tagged(self):
        t = Tracer()
        try:
            with t.span("boom"):
                raise ValueError("x")
        except ValueError:
            pass
        (span,) = t.finished_spans()
        assert "ValueError" in span["tags"]["error"]

    def test_ring_buffer_overflow_keeps_newest(self):
        """The finished-span ring is bounded: overflow evicts oldest-first
        and never grows past capacity."""
        t = Tracer(capacity=4)
        for i in range(10):
            with t.span(f"s{i}"):
                pass
        spans = t.finished_spans()
        assert len(spans) == 4
        assert [s["name"] for s in spans] == ["s6", "s7", "s8", "s9"]
        # clear() empties it; the ring keeps working afterwards
        t.clear()
        assert t.finished_spans() == []
        with t.span("after"):
            pass
        assert [s["name"] for s in t.finished_spans()] == ["after"]

    def test_b3_single_header_round_trip(self):
        """`b3: {trace}-{span}-{sampled}` extraction round-trips through
        inject_headers and back through a downstream extraction, for both
        the sampled and unsampled decisions."""
        trace, upstream_span = "ab" * 16, "cd" * 8
        t = Tracer()
        with t.root_from_headers(
            {"b3": f"{trace}-{upstream_span}-1"}, "srv"
        ) as root:
            assert root.span.trace_id == trace
            assert root.span.parent_id == upstream_span
            hdrs = t.inject_headers()
            assert hdrs["X-B3-TraceId"] == trace
            assert hdrs["X-B3-Sampled"] == "1"
            single = (
                f"{hdrs['X-B3-TraceId']}-{hdrs['X-B3-SpanId']}"
                f"-{hdrs['X-B3-Sampled']}"
            )
        t2 = Tracer()
        with t2.root_from_headers({"b3": single}, "downstream") as child:
            assert child.span.trace_id == trace
            assert child.span.parent_id == root.span.span_id
            assert child.span.sampled
        assert [s["name"] for s in t2.finished_spans()] == ["downstream"]

        # Unsampled: the deny decision survives the round trip AND
        # suppresses recording on both hops.
        t3 = Tracer()
        with t3.root_from_headers(
            {"b3": f"{trace}-{upstream_span}-0"}, "srv"
        ):
            hdrs0 = t3.inject_headers()
            assert hdrs0["X-B3-Sampled"] == "0"
            single0 = (
                f"{hdrs0['X-B3-TraceId']}-{hdrs0['X-B3-SpanId']}-0"
            )
        assert t3.finished_spans() == []
        t4 = Tracer()
        with t4.root_from_headers({"b3": single0}, "downstream") as child0:
            assert child0.span.trace_id == trace
            assert not child0.span.sampled
        assert t4.finished_spans() == []


class TestServingTrace:
    def test_predicate_trace_structure_and_debug_route(self):
        """HTTP predicate produces a predicate -> select-node -> solve chain
        joined by one traceId, honoring the caller's b3 trace id."""
        from spark_scheduler_tpu.server.app import build_scheduler_app
        from spark_scheduler_tpu.server.config import InstallConfig
        from spark_scheduler_tpu.server.http import SchedulerHTTPServer
        from spark_scheduler_tpu.server.kube_io import pod_to_k8s
        from spark_scheduler_tpu.store.backend import InMemoryBackend
        from spark_scheduler_tpu.testing.harness import (
            INSTANCE_GROUP_LABEL,
            new_node,
            static_allocation_spark_pods,
        )

        t = set_tracer(Tracer())
        log_stream = io.StringIO()
        set_svc1log(Svc1Logger(stream=log_stream))
        try:
            backend = InMemoryBackend()
            names = []
            for i in range(4):
                n = new_node(f"n{i}")
                backend.add_node(n)
                names.append(n.name)
            app = build_scheduler_app(
                backend,
                InstallConfig(
                    fifo=True,
                    sync_writes=True,
                    instance_group_label=INSTANCE_GROUP_LABEL,
                ),
            )
            server = SchedulerHTTPServer(
                app, host="127.0.0.1", port=0, debug_routes=True
            )
            server.start()
            try:
                pods = static_allocation_spark_pods("trace-app", 2)
                backend.add_pod(pods[0])
                conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)
                trace_id = "12" * 16
                conn.request(
                    "POST",
                    "/predicates",
                    body=json.dumps(
                        {"Pod": pod_to_k8s(pods[0]), "NodeNames": names}
                    ).encode(),
                    headers={"X-B3-TraceId": trace_id, "X-B3-SpanId": "ab" * 8},
                )
                resp = json.loads(conn.getresponse().read())
                assert resp["NodeNames"], resp

                conn.request("GET", "/debug/traces")
                spans = json.loads(conn.getresponse().read())["spans"]
                conn.close()
            finally:
                server.stop()
            by_name = {s["name"]: s for s in spans}
            assert {"predicate", "select-node", "solve"} <= set(by_name)
            # one joined trace, continuing the caller's id
            assert {s["traceId"] for s in spans} == {trace_id}
            assert by_name["predicate"]["parentId"] == "ab" * 8
            # A lone driver rides the WINDOW path: the body decode, the
            # window's featurize, solve (the decision pull), commit (the
            # decisions applied) and the response encode are children of
            # the request's predicate span; select-node is the decision
            # apply inside commit, fetch-wait the blocking pull in solve.
            root_id = by_name["predicate"]["id"]
            for child in (
                "predicate:decode", "featurize", "solve-dispatch", "solve",
                "commit", "predicate:encode",
            ):
                assert by_name[child]["parentId"] == root_id, child
            assert by_name["select-node"]["parentId"] == by_name["commit"]["id"]
            assert by_name["fetch-wait"]["parentId"] == by_name["solve"]["id"]
            assert by_name["featurize-fifo"]["parentId"] == by_name["featurize"]["id"]
            assert by_name["predicate"]["tags"]["pod"] == (
                f"{pods[0].namespace}/{pods[0].name}"
            )
            assert by_name["select-node"]["tags"]["outcome"] == "success"
            assert by_name["predicate"]["tags"]["outcome"] == "success"
            assert by_name["solve"]["tags"]["batched"] is True
            # write-back ran under the trace too (sync_writes drains inline)
            assert "write-back" in by_name
            # svc1log carried safe params + trace join
            logs = [json.loads(line) for line in log_stream.getvalue().splitlines()]
            entry = next(e for e in logs if e["message"] == "predicate")
            assert entry["params"]["podName"] == pods[0].name
            assert entry["params"]["outcome"] == "success"
            assert entry["traceId"] == trace_id
        finally:
            set_tracer(Tracer())
            set_svc1log(Svc1Logger())


class TestDebugRouteGating:
    def test_debug_routes_disabled_by_default(self):
        from spark_scheduler_tpu.server.app import build_scheduler_app
        from spark_scheduler_tpu.server.config import InstallConfig
        from spark_scheduler_tpu.server.http import SchedulerHTTPServer
        from spark_scheduler_tpu.store.backend import InMemoryBackend
        from spark_scheduler_tpu.testing.harness import new_node

        backend = InMemoryBackend()
        backend.add_node(new_node("n0"))
        app = build_scheduler_app(backend, InstallConfig(sync_writes=True))
        server = SchedulerHTTPServer(app, host="127.0.0.1", port=0)
        server.start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=5)
            for method, path in (
                ("GET", "/debug/traces"),
                ("POST", "/debug/profile/start"),
                ("POST", "/debug/profile/stop"),
            ):
                conn.request(method, path)
                resp = conn.getresponse()
                resp.read()
                assert resp.status == 404, (method, path)
            conn.close()
        finally:
            server.stop()


class TestSafeParams:
    def test_pod_demand_rr_safe_params(self):
        from spark_scheduler_tpu.models.demands import (
            Demand,
            DemandSpec,
            DemandUnit,
        )
        from spark_scheduler_tpu.models.reservations import (
            Reservation,
            ReservationSpec,
            ReservationStatus,
            ResourceReservation,
        )
        from spark_scheduler_tpu.models.resources import Resources
        from spark_scheduler_tpu.testing.harness import static_allocation_spark_pods

        pod = static_allocation_spark_pods("sp-app", 1)[0]
        p = pod_safe_params(pod)
        assert p == {
            "podName": pod.name,
            "podNamespace": pod.namespace,
            "podSparkRole": "driver",
            "podSparkAppID": "sp-app",
        }
        d = Demand(
            name="demand-x",
            namespace="ns",
            spec=DemandSpec(
                units=[DemandUnit(resources=Resources.from_quantities("1", "1Gi"), count=2)],
                instance_group="ig",
            ),
        )
        dp = demand_safe_params(d)
        assert dp["demandUnits"] == [{"count": 2, "cpu": 1000, "memoryKib": 1048576}]
        rr = ResourceReservation(
            name="app",
            namespace="ns",
            spec=ReservationSpec(
                {"driver": Reservation("n1", Resources.from_quantities("1", "1Gi"))}
            ),
            status=ReservationStatus({"driver": "app-driver"}),
        )
        rp = rr_safe_params(rr)
        assert rp["reservationNodes"] == ["n1"]
        assert rp["reservationPodNames"] == ["app-driver"]


class TestJaxProfiler:
    def test_failed_flush_does_not_wedge_profiler(self, tmp_path):
        """stop_trace raising (unwritable dir) must not leave jax's internal
        profile state 'started' — the next capture must work end to end."""
        import jax.numpy as jnp
        import pytest as _pytest

        assert start_jax_profile("/proc/nonexistent-dir/x")
        (jnp.ones((4, 4)) @ jnp.ones((4, 4))).block_until_ready()
        with _pytest.raises(Exception):
            stop_jax_profile()
        good = str(tmp_path / "recovered")
        assert start_jax_profile(good), "profiler wedged after failed flush"
        (jnp.ones((4, 4)) @ jnp.ones((4, 4))).block_until_ready()
        assert stop_jax_profile() == good

    def test_profile_capture_produces_artifact(self, tmp_path):
        import jax.numpy as jnp

        log_dir = str(tmp_path / "trace")
        assert start_jax_profile(log_dir)
        assert not start_jax_profile(log_dir)  # already running -> False
        (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
        out = stop_jax_profile()
        assert out == log_dir
        assert stop_jax_profile() is None  # idempotent
        # an xplane artifact exists somewhere under the trace dir
        found = [
            f
            for root, _, files in os.walk(log_dir)
            for f in files
            if f.endswith(".xplane.pb") or f.endswith(".trace.json.gz")
        ]
        assert found, f"no trace artifact under {log_dir}"


def _serve_one_app(tmp_log_dir=None, options=None):
    """Boot a small server, warm it with one app, then serve a second
    app's driver and one executor; with `tmp_log_dir`, the second app is
    served under a start_jax_profile capture. Returns the driver's
    flight-recorder record."""
    from spark_scheduler_tpu.server.app import build_scheduler_app
    from spark_scheduler_tpu.server.config import InstallConfig
    from spark_scheduler_tpu.server.http import SchedulerHTTPServer
    from spark_scheduler_tpu.server.kube_io import pod_to_k8s
    from spark_scheduler_tpu.store.backend import InMemoryBackend
    from spark_scheduler_tpu.testing.harness import (
        INSTANCE_GROUP_LABEL,
        new_node,
        static_allocation_spark_pods,
    )

    backend = InMemoryBackend()
    names = []
    for i in range(4):
        n = new_node(f"n{i}")
        backend.add_node(n)
        names.append(n.name)
    app = build_scheduler_app(
        backend,
        InstallConfig(
            fifo=True, sync_writes=True,
            instance_group_label=INSTANCE_GROUP_LABEL,
        ),
    )
    server = SchedulerHTTPServer(app, host="127.0.0.1", port=0)
    server.start()
    conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=60)

    def post(pod):
        conn.request(
            "POST", "/predicates",
            body=json.dumps(
                {"Pod": pod_to_k8s(pod), "NodeNames": names}
            ).encode(),
        )
        return json.loads(conn.getresponse().read())

    try:
        warm = static_allocation_spark_pods("warm", 1)
        backend.add_pod(warm[0])
        assert post(warm[0])["NodeNames"]  # compiles outside the capture
        pods = static_allocation_spark_pods("traced", 1)
        for p in pods:
            backend.add_pod(p)
        if tmp_log_dir is not None:
            assert start_jax_profile(tmp_log_dir, options)
        try:
            got = post(pods[0])["NodeNames"]
            assert got
            backend.bind_pod(pods[0], got[0])
            assert post(pods[1])["NodeNames"]
        finally:
            if tmp_log_dir is not None:
                assert stop_jax_profile() == tmp_log_dir
        conn.close()
    finally:
        server.stop()
    (record,) = app.recorder.query(app="traced", role="driver")
    return record


def _nested(inner, outer) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


class TestProfilerBridge:
    def test_program_spans_land_on_the_capture_host_lines(self, tmp_path):
        """Under start_jax_profile, the handler thread's line holds the
        request root around its body decode and response encode, and the
        dispatcher's line holds the window dispatch, the blocking fetch
        wait inside its solve, and the executor lookup — all on the
        profiler's clock, properly nested."""
        import jax
        from jax.profiler import ProfileData

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        log_dir = str(tmp_path / "trace")
        _serve_one_app(log_dir, opts)
        (path,) = glob.glob(
            os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
        lines = []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                spans: dict[str, list] = {}
                for e in line.events:
                    start = int(e.start_ns)
                    spans.setdefault(e.name, []).append(
                        (start, start + int(e.duration_ns))
                    )
                lines.append(spans)
        handler = next(ln for ln in lines if "predicate:decode" in ln)
        assert len(handler["predicate"]) == 2  # driver, then executor
        for name in ("predicate:decode", "predicate:encode"):
            assert len(handler[name]) == 2
            for iv in handler[name]:
                assert any(_nested(iv, root) for root in handler["predicate"])
        dispatcher = next(ln for ln in lines if "solve-dispatch" in ln)
        assert dispatcher is not handler
        (wait,) = dispatcher["fetch-wait"]
        (solve,) = dispatcher["solve"]
        assert _nested(wait, solve)
        (lookup,) = dispatcher["executor-lookup"]
        assert any(_nested(lookup, s) for s in dispatcher["select-node"])
        for name in ("featurize", "featurize-fifo", "commit"):
            assert name in dispatcher, name

    def test_window_records_carry_dispatch_and_fetch_wait(self):
        """The driver's flight-recorder record splits its solve into the
        host dispatch and the blocking fetch wait, both inside solve_ms."""
        phases = _serve_one_app()["phases"]
        assert phases["dispatch_ms"] >= 0 and phases["fetch_wait_ms"] >= 0
        assert phases["dispatch_ms"] + phases["fetch_wait_ms"] <= (
            phases["solve_ms"] + 1e-3
        )

    def test_no_capture_never_calls_jax_profiler(self, monkeypatch, tmp_path):
        """With no capture running — before any, and after one stopped —
        spans never construct a TraceAnnotation."""
        import jax

        class Refused:
            def __init__(self, *a, **kw):
                raise AssertionError("TraceAnnotation called without a capture")

        def exercise():
            t = Tracer()
            with t.root_from_headers({"b3": "aa-bb-1"}, "root"):
                with t.span("child"):
                    with t.span("grandchild"):
                        pass
            return [s["name"] for s in t.finished_spans()]

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
        assert exercise() == ["grandchild", "child", "root"]
        log_dir = str(tmp_path / "trace")
        assert start_jax_profile(log_dir)
        assert stop_jax_profile() == log_dir
        assert exercise() == ["grandchild", "child", "root"]

    def test_capture_wraps_each_span_in_an_annotation(self, monkeypatch, tmp_path):
        """While a capture runs, every scoped span enters and exits an
        annotation of its own name, innermost first out."""
        import jax

        events = []

        class Recorded:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                events.append(("enter", self.name))

            def __exit__(self, *exc):
                events.append(("exit", self.name))

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorded)
        log_dir = str(tmp_path / "trace")
        assert start_jax_profile(log_dir)
        try:
            t = Tracer()
            with t.root_from_headers({}, "root"):
                with pytest.raises(ValueError):
                    with t.span("child"):
                        raise ValueError("x")
        finally:
            assert stop_jax_profile() == log_dir
        assert events == [
            ("enter", "root"), ("enter", "child"),
            ("exit", "child"), ("exit", "root"),
        ]


@pytest.mark.parametrize(
    "kind,solver_kw",
    [
        ("single-device", {}),
        ("pooled", {"device_pool": 2}),
        ("pruned", {"prune_top_k": 4, "prune_slack": 0.75}),
        ("fused", {}),
    ],
)
def test_every_window_path_times_dispatch_and_fetch_wait(kind, solver_kw):
    """Each solver window path (single device, device pool, pruned
    top-K, fused views) leaves the host launch and the blocking fetch
    wait on its handle, from spans of those names."""
    from spark_scheduler_tpu.core.solver import PlacementSolver, WindowRequest
    from spark_scheduler_tpu.models.kube import ZONE_LABEL, Node
    from spark_scheduler_tpu.models.resources import Resources

    nodes = [
        Node(
            name=f"n{i:03d}",
            allocatable=Resources.from_quantities("8", "8Gi", "1", round_up=False),
            labels={ZONE_LABEL: f"z{i % 2}"},
        )
        for i in range(16)
    ]
    one = Resources.from_quantities("1", "1Gi")
    names = [n.name for n in nodes]
    windows = [
        [WindowRequest(rows=[(one, one, 2, False)], driver_candidate_names=names,
                       domain_node_names=None)]
        for _ in range(2 if kind == "fused" else 1)
    ]
    t = set_tracer(Tracer())
    try:
        solver = PlacementSolver(use_native=False, **solver_kw)
        for _ in range(2):  # the second window rides the resident carry
            tensors = solver.build_tensors_pipelined(nodes, {}, {})
            if kind == "fused":
                handles = solver.pack_windows_dispatch("tightly-pack", tensors, windows)
            else:
                handles = [solver.pack_window_dispatch("tightly-pack", tensors, windows[0])]
            for h in handles:
                (decision,) = solver.pack_window_fetch(h)
                assert decision.admitted
                assert h.dispatch_ms is not None and h.dispatch_ms >= 0
                assert h.fetch_wait_ms is not None and h.fetch_wait_ms >= 0
        if kind == "pruned":
            assert h.info["path"] == "xla-pruned"
        solver.close()
        names_seen = {s["name"] for s in t.finished_spans()}
        assert {"solve-dispatch", "solve", "fetch-wait"} <= names_seen
    finally:
        set_tracer(Tracer())
