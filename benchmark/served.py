"""The system under test: the scheduler booted the way the `server`
command builds it, behind its HTTP server, on an in-memory apiserver.

This is the one file of the benchmark that imports the program. Cluster
objects arrive as Kubernetes JSON and are decoded with the program's own
codec, as its watch ingestion would decode them.
"""

from __future__ import annotations

import collections

from spark_scheduler_tpu.events import EventEmitter
from spark_scheduler_tpu.metrics import MetricRegistry, SchedulerMetrics, WasteReporter
from spark_scheduler_tpu.models.reservations import (
    executor_reservation_name,
    new_resource_reservation,
)
from spark_scheduler_tpu.server.app import build_scheduler_app
from spark_scheduler_tpu.server.config import InstallConfig
from spark_scheduler_tpu.server.http import SchedulerHTTPServer
from spark_scheduler_tpu.server.kube_io import node_from_k8s, pod_from_k8s
from spark_scheduler_tpu.core.sparkpods import spark_resources
from spark_scheduler_tpu.store.backend import DEMAND_CRD, InMemoryBackend
from spark_scheduler_tpu.testing.harness import overcommit_violations
from spark_scheduler_tpu.tracing import svc1log


class Served:
    """Boots on a backend that already holds the cluster: nodes, running
    apps' bound pods with the ResourceReservations the CRDs persist, and
    pending drivers. Reconciles before it serves, as a restart on a live
    cluster does."""

    def __init__(self, install: dict, runtime: dict, nodes: list[dict], pods: list[dict],
                 apps: list[tuple[str, str, list[str], list[str]]]):
        """`apps`: (driver pod name, driver node, executor nodes, executor
        pod names) of every running app."""
        svc1log().set_level(runtime.get("logging-level", "INFO"))
        self.config = InstallConfig.from_dict(install)
        registry = MetricRegistry()
        backend = self.backend = InMemoryBackend()
        backend.register_crd(DEMAND_CRD)
        for raw in nodes:
            backend.add_node(node_from_k8s(raw))
        for raw in pods:
            backend.add_pod(pod_from_k8s(raw))
        ns = pods[0]["metadata"]["namespace"] if pods else "default"
        for driver_name, driver_node, exec_nodes, exec_pods in apps:
            driver = backend.get("pods", ns, driver_name)
            res = spark_resources(driver)
            rr = new_resource_reservation(
                driver_node, exec_nodes, driver,
                res.driver_resources, res.executor_resources,
            )
            for i, name in enumerate(exec_pods):
                rr.status.pods[executor_reservation_name(i)] = name
            backend.create("resourcereservations", rr)
        label = self.config.instance_group_label
        self.app = build_scheduler_app(
            backend, self.config,
            metrics=SchedulerMetrics(registry, label),
            events=EventEmitter(instance_group_label=label),
            waste=WasteReporter(registry, label),
        )
        self.app.reconciler.sync_resource_reservations_and_demands()
        self.server = SchedulerHTTPServer(
            self.app, registry, host="127.0.0.1", port=0,
            request_timeout_s=self.config.request_timeout_s,
        )
        self.server.start()
        self.port = self.server.port
        self.namespace = ns

    # ------------------------------------------- the apiserver's side

    def create_pod(self, raw: dict) -> None:
        self.backend.add_pod(pod_from_k8s(raw))

    def bind(self, name: str, node: str) -> None:
        self.backend.bind_pod(self.backend.get("pods", self.namespace, name), node)

    def complete(self, app_id: str, pod_names: list[str]) -> None:
        """The app's pods end and are deleted, and garbage collection
        deletes the reservation they owned."""
        for name in pod_names:
            self.backend.delete("pods", self.namespace, name)
        self.app.rr_cache.delete(self.namespace, app_id)

    def reservation(self, app_id: str) -> tuple[str, list[str]] | None:
        rr = self.app.rr_cache.get(self.namespace, app_id)
        if rr is None:
            return None
        slots = rr.spec.reservations
        return slots["driver"].node, [r.node for k, r in slots.items() if k != "driver"]

    # ------------------------------------------------------ readings

    def recorder_seq(self) -> int:
        return self.app.recorder.total_recorded

    def driver_phases(self, since: int) -> tuple[list[dict], int]:
        """Phases of the driver decisions recorded after `since`, and how
        many records of that span the ring no longer holds."""
        rec = self.app.recorder
        stats = rec.stats()
        lost = max(0, (stats["total_recorded"] - since) - stats["size"])
        out = [
            dict(r["phases"], queue_position=r["queue_position"])
            for r in rec.query(role="driver", limit=rec.capacity)
            if r["seq"] > since and r["phases"]
        ]
        return out, lost

    def invariants(self, admitted: dict[str, tuple[str, list[str]]]) -> dict:
        """Program-side guarantees: no node over-committed by bound pods
        or by the scheduler's reservation accounting, and every app
        admitted in this run and still running holds the reservation it
        was admitted with, with its bound executors on nodes it holds."""
        import numpy as np

        nodes = self.backend.list_nodes()
        index = {n.name: i for i, n in enumerate(nodes)}
        alloc = np.stack([n.allocatable.as_array() for n in nodes]).astype(np.int64)
        used = np.zeros_like(alloc)
        for pod in self.backend.list_pods():
            if pod.node_name:
                used[index[pod.node_name]] += pod.request().as_array()
        bound_over = int(np.any(used > alloc, axis=1).sum())
        reserved_over = len(overcommit_violations(self.app, self.backend))
        off = 0
        for app_id, (driver_node, exec_nodes) in admitted.items():
            held = self.reservation(app_id)
            bound = collections.Counter(
                p.node_name
                for p in self.backend.list_pods(self.namespace, {"spark-app-id": app_id})
                if p.node_name and p.labels.get("spark-role") == "executor"
            )
            if (
                held is None or held[0] != driver_node
                or sorted(held[1]) != sorted(exec_nodes)
                or bound - collections.Counter(held[1])
            ):
                off += 1
        solver = self.app.solver
        d = solver.degraded
        return {
            "bound_overcommitted_nodes": bound_over,
            "reserved_overcommitted_nodes": reserved_over,
            "apps_off_reservation": off,
            "window_paths": dict(solver.window_path_counts),
            "degraded_engagements": 0 if d is None else int(d.engagements),
        }

    def stop(self) -> None:
        self.server.stop()  # stops the app and drains its write-back

