"""The cell's cluster and pods, made from its configuration and the seed.

Nothing here imports the scheduler. Nodes and pods are plain data: node
rows for the reference, and pods as the Kubernetes JSON that the
apiserver and kube-scheduler would hand the extender.
"""

from __future__ import annotations

import dataclasses
import datetime
import json
import re

import numpy as np

ZONE_LABEL = "failure-domain.beta.kubernetes.io/zone"
ROLE_LABEL = "spark-role"
APP_ID_LABEL = "spark-app-id"
SCHEDULER_NAME = "spark-scheduler"
NAMESPACE = "spark"
# Nodes joined the cluster at this instant.
EPOCH = datetime.datetime(2024, 1, 1, tzinfo=datetime.timezone.utc)

_QTY = re.compile(r"^([0-9]+)(m|Ki|Mi|Gi|Ti)?$")
_SCALE = {None: 1, "Ki": 1 << 10, "Mi": 1 << 20, "Gi": 1 << 30, "Ti": 1 << 40}


def cpu_milli(q: str) -> int:
    m = _QTY.match(str(q))
    if m is None or m.group(2) not in (None, "m"):
        raise ValueError(f"cpu quantity {q!r}")
    return int(m.group(1)) * (1 if m.group(2) == "m" else 1000)


def mem_bytes(q: str) -> int:
    m = _QTY.match(str(q))
    if m is None or m.group(2) == "m":
        raise ValueError(f"memory quantity {q!r}")
    return int(m.group(1)) * _SCALE[m.group(2)]


@dataclasses.dataclass
class Cluster:
    names: list[str]
    zone: np.ndarray  # [N] int: index into zone_names
    zone_names: list[str]
    alloc: np.ndarray  # [N, 2] int64: cpu milli, memory bytes
    shape: list[str]  # machine type per node
    names_json: bytes  # the NodeNames array as kube-scheduler sends it

    @property
    def n(self) -> int:
        return len(self.names)


def make_cluster(config: dict, seed: int) -> Cluster:
    """N nodes, zone i % zones, and per zone an equal share of each
    machine shape in an order drawn from the seed: every seed holds the
    same nodes, placed differently."""
    n = int(config["nodes"])
    zones = int(config["zones"])
    shapes = config["node_shapes"]
    rng = np.random.default_rng(seed % (1 << 63))
    shape_of = np.empty(n, np.int64)
    for z in range(zones):
        members = np.arange(z, n, zones)
        shape_of[members] = rng.permutation(np.arange(members.size) % len(shapes))
    width = max(5, len(str(n - 1)))
    names = [f"node-{i:0{width}d}" for i in range(n)]
    alloc = np.array(
        [[cpu_milli(shapes[s]["cpu"]), mem_bytes(shapes[s]["memory"])] for s in shape_of],
        np.int64,
    ).reshape(n, 2)
    return Cluster(
        names=names,
        zone=np.arange(n) % zones,
        zone_names=[f"zone-{chr(ord('a') + z)}" for z in range(zones)],
        alloc=alloc,
        shape=[shapes[s]["machine"] for s in shape_of],
        names_json=json.dumps(names).encode(),
    )


def node_json(cluster: Cluster, i: int, config: dict) -> dict:
    shape = next(s for s in config["node_shapes"] if s["machine"] == cluster.shape[i])
    return {
        "kind": "Node",
        "apiVersion": "v1",
        "metadata": {
            "name": cluster.names[i],
            "labels": {
                ZONE_LABEL: cluster.zone_names[cluster.zone[i]],
                config["install"]["instance-group-label"]: config["instance_group"],
                "node.kubernetes.io/instance-type": shape["machine"],
            },
            "creationTimestamp": EPOCH.isoformat().replace("+00:00", "Z"),
        },
        "spec": {},
        "status": {
            "allocatable": {"cpu": shape["cpu"], "memory": shape["memory"]},
            "conditions": [{"type": "Ready", "status": "True"}],
        },
    }


@dataclasses.dataclass
class Gang:
    """Resource shape of one Spark application: [cpu milli, memory bytes]."""

    driver: np.ndarray
    executor: np.ndarray
    count: int

    @classmethod
    def from_config(cls, config: dict) -> "Gang":
        g = config["gang"]
        return cls(
            driver=np.array([cpu_milli(g["driver"]["cpu"]), mem_bytes(g["driver"]["memory"])], np.int64),
            executor=np.array([cpu_milli(g["executor"]["cpu"]), mem_bytes(g["executor"]["memory"])], np.int64),
            count=int(g["executors"]),
        )


class PodFactory:
    """Pod JSON for one configuration, with creation times one second
    apart (the wire's resolution, which FIFO order compares) in the order
    pods are made, the first at `start` (seconds since the Unix epoch)."""

    def __init__(self, config: dict, start: int):
        self.config = config
        self.start = datetime.datetime.fromtimestamp(start, datetime.timezone.utc)
        self.seq = 0
        g = config["gang"]
        label = config["install"]["instance-group-label"]
        self._selector = {label: config["instance_group"]}
        self._driver_ann = {
            "spark-driver-cpu": g["driver"]["cpu"],
            "spark-driver-mem": g["driver"]["memory"],
            "spark-executor-cpu": g["executor"]["cpu"],
            "spark-executor-mem": g["executor"]["memory"],
            "spark-executor-count": str(g["executors"]),
        }

    def _stamp(self) -> str:
        t = self.start + datetime.timedelta(seconds=self.seq)
        self.seq += 1
        return t.isoformat().replace("+00:00", "Z")

    def _pod(self, name: str, app_id: str, role: str, req: dict, ann: dict) -> dict:
        return {
            "kind": "Pod",
            "apiVersion": "v1",
            "metadata": {
                "name": name,
                "namespace": NAMESPACE,
                "uid": f"uid-{name}",
                "labels": {ROLE_LABEL: role, APP_ID_LABEL: app_id},
                "annotations": ann,
                "creationTimestamp": self._stamp(),
            },
            "spec": {
                "schedulerName": SCHEDULER_NAME,
                "nodeSelector": dict(self._selector),
                "containers": [{
                    "name": f"spark-kubernetes-{role}",
                    "resources": {"requests": {"cpu": req["cpu"], "memory": req["memory"]}},
                }],
            },
            "status": {"phase": "Pending"},
        }

    def driver(self, app_id: str) -> dict:
        return self._pod(
            f"{app_id}-driver", app_id, "driver",
            self.config["gang"]["driver"], dict(self._driver_ann),
        )

    def executor(self, app_id: str, k: int) -> dict:
        return self._pod(
            f"{app_id}-exec-{k + 1}", app_id, "executor",
            self.config["gang"]["executor"], {},
        )


def bound(pod: dict, node: str) -> dict:
    """The pod as the apiserver holds it once it is bound and running."""
    return {**pod, "spec": {**pod["spec"], "nodeName": node},
            "status": {**pod["status"], "phase": "Running"}}


def predicate_body(pod: dict, cluster: Cluster) -> bytes:
    """ExtenderArgs with every node offered (percentageOfNodesToScore
    100). The NodeNames array is encoded once per run."""
    return b'{"Pod": ' + json.dumps(pod).encode() + b', "NodeNames": ' + cluster.names_json + b"}"
