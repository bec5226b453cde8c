"""The backend's pending-driver index: `list_pending_drivers` reads the
FIFO queue from an incrementally kept index instead of scanning every
driver pod, and returns exactly what the scan returned.

The oracle is the scan it replaced: the driver role bucket, filtered to
unbound undeleted pods, stably sorted by creation time. Random sequences
of pod writes (equal creation timestamps included, so the tie order is
checked too) must keep the two identical after every step, on the
in-memory backend, on a durable backend registered after a WAL replay,
and on a follower applying the tailed log. The engagement tests pin the
counters (`pending_index_builds` 1, `pending_index_reads` one per FIFO
driver window, both on `/metrics`) and that no read touches the bucket.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import random

import pytest

from spark_scheduler_tpu.core.sparkpods import (
    ROLE_DRIVER,
    ROLE_EXECUTOR,
    SPARK_APP_ID_LABEL,
    SPARK_ROLE_LABEL,
    SparkPodLister,
)
from spark_scheduler_tpu.metrics import MetricRegistry, SchedulerMetrics
from spark_scheduler_tpu.models.kube import Pod
from spark_scheduler_tpu.server.app import build_scheduler_app
from spark_scheduler_tpu.server.config import InstallConfig
from spark_scheduler_tpu.server.http import SchedulerHTTPServer
from spark_scheduler_tpu.server.kube_io import pod_to_k8s
from spark_scheduler_tpu.store.backend import (
    DEMAND_CRD,
    InMemoryBackend,
    NotFoundError,
)
from spark_scheduler_tpu.store.durable import DurableBackend
from spark_scheduler_tpu.testing.harness import (
    INSTANCE_GROUP_LABEL,
    new_node,
    static_allocation_spark_pods,
)

NAMES = [f"p{i}" for i in range(16)]


def oracle(backend) -> list[Pod]:
    """The scan the index replaced, written out: every driver pod of the
    role bucket, unbound and undeleted, stably sorted by creation time."""
    out = [
        p
        for p in backend.list_pods(labels={SPARK_ROLE_LABEL: ROLE_DRIVER})
        if not p.node_name and p.deletion_timestamp is None
    ]
    out.sort(key=lambda p: p.creation_timestamp)
    return out


def _new_pod(rng: random.Random, name: str) -> Pod:
    role = rng.choice([ROLE_DRIVER, ROLE_DRIVER, ROLE_EXECUTOR])
    return Pod(
        name=name,
        namespace=rng.choice(["a", "b"]),
        labels={SPARK_ROLE_LABEL: role, SPARK_APP_ID_LABEL: name},
        # Three values only: most pods tie with another on creation time.
        creation_timestamp=float(rng.randint(1, 3)),
        node_name=rng.choice(["", "", "", "", "n1"]),
    )


def _random_step(rng: random.Random, backend) -> None:
    """One random pod write through the public mutators."""
    live = backend.list("pods")
    op = rng.choice(
        ["create", "create", "create", "update", "update", "update",
         "bind", "delete"]
    )
    if op == "create" or not live:
        taken = {(p.namespace, p.name) for p in live}
        pod = _new_pod(rng, rng.choice(NAMES))
        if (pod.namespace, pod.name) not in taken:
            backend.add_pod(pod)
        return
    cur = rng.choice(live)
    if op == "delete":
        backend.delete_pod(cur)
        return
    if op == "bind":
        backend.bind_pod(cur, rng.choice(["n1", "n2"]))
        return
    change = rng.choice(
        ["delete_ts", "undelete", "undelete", "role", "unbind", "unbind",
         "bind", "ts", "none", "none"]
    )
    new = dataclasses.replace(cur, labels=dict(cur.labels))
    if change == "delete_ts":
        new.deletion_timestamp = 10.0
    elif change == "undelete":
        new.deletion_timestamp = None
    elif change == "role":
        new.labels[SPARK_ROLE_LABEL] = (
            ROLE_EXECUTOR
            if cur.labels.get(SPARK_ROLE_LABEL) == ROLE_DRIVER
            else ROLE_DRIVER
        )
    elif change == "unbind":
        new.node_name = ""
    elif change == "bind":
        new.node_name = "n3"
    elif change == "ts":
        new.creation_timestamp = float(rng.randint(1, 3))
    backend.update_pod(new)


def _assert_same(lister: SparkPodLister, backend) -> None:
    got = lister.list_pending_drivers()
    want = oracle(backend)
    assert [p.key for p in got] == [p.key for p in want]
    assert all(a is b for a, b in zip(got, want))


@pytest.mark.parametrize("role_index_first", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_index_matches_scan_after_every_write(seed, role_index_first):
    rng = random.Random(seed)
    backend = InMemoryBackend()
    if role_index_first:
        # Updates before the lister registers move pods to the end of the
        # role bucket but not of the store: the build must take the
        # bucket's order.
        backend.register_pod_index(SPARK_ROLE_LABEL)
    # Some pods exist before the index is registered: the build covers them.
    for _ in range(60 if role_index_first else 20):
        _random_step(rng, backend)
    lister = SparkPodLister(backend, INSTANCE_GROUP_LABEL)
    _assert_same(lister, backend)
    for _ in range(400):
        _random_step(rng, backend)
        _assert_same(lister, backend)
    assert backend.pending_index_builds == 1


def test_list_is_a_fresh_list_of_the_stored_pods():
    backend = InMemoryBackend()
    lister = SparkPodLister(backend, INSTANCE_GROUP_LABEL)
    pods = static_allocation_spark_pods("fresh", 1)
    for p in pods:
        backend.add_pod(p)
    first = lister.list_pending_drivers()
    assert first == [pods[0]] and first[0] is pods[0]
    first.clear()
    assert lister.list_pending_drivers() == [pods[0]]


def test_bind_pod_leaves_the_index_and_a_missing_pod_raises():
    backend = InMemoryBackend()
    lister = SparkPodLister(backend, INSTANCE_GROUP_LABEL)
    driver = static_allocation_spark_pods("bound", 0)[0]
    backend.add_pod(driver)
    backend.bind_pod(driver, "n1")
    assert lister.list_pending_drivers() == []
    backend.delete_pod(driver)
    with pytest.raises(NotFoundError):
        backend.bind_pod(driver, "n1")
    assert lister.list_pending_drivers() == []


@pytest.mark.parametrize("seed", [0, 1])
def test_durable_index_after_wal_replay(tmp_path, seed):
    rng = random.Random(seed)
    path = str(tmp_path / "wal.jsonl")
    writer = DurableBackend(path, compact_on_load=False)
    for _ in range(150):
        _random_step(rng, writer)
    writer.close()
    last = writer
    # Replay fills the store before any consumer registers the index; each
    # replayed backend writes on, and the next one replays that too.
    for compact in (False, True):
        replayed = DurableBackend(path, compact_on_load=compact)
        lister = SparkPodLister(replayed, INSTANCE_GROUP_LABEL)
        _assert_same(lister, replayed)
        assert {p.key for p in lister.list_pending_drivers()} == {
            p.key for p in oracle(last)
        }
        for _ in range(100):
            _random_step(rng, replayed)
            _assert_same(lister, replayed)
        replayed.close()
        last = replayed


@pytest.mark.parametrize("seed", [0, 1])
def test_durable_follower_index_tracks_tailed_records(tmp_path, seed):
    rng = random.Random(seed)
    path = str(tmp_path / "wal.jsonl")
    writer = DurableBackend(path, compact_on_load=False)
    for _ in range(30):
        _random_step(rng, writer)
    writer.wal_flush()
    follower = DurableBackend(path, follow=True)
    lister = SparkPodLister(follower, INSTANCE_GROUP_LABEL)
    _assert_same(lister, follower)
    for _ in range(300):
        _random_step(rng, writer)
        writer.wal_flush()
        follower.poll_log()
        _assert_same(lister, follower)
        assert {p.key for p in lister.list_pending_drivers()} == {
            p.key for p in oracle(writer)
        }
    assert follower.pending_index_builds == 1
    writer.close()


class _NoIter(dict):
    """A dict that fails the test when anything walks it."""

    def _walked(self, *a, **k):
        raise AssertionError("the driver role bucket was iterated")

    __iter__ = values = items = keys = _walked


def test_list_never_walks_the_driver_bucket_at_5k_drivers():
    backend = InMemoryBackend()
    lister = SparkPodLister(backend, INSTANCE_GROUP_LABEL)
    for i in range(5000):
        backend.add_pod(
            Pod(
                name=f"d{i}",
                labels={SPARK_ROLE_LABEL: ROLE_DRIVER},
                creation_timestamp=float(i % 7),
                node_name="" if i % 40 == 0 else "n1",
            )
        )
    want = [p.key for p in oracle(backend)]
    assert len(want) == 125
    role_idx = backend._pod_indexes[SPARK_ROLE_LABEL]
    role_idx[ROLE_DRIVER] = _NoIter(role_idx[ROLE_DRIVER])
    backend._objects["pods"] = _NoIter(backend._objects["pods"])
    reads = backend.pending_index_reads
    assert [p.key for p in lister.list_pending_drivers()] == want
    # Writes keep the index without walking the bucket either.
    late = Pod(name="late", labels={SPARK_ROLE_LABEL: ROLE_DRIVER},
               creation_timestamp=-1.0)
    backend.add_pod(late)
    backend.bind_pod(backend.get("pods", "default", "d0"), "n2")
    got = [p.key for p in lister.list_pending_drivers()]
    assert got[0] == late.key and ("default", "d0") not in got
    assert len(got) == 125
    assert backend.pending_index_reads == reads + 2
    assert backend.pending_index_builds == 1


def _post(port, pod, names):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request(
        "POST",
        "/predicates",
        body=json.dumps({"Pod": pod_to_k8s(pod), "NodeNames": names}).encode(),
    )
    out = json.loads(conn.getresponse().read())
    conn.close()
    return out


def _gauge(text: str, name: str) -> float:
    for line in text.splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            return float(line.rsplit(" ", 1)[1])
    raise AssertionError(f"{name} not in /metrics")


def test_fifo_driver_windows_read_the_index_once_each():
    """N FIFO driver windows through an app built as the server builds it:
    one index build for the process, one read per window, both on
    /metrics."""
    backend = InMemoryBackend()
    backend.register_crd(DEMAND_CRD)
    names = [f"n{i}" for i in range(4)]
    for n in names:
        backend.add_node(new_node(n))
    registry = MetricRegistry()
    app = build_scheduler_app(
        backend,
        InstallConfig(
            fifo=True,
            sync_writes=True,
            instance_group_label=INSTANCE_GROUP_LABEL,
        ),
        metrics=SchedulerMetrics(registry, INSTANCE_GROUP_LABEL),
    )
    srv = SchedulerHTTPServer(app, registry, port=0, request_timeout_s=120.0)
    srv.start()
    try:
        assert backend.pending_index_builds == 1
        reads0 = backend.pending_index_reads
        n = 5
        for i in range(n):
            driver = static_allocation_spark_pods(f"eng-{i}", 1)[0]
            backend.add_pod(driver)
            assert _post(srv.port, driver, names)["NodeNames"]
        assert backend.pending_index_reads == reads0 + n
        assert backend.pending_index_builds == 1
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=120)
        conn.request("GET", "/metrics", headers={"Accept": "text/plain"})
        text = conn.getresponse().read().decode()
        conn.close()
    finally:
        srv.stop()
    reads = [ln for ln in text.splitlines() if "pending_index_reads" in ln]
    builds = [ln for ln in text.splitlines() if "pending_index_builds" in ln]
    assert reads and builds, text
    metric = lambda lines: lines[-1].split("{")[0].split(" ")[0]  # noqa: E731
    assert _gauge(text, metric(reads)) == reads0 + n
    assert _gauge(text, metric(builds)) == 1
