"""Native C++ runtime parity: the ClusterArena-backed tensor builder against
the pure-Python builder, and the native sharded queue against the Python
queue's dedup/shard/ordering semantics."""

import threading

import numpy as np
import pytest

from spark_scheduler_tpu import native
from spark_scheduler_tpu.core.solver import PlacementSolver
from spark_scheduler_tpu.models.kube import Node
from spark_scheduler_tpu.models.resources import Resources
from spark_scheduler_tpu.store.queue import (
    Request,
    RequestType,
    ShardedUniqueQueue,
    make_sharded_queue,
)

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native runtime not built"
)


def _node(name, cpu="8", mem="8Gi", gpu="0", zone="z1", ready=True,
          unschedulable=False, labels=None):
    return Node(
        name=name,
        allocatable=Resources.from_quantities(cpu, mem, gpu),
        labels={"topology.kubernetes.io/zone": zone, **(labels or {})},
        ready=ready,
        unschedulable=unschedulable,
    )


def _rand_cluster(rng, n):
    return [
        _node(
            f"n{i:04d}",
            cpu=str(int(rng.integers(1, 64))),
            mem=f"{int(rng.integers(1, 64))}Gi",
            gpu=str(int(rng.integers(0, 2))),
            zone=f"z{int(rng.integers(0, 4))}",
            ready=bool(rng.random() > 0.1),
            unschedulable=bool(rng.random() < 0.1),
        )
        for i in range(n)
    ]


def _tensors_equal_on_valid(a, b):
    """Equality of every field on valid slots; name_rank compared by ORDER
    (the native path uses global ranks — values differ, order must not)."""
    assert np.array_equal(a.valid, b.valid)
    v = np.asarray(a.valid)
    for field in ("available", "schedulable", "zone_id", "label_rank_driver",
                  "label_rank_executor", "unschedulable", "ready"):
        fa, fb = np.asarray(getattr(a, field)), np.asarray(getattr(b, field))
        assert np.array_equal(fa[v], fb[v]), field
    ra, rb = np.asarray(a.name_rank)[v], np.asarray(b.name_rank)[v]
    assert np.array_equal(np.argsort(ra, stable=True), np.argsort(rb, stable=True))


def test_arena_solver_matches_python_builder():
    rng = np.random.default_rng(0)
    nodes = _rand_cluster(rng, 50)
    usage = {"n0003": Resources.from_quantities("2", "2Gi"),
             "n0017": Resources.from_quantities("1", "512Mi")}
    overhead = {"n0005": Resources.from_quantities("1", "1Gi")}

    s_native = PlacementSolver(use_native=True)
    s_python = PlacementSolver(use_native=False)
    assert s_native.uses_native_arena and not s_python.uses_native_arena

    t_n = s_native.build_tensors(nodes, usage, overhead)
    t_p = s_python.build_tensors(nodes, usage, overhead)
    _tensors_equal_on_valid(t_n, t_p)

    # Node churn: update one node, drop some from the candidate set, add new.
    nodes[7] = _node("n0007", cpu="2", mem="1Gi", unschedulable=True)
    subset = nodes[:30] + [_node("extra-1", cpu="4", mem="4Gi", zone="z9")]
    t_n2 = s_native.build_tensors(subset, {}, overhead)
    t_p2 = s_python.build_tensors(subset, {}, overhead)
    # Python solver's registry has interned dropped nodes too; valid masks
    # agree because both mark only the passed subset valid.
    _tensors_equal_on_valid(t_n2, t_p2)


def test_arena_solver_same_placements_with_label_priorities():
    rng = np.random.default_rng(1)
    nodes = [
        _node(f"m{i}", cpu="8", mem="8Gi",
              labels={"tier": ["gold", "silver", "bronze"][i % 3]})
        for i in range(12)
    ]
    prio = ("tier", ["gold", "silver"])
    for strategy in ("tightly-pack", "distribute-evenly", "minimal-fragmentation"):
        s_n = PlacementSolver(driver_label_priority=prio, use_native=True)
        s_p = PlacementSolver(driver_label_priority=prio, use_native=False)
        names = [n.name for n in nodes]
        d = Resources.from_quantities("1", "1Gi")
        e = Resources.from_quantities("2", "2Gi")
        t_n = s_n.build_tensors(nodes, {}, {})
        t_p = s_p.build_tensors(nodes, {}, {})
        p_n = s_n.pack(strategy, t_n, d, e, 5, names)
        p_p = s_p.pack(strategy, t_p, d, e, 5, names)
        assert p_n.has_capacity == p_p.has_capacity
        assert p_n.driver_node == p_p.driver_node, strategy
        assert p_n.executor_nodes == p_p.executor_nodes, strategy


def test_native_queue_is_selected_and_python_fallback_works():
    q = make_sharded_queue(5)
    assert isinstance(q, native.NativeShardedQueue)
    q2 = make_sharded_queue(5, prefer_native=False)
    assert isinstance(q2, ShardedUniqueQueue)


def _req(ns, name, typ=RequestType.CREATE):
    return Request(key=(ns, name), type=typ)


def test_native_queue_dedup_and_delete_semantics():
    for q in (make_sharded_queue(4), ShardedUniqueQueue(4)):
        q.add_if_absent(_req("ns", "a"))
        q.add_if_absent(_req("ns", "a", RequestType.UPDATE))  # deduped
        q.add_if_absent(_req("ns", "a", RequestType.DELETE))  # never deduped
        assert sum(q.queue_lengths()) == 2, type(q).__name__

        # Pop everything from every bucket; keys release on pop.
        popped = []
        for b in range(q.num_buckets):
            while (r := q.pop(b, timeout_s=0)) is not None:
                popped.append(r)
        assert [r.type for r in popped] == [RequestType.CREATE, RequestType.DELETE]
        # After release, the same key enqueues again.
        q.add_if_absent(_req("ns", "a", RequestType.UPDATE))
        assert sum(q.queue_lengths()) == 1


def test_native_queue_same_key_same_bucket_and_blocking_pop():
    q = make_sharded_queue(4)
    assert isinstance(q, native.NativeShardedQueue)
    buckets = set()
    for i in range(32):
        q.add_if_absent(_req("ns", "same-key") if False else _req("ns", f"k{i}"))
    lengths = q.queue_lengths()
    assert sum(lengths) == 32 and len(lengths) == 4

    # Same key always lands on the same bucket: drain, re-add twice.
    q2 = make_sharded_queue(4)
    q2.add_if_absent(_req("ns", "stable"))
    b1 = [i for i, n in enumerate(q2.queue_lengths()) if n][0]
    assert q2.pop(b1, timeout_s=0).key == ("ns", "stable")
    q2.add_if_absent(_req("ns", "stable"))
    b2 = [i for i, n in enumerate(q2.queue_lengths()) if n][0]
    assert b1 == b2
    buckets.add(b1)

    # Blocking pop wakes when a producer adds from another thread.
    got = []
    t = threading.Thread(target=lambda: got.append(q2.pop(b1, timeout_s=5.0)))
    q2.pop(b1, timeout_s=0)  # drain first
    t.start()
    q2.add_if_absent(_req("ns", "stable"))
    t.join(timeout=10)
    assert got and got[0] is not None and got[0].key == ("ns", "stable")


def test_native_queue_try_add_full_buffer():
    q = native.NativeShardedQueue(1, buffer_size=2)
    assert q.try_add_if_absent(_req("ns", "x1"))
    assert q.try_add_if_absent(_req("ns", "x2"))
    assert not q.try_add_if_absent(_req("ns", "x3"))  # full -> False
    assert q.try_add_if_absent(_req("ns", "x1", RequestType.UPDATE))  # dedup -> True
    # The full-rollback removed x3 from inflight, so after draining it can
    # be re-added (queue.go:73-88 rollback semantics).
    q.pop(0, timeout_s=0)
    assert q.try_add_if_absent(_req("ns", "x3"))


def test_native_queue_concurrent_producers_consumers():
    q = make_sharded_queue(3, buffer_size=1000)
    n_per, n_prod = 200, 4
    consumed = []
    consumed_lock = threading.Lock()
    stop = threading.Event()

    def consumer(bucket):
        while not stop.is_set():
            r = q.pop(bucket, timeout_s=0.02)
            if r is not None:
                with consumed_lock:
                    consumed.append(r.key)

    consumers = [threading.Thread(target=consumer, args=(b,)) for b in range(3)]
    [c.start() for c in consumers]

    def producer(p):
        for i in range(n_per):
            q.add_if_absent(_req(f"ns{p}", f"key-{p}-{i}"))

    producers = [threading.Thread(target=producer, args=(p,)) for p in range(n_prod)]
    [t.start() for t in producers]
    [t.join() for t in producers]
    deadline = threading.Event()
    for _ in range(200):
        with consumed_lock:
            if len(consumed) == n_per * n_prod:
                break
        deadline.wait(0.05)
    stop.set()
    [c.join(timeout=5) for c in consumers]
    assert len(consumed) == n_per * n_prod  # distinct keys: nothing deduped
    assert len(set(consumed)) == n_per * n_prod


def test_library_built_from_other_source_is_rebuilt(tmp_path, monkeypatch):
    """A .so whose recorded source digest does not match runtime.cpp is
    never loaded: it is rebuilt from the source as it is."""
    import hashlib
    import shutil

    from spark_scheduler_tpu import native

    src = tmp_path / "runtime.cpp"
    shutil.copy(native._SRC, src)
    so = tmp_path / "build" / "libsched_runtime.so"
    so.parent.mkdir()
    so.write_bytes(b"built elsewhere")
    digest = tmp_path / "build" / "libsched_runtime.so.sha256"
    digest.write_text("0" * 64)
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_SO", str(so))
    monkeypatch.setattr(native, "_SO_DIGEST", str(digest))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_failed", False)
    assert native._load() is not None
    assert digest.read_text() == hashlib.sha256(src.read_bytes()).hexdigest()
    assert so.read_bytes() != b"built elsewhere"
