"""Spark dynamic allocation served end to end, answer by answer, against a
plain reference written here in numpy from the upstream semantics
(palantir/k8s-spark-scheduler README and resourcereservations.go; Spark's
dynamic resource allocation):

- a driver is admitted as a tightly-packed gang of its min executors
  against allocatable less every hard and soft reservation, and the FIFO
  earlier-driver check packs earlier drivers with their min executors;
- an executor binds to the first offered node holding an unbound hard
  slot of its app; with none, and fewer than max - min soft reservations,
  it goes on the first node in the tightly-pack order where it fits, on a
  soft reservation, or is denied failure-fit;
- a deleted executor frees its soft reservation at once; its hard slot
  stays on its node until the next request, whose compaction moves live
  soft-reserved executors of the app, oldest first, into the free slots:
  the slot moves to the executor's node and its soft reservation goes;
- at a restart the soft reservations are rebuilt from the bound pods, in
  the order the apiserver lists them (their creation order).

Departures from upstream, where it leaves the choice open: compaction
takes soft-reserved executors oldest first (upstream walks a Go map), and
slots are compared as a multiset of (node, holder), never by slot name.

Requests go through the extender's window API as the server's batcher
calls it: a lone driver is a one-row device window, a lone executor the
solo ladder.
"""

from __future__ import annotations

import itertools
import threading

import numpy as np
import pytest

from spark_scheduler_tpu.core.extender import (
    FAILURE_EARLIER_DRIVER,
    FAILURE_FIT,
    SUCCESS,
    SUCCESS_SCHEDULED_EXTRA_EXECUTOR,
    ExtenderArgs,
)
from spark_scheduler_tpu.core.sparkpods import (
    DA_MAX_EXECUTOR_COUNT,
    DA_MIN_EXECUTOR_COUNT,
    DRIVER_CPU,
    DRIVER_MEMORY,
    DYNAMIC_ALLOCATION_ENABLED,
    EXECUTOR_CPU,
    EXECUTOR_MEMORY,
    ROLE_DRIVER,
    ROLE_EXECUTOR,
    SPARK_APP_ID_LABEL,
    SPARK_ROLE_LABEL,
    SPARK_SCHEDULER_NAME,
    spark_resources,
)
from spark_scheduler_tpu.metrics import MetricRegistry, SchedulerMetrics
from spark_scheduler_tpu.models.reservations import (
    executor_reservation_name,
    new_resource_reservation,
)
from spark_scheduler_tpu.models.kube import Container, Node, Pod, ZONE_LABEL
from spark_scheduler_tpu.models.resources import Resources
from spark_scheduler_tpu.server.app import build_scheduler_app
from spark_scheduler_tpu.server.config import InstallConfig
from spark_scheduler_tpu.store.backend import DEMAND_CRD, InMemoryBackend
from spark_scheduler_tpu.tracing import tracer

GROUP_LABEL = "instance-group"
GROUP = "batch"
NS = "spark"
DRIVER = np.array([1000, 1 << 20])  # cpu milli, memory KiB: 1 CPU / 1Gi
EXECUTOR = np.array([2000, 4 << 20])  # 2 CPU / 4Gi
SHAPES = ((4, 16), (8, 32), (16, 64))  # vCPU, GiB
EXTRA_FIT = "not enough capacity to reschedule the executor"

# ------------------------------------------------------------ reference


class Reference:
    """Cluster state as upstream's semantics move it: per node the
    availability ([N, 2] cpu milli, memory KiB), and per app its driver
    node, hard slots [node, holder or None] and soft reservations."""

    def __init__(self, alloc: np.ndarray, zone: np.ndarray, lo: int, hi: int):
        self.alloc = alloc.astype(np.int64)
        self.zone = zone
        self.avail = self.alloc.copy()
        self.lo, self.hi = lo, hi
        self.driver: dict[str, int] = {}
        self.slots: dict[str, list[list]] = {}
        self.soft: dict[str, dict[str, int]] = {}
        self.queued: set[str] = set()

    def order(self) -> np.ndarray:
        """Node indices in the tightly-pack order: zones by (available
        memory, available CPU, name) summed, then nodes by (zone rank,
        available memory, available CPU, name), all ascending."""
        a = self.avail
        n_z = int(self.zone.max()) + 1
        mem = np.bincount(self.zone, weights=a[:, 1], minlength=n_z)
        cpu = np.bincount(self.zone, weights=a[:, 0], minlength=n_z)
        zrank = np.empty(n_z, np.int64)
        zrank[sorted(range(n_z), key=lambda z: (mem[z], cpu[z], z))] = np.arange(n_z)
        return np.lexsort((np.arange(len(a)), a[:, 0], a[:, 1], zrank[self.zone]))

    @staticmethod
    def capacity(avail: np.ndarray, req: np.ndarray) -> np.ndarray:
        cap = np.minimum(avail[..., 0] // req[0], avail[..., 1] // req[1])
        return np.where((avail < 0).any(axis=-1), 0, cap)

    def gang(self, avail: np.ndarray, order, count: int) -> tuple[int, list[int]] | None:
        """The tightly-packed placement of a driver and `count` executors:
        the first driver node in the order after which the executors still
        fit, the executors filling nodes in the order."""
        cap = self.capacity(avail, EXECUTOR)
        for d in order:
            if (avail[d] < DRIVER).any():
                continue
            with_driver = cap.copy()
            with_driver[d] = self.capacity(avail[d] - DRIVER, EXECUTOR)
            if with_driver.sum() < count:
                continue
            execs: list[int] = []
            for i in order:
                execs += [int(i)] * int(min(with_driver[i], count - len(execs)))
                if len(execs) == count:
                    return int(d), execs
        return None

    def _take(self, node: int, req: np.ndarray, sign: int) -> None:
        self.avail[node] -= sign * req

    def driver_request(self, earlier: list[int]) -> tuple[str, int | None, list[int]]:
        """("earlier" | "fit", None, []) or ("admit", driver, executors):
        each earlier pending driver (given by its min executors) packed
        first, against what the ones before it left."""
        order = self.order()
        avail = self.avail.copy()
        for count in earlier:
            p = self.gang(avail, order, count)
            if p is None:
                return "earlier", None, []
            avail[p[0]] -= DRIVER
            for e in p[1]:
                avail[e] -= EXECUTOR
        p = self.gang(avail, order, self.lo)
        return ("fit", None, []) if p is None else ("admit", p[0], p[1])

    def admit(self, app: str, driver: int, execs: list[int]) -> None:
        self._take(driver, DRIVER, +1)
        for e in execs:
            self._take(e, EXECUTOR, +1)
        self.driver[app] = driver
        self.slots[app] = [[e, None] for e in execs]
        self.soft[app] = {}

    def executor_request(self, app: str) -> tuple[str, int | None]:
        free = [n for n, holder in self.slots[app] if holder is None]
        if free:
            return "bound", min(free)
        if len(self.soft[app]) >= self.hi - self.lo:
            return "no-spots", None
        fit = np.nonzero((self.avail >= EXECUTOR).all(axis=1))[0]
        if not fit.size:
            return "extra", None
        rank = np.empty(len(self.avail), np.int64)
        rank[self.order()] = np.arange(len(self.avail))
        return "extra", int(fit[np.argmin(rank[fit])])

    def place(self, app: str, pod: str, node: int) -> None:
        for slot in self.slots[app]:
            if slot[0] == node and slot[1] is None:
                slot[1] = pod
                return
        self.soft[app][pod] = node
        self._take(node, EXECUTOR, +1)

    def release(self, app: str, pod: str) -> None:
        if pod in self.soft[app]:
            self._take(self.soft[app].pop(pod), EXECUTOR, -1)
        for slot in self.slots[app]:
            if slot[1] == pod:
                slot[1] = None
        self.queued.add(app)

    def compact(self) -> None:
        for app in sorted(self.queued):
            free = [s for s in self.slots.get(app, []) if s[1] is None]
            for pod, node in list(self.soft.get(app, {}).items()):
                if not free:
                    break
                slot = next((s for s in free if s[0] == node), free[0])
                free.remove(slot)
                self._take(slot[0], EXECUTOR, -1)
                slot[0], slot[1] = node, pod
                del self.soft[app][pod]
        self.queued.clear()

    def complete(self, app: str) -> None:
        self._take(self.driver.pop(app), DRIVER, -1)
        for node, _ in self.slots.pop(app):
            self._take(node, EXECUTOR, -1)
        for node in self.soft.pop(app).values():
            self._take(node, EXECUTOR, -1)
        self.queued.discard(app)


# --------------------------------------------------------- served side

_created = itertools.count(1)


def make_cluster(seed: int, n: int, shapes=SHAPES):
    """n nodes in 3 zones round-robin, shapes in equal seeded shares."""
    rng = np.random.default_rng(seed)
    shape = rng.permutation(np.arange(n) % len(shapes))
    names = [f"node-{i:05d}" for i in range(n)]
    zone = np.arange(n) % 3
    nodes = [
        Node(
            name=names[i],
            allocatable=Resources.from_quantities(str(shapes[s][0]), f"{shapes[s][1]}Gi"),
            labels={ZONE_LABEL: f"zone-{'abc'[zone[i]]}", GROUP_LABEL: GROUP},
        )
        for i, s in enumerate(shape)
    ]
    alloc = np.array([[shapes[s][0] * 1000, shapes[s][1] << 20] for s in shape])
    return names, nodes, alloc, zone


def pod(app: str, role: str, k: int = 0, lo: int = 0, hi: int = 0) -> Pod:
    ann = {}
    if role == ROLE_DRIVER:
        ann = {
            DRIVER_CPU: "1", DRIVER_MEMORY: "1Gi", EXECUTOR_CPU: "2", EXECUTOR_MEMORY: "4Gi",
            DYNAMIC_ALLOCATION_ENABLED: "true",
            DA_MIN_EXECUTOR_COUNT: str(lo), DA_MAX_EXECUTOR_COUNT: str(hi),
        }
    req = ("1", "1Gi") if role == ROLE_DRIVER else ("2", "4Gi")
    return Pod(
        name=f"{app}-driver" if role == ROLE_DRIVER else f"{app}-exec-{k}",
        namespace=NS,
        labels={SPARK_ROLE_LABEL: role, SPARK_APP_ID_LABEL: app},
        annotations=ann,
        creation_timestamp=float(next(_created)),
        scheduler_name=SPARK_SCHEDULER_NAME,
        node_selector={GROUP_LABEL: GROUP},
        containers=[Container(requests=Resources.from_quantities(*req))],
    )


class Served:
    """The scheduler app on an in-memory apiserver, called the way the
    server's batcher calls it."""

    def __init__(self, backend, nodes=()):
        self.backend = backend
        for n in nodes:
            backend.add_node(n)
        self.registry = MetricRegistry()
        self.app = build_scheduler_app(
            backend,
            InstallConfig(fifo=True, binpack_algo="tightly-pack",
                          instance_group_label=GROUP_LABEL, sync_writes=True),
            metrics=SchedulerMetrics(self.registry, GROUP_LABEL),
        )
        self.app.extender._last_request = float("inf")
        self.app.reconciler.sync_resource_reservations_and_demands()

    def call(self, p: Pod, names: list[str]):
        if self.backend.get("pods", p.namespace, p.name) is None:
            self.backend.add_pod(p)
        res = self.app.extender.predicate_batch([ExtenderArgs(pod=p, node_names=names)])[0]
        if res.ok:
            self.backend.bind_pod(p, res.node_names[0])
        return res

    def delete(self, name: str) -> None:
        self.backend.delete_pod(self.backend.get("pods", NS, name))

    def slots(self, app: str) -> list[tuple[str, str | None]]:
        rr = self.app.rr_cache.get(NS, app)
        live = {p.name for p in self.backend.list_pods(NS, {SPARK_APP_ID_LABEL: app})}
        return sorted(
            ((r.node, h if (h := rr.status.pods.get(k)) in live else None)
             for k, r in rr.spec.reservations.items() if k != "driver"),
            key=lambda s: (s[0], s[1] or ""),
        )

    def soft(self) -> dict[str, dict[str, str]]:
        return {a: {p: r.node for p, r in sr.reservations.items()}
                for a, sr in self.app.soft_store.get_all_copy().items()}

    def reserved(self) -> dict[str, tuple[int, int]]:
        return {n: (r.cpu_milli, r.mem_kib)
                for n, r in self.app.reservation_manager.get_reserved_resources().items()}


class Run:
    """A served app and the reference side by side; every answer is
    compared as it comes, and the state on demand."""

    def __init__(self, seed: int, n: int, lo: int, hi: int, shapes=SHAPES):
        self.names, nodes, alloc, zone = make_cluster(seed, n, shapes)
        self.index = {x: i for i, x in enumerate(self.names)}
        self.lo, self.hi = lo, hi
        self.ref = Reference(alloc, zone, lo, hi)
        self.served = Served(InMemoryBackend(), nodes)
        self.served.backend.register_crd(DEMAND_CRD)
        self.live: dict[str, list[str]] = {}  # app -> live executor pods, oldest first
        self.made: dict[str, int] = {}
        self.pending: list[tuple[str, int]] = []  # (app, min executors)
        self.outcomes: list[str] = []

    def arrive(self, app: str, lo: int | None = None) -> None:
        lo = self.lo if lo is None else lo
        self.served.backend.add_pod(pod(app, ROLE_DRIVER, lo=lo, hi=self.hi))
        self.pending.append((app, lo))

    def driver(self, app: str) -> bool:
        d = self.served.backend.get("pods", NS, f"{app}-driver")
        apps = [a for a, _ in self.pending]
        earlier = [lo for _, lo in self.pending[: apps.index(app)]]
        self.ref.compact()
        verdict, want_driver, want_execs = self.ref.driver_request(earlier)
        res = self.served.call(d, self.names)
        self.outcomes.append(res.outcome)
        if verdict != "admit":
            assert not res.ok, (app, res.outcome)
            assert res.outcome == (FAILURE_EARLIER_DRIVER if verdict == "earlier" else FAILURE_FIT)
            return False
        rr = self.served.app.rr_cache.get(NS, app)
        got = sorted(self.index[r.node] for k, r in rr.spec.reservations.items() if k != "driver")
        assert (self.index[res.node_names[0]], got) == (want_driver, sorted(want_execs)), app
        del self.pending[apps.index(app)]
        self.ref.admit(app, want_driver, want_execs)
        self.live[app], self.made[app] = [], 0
        return True

    def executors(self, app: str, count: int) -> None:
        pods = []
        for _ in range(count):
            self.made[app] += 1
            pods.append(pod(app, ROLE_EXECUTOR, k=self.made[app]))
            self.served.backend.add_pod(pods[-1])
        for p in pods:
            self.ref.compact()
            rung, want = self.ref.executor_request(app)
            res = self.served.call(p, self.names)
            self.outcomes.append(res.outcome)
            if want is None:
                assert not res.ok and res.outcome == FAILURE_FIT, (p.name, res.outcome)
                assert set(res.failed_nodes.values()) == {EXTRA_FIT}
                continue
            assert res.ok and self.index[res.node_names[0]] == want, (p.name, rung, res)
            assert res.outcome == (SUCCESS if rung == "bound" else SUCCESS_SCHEDULED_EXTRA_EXECUTOR)
            self.ref.place(app, p.name, want)
            self.live[app].append(p.name)

    def ramp(self, app: str, rounds) -> None:
        self.executors(app, self.lo)
        for r in rounds:
            self.executors(app, r)

    def release(self, app: str, pods: list[str]) -> None:
        for name in pods:
            self.served.delete(name)
            self.live[app].remove(name)
            self.ref.release(app, name)

    def complete(self, app: str) -> None:
        for name in [f"{app}-driver", *self.live.pop(app)]:
            self.served.delete(name)
        self.served.app.rr_cache.delete(NS, app)
        self.ref.complete(app)

    def check_state(self) -> None:
        """Soft reservations, hard slots and per-node reserved usage."""
        names = self.names
        want_soft = {a: {p: names[n] for p, n in s.items()} for a, s in self.ref.soft.items()}
        assert self.served.soft() == want_soft
        for app, slots in self.ref.slots.items():
            want = sorted(((names[n], h) for n, h in slots), key=lambda s: (s[0], s[1] or ""))
            assert self.served.slots(app) == want, app
        used = self.ref.alloc - self.ref.avail
        got = self.served.reserved()
        for i, name in enumerate(names):
            assert tuple(got.get(name, (0, 0))) == tuple(used[i]), name

    def restart(self) -> None:
        """A new scheduler on the same apiserver: reservations are read
        back, soft reservations rebuilt from the bound pods."""
        self.served.app.stop()
        self.served = Served(self.served.backend)
        order = {p.name: i for i, p in enumerate(self.served.backend.list_pods())}
        for app, soft in self.ref.soft.items():
            self.ref.soft[app] = dict(sorted(soft.items(), key=lambda kv: order[kv[0]]))


LO, HI, ROUNDS, RELEASE = 4, 12, (1, 2, 4, 1), 6


def _cycle(run: Run, seq: itertools.count, rng: np.random.Generator, full: list[str]) -> None:
    """The oldest app ends, the oldest app at max releases RELEASE of its
    executors, and a new app is admitted and ramps up."""
    oldest = next(iter(run.live))
    run.complete(oldest)
    if oldest in full:
        full.remove(oldest)
    if full:
        app = full.pop(0)
        live = run.live[app]
        run.release(app, [live[i] for i in sorted(rng.choice(len(live), RELEASE, replace=False))])
    app = f"app-{next(seq):04d}"
    run.arrive(app)
    assert run.driver(app)
    run.ramp(app, ROUNDS)
    full.append(app)


@pytest.mark.parametrize("seed", [3, 17, 2**31 + 5])
def test_ramp_scale_down_compaction_and_restart_match_reference(seed):
    run = Run(seed, 150, LO, HI)
    rng = np.random.default_rng(seed + 1)
    seq = itertools.count()
    full: list[str] = []
    try:
        for _ in range(8):
            app = f"app-{next(seq):04d}"
            run.arrive(app)
            assert run.driver(app)
            run.ramp(app, ROUNDS)
            full.append(app)
        run.check_state()
        for _ in range(4):
            _cycle(run, seq, rng, full)
            run.check_state()
        run.restart()
        run.check_state()
        for _ in range(2):
            _cycle(run, seq, rng, full)
            run.check_state()
        assert SUCCESS_SCHEDULED_EXTRA_EXECUTOR in run.outcomes
    finally:
        run.served.app.stop()


def test_extras_denied_failure_fit_when_the_pool_is_full():
    """4 nodes of 4 vCPU: the driver and 2 min executors take 5 of the 16,
    five extras 10 more, and the sixth and seventh find no node with 2
    vCPU free."""
    run = Run(5, 4, 2, 20, shapes=((4, 16),))
    try:
        run.arrive("app-full")
        assert run.driver("app-full")
        run.executors("app-full", 2 + 7)
        assert run.outcomes.count(SUCCESS_SCHEDULED_EXTRA_EXECUTOR) == 5
        assert run.outcomes[-2:] == [FAILURE_FIT, FAILURE_FIT]
        run.check_state()
        prefix = "foundry.spark.scheduler.softreservation.extra."
        counters = {k: v[0]["value"] for k, v in run.served.registry.snapshot().items()
                    if k.startswith(prefix)}
        assert counters == {prefix + "placed": 5, prefix + "failed": 2}
    finally:
        run.served.app.stop()


@pytest.mark.parametrize("earlier_min,admitted", [(2, True), (8, False)])
def test_fifo_earlier_driver_check_counts_min_executors(earlier_min, admitted):
    """An earlier pending driver is packed with its min executors, not its
    max: with min 2 (max 30, which would not fit) the later driver is
    served; with min 8 the earlier one cannot fit and blocks it."""
    run = Run(7, 2, 2, 30, shapes=((8, 32),))
    try:
        run.arrive("app-early", lo=earlier_min)
        run.arrive("app-late")
        assert run.driver("app-late") == admitted
        assert run.outcomes[-1] == (SUCCESS if admitted else FAILURE_EARLIER_DRIVER)
    finally:
        run.served.app.stop()


def test_spans_and_counters_of_extras_soft_writes_and_compaction():
    run = Run(11, 24, LO, HI)
    tracer().clear()
    try:
        for name in ("app-a", "app-b"):
            run.arrive(name)
            assert run.driver(name)
            run.ramp(name, ROUNDS)
        live = run.live["app-a"]
        run.release("app-a", live[:2] + live[-2:])  # two hard-slot holders
        run.arrive("app-c")
        assert run.driver("app-c")  # compacts first
        run.check_state()
        names = {s["name"] for s in tracer().finished_spans()}
        assert {"executor-reschedule", "soft-reserve", "compact"} <= names
        snap = run.served.registry.snapshot()
        prefix = "foundry.spark.scheduler.softreservation."
        assert snap[prefix + "extra.placed"][0]["value"] == 2 * (HI - LO)
        assert snap[prefix + "compaction.slots"][0]["value"] == 2
        assert snap[prefix + "compaction.time"][0]["count"] == 1
        rec = run.served.app.recorder.query(limit=200)
        assert any(r["role"] == "executor" and "solve_ms" in r["phases"] for r in rec)
    finally:
        run.served.app.stop()


def test_compaction_moves_the_slot_to_the_soft_executors_node():
    """Upstream binds the freed slot on the compacted executor's node: a
    slot left on the dead executor's node would leave the live one's node
    unreserved and free the slot for a second executor."""
    run = Run(13, 2, 1, 3, shapes=((4, 16),))
    try:
        run.arrive("app-move")
        assert run.driver("app-move")
        run.executors("app-move", 2)  # the hard slot beside the driver, an extra elsewhere
        hard, extra = run.live["app-move"]
        node_of = {p: run.served.backend.get("pods", NS, p).node_name for p in (hard, extra)}
        assert node_of[hard] != node_of[extra]
        run.release("app-move", [hard])
        run.arrive("app-next")
        run.driver("app-next")  # compacts first
        run.check_state()
        assert run.served.slots("app-move") == [(node_of[extra], extra)]
    finally:
        run.served.app.stop()


def test_a_window_of_extra_executors_is_one_spanned_reschedule_pass():
    """Two extra executors in one batcher window take the grouped straggler
    pass (one solve for both) and land where serving them one by one
    would put them."""
    run = Run(19, 24, 2, 6)
    tracer().clear()
    try:
        run.arrive("app-w")
        assert run.driver("app-w")
        run.executors("app-w", 2)
        pods = [pod("app-w", ROLE_EXECUTOR, k=k) for k in (3, 4)]
        want = []
        for p in pods:
            run.served.backend.add_pod(p)
            rung, node = run.ref.executor_request("app-w")
            assert rung == "extra"
            run.ref.place("app-w", p.name, node)
            want.append(run.names[node])
        res = run.served.app.extender.predicate_batch(
            [ExtenderArgs(pod=p, node_names=run.names) for p in pods])
        assert [r.node_names for r in res] == [[n] for n in want]
        assert [s["name"] for s in tracer().finished_spans()].count("executor-reschedule") == 1
    finally:
        run.served.app.stop()


def test_restart_rebuilds_soft_reservations_without_rewriting_reservations():
    """A restart over 1,200 running dynamic-allocation apps whose hard slots
    are all held and whose extra executor is bound with no reservation: the
    reconcile rebuilds each extra's soft reservation and writes no
    reservation back, since none changed. So it ends before the write-back
    workers start, however many apps there are (their queue holds 100
    writes a shard)."""
    names, nodes, _, _ = make_cluster(23, 300, shapes=((32, 128),))
    backend = InMemoryBackend()
    backend.register_crd(DEMAND_CRD)
    for n in nodes:
        backend.add_node(n)
    apps = [f"app-{i:05d}" for i in range(1200)]
    for i, app_id in enumerate(apps):
        node = names[i // 4]
        pods = [pod(app_id, ROLE_DRIVER, lo=2, hi=4)] + [pod(app_id, ROLE_EXECUTOR, k=k) for k in (1, 2, 3)]
        for p in pods:
            p.node_name, p.phase = node, "Running"
            backend.add_pod(p)
        res = spark_resources(pods[0])
        rr = new_resource_reservation(node, [node, node], pods[0],
                                      res.driver_resources, res.executor_resources)
        for j, e in enumerate(pods[1:3]):
            rr.status.pods[executor_reservation_name(j)] = e.name
        backend.create("resourcereservations", rr)
    app = build_scheduler_app(
        backend,
        InstallConfig(fifo=True, binpack_algo="tightly-pack", instance_group_label=GROUP_LABEL),
    )
    out: dict = {}
    worker = threading.Thread(
        target=lambda: out.update(app.reconciler.sync_resource_reservations_and_demands()),
        daemon=True,
    )
    worker.start()
    worker.join(timeout=60)
    assert not worker.is_alive(), "the boot reconcile blocked on the write-back queue"
    try:
        assert out == {"stale_apps": 1200, "created": 0, "patched": 0, "soft_added": 1200}
        soft = app.soft_store.get_all_copy()
        assert {a: set(sr.reservations) for a, sr in soft.items()} == {
            a: {f"{a}-exec-3"} for a in apps
        }
    finally:
        app.stop()
