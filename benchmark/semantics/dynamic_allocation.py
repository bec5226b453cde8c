"""Spark dynamic allocation: a driver is admitted with its min executors
hard-reserved, executors beyond min (up to max) arrive in rounds that
double and ride soft reservations, and an app that scales down leaves
hard slots that its live soft-reserved executors take over. The world is
cluster.py's pool with running dynamic-allocation apps, the load a
kube-scheduler serial loop over apps that ramp up and scale down, and
the reference reference_da.py's.

Checks beyond the harness's, each held to 0:

  extra_executor_mismatches    extra executor answers (node or denial)
                               that differ from the reference's;
  soft_reservation_mismatches  apps whose soft reservations (pod -> node)
                               differ, in a snapshot after the boot
                               reconcile and one as the run closes;
  compaction_mismatches        in the same snapshots, apps whose hard
                               slots, as a multiset of (node, live
                               holder), differ, and nodes whose reserved
                               usage (drivers, hard slots and soft
                               reservations) differs.

The harness's `apps_off_reservation` holds bound executors to hard-slot
nodes, so `expected_reservations()` names only the apps whose live
executors all hold hard slots: every app, with or without extras, is held
exactly by the snapshots.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time

import numpy as np

import check
import cluster as C
import reference as R
import reference_da as D
from loadgen import LoadGen
from semantics import World

EXTRA_FIT = "not enough capacity to reschedule the executor"
NO_SPOTS = "application has no free executor spots to schedule this one"
# The program boots on the world (served.Served: load, reconcile, serve)
# between build_world and make_generator. A boot that has not ended by
# then never will: a reconcile that writes back more reservations than
# the write-back queue holds before its workers start (100 a shard, five
# shards) blocks for good. The run then exits with this code.
BOOT_LIMIT_S = 120.0
BOOT_STUCK_EXIT = 3


def _gang(config: dict) -> tuple[C.Gang, int, int, list[int]]:
    g, da = config["gang"], config["dynamic_allocation"]
    lo, hi = int(da["min_executors"]), int(da["max_executors"])
    gang = C.Gang(
        driver=np.array([C.cpu_milli(g["driver"]["cpu"]), C.mem_bytes(g["driver"]["memory"])], np.int64),
        executor=np.array([C.cpu_milli(g["executor"]["cpu"]), C.mem_bytes(g["executor"]["memory"])], np.int64),
        count=lo,
    )
    ramp = [int(r) for r in da["ramp"]]
    if sum(ramp) != hi - lo:
        raise ValueError(f"ramp {ramp} does not add up to max - min = {hi - lo}")
    return gang, lo, hi, ramp


class PodFactory(C.PodFactory):
    """cluster.PodFactory with the dynamic-allocation annotations in place
    of `spark-executor-count`."""

    def __init__(self, config: dict, start: int, lo: int, hi: int):
        cfg = {**config, "gang": {**config["gang"], "executors": lo}}
        super().__init__(cfg, start)
        del self._driver_ann["spark-executor-count"]
        self._driver_ann.update({
            "spark-dynamic-allocation-enabled": "true",
            "spark-dynamic-allocation-min-executor-count": str(lo),
            "spark-dynamic-allocation-max-executor-count": str(hi),
        })


def build_world(config: dict, traffic: dict, seed: int, now: float) -> World:
    """The pool at `prefill.cpu_share` of its vCPU with running apps, the
    older half scaled down to max - `release` executors and the younger
    half at max, each placed as the reference would: the driver with its
    min executors as a gang, then each extra executor on the first node
    in the order (taken once per app) where it fits. Extras are bound pods
    with no reservation: the program's boot reconcile rebuilds them."""
    cl = C.make_cluster(config, seed)
    gang, lo, hi, _ = _gang(config)
    ref = R.Reference(cl.alloc, cl.zone, len(cl.zone_names), config["install"]["binpack-algo"], gang)
    scaled = hi - int(traffic["release"])
    mean_execs = (scaled + hi) / 2
    app_cpu = float(gang.driver[0] + mean_execs * gang.executor[0])
    n_apps = round(traffic["prefill"]["cpu_share"] * int(cl.alloc[:, 0].sum()) / app_cpu)
    older = n_apps // 2
    avail = ref.alloc.copy()
    layout = []  # (driver, hard slot nodes, extra nodes)
    for i in range(n_apps):
        key = ref.order_key(avail)
        ps = ref.pack(avail, key)
        if not ps:
            raise RuntimeError(f"only {i} of {n_apps} apps fit")
        ref.apply(avail, ps[0], +1)
        extras = []
        for _ in range((scaled if i < older else hi) - lo):
            fit = R.fits(avail, gang.executor)
            if not fit.any():
                raise RuntimeError(f"app {i}'s extra executors do not fit")
            node = int(np.argmin(np.where(fit, key, R.BIG)))
            avail[node] -= gang.executor
            extras.append(node)
        layout.append((ps[0].driver, list(ps[0].executors), extras))
    n_pods = sum(1 + len(h) + len(x) for _, h, x in layout)
    factory = PodFactory(config, int(now) - int(traffic["pending_age_s"]) - n_pods, lo, hi)
    pods, apps, running = [], [], []
    for i, (driver, hard, extras) in enumerate(layout):
        app = f"app-{i:07d}"
        d = factory.driver(app)
        pods.append(C.bound(d, cl.names[driver]))
        hard_pods, extra_pods = [], []
        for k, node in enumerate(hard + extras):
            ex = factory.executor(app, k)
            pods.append(C.bound(ex, cl.names[node]))
            (hard_pods if k < lo else extra_pods).append(ex["metadata"]["name"])
        apps.append((d["metadata"]["name"], cl.names[driver], [cl.names[n] for n in hard], hard_pods))
        running.append({"app": app, "driver": d["metadata"]["name"], "hard": hard_pods,
                        "extras": extra_pods, "full": i >= older})
    nodes = [C.node_json(cl, i, config) for i in range(cl.n)]
    watch = threading.Timer(BOOT_LIMIT_S, _boot_stuck, args=(len(apps),))
    watch.daemon = True
    watch.start()
    return World(
        nodes=nodes, pods=pods, apps=apps, n_nodes=cl.n, executors=lo,
        running=running, pending=[],
        own={"cluster": cl, "reference": ref, "layout": layout, "factory": factory,
             "min": lo, "max": hi, "seed": seed, "boot_watch": watch},
    )


def _boot_stuck(n_apps: int) -> None:
    print(f"dynamic_allocation: the program had not booted on {n_apps} running apps after "
          f"{BOOT_LIMIT_S:.0f} s (its boot reconcile is blocked); exiting {BOOT_STUCK_EXIT}",
          file=sys.stderr, flush=True)
    os._exit(BOOT_STUCK_EXIT)


class Gen(LoadGen):
    """kube-scheduler's serial loop over dynamic-allocation apps. A cycle:
    the oldest app ends; the oldest app at max releases `release` of its
    executors, picked by the seed, by pod deletion; a new driver arrives
    and is admitted; its min executors bind; its extra executors arrive in
    the configured rounds, each round's pods created before its calls."""

    def __init__(self, world: World, config: dict, traffic: dict, served, annotate):
        own = world.own
        self.lo, self.hi = own["min"], own["max"]
        _, _, _, self.ramp = _gang(config)
        cfg = {**config, "gang": {**config["gang"], "executors": self.lo}}
        super().__init__(own["cluster"], cfg, traffic, own["factory"], served, [], [],
                         annotate=annotate)
        self.release = int(traffic["release"])
        self.rng = np.random.default_rng([own["seed"] % (1 << 63), 1])
        self.live: dict[str, list[str]] = {}  # app -> live pod names, driver first
        self.full: collections.deque[str] = collections.deque()
        for r in world.running:
            self.live[r["app"]] = [r["driver"], *r["hard"], *r["extras"]]
            self.running.append((r["app"], self.live[r["app"]]))
            if r["full"]:
                self.full.append(r["app"])
        self.next_app = len(world.running)
        self.snapshot("boot")

    # ------------------------------------------------------- the world

    def snapshot(self, when: str) -> None:
        """What the program holds now, for the replay: every app's soft
        reservations, each running app's hard slots as (node, live
        holder), and the reserved usage of every node."""
        t0 = time.perf_counter()
        app = self.served.app
        soft = {a: {pod: r.node for pod, r in sr.reservations.items()}
                for a, sr in app.soft_store.get_all_copy().items()}
        hard = {}
        for a, pods in self.live.items():
            rr = app.rr_cache.get(self.served.namespace, a)
            if rr is None:
                hard[a] = None
                continue
            alive = set(pods)
            hard[a] = _slots(
                (r.node, rr.status.pods.get(k) if rr.status.pods.get(k) in alive else None)
                for k, r in rr.spec.reservations.items() if k != "driver"
            )
        usage = {n: (int(r.cpu_milli), int(r.mem_kib))
                 for n, r in app.reservation_manager.get_reserved_resources().items()}
        self.log.append(("snapshot", when, soft, hard, usage))
        self.watch_s += time.perf_counter() - t0

    def close(self) -> None:
        self.snapshot("close")
        super().close()

    def expected_reservations(self) -> dict[str, tuple[str, list[str]]]:
        """Apps admitted in this run with no extra executor yet."""
        return self.admitted

    def _complete_oldest(self) -> None:
        app = self.running[0][0]
        super()._complete_oldest()
        del self.live[app]
        if app in self.full:
            self.full.remove(app)

    def _scale_down(self) -> None:
        if not self.full:
            return
        app = self.full.popleft()
        execs = self.live[app][1:]
        picked = sorted(self.rng.choice(len(execs), size=min(self.release, len(execs)),
                                        replace=False))
        gone = [execs[i] for i in picked]
        for name in gone:
            self._watch(self.served.delete_pod, name)
            self.live[app].remove(name)
        self.log.append(("release", app, tuple(gone)))

    # ------------------------------------------------------- the calls

    def _executor(self, app: str, pod: dict) -> None:
        t0 = time.perf_counter()
        body = C.predicate_body(pod, self.cluster)
        self.client_s += time.perf_counter() - t0
        got = self._node_of(self._post("executor", body))
        name = pod["metadata"]["name"]
        node = got if isinstance(got, str) else None
        if node is not None:
            self._watch(self.served.bind, name, node)
            self.live[app].append(name)
        self.log.append(("exec", app, name, node, got if isinstance(got, bytes) else None))

    def _ramp(self, app: str) -> None:
        made = 0
        for size in [self.lo, *self.ramp]:
            pods = [self.factory.executor(app, made + k) for k in range(size)]
            made += size
            for pod in pods:
                self._watch(self.served.create_pod, pod)
            for pod in pods:
                self._executor(app, pod)
                if made > self.lo:
                    self.admitted.pop(app, None)
        self.full.append(app)

    def cycle(self) -> None:
        if self.running:
            self._complete_oldest()
        self._scale_down()
        if not self.pending:
            self._arrive()
        for app in [app for app in list(self.pending) if self._driver(app)]:
            self.running.append((app, self.live.setdefault(app, [f"{app}-driver"])))
            self._ramp(app)


def make_generator(world: World, config: dict, traffic: dict, served, annotate) -> Gen:
    world.own["boot_watch"].cancel()
    return Gen(world, config, traffic, served, annotate)


def _reasons(bodies: dict[bytes, bytes]) -> dict[bytes, str | None]:
    """The one failure reason of each denial body (None where it has not
    exactly one)."""
    out = {}
    for digest, raw in bodies.items():
        try:
            reasons = set(json.loads(raw)["FailedNodes"].values())
        except (ValueError, KeyError, TypeError, AttributeError):
            reasons = set()
        out[digest] = next(iter(reasons)) if len(reasons) == 1 else None
    return out


def _slots(slots) -> list[tuple[str, str | None]]:
    """Hard slots as a multiset of (node, live holder or None)."""
    return sorted(slots, key=lambda s: (s[0], s[1] or ""))


def _compare(state: D.DAState, names: list[str], snap: tuple) -> tuple[int, int]:
    """(soft reservation mismatches, compaction mismatches) of one
    snapshot against the reference's state at that point."""
    _, _, soft, hard, usage = snap
    want_soft = {a: {pod: names[n] for pod, n in s.items()} for a, s in state.soft.items()}
    soft_bad = sum(soft.get(a) != want_soft.get(a) for a in set(soft) | set(want_soft))
    hard_bad = 0
    for a in set(hard) | set(state.slots):
        want = state.slots.get(a)
        want = None if want is None else _slots((names[n], h) for n, h in want)
        hard_bad += hard.get(a) != want
    res = state.reserved_usage()
    for i, name in enumerate(names):
        want_use = (int(res[i, 0]), int(res[i, 1]) // 1024)
        hard_bad += tuple(usage.get(name, (0, 0))) != want_use
    return soft_bad, hard_bad


def replay(world: World, log: list[tuple], bodies: dict[bytes, bytes]) -> dict:
    own = world.own
    names = own["cluster"].names
    index = {n: i for i, n in enumerate(names)}
    lo = own["min"]
    state = D.DAState(own["reference"], lo, own["max"])
    for i, (driver, hard, extras) in enumerate(own["layout"]):
        app = f"app-{i:07d}"
        state.hold(app, driver, hard, holders=[f"{app}-exec-{k + 1}" for k in range(lo)],
                   soft={f"{app}-exec-{lo + k + 1}": n for k, n in enumerate(extras)})
    kinds, bad_maps = check.denial_kinds(bodies, names)
    reasons = _reasons(bodies)
    out = {
        "driver_mismatches": 0, "executor_mismatches": 0, "denials_bad_map": bad_maps,
        "drivers_checked": 0, "executors_checked": 0, "extras_checked": 0,
        "extras_denied": 0, "denials": 0, "admits": 0, "zone_ties": 0,
        "released": 0, "compacted_slots": 0, "snapshots": 0,
    }
    extra_bad = soft_bad = hard_bad = 0
    for ev in log:
        kind = ev[0]
        if kind in ("driver", "exec"):
            out["compacted_slots"] += state.compact()
        if kind == "arrive":
            state.arrive(ev[1])
        elif kind == "complete":
            state.complete(ev[1])
        elif kind == "release":
            for pod in ev[2]:
                state.release(ev[1], pod)
            out["released"] += len(ev[2])
        elif kind == "driver":
            _, app, node, execs, digest = ev
            want = state.decide_driver(app)
            out["drivers_checked"] += 1
            out["zone_ties"] += len(want.placements) > 1
            if node is None:
                out["denials"] += 1
                if want.deny is None or kinds.get(digest) != want.deny:
                    out["driver_mismatches"] += 1
                continue
            out["admits"] += 1
            slots = [index[e] for e in execs or []]
            got = R.Placement(index[node], tuple(sorted(slots)))
            if got not in want.placements or len(slots) != lo:
                out["driver_mismatches"] += 1
            state.admit(app, index[node], slots)
        elif kind == "exec":
            _, app, pod, node, digest = ev
            rung, want = state.executor(app)
            got = None if node is None else index[node]
            if rung == D.BOUND:
                out["executors_checked"] += 1
                out["executor_mismatches"] += got != want
            else:
                out["extras_checked"] += 1
                if want is None:
                    out["extras_denied"] += 1
                    expect = EXTRA_FIT if rung == D.EXTRA else NO_SPOTS
                    extra_bad += got is not None or reasons.get(digest) != expect
                else:
                    extra_bad += got != want
            # The replay follows the served answer.
            if got is not None and not state.bind(app, got, pod):
                state.soft_reserve(app, pod, got)
        elif kind == "snapshot":
            out["snapshots"] += 1
            s, h = _compare(state, names, ev)
            soft_bad += s
            hard_bad += h
    out["reference_overcommitted_nodes"] = state.overcommitted()
    out["checks"] = {
        "extra_executor_mismatches": extra_bad,
        "soft_reservation_mismatches": soft_bad,
        "compaction_mismatches": hard_bad,
    }
    return out


# Planted faults and a control, for harness.run_cell(overrides=..., tamper=...):
# each entry is (overrides, tamper(served)).


def _compaction_skipped(served) -> None:
    served.app.reservation_manager.compact_dynamic_allocation_applications = lambda: None


def _soft_write_dropped(served) -> None:
    served.app.soft_store.add_reservation_for_pod = lambda app_id, pod_name, reservation: None


def _extra_next_node(served) -> None:
    """Each extra executor goes on the next node with room after the one
    the solve chose: the same solve with that node left out."""
    ext = served.app.extender
    solve = ext._reschedule_executor

    def moved(executor, node_names, is_extra, **kw):
        node, outcome, msg = solve(executor, node_names, is_extra, **kw)
        if node is None or not is_extra:
            return node, outcome, msg
        alt = solve(executor, [n for n in node_names if n != node], is_extra, **kw)
        return alt if alt[0] is not None else (node, outcome, msg)

    ext._reschedule_executor = moved


FAULTS = {
    "compaction-skipped": ({}, _compaction_skipped),
    "soft-write-dropped": ({}, _soft_write_dropped),
    "extra-next-node": ({}, _extra_next_node),
}
CONTROLS = {
    "distribute-evenly": ({"program": {"binpack-algo": "distribute-evenly"}}, None),
}
