"""A Spark app is one fixed gang: a driver and the `gang.executors`
executors its annotations ask for, reserved together, bound one by one,
and ended together. The world is cluster.py's, the load loadgen.py's,
and the reference reference.py's, replayed by check.py.
"""

from __future__ import annotations

import check
import cluster as C
import reference as R
from loadgen import LoadGen
from semantics import World


def build_world(config: dict, traffic: dict, seed: int, now: float) -> World:
    """The cluster, the running apps placed by the reference, and the
    pending drivers, all from the seed. Pods are dated so that the first
    pending driver was created `pending_age_s` before `now`: the queue is
    then younger than the unschedulable-pod timeout, as a queue that
    drains is, and the clock reads no run differently from another."""
    cl = C.make_cluster(config, seed)
    gang = C.Gang.from_config(config)
    ref = R.Reference(cl.alloc, cl.zone, len(cl.zone_names), config["install"]["binpack-algo"], gang)
    pre = traffic["prefill"]
    if pre.get("saturate"):
        n_apps = None
    else:
        app_cpu = int(gang.driver[0] + gang.count * gang.executor[0])
        n_apps = round(pre["cpu_share"] * int(cl.alloc[:, 0].sum()) / app_cpu)
    placements = R.prefill(ref, n_apps)
    # Apps end in creation order, so the oldest running app is the first
    # placed.
    n_running_pods = len(placements) * (1 + gang.count)
    factory = C.PodFactory(config, int(now) - int(traffic["pending_age_s"]) - n_running_pods)
    pods, apps, running = [], [], []
    for i, p in enumerate(placements):
        app = f"app-{i:07d}"
        driver = factory.driver(app)
        pods.append(C.bound(driver, cl.names[p.driver]))
        names = [driver["metadata"]["name"]]
        for k, node in enumerate(p.executors):
            ex = factory.executor(app, k)
            pods.append(C.bound(ex, cl.names[node]))
            names.append(ex["metadata"]["name"])
        apps.append((names[0], cl.names[p.driver], [cl.names[e] for e in p.executors], names[1:]))
        running.append((app, names))
    pending = []
    for j in range(int(traffic["pending_target"]) if traffic.get("pending_at_start", True) else 0):
        app = f"app-{len(placements) + j:07d}"
        driver = factory.driver(app)
        pods.append(driver)
        pending.append((app, driver))
    nodes = [C.node_json(cl, i, config) for i in range(cl.n)]
    return World(
        nodes=nodes, pods=pods, apps=apps, n_nodes=cl.n, executors=gang.count,
        running=running, pending=pending,
        own={"cluster": cl, "reference": ref, "placements": placements, "factory": factory},
    )


def make_generator(world: World, config: dict, traffic: dict, served, annotate) -> LoadGen:
    return LoadGen(world.own["cluster"], config, traffic, world.own["factory"], served,
                   world.running, world.pending, annotate=annotate)


def replay(world: World, log: list[tuple], bodies: dict[bytes, bytes]) -> dict:
    own = world.own
    running = [(app, p) for (app, _), p in zip(world.running, own["placements"])]
    return check.replay(own["reference"], own["cluster"].names, running,
                        [app for app, _ in world.pending], log, bodies)
