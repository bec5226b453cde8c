"""Profile the HOST-side cost of one serving window cycle
(predicate_window_dispatch + predicate_window_complete), bench-shaped:
500 nodes, FIFO on, windows of 32 drivers x 8 executors.

Run: python hack/profile_window_host.py [--windows N] [--window-size K]
CPU-pinned (jax_platforms=cpu) — with the device round trip hidden by the
pipeline, host work is what bounds serving throughput
(VERDICT r3 weak #1).
"""

import argparse
import cProfile
import io
import pstats
import sys
import time

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, ".")

from spark_scheduler_tpu.core.extender import ExtenderArgs  # noqa: E402
from spark_scheduler_tpu.server.app import build_scheduler_app  # noqa: E402
from spark_scheduler_tpu.server.config import InstallConfig  # noqa: E402
from spark_scheduler_tpu.store.backend import InMemoryBackend  # noqa: E402
from spark_scheduler_tpu.testing.harness import (  # noqa: E402
    INSTANCE_GROUP_LABEL,
    new_node,
    static_allocation_spark_pods,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--windows", type=int, default=10)
    ap.add_argument("--window-size", type=int, default=32)
    ap.add_argument("--nodes", type=int, default=500)
    ap.add_argument("--execs", type=int, default=8)
    ap.add_argument("--sort", default="cumulative")
    ap.add_argument("--limit", type=int, default=45)
    args = ap.parse_args()

    backend = InMemoryBackend()
    node_names = []
    for i in range(args.nodes):
        n = new_node(f"bench-n{i}", zone=f"zone{i % 4}")
        backend.add_node(n)
        node_names.append(n.name)
    app = build_scheduler_app(
        backend,
        InstallConfig(
            fifo=True, sync_writes=True, instance_group_label=INSTANCE_GROUP_LABEL
        ),
    )
    ext = app.extender

    def run_window(tag):
        drivers = []
        for c in range(args.window_size):
            d = static_allocation_spark_pods(f"{tag}-{c}", args.execs)[0]
            backend.add_pod(d)
            drivers.append(d)
        t = ext.predicate_window_dispatch(
            [ExtenderArgs(pod=d, node_names=list(node_names)) for d in drivers]
        )
        results = ext.predicate_window_complete(t)
        for d, r in zip(drivers, results):
            if not r.node_names:
                raise RuntimeError(f"{d.name}: {r.outcome}")
            backend.bind_pod(d, r.node_names[0])

    # Warm: XLA compiles + caches.
    for w in range(3):
        run_window(f"warm-{w}")

    t0 = time.perf_counter()
    pr = cProfile.Profile()
    pr.enable()
    for w in range(args.windows):
        run_window(f"run-{w}")
    pr.disable()
    wall = time.perf_counter() - t0
    print(
        f"== {args.windows} windows x {args.window_size} drivers "
        f"({args.nodes} nodes, fifo): {wall*1e3/args.windows:.1f} ms/window, "
        f"{args.windows*args.window_size/wall:.1f} decisions/s (CPU device)"
    )
    s = io.StringIO()
    ps = pstats.Stats(pr, stream=s).sort_stats(args.sort)
    ps.print_stats(args.limit)
    print(s.getvalue())


if __name__ == "__main__":
    main()
