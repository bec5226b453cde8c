"""In-process serving latency on a LOCAL jax backend (VERDICT r4 #7).

The HTTP solo-predicate p50 includes the HTTP hop and the decision
pull. This script runs the SAME serving path — predicate_batch -> window
solve -> reservation write-back — entirely in process against the CPU
backend, so the per-call cost is the solve and host cycle itself.

Run by bench.py as a subprocess (one JSON line on stdout); standalone:
    python hack/inprocess_bench.py
"""

from __future__ import annotations

import jax

jax.config.update("jax_platforms", "cpu")  # before any jax op

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np


def main() -> int:
    from spark_scheduler_tpu.core.extender import ExtenderArgs
    from spark_scheduler_tpu.server.app import build_scheduler_app
    from spark_scheduler_tpu.server.config import InstallConfig
    from spark_scheduler_tpu.store.backend import InMemoryBackend
    from spark_scheduler_tpu.testing.harness import (
        INSTANCE_GROUP_LABEL,
        new_node,
        static_allocation_spark_pods,
    )
    from spark_scheduler_tpu.tracing import Svc1Logger, set_svc1log

    set_svc1log(Svc1Logger(stream=open(os.devnull, "w")))
    n_nodes = int(os.environ.get("INPROC_NODES", "500"))
    backend = InMemoryBackend()
    names = []
    for i in range(n_nodes):
        node = new_node(f"n{i}", zone=f"zone{i % 4}")
        backend.add_node(node)
        names.append(node.name)
    app = build_scheduler_app(
        backend,
        InstallConfig(
            fifo=True, sync_writes=True,
            instance_group_label=INSTANCE_GROUP_LABEL,
        ),
    )
    ext = app.extender
    lats = []
    n_requests, warmup = 48, 8  # warmup covers the row-bucket compiles
    for i in range(n_requests):
        driver = static_allocation_spark_pods(f"ip-{i}", 8)[0]
        backend.add_pod(driver)
        t0 = time.perf_counter()
        res = ext.predicate_batch(
            [ExtenderArgs(pod=driver, node_names=list(names))]
        )[0]
        dt_ms = (time.perf_counter() - t0) * 1e3
        if not res.node_names:
            raise RuntimeError(f"in-process request {i} failed: {res}")
        backend.bind_pod(driver, res.node_names[0])
        if i >= warmup:
            lats.append(dt_ms)
    print(
        json.dumps(
            {
                "p50_ms": round(float(np.percentile(lats, 50)), 3),
                "p95_ms": round(float(np.percentile(lats, 95)), 3),
                "n": len(lats),
                "nodes": n_nodes,
                "device": str(jax.devices()[0]),
                "fused_k": 1,
                "path": "in-process predicate_batch (no HTTP)",
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
