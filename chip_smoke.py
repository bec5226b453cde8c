"""Chip smoke: the served gang-scheduling path on a TPU, end to end.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the pooled path across four chips

One chip: the on-device golden parity sweep (hack/tpu_parity_smoke.py),
then, for `tightly-pack` and `single-az-tightly-pack`, a scheduler built
the way `python -m spark_scheduler_tpu server` builds it, serving a
seeded 10,000-node cluster over HTTP `POST /predicates`:

  - a sequential phase, every answer checked against the host greedy
    oracle (core/greedy.py) on the same request sequence;
  - a concurrent phase of 16 client threads, so the batcher forms
    multi-request windows;
  - invariants after both: no node over-committed, every admitted gang's
    executors bound to its reserved nodes, every denial carrying a
    per-node failure map.

Four chips: the same seeded sequence served with `device-pool: 4` and
`device-pool: 1` in this one process; the answers must be byte-identical
and the pool must hold 4 slots on 4 distinct devices.

Everything runs in this process, which holds the chip. The run fails,
with a non-zero exit and no `"ok": true` line, when the backend is not a
TPU, when any window of the one-chip phases ran anywhere but the Mosaic
kernel, when degraded mode fired, or when any phase raised. The last
stdout line is the contract line; the lines before it are smoke timings,
not benchmark numbers.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
TIME_LIMIT_S = 1100.0  # the driver allows 1200 s; fail before it kills us

N_NODES = 10_000
ZONES = 4
EXECUTORS = 8
SEQ_GANGS = 100
DENIED_EVERY = 25  # every 25th sequential gang has a driver no node fits
THREADS = 16
GANGS_PER_THREAD = 8
POOL_GANGS = 64
STRATEGIES = ("tightly-pack", "single-az-tightly-pack")
# (cpu, memory) node shapes the seed draws from: general-purpose VM sizes
# at a 1:4 CPU:memory ratio.
NODE_SHAPES = (("4", "16Gi"), ("8", "32Gi"), ("16", "64Gi"), ("32", "128Gi"))


def log(**fields) -> None:
    print(json.dumps(fields, sort_keys=True), flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.stdout.flush()
    os._exit(1)


def _watchdog() -> None:
    time.sleep(TIME_LIMIT_S)
    fail(f"did not finish within {TIME_LIMIT_S:.0f} s")


def quantiles(lat_ms: list[float]) -> dict:
    import numpy as np

    if not lat_ms:
        return {"n": 0}
    a = np.asarray(lat_ms)
    return {
        "n": len(lat_ms),
        "p50_ms": float(np.percentile(a, 50)),
        "p99_ms": float(np.percentile(a, 99)),
    }


def compile_totals() -> dict:
    from spark_scheduler_tpu.observability.telemetry import compile_stats

    return compile_stats()


# ------------------------------------------------------------------ cluster


def make_nodes(seed: int, n_nodes: int):
    import numpy as np

    from spark_scheduler_tpu.models.kube import ZONE_LABEL, Node
    from spark_scheduler_tpu.models.resources import Resources
    from spark_scheduler_tpu.testing.harness import (
        DEFAULT_INSTANCE_GROUP,
        INSTANCE_GROUP_LABEL,
    )

    rng = np.random.default_rng(seed)
    shapes = rng.integers(0, len(NODE_SHAPES), size=n_nodes)
    return [
        Node(
            name=f"node-{i:05d}",
            allocatable=Resources.from_quantities(
                *NODE_SHAPES[shapes[i]], "0", round_up=False
            ),
            labels={
                ZONE_LABEL: f"zone{i % ZONES}",
                INSTANCE_GROUP_LABEL: DEFAULT_INSTANCE_GROUP,
            },
        )
        for i in range(n_nodes)
    ]


class Served:
    """A scheduler app behind its HTTP server, built like the `server`
    command builds it, on an in-memory backend holding `nodes`."""

    def __init__(self, nodes, strategy: str, device_pool: int = 1):
        from spark_scheduler_tpu.events import EventEmitter
        from spark_scheduler_tpu.metrics import (
            MetricRegistry,
            SchedulerMetrics,
            WasteReporter,
        )
        from spark_scheduler_tpu.server.app import build_scheduler_app
        from spark_scheduler_tpu.server.config import InstallConfig
        from spark_scheduler_tpu.server.http import SchedulerHTTPServer
        from spark_scheduler_tpu.store.backend import (
            DEMAND_CRD,
            InMemoryBackend,
        )
        from spark_scheduler_tpu.testing.harness import INSTANCE_GROUP_LABEL

        # examples/extender.yml's install.yml, minus what needs a real
        # cluster (TLS files, kube-api-url, conversion webhook). The
        # request timeout covers a cold Mosaic compile of a new window
        # shape (~30 s at this node bucket).
        config = InstallConfig.from_dict({
            "server": {
                "transport": "threaded",
                "max-connections": 512,
                "shed-queue-depth": 256,
            },
            "fifo": True,
            "binpack-algo": strategy,
            "instance-group-label": INSTANCE_GROUP_LABEL,
            "request-timeout": "600s",
            "solver": {"device-pool": device_pool},
        })
        registry = MetricRegistry()
        self.backend = InMemoryBackend()
        self.backend.register_crd(DEMAND_CRD)
        for node in nodes:
            self.backend.add_node(node)
        self.app = build_scheduler_app(
            self.backend,
            config,
            metrics=SchedulerMetrics(registry, config.instance_group_label),
            events=EventEmitter(
                instance_group_label=config.instance_group_label
            ),
            waste=WasteReporter(registry, config.instance_group_label),
        )
        self.server = SchedulerHTTPServer(
            self.app,
            registry,
            host="127.0.0.1",
            port=0,
            request_timeout_s=config.request_timeout_s,
        )
        self.server.start()
        self.node_names = [n.name for n in nodes]
        self._local = threading.local()

    def post(self, pod) -> tuple[bytes, float]:
        """POST /predicates for `pod` over a per-thread keep-alive
        connection; returns (raw response body, latency ms)."""
        from spark_scheduler_tpu.server.kube_io import pod_to_k8s

        conn = getattr(self._local, "conn", None)
        if conn is None:
            conn = http.client.HTTPConnection(
                "127.0.0.1", self.server.port, timeout=900
            )
            self._local.conn = conn
        body = json.dumps(
            {"Pod": pod_to_k8s(pod), "NodeNames": self.node_names}
        ).encode()
        t0 = time.perf_counter()
        conn.request("POST", "/predicates", body=body)
        resp = conn.getresponse()
        raw = resp.read()
        ms = (time.perf_counter() - t0) * 1e3
        if resp.status != 200:
            raise RuntimeError(f"{pod.name}: HTTP {resp.status}: {raw[:300]!r}")
        return raw, ms

    def stop(self) -> None:
        self.server.stop()


class Gang:
    """One Spark application: a driver and its executors."""

    def __init__(self, app_id: str, infeasible: bool = False):
        from spark_scheduler_tpu.testing.harness import (
            static_allocation_spark_pods,
        )

        self.app_id = app_id
        self.pods = static_allocation_spark_pods(app_id, EXECUTORS)
        if infeasible:
            # A driver larger than any node shape: the gang cannot fit.
            from spark_scheduler_tpu.core.sparkpods import DRIVER_MEMORY

            self.pods[0].annotations[DRIVER_MEMORY] = "1Ti"
        self.driver_node: str | None = None
        self.executor_nodes: list[str] = []
        self.denied = False
        self.raw: list[bytes] = []

    @property
    def driver(self):
        return self.pods[0]


def schedule_gang(served: Served, gang: Gang, lat_ms: list) -> None:
    """Drive one gang through the extender the way kube-scheduler and the
    Spark driver do: create and schedule the driver; once it is bound,
    create and schedule each executor. A denied driver is deleted (its
    client gives up), so it does not hold the FIFO queue."""
    backend = served.backend
    backend.add_pod(gang.driver)
    raw, ms = served.post(gang.driver)
    lat_ms.append(ms)
    gang.raw.append(raw)
    resp = json.loads(raw)
    if resp.get("Error"):
        raise RuntimeError(f"{gang.app_id} driver: {resp['Error']}")
    if not resp["NodeNames"]:
        failed = resp.get("FailedNodes") or {}
        if set(failed) != set(served.node_names):
            raise RuntimeError(
                f"{gang.app_id}: denial without a per-node failure map "
                f"({len(failed)} of {len(served.node_names)} nodes)"
            )
        gang.denied = True
        backend.delete_pod(gang.driver)
        return
    gang.driver_node = resp["NodeNames"][0]
    backend.bind_pod(gang.driver, gang.driver_node)
    for pod in gang.pods[1:]:
        backend.add_pod(pod)
        raw, ms = served.post(pod)
        lat_ms.append(ms)
        gang.raw.append(raw)
        resp = json.loads(raw)
        if resp.get("Error") or len(resp["NodeNames"]) != 1:
            raise RuntimeError(f"{pod.name}: not placed: {resp}")
        backend.bind_pod(pod, resp["NodeNames"][0])
        gang.executor_nodes.append(resp["NodeNames"][0])


# ------------------------------------------------------------------- oracle


class Oracle:
    """The host greedy oracle over the same cluster: availability is
    allocatable less every admitted gang's reservation, and each request
    packs through core/greedy.py's strategy entry point."""

    def __init__(self, nodes, strategy: str):
        import numpy as np

        self.strategy = strategy
        self.names = [n.name for n in nodes]
        self.zones = [n.zone for n in nodes]
        self.alloc = np.stack([n.allocatable.as_array() for n in nodes])
        self.avail = self.alloc.astype(np.int64)
        n = len(nodes)
        self.ones = np.ones(n, bool)
        self.zeros = np.zeros(n, bool)

    def decide(self, gang: Gang):
        from spark_scheduler_tpu.core.greedy import greedy_strategy_pack
        from spark_scheduler_tpu.core.sparkpods import spark_resources

        res = spark_resources(gang.driver)
        drv = res.driver_resources.as_array()
        exc = res.executor_resources.as_array()
        d, ex, ok = greedy_strategy_pack(
            self.strategy,
            avail=self.avail,
            schedulable=self.alloc,
            zone_of=self.zones,
            names=self.names,
            valid=self.ones,
            unschedulable=self.zeros,
            ready=self.ones,
            label_rank_driver=None,
            label_rank_executor=None,
            cand_mask=self.ones,
            domain_mask=self.ones,
            driver_req=drv,
            exec_req=exc,
            count=res.min_executor_count,
        )
        if ok:
            self.avail[d] -= drv
            for e in ex:
                self.avail[e] -= exc
        return (
            (self.names[d], sorted(self.names[e] for e in ex)) if ok else None
        )


# --------------------------------------------------------------- invariants


def check_invariants(served: Served, gangs: list[Gang]) -> dict:
    """No node over-committed (by bound pods AND by the scheduler's own
    reservation accounting), and every admitted gang's executors bound to
    the nodes its reservation holds."""
    import numpy as np

    from spark_scheduler_tpu.testing.harness import overcommit_violations

    nodes = served.backend.list_nodes()
    index = {n.name: i for i, n in enumerate(nodes)}
    alloc = np.stack([n.allocatable.as_array() for n in nodes]).astype(
        np.int64
    )
    used = np.zeros_like(alloc)
    for pod in served.backend.list_pods():
        if pod.node_name:
            used[index[pod.node_name]] += pod.request().as_array()
    over = int(np.any(used > alloc, axis=1).sum())
    if over:
        raise RuntimeError(f"{over} nodes over-committed by bound pods")
    violations = overcommit_violations(served.app, served.backend)
    if violations:
        raise RuntimeError(f"reservation over-commit: {violations[:5]}")
    admitted = 0
    for gang in gangs:
        if gang.denied:
            continue
        admitted += 1
        rr = served.app.rr_cache.get(gang.driver.namespace, gang.app_id)
        if rr is None:
            raise RuntimeError(f"{gang.app_id}: admitted without reservation")
        slots = rr.spec.reservations
        if slots["driver"].node != gang.driver_node:
            raise RuntimeError(f"{gang.app_id}: driver off its reservation")
        reserved = sorted(r.node for k, r in slots.items() if k != "driver")
        if reserved != sorted(gang.executor_nodes):
            raise RuntimeError(
                f"{gang.app_id}: executors {sorted(gang.executor_nodes)} "
                f"!= reserved {reserved}"
            )
    return {"admitted_gangs": admitted, "overcommitted_nodes": over}


def check_device_path(served: Served, allowed: set) -> dict:
    """Every window ran on an allowed device path and degraded mode never
    fired."""
    solver = served.app.solver
    counts = dict(solver.window_path_counts)
    bad = {p: c for p, c in counts.items() if p not in allowed}
    if bad or not counts:
        raise RuntimeError(f"window_path_counts {counts}: want only {allowed}")
    d = solver.degraded
    if d is not None and (d.active or d.engagements):
        raise RuntimeError(f"degraded mode fired: {d.snapshot()}")
    return counts


# ------------------------------------------------------------------- phases


def sequential_phase(served: Served, gangs: list[Gang], oracle=None) -> dict:
    lat: list[float] = []
    t0 = time.perf_counter()
    for gang in gangs:
        want = oracle.decide(gang) if oracle is not None else None
        schedule_gang(served, gang, lat)
        if oracle is None:
            continue
        got = (
            None if gang.denied
            else (gang.driver_node, sorted(gang.executor_nodes))
        )
        if got != want:
            raise RuntimeError(
                f"{gang.app_id}: served {got}, greedy oracle {want}"
            )
    return {"wall_s": time.perf_counter() - t0, **quantiles(lat)}


def concurrent_phase(served: Served, per_thread: list[list[Gang]]) -> dict:
    lat: list[float] = []
    errors: list[BaseException] = []
    lock = threading.Lock()

    def client(gangs):
        mine: list[float] = []
        try:
            for gang in gangs:
                schedule_gang(served, gang, mine)
        except BaseException as exc:  # surfaced below, never swallowed
            errors.append(exc)
        with lock:
            lat.extend(mine)

    threads = [threading.Thread(target=client, args=(g,)) for g in per_thread]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return {"wall_s": wall, **quantiles(lat)}


def run_strategy(nodes, strategy: str, *, device_path: set | None) -> None:
    """Both served phases for one strategy on a fresh server."""
    t0 = time.perf_counter()
    c0 = compile_totals()
    served = Served(nodes, strategy)
    try:
        boot_s = time.perf_counter() - t0
        oracle = Oracle(nodes, strategy)
        tag = "saz" if strategy.startswith("single-az") else "tp"
        seq = [
            Gang(f"{tag}-seq-{i}", infeasible=i % DENIED_EVERY == DENIED_EVERY - 1)
            for i in range(SEQ_GANGS)
        ]
        seq_stats = sequential_phase(served, seq, oracle)
        if not any(g.denied for g in seq):
            raise RuntimeError("the sequential phase produced no denial")
        conc = [
            [Gang(f"{tag}-c{t}-{i}") for i in range(GANGS_PER_THREAD)]
            for t in range(THREADS)
        ]
        conc_stats = concurrent_phase(served, conc)
        gangs = seq + [g for grp in conc for g in grp]
        inv = check_invariants(served, gangs)
        batcher = served.server.batcher.stats()
        if batcher["max_window_seen"] < 2:
            raise RuntimeError("the concurrent phase formed no multi-request window")
        paths = (
            check_device_path(served, device_path)
            if device_path is not None
            else dict(served.app.solver.window_path_counts)
        )
        c1 = compile_totals()
        log(
            phase="served",
            strategy=strategy,
            nodes=len(nodes),
            gangs_served=len(gangs),
            gangs_denied=sum(g.denied for g in gangs),
            decisions=sum(len(g.raw) for g in gangs),
            boot_s=boot_s,
            sequential=seq_stats,
            concurrent=conc_stats,
            oracle_checked_gangs=len(seq),
            windows_served=batcher["windows_served"],
            max_window_seen=batcher["max_window_seen"],
            mean_window=batcher["mean_window"],
            window_path_counts=paths,
            compiles=c1["count"] - c0["count"],
            compile_s=c1["seconds"] - c0["seconds"],
            wall_s=time.perf_counter() - t0,
            timings="smoke timings, not benchmark numbers",
            **inv,
        )
    finally:
        served.stop()


def run_pool(nodes, chips: int) -> None:
    """The pooled path across `chips` devices against device-pool 1, on
    the same seeded sequence: byte-identical answers."""
    import jax

    results = {}
    for pool in (chips, 1):
        c0 = compile_totals()
        served = Served(nodes, "tightly-pack", device_pool=pool)
        try:
            solver = served.app.solver
            if solver.pool_size != pool:
                raise RuntimeError(
                    f"device-pool {pool} built {solver.pool_size} slots "
                    f"on {len(jax.devices())} devices"
                )
            if pool > 1:
                ids = sorted({s.placement.id for s in solver._pool.slots})
                if len(ids) != pool:
                    raise RuntimeError(f"pool slots share devices: {ids}")
            gangs = [
                Gang(f"pool-{i}", infeasible=i % DENIED_EVERY == DENIED_EVERY - 1)
                for i in range(POOL_GANGS)
            ]
            stats = sequential_phase(served, gangs)
            inv = check_invariants(served, gangs)
            # A pooled server sends its pipelined windows round the pool
            # (the XLA scan); any other window takes the Mosaic path.
            allowed = {"pool", "pallas"} if pool > 1 else {"pallas"}
            paths = check_device_path(served, allowed)
            if pool > 1 and not paths.get("pool"):
                raise RuntimeError(f"no window ran on the pool: {paths}")
            c1 = compile_totals()
            results[pool] = [raw for g in gangs for raw in g.raw]
            log(
                phase="pool",
                device_pool=pool,
                slots=(
                    [s.label for s in solver._pool.slots] if pool > 1 else None
                ),
                gangs_served=len(gangs),
                decisions=len(results[pool]),
                window_path_counts=paths,
                compiles=c1["count"] - c0["count"],
                compile_s=c1["seconds"] - c0["seconds"],
                timings="smoke timings, not benchmark numbers",
                **stats,
                **inv,
            )
        finally:
            served.stop()
    if results[chips] != results[1]:
        diff = next(
            i for i, (a, b) in enumerate(zip(results[chips], results[1]))
            if a != b
        )
        raise RuntimeError(
            f"device-pool {chips} and device-pool 1 differ at decision {diff}"
        )
    log(phase="pool-compare", decisions=len(results[1]), byte_identical=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    threading.Thread(target=_watchdog, daemon=True).start()
    t_start = time.perf_counter()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX backend is {devices[0].platform}")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} devices")
    sys.path.insert(0, REPO)
    from spark_scheduler_tpu.server.config import InstallConfig
    from spark_scheduler_tpu.tracing import svc1log

    cache_dir = InstallConfig.enable_jax_compile_cache()
    # runtime.yml's `logging.level`: per-predicate INFO lines would bury
    # any warning in the thousands of requests below.
    svc1log().set_level("WARN")
    log(
        phase="devices",
        devices=[str(d) for d in devices],
        kind=devices[0].device_kind,
        compile_cache_dir=cache_dir,
    )
    nodes = make_nodes(args.seed, N_NODES)
    if args.chips == 1:
        sys.path.insert(0, os.path.join(REPO, "hack"))
        import tpu_parity_smoke

        t0 = time.perf_counter()
        c0 = compile_totals()
        parity = tpu_parity_smoke.run(require_tpu=True)
        c1 = compile_totals()
        log(
            phase="parity",
            cases_checked=parity["cases_checked"],
            wall_s=time.perf_counter() - t0,
            compiles=c1["count"] - c0["count"],
            compile_s=c1["seconds"] - c0["seconds"],
        )
        for strategy in STRATEGIES:
            run_strategy(nodes, strategy, device_path={"pallas"})
    else:
        run_pool(nodes, args.chips)
    total = compile_totals()
    log(
        phase="total",
        wall_s=time.perf_counter() - t_start,
        compiles=total["count"],
        compile_s=total["seconds"],
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException as exc:  # any phase that raised fails the run
        import traceback

        traceback.print_exc()
        fail(f"{type(exc).__name__}: {exc}")
    sys.stdout.flush()
    os._exit(code)
