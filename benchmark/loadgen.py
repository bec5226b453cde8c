"""The general load generator: kube-scheduler's serial scheduling loop and
the Spark drivers behind it, driven by one traffic file.

kube-scheduler runs one scheduling cycle at a time and calls the
extender's filter inside it, so the client is closed and serial: the next
predicate goes out when the last one is answered. A traffic file sets

  prefill.cpu_share          running apps hold this share of the pool's
                             vCPU at the start (or `saturate`: place apps
                             until the next does not fit);
  pending_target             drivers kept pending in FIFO order;
  completions_per_cycle      oldest running apps that end as a cycle opens;
  completions_per_admission  oldest running apps that end after each
                             admitted app's executors are placed;
  warmup_cycles              cycles run before the window, at the least.

A cycle: apps end, new drivers arrive to keep `pending_target` pending,
kube-scheduler retries every pending driver in creation order (as after a
pod deletion moves them back to its active queue), and the executors of
each admitted driver follow. The sequence of requests is a function of the
seed and of the answers, and the log records it for the reference.

Binds, pod creation and app completions reach the scheduler's backend as
the apiserver's watch would deliver them, in this process, between calls.
"""

from __future__ import annotations

import collections
import hashlib
import http.client
import time

from cluster import PodFactory, predicate_body

ADMIT_PREFIX = b'{"NodeNames": ["'
DENY_PREFIX = b'{"NodeNames": [], '


class Stop(Exception):
    """The window's deadline passed."""


class LoadGen:
    def __init__(self, cluster, config: dict, traffic: dict, factory: PodFactory,
                 served, running: list[tuple[str, list[str]]],
                 pending: list[tuple[str, dict]], annotate=None):
        """`running`: (app id, pod names) oldest first; `pending`: (app id,
        driver pod) of pending drivers in creation order."""
        self.cluster = cluster
        self.traffic = traffic
        self.factory = factory
        self.served = served
        self.running = collections.deque(running)
        self.pending = [app for app, _ in pending]
        # A pending driver's request body is built once, when it arrives,
        # and sent again on every retry.
        self._bodies = {app: predicate_body(pod, cluster) for app, pod in pending}
        self.count = int(config["gang"]["executors"])
        self.next_app = len(running) + len(pending)
        self.log: list[tuple] = []
        self.bodies: dict[bytes, bytes] = {}  # denial digest -> first body seen
        self.errors: list[bytes] = []
        self.calls: list[tuple[str, float, bool]] = []  # role, ms, in window
        self.admitted: dict[str, tuple[str, list[str]]] = {}
        self.in_window = False
        self.deadline = float("inf")
        self.client_s = 0.0  # the client's own work: bodies and answers
        self.watch_s = 0.0  # backend updates the watch would deliver
        self._annotate = annotate
        self._conn = http.client.HTTPConnection("127.0.0.1", served.port, timeout=900)

    def close(self) -> None:
        self._conn.close()

    def expected_reservations(self) -> dict[str, tuple[str, list[str]]]:
        """Apps admitted in this run and still running: (driver node,
        executor nodes) each was admitted with."""
        return self.admitted

    # --------------------------------------------------------- the world

    def _watch(self, fn, *args) -> None:
        t0 = time.perf_counter()
        if self._annotate is not None:
            with self._annotate("watch:apply"):
                fn(*args)
        else:
            fn(*args)
        self.watch_s += time.perf_counter() - t0

    def _arrive(self) -> None:
        app = f"app-{self.next_app:07d}"
        self.next_app += 1
        pod = self.factory.driver(app)
        self._watch(self.served.create_pod, pod)
        t0 = time.perf_counter()
        self._bodies[app] = predicate_body(pod, self.cluster)
        self.client_s += time.perf_counter() - t0
        self.pending.append(app)
        self.log.append(("arrive", app))

    def _complete_oldest(self) -> None:
        app, pods = self.running.popleft()
        self._watch(self.served.complete, app, pods)
        self.admitted.pop(app, None)
        self.log.append(("complete", app))

    # --------------------------------------------------------- the calls

    def _post(self, role: str, body: bytes) -> bytes:
        if time.perf_counter() >= self.deadline:
            raise Stop
        t1 = time.perf_counter()
        if self._annotate is not None:
            with self._annotate(f"predicate:{role}"):
                self._conn.request("POST", "/predicates", body=body)
                resp = self._conn.getresponse()
                raw = resp.read()
        else:
            self._conn.request("POST", "/predicates", body=body)
            resp = self._conn.getresponse()
            raw = resp.read()
        t2 = time.perf_counter()
        self.calls.append((role, (t2 - t1) * 1e3, self.in_window))
        if resp.status != 200:
            self.errors.append(raw[:2000])
        return raw

    def _node_of(self, raw: bytes) -> str | bytes | None:
        """The answered node; for a denial the digest of its body, which is
        kept once per distinct digest for the check; None otherwise."""
        t0 = time.perf_counter()
        try:
            if raw.startswith(ADMIT_PREFIX):
                if not raw.endswith(b'"], "FailedNodes": {}, "Error": ""}'):
                    self.errors.append(raw[:2000])
                return raw[len(ADMIT_PREFIX):raw.index(b'"', len(ADMIT_PREFIX))].decode()
            if raw.startswith(DENY_PREFIX):
                digest = hashlib.sha1(raw).digest()
                self.bodies.setdefault(digest, raw)
                return digest
            self.errors.append(raw[:2000])
            return None
        finally:
            self.client_s += time.perf_counter() - t0

    def _driver(self, app: str) -> bool:
        got = self._node_of(self._post("driver", self._bodies[app]))
        if isinstance(got, str):
            t0 = time.perf_counter()
            held = self.served.reservation(app)
            self.served.bind(f"{app}-driver", got)
            self.watch_s += time.perf_counter() - t0
            execs = None if held is None else held[1]
            self.log.append(("driver", app, got, execs, None))
            self.pending.remove(app)
            del self._bodies[app]
            self.admitted[app] = (got, execs or [])
            return True
        self.log.append(("driver", app, None, None, got))
        return False

    def _executors(self, app: str) -> None:
        names = [f"{app}-driver"]
        for k in range(self.count):
            pod = self.factory.executor(app, k)
            self._watch(self.served.create_pod, pod)
            t0 = time.perf_counter()
            body = predicate_body(pod, self.cluster)
            self.client_s += time.perf_counter() - t0
            node = self._node_of(self._post("executor", body))
            if isinstance(node, str):
                self._watch(self.served.bind, pod["metadata"]["name"], node)
            self.log.append(("executor", app, k, node if isinstance(node, str) else None))
            names.append(pod["metadata"]["name"])
        self.running.append((app, names))

    # ----------------------------------------------------------- cycles

    def cycle(self) -> None:
        t = self.traffic
        for _ in range(int(t.get("completions_per_cycle", 0))):
            self._complete_oldest()
        while len(self.pending) < int(t["pending_target"]):
            self._arrive()
        admitted = [app for app in list(self.pending) if self._driver(app)]
        for app in admitted:
            self._executors(app)
            for _ in range(int(t.get("completions_per_admission", 0))):
                self._complete_oldest()

    def run_until(self, deadline: float) -> float:
        """Cycles until the deadline; returns when the last call ended."""
        self.deadline = deadline
        try:
            while True:
                self.cycle()
        except Stop:
            pass
        return time.perf_counter()
