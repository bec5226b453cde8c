"""The plain reference: the extender's gang admission, written from the
upstream semantics with nothing taken from the program under test.

Semantics (palantir/k8s-spark-scheduler, internal/extender and
internal/binpack):

- Availability of a node is its allocatable less every live reservation.
- Nodes are ordered once per request: zones by (available memory, available
  CPU, zone name) summed over the instance group's nodes, then nodes by
  (zone rank, available memory, available CPU, name), all ascending.
- A driver is admitted on the first node in that order where the driver
  fits and the executors still fit under the strategy's fill, with the
  driver's own request counted on its node.
- `tightly-pack` walks the order and fills each node before the next.
  `minimal-fragmentation` prefers the smallest node that takes all that
  is left, else fills the largest nodes whole.
- `single-az-<fill>` runs the fill in each zone (zones in driver-order
  appearance) and keeps the zone of highest average packing efficiency,
  replacing only on strictly greater.
- FIFO: before its own application, a driver request packs every pending
  driver of its instance group created strictly earlier, oldest first,
  each against the availability the ones before it left; the first that
  does not fit blocks the request (failure-earlier-driver). The order is
  not recomputed between those packs.
- An executor binds to the first offered node that holds an unbound
  executor slot of its application.

Efficiencies are compared in float64. Where two zones lie within
`TIE_EPS` of each other, rounding in a lower precision may pick either,
so both placements are accepted, and the replay follows the one served.
"""

from __future__ import annotations

import dataclasses

import numpy as np

TIE_EPS = 1e-6
MAX_BRANCHES = 8
BIG = np.iinfo(np.int64).max

DENY_FIT = "fit"
DENY_EARLIER = "earlier"


def parse_strategy(name: str) -> tuple[str, bool]:
    single_az = name.startswith("single-az-")
    fill = name[len("single-az-"):] if single_az else name
    if fill not in ("tightly-pack", "minimal-fragmentation"):
        raise ValueError(f"strategy {name!r} has no reference here")
    return fill, single_az


def capacity(avail: np.ndarray, req: np.ndarray) -> np.ndarray:
    """How many `req` fit on each node of `avail` ([N,2] -> [N]). The
    quotients are small whole numbers of far fewer than 53 bits, so the
    float division floors exactly."""
    cap = np.full(avail.shape[0], float(BIG))
    short = np.zeros(avail.shape[0], bool)
    for d in range(avail.shape[1]):
        col = avail[:, d]
        short |= col < 0
        if req[d] > 0:
            cap = np.minimum(cap, np.floor(col / float(req[d])))
    cap[short] = 0
    return cap.astype(np.int64)


def fits(avail: np.ndarray, req: np.ndarray) -> np.ndarray:
    ok = avail[:, 0] >= req[0]
    for d in range(1, avail.shape[1]):
        ok &= avail[:, d] >= req[d]
    return ok


@dataclasses.dataclass(frozen=True)
class Placement:
    driver: int
    executors: tuple[int, ...]  # sorted node indices, one per executor


class Reference:
    def __init__(self, alloc, zone, n_zones, strategy, gang):
        self.alloc = np.asarray(alloc, np.int64)
        self.zone = np.asarray(zone, np.int64)
        self.n_zones = n_zones
        self.fill, self.single_az = parse_strategy(strategy)
        self.gang = gang
        self.n = self.alloc.shape[0]
        self.zone_nodes = [np.nonzero(self.zone == z)[0] for z in range(n_zones)]
        self._all = np.arange(self.n)
        # One int64 holds (zone rank, memory KiB, CPU milli, name) when
        # the cluster's sizes allow; else the order falls back to a sort.
        self._m_mem = int(self.alloc[:, 1].max()) // 1024 + 1
        self._m_cpu = int(self.alloc[:, 0].max()) + 1
        self._packed_key = n_zones * self._m_mem * self._m_cpu * self.n < (1 << 62)

    def order_key(self, avail: np.ndarray) -> np.ndarray:
        """The request's node order as a key per node (smaller = earlier),
        from the availability at its start."""
        zone, n = self.zone, self.n_zones
        mem = np.bincount(zone, weights=avail[:, 1] / 1024.0, minlength=n)
        cpu = np.bincount(zone, weights=avail[:, 0].astype(float), minlength=n)
        zorder = sorted(range(n), key=lambda z: (mem[z], cpu[z], z))
        zrank = np.empty(n, np.int64)
        zrank[zorder] = np.arange(n)
        if self._packed_key:
            mem_k = np.clip(avail[:, 1] // 1024, 0, None)
            cpu_m = np.clip(avail[:, 0], 0, None)
            return ((zrank[zone] * self._m_mem + mem_k) * self._m_cpu + cpu_m) * self.n + self._all
        order = np.lexsort((self._all, avail[:, 0], avail[:, 1], zrank[zone]))
        key = np.empty(self.n, np.int64)
        key[order] = self._all
        return key

    # -------------------------------------------------------------- fills

    def _fill(self, cap: np.ndarray, key: np.ndarray, count: int):
        """Executor positions (into cap/key) for `count` executors, or None."""
        if int(cap.sum()) < count:
            return None
        if count == 0:
            return ()
        if self.fill == "tightly-pack":
            nz = np.nonzero(cap > 0)[0]
            k = min(count, nz.size)
            first = nz[np.argpartition(key[nz], k - 1)[:k]]
            out, left = [], count
            for i in first[np.argsort(key[first])]:
                take = min(int(cap[i]), left)
                out += [int(i)] * take
                left -= take
                if left == 0:
                    return tuple(out)
            raise AssertionError("capacity sum and walk disagree")
        cap = cap.copy()
        out, left = [], count
        while True:
            alive = cap > 0
            fits = alive & (cap >= left)
            if fits.any():
                least = cap[fits].min()
                i = int(np.argmin(np.where(fits & (cap == least), key, BIG)))
                return tuple(out + [i] * left)
            top = int(cap[alive].max())
            mx = np.nonzero(alive & (cap == top))[0]
            mx = mx[np.argsort(key[mx])][: left // top]
            for i in mx:
                out += [int(i)] * top
            left -= top * mx.size
            if left == 0:
                return tuple(out)
            cap[mx] = 0

    def _caps(self, avail):
        """Per node: does the driver fit, and executor capacity before and
        after the driver's own request."""
        g = self.gang
        return (fits(avail, g.driver), capacity(avail, g.executor),
                capacity(avail - g.driver, g.executor))

    def _bin_pack(self, caps, key, sub) -> Placement | None:
        """binpack.go over the nodes `sub` (None: all): the first driver
        candidate whose executors still fit."""
        g = self.gang
        fits_d, cap, cap_after = caps if sub is None else (c[sub] for c in caps)
        k = key if sub is None else key[sub]
        ok = fits_d & (int(cap.sum()) - cap + cap_after >= g.count)
        if not ok.any():
            return None
        d = int(np.argmin(np.where(ok, k, BIG)))
        cap = cap.copy()
        cap[d] = cap_after[d]
        ex = self._fill(cap, k, g.count)
        if ex is None:
            raise AssertionError("driver chosen with no executor fill")
        node = (lambda i: int(i)) if sub is None else (lambda i: int(sub[i]))
        return Placement(node(d), tuple(sorted(node(i) for i in ex)))

    def _efficiency(self, avail, p: Placement) -> float:
        g = self.gang
        new = {p.driver: g.driver}
        if self.fill != "minimal-fragmentation":
            for e in p.executors:
                new[e] = new.get(e, 0) + g.executor
        entries = [p.driver, *p.executors]
        total = 0.0
        for n in entries:
            used = (self.alloc[n] - avail[n]) + new.get(n, 0)
            total += float(np.max(used / np.where(self.alloc[n] == 0, 1, self.alloc[n])))
        return total / len(entries)

    def pack(self, avail, key) -> list[Placement]:
        """Every placement the strategy may make for one gang: none when it
        does not fit, more than one only on an efficiency tie."""
        caps = self._caps(avail)
        if not self.single_az:
            p = self._bin_pack(caps, key, None)
            return [] if p is None else [p]
        scored = []
        zones = sorted(
            (z for z in range(self.n_zones) if self.zone_nodes[z].size),
            key=lambda z: key[self.zone_nodes[z]].min(),
        )
        for z in zones:
            p = self._bin_pack(caps, key, self.zone_nodes[z])
            if p is not None:
                scored.append((self._efficiency(avail, p), p))
        best = max((e for e, _ in scored), default=0.0)
        if best <= 0.0:
            return []
        return [p for e, p in scored if e >= best - TIE_EPS]

    def apply(self, avail: np.ndarray, p: Placement, sign: int) -> None:
        """avail -= sign * (the placement's requests), in place."""
        avail[p.driver] -= sign * self.gang.driver
        for e in p.executors:
            avail[e] -= sign * self.gang.executor


@dataclasses.dataclass
class Outcome:
    """One driver decision: admitted with one of `placements`, or denied."""

    placements: list[Placement]
    deny: str | None


class State:
    """Cluster state as the reference sees it, advanced by the served
    decisions: committed availability, live reservations, pending
    drivers in creation order, and unbound executor slots per app."""

    def __init__(self, ref: Reference):
        self.ref = ref
        self.avail = ref.alloc.copy()
        self.reserved: dict[str, Placement] = {}
        self.unbound: dict[str, list[int]] = {}
        self.pending: list[str] = []
        self._prefix = None

    def reserve(self, app: str, p: Placement) -> None:
        self.ref.apply(self.avail, p, +1)
        self.reserved[app] = p
        self.unbound[app] = sorted(p.executors)
        self._prefix = None

    def complete(self, app: str) -> None:
        self.ref.apply(self.avail, self.reserved.pop(app), -1)
        self.unbound.pop(app, None)
        self._prefix = None

    def arrive(self, app: str) -> None:
        self.pending.append(app)

    def overcommitted(self) -> int:
        return int((~fits(self.avail, np.zeros(2, np.int64))).sum())

    def _prefix_branches(self, k: int):
        """Availability branches after packing the first k pending drivers
        hypothetically: [(avail, blocked)], shared by every request of one
        state (each request re-packs a prefix of the same queue)."""
        pre = self._prefix
        if pre is None or pre["k"] > k:
            pre = self._prefix = {
                "k": 0,
                "key": self.ref.order_key(self.avail),
                "branches": [(self.avail.copy(), False)],
            }
        while pre["k"] < k:
            nxt = []
            for avail, blocked in pre["branches"]:
                ps = [] if blocked else self.ref.pack(avail, pre["key"])
                if not ps:
                    nxt.append((avail, True))
                for p in ps:
                    a = avail.copy()
                    self.ref.apply(a, p, +1)
                    nxt.append((a, False))
            if len(nxt) > MAX_BRANCHES:
                raise RuntimeError("efficiency ties branch too far to replay")
            pre["branches"] = nxt
            pre["k"] += 1
        return pre["key"], pre["branches"]

    def decide_driver(self, app: str) -> Outcome:
        key, branches = self._prefix_branches(self.pending.index(app))
        placements: list[Placement] = []
        denies = set()
        for avail, blocked in branches:
            ps = [] if blocked else self.ref.pack(avail, key)
            if blocked:
                denies.add(DENY_EARLIER)
            elif not ps:
                denies.add(DENY_FIT)
            placements += [p for p in ps if p not in placements]
        if (placements and denies) or len(denies) > 1:
            raise RuntimeError("efficiency ties reach different verdicts")
        return Outcome(placements, next(iter(denies)) if denies else None)

    def admit(self, app: str, p: Placement) -> None:
        self.pending.remove(app)
        self.reserve(app, p)

    def expected_executor(self, app: str) -> int | None:
        slots = self.unbound.get(app)
        return slots[0] if slots else None

    def bind_executor(self, app: str, node: int) -> None:
        slots = self.unbound.get(app, [])
        if node in slots:
            slots.remove(node)


def prefill(ref: Reference, n_apps: int | None) -> list[Placement]:
    """Place applications one after another on an empty cluster, as the
    strategy would, until `n_apps` are placed or (None) the next does not
    fit. Returns the placements in admission order."""
    avail = ref.alloc.copy()
    out: list[Placement] = []
    while n_apps is None or len(out) < n_apps:
        ps = ref.pack(avail, ref.order_key(avail))
        if not ps:
            if n_apps is not None:
                raise RuntimeError(f"only {len(out)} of {n_apps} apps fit")
            break
        ref.apply(avail, ps[0], +1)
        out.append(ps[0])
    return out
