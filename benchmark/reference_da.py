"""The plain reference of Spark dynamic allocation under the extender,
written from the upstream semantics with nothing taken from the program
under test. Gang admission, the node order and the executor binding are
reference.py's; this module adds what dynamic allocation changes.

Semantics (palantir/k8s-spark-scheduler README and internal/extender:
resource.go, resourcereservations.go; internal/cache/softreservations.go;
Apache Spark, Job Scheduling > Dynamic Resource Allocation):

- A driver with `spark-dynamic-allocation-enabled` is admitted as a gang
  of its **min** executors, placed as reference.py places a gang, against
  allocatable less every hard **and soft** reservation. The FIFO
  earlier-driver check packs each earlier driver with its min executors.
- An executor binds to the first offered node that holds an unbound hard
  slot of its app. With none, and fewer than max - min soft reservations,
  it is an extra executor: it goes on the first offered node in the
  executor order (reference.py's node order, from the availability at the
  request's start) where its request fits, and a soft reservation holds
  it there; where none fits it is denied (failure-fit). With no spot left
  it is denied (failure-unbound).
- A deleted executor frees what it held: its soft reservation at once; a
  hard slot stays reserved on its node, unbound, until compaction.
- Compaction: before the next request is served, the live soft-reserved
  executors of each app with an unbound hard slot take those slots over.
  The slot moves to the executor's node, and its soft reservation goes.
- An app's end frees its driver, its hard slots and its soft
  reservations.

Departures from upstream, where upstream leaves the choice open:

- Upstream walks an app's soft reservations in Go map order when it
  compacts, so any live soft-reserved executor may take a free slot. Here
  they go oldest first: in the order their soft reservations were made,
  and, for those rebuilt at a restart, in the order the apiserver lists
  the pods.
- Upstream takes any unbound slot for a compacted executor. Here it takes
  one on the executor's own node if there is one, else the first in slot
  order. The check compares an app's slots as a multiset of (node,
  holder), never a slot's name, so this choice shows only where an app
  has fewer live soft-reserved executors than free slots and one of the
  slots left free is not the one upstream would leave.
"""

from __future__ import annotations

import numpy as np

import reference as R

BOUND = "bound"
EXTRA = "extra"
NO_SPOTS = "no-spots"


class DAState(R.State):
    """reference.State with each app's hard slots as [node, holder] (the
    holder None while the slot is unbound), its soft reservations, and the
    apps whose deleted executors await compaction."""

    def __init__(self, ref: R.Reference, min_executors: int, max_executors: int):
        super().__init__(ref)
        self.spots = max_executors - min_executors
        self.driver: dict[str, int] = {}
        self.slots: dict[str, list[list]] = {}
        self.soft: dict[str, dict[str, int]] = {}
        self.queued: dict[str, None] = {}

    def _take(self, node: int, req: np.ndarray, sign: int) -> None:
        self.avail[node] -= sign * req
        self._prefix = None

    def hold(self, app: str, driver: int, slot_nodes, holders=None, soft=None) -> None:
        """A running app: its driver, hard slots in slot order with their
        holders, and its soft reservations {pod: node}, oldest first."""
        g = self.ref.gang
        self._take(driver, g.driver, +1)
        holders = holders or [None] * len(slot_nodes)
        self.driver[app] = driver
        self.slots[app] = [[int(n), h] for n, h in zip(slot_nodes, holders)]
        for n in slot_nodes:
            self._take(int(n), g.executor, +1)
        self.soft[app] = {}
        for pod, node in (soft or {}).items():
            self.soft_reserve(app, pod, node)

    def admit(self, app: str, driver: int, slot_nodes) -> None:
        self.pending.remove(app)
        self.hold(app, driver, slot_nodes)

    def complete(self, app: str) -> None:
        g = self.ref.gang
        self._take(self.driver.pop(app), g.driver, -1)
        for node, _ in self.slots.pop(app):
            self._take(node, g.executor, -1)
        for node in self.soft.pop(app).values():
            self._take(node, g.executor, -1)
        self.queued.pop(app, None)

    def release(self, app: str, pod: str) -> None:
        """One executor of the app deleted."""
        soft = self.soft[app]
        if pod in soft:
            self._take(soft.pop(pod), self.ref.gang.executor, -1)
        for slot in self.slots[app]:
            if slot[1] == pod:
                slot[1] = None
        self.queued[app] = None

    def compact(self) -> int:
        """The pass before a request: returns the slots taken over."""
        moved = 0
        for app in self.queued:
            free = [s for s in self.slots.get(app, []) if s[1] is None]
            for pod, node in list(self.soft.get(app, {}).items()):
                if not free:
                    break
                slot = next((s for s in free if s[0] == node), free[0])
                free.remove(slot)
                # The slot leaves its node; the pod's soft usage becomes
                # the slot's.
                self._take(slot[0], self.ref.gang.executor, -1)
                slot[0], slot[1] = node, pod
                del self.soft[app][pod]
                moved += 1
        self.queued = {}
        return moved

    def executor(self, app: str) -> tuple[str, int | None]:
        """What an executor call of the app should get: (BOUND, node) of
        the first unbound slot's node, (EXTRA, node or None where none
        fits), or (NO_SPOTS, None)."""
        free = [node for node, holder in self.slots[app] if holder is None]
        if free:
            return BOUND, min(free)
        if len(self.soft[app]) >= self.spots:
            return NO_SPOTS, None
        fit = R.fits(self.avail, self.ref.gang.executor)
        if not fit.any():
            return EXTRA, None
        key = self.ref.order_key(self.avail)
        return EXTRA, int(np.argmin(np.where(fit, key, R.BIG)))

    def bind(self, app: str, node: int, pod: str) -> bool:
        """The pod takes the first unbound slot on the node; False where
        the app has none there."""
        for slot in self.slots[app]:
            if slot[0] == node and slot[1] is None:
                slot[1] = pod
                return True
        return False

    def soft_reserve(self, app: str, pod: str, node: int) -> None:
        self.soft[app][pod] = int(node)
        self._take(int(node), self.ref.gang.executor, +1)

    def reserved_usage(self) -> np.ndarray:
        """Per node: everything reserved, drivers, hard slots and soft
        reservations ([N,2]: cpu milli, memory bytes)."""
        return self.ref.alloc - self.avail
