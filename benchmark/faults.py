"""Controls and planted faults: runs the check has to call incorrect.

A control breaks one guarantee the configuration states, by a switch of
the program's own where it has one:

  fifo-off        the program installed without FIFO (`fifo: false`):
                  later drivers are admitted past earlier ones that do
                  not fit;
  distribute-evenly
                  the program installed with `binpack-algo:
                  distribute-evenly`, the fill a change would be tempted
                  to trade for tightly-pack's node order: drivers and
                  executors land on other nodes than the stated fill's;
  executor-slot-order
                  an executor binds to its application's first unbound
                  reservation slot, not to the first offered node that
                  holds one (a guarantee the configurations state): the
                  scan of the offered names a change would be tempted to
                  skip.

A fault breaks the timed path underneath one run:

  commit-skipped  a step that returns its state unchanged: admitted
                  gangs' reservations are never written;
  half-nodes      half of the batch left out: each window skips every
                  other tile of 128 nodes (of fewer on a small cluster),
                  from the first, as a kernel that steps over half of
                  its node tiles would. The first tile holds the oldest
                  apps, which end first: a saturated pool admits into
                  their room, so the window needs it. (Leaving out the
                  second half of the nodes, or the second tile of each
                  pair, went unseen in `sazmf10k-fifo-backlog`.)
  answer-altered  an answer altered where it is produced: each admitted
                  driver's node is moved to the next node of the cluster.

Each entry gives overrides (under "program": install keys the program
alone gets, the reference keeping the configuration's) and a
`tamper(served)` applied to the booted program. No change here touches a file of the program.
"""

from __future__ import annotations

TILE = 128


def _executor_slot_order(served) -> None:
    rrm = served.app.reservation_manager
    reserve = rrm.reserve_executor_on_unbound

    def first_slot(executor, node_names):
        rr = rrm.get_resource_reservation(
            executor.labels.get("spark-app-id", ""), executor.namespace
        )
        if rr is None:
            return reserve(executor, node_names)
        slots = [r.node for k, r in rr.spec.reservations.items()
                 if k != "driver" and k not in rr.status.pods]
        return reserve(executor, slots + list(node_names))

    rrm.reserve_executor_on_unbound = first_slot


def _commit_skipped(served) -> None:
    rrm = served.app.reservation_manager
    rrm.create_reservations_batch = lambda entries: [None] * len(entries)


def _half_nodes(served) -> None:
    solver = served.app.solver
    dispatch = solver.pack_window_dispatch
    names = sorted(n.name for n in served.backend.list_nodes())
    # Tiles of 128 nodes, smaller on a cluster of fewer than 16 of them.
    tile = max(1, min(TILE, len(names) // 16))
    half = [name for i, name in enumerate(names) if i // tile % 2 == 1]

    def halved(strategy, tensors, requests):
        cut = [r._replace(driver_candidate_names=half, domain_node_names=half) for r in requests]
        return dispatch(strategy, tensors, cut)

    solver.pack_window_dispatch = halved


def _answer_altered(served) -> None:
    solver = served.app.solver
    fetch = solver.pack_window_fetch
    names = [n.name for n in served.backend.list_nodes()]
    nxt = {a: b for a, b in zip(names, names[1:] + names[:1])}

    def altered(handle):
        out = []
        for d in fetch(handle):
            p = d.packing
            if d.admitted and p.driver_node is not None:
                d = d._replace(packing=p._replace(driver_node=nxt[p.driver_node]))
            out.append(d)
        return out

    solver.pack_window_fetch = altered


CONTROLS = {
    "fifo-off": ({"program": {"fifo": False}}, None),
    "distribute-evenly": ({"program": {"binpack-algo": "distribute-evenly"}}, None),
    "executor-slot-order": ({}, _executor_slot_order),
}

FAULTS = {
    "commit-skipped": ({}, _commit_skipped),
    "half-nodes": ({}, _half_nodes),
    "answer-altered": ({}, _answer_altered),
}
