"""The comparison that decides `correct`: every answer the run logged,
replayed in order against the plain reference.

Each number is a count of answers or nodes that break a guarantee, and
each limit is 0: the comparison is exact (an efficiency tie between zones
accepts either zone, see reference.py).
"""

from __future__ import annotations

import json

import reference as R

MESSAGES = {
    "application does not fit to the cluster": R.DENY_FIT,
    "earlier drivers do not fit to the cluster": R.DENY_EARLIER,
}


def denial_kinds(bodies: dict[bytes, bytes], names: list[str]) -> tuple[dict, int]:
    """Kind of each distinct denial body, and how many of those bodies
    lack a failure map that names every offered node with one reason."""
    kinds, bad = {}, 0
    want = set(names)
    for digest, raw in bodies.items():
        try:
            body = json.loads(raw)
            failed = body["FailedNodes"]
            reasons = set(failed.values())
            ok = (
                body["NodeNames"] == [] and body["Error"] == ""
                and len(failed) == len(names) and set(failed) == want
                and len(reasons) == 1
            )
        except (ValueError, KeyError, TypeError, AttributeError):
            ok, reasons = False, set()
        if not ok:
            bad += 1
        kinds[digest] = MESSAGES.get(next(iter(reasons)), None) if reasons else None
    return kinds, bad


def replay(ref: R.Reference, names: list[str], running: list[tuple[str, R.Placement]],
           pending: list[str], log: list[tuple], bodies: dict[bytes, bytes]) -> dict:
    index = {n: i for i, n in enumerate(names)}
    state = R.State(ref)
    for app, p in running:
        state.reserve(app, p)
    for app in pending:
        state.arrive(app)
    kinds, bad_maps = denial_kinds(bodies, names)
    out = {
        "driver_mismatches": 0, "executor_mismatches": 0, "denials_bad_map": bad_maps,
        "drivers_checked": 0, "executors_checked": 0, "denials": 0, "admits": 0,
        "zone_ties": 0,
    }
    for ev in log:
        kind = ev[0]
        if kind == "arrive":
            state.arrive(ev[1])
        elif kind == "complete":
            state.complete(ev[1])
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
            got = R.Placement(index[node], tuple(sorted(index[e] for e in execs or [])))
            if got not in want.placements or len(got.executors) != ref.gang.count:
                out["driver_mismatches"] += 1
            state.admit(app, got)
        elif kind == "executor":
            _, app, _k, node = ev
            out["executors_checked"] += 1
            want = state.expected_executor(app)
            if node is None or want is None or index.get(node) != want:
                out["executor_mismatches"] += 1
            if node is not None:
                state.bind_executor(app, index[node])
    out["reference_overcommitted_nodes"] = state.overcommitted()
    return out
