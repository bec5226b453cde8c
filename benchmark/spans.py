"""Program spans on the device trace's clock, for one cell.

    python3 benchmark/spans.py --workload <name> --seeds 1,2,3 [--seconds 4]

Runs the cell as `run.py --trace 1` does, whose capture goes through
the program's own `start_jax_profile`, which writes the program's trace
spans (`predicate`, `predicate:decode`, `solve-dispatch`, `fetch-wait`,
...) into it beside the device operations. Prints one line per
seed, `SPANS ` and a JSON object: `correct`, the client's driver and
executor p50, the mean of each program span, the batcher's thread
hand-off, the solve-wait and executor-call accounts, the device's idle
share and the part of it that no program span covers, and the ten longest
device-idle gaps labelled by the program span whose self time overlaps
them most. On a program whose spans do not reach the capture, the labels
are the client's, as `devtrace.reduce` gives them.

The benchmark's own runs never run this.
"""

import argparse
import bisect
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import devtrace  # noqa: E402

# The program's span names (spark_scheduler_tpu: server/routing.py,
# core/extender.py, core/solver.py). ROOT is the request's own span: on
# the handler's thread most of its self time is the wait while the
# dispatcher thread works, so it names a gap only where no other does.
ROOT = "predicate"
PROGRAM_SPANS = (
    ROOT, "predicate:decode", "predicate:encode", "featurize",
    "featurize-fifo", "solve-dispatch", "solve", "fetch-wait", "commit",
    "select-node", "executor-lookup", "predicate-window",
    "predicate-window-complete", "write-back",
)
GAPS = 10


def load_program(path: str, names=PROGRAM_SPANS) -> list[tuple[str, int, int, int]]:
    """(name, thread line, start ns, end ns) of every program span in the
    trace's host planes: each event named in `names`."""
    from jax.profiler import ProfileData

    out = []
    line_no = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            line_no += 1
            for e in line.events:
                if e.name in names:
                    s = int(e.start_ns)
                    out.append((e.name, line_no, s, s + int(e.duration_ns)))
    return out


def self_times(spans) -> list[tuple[str, int, int]]:
    """Each span's interval less its children on the same thread line:
    (name, start, end) pieces."""
    pieces = []
    by_line: dict[int, list] = {}
    for name, line, s, e in spans:
        by_line.setdefault(line, []).append((s, -e, name))
    for items in by_line.values():
        items.sort()
        stack: list[list] = []  # [name, start, end, children]

        def close(frame):
            name, s, e, kids = frame
            cur = s
            for ks, ke in devtrace.union(kids):
                if ks > cur:
                    pieces.append((name, cur, ks))
                cur = max(cur, ke)
            if e > cur:
                pieces.append((name, cur, e))

        for s, neg_e, name in items:
            e = -neg_e
            while stack and stack[-1][2] <= s:
                close(stack.pop())
            if stack:
                stack[-1][3].append((s, min(e, stack[-1][2])))
            stack.append([name, s, e, []])
        while stack:
            close(stack.pop())
    return pieces


def idle_intervals(events: dict) -> tuple[int, int, list[list[tuple[int, int]]]]:
    """The traced window and, per device plane, its idle intervals in it,
    computed as devtrace.reduce computes its gaps."""
    lo, hi = next((s, s + d) for n, s, d in events["host"] if n == devtrace.WINDOW_SPAN)
    idle = []
    for dev in events["devices"].values():
        merged = devtrace.union(devtrace.clip([(s, s + d) for _, s, d in dev["ops"]], lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle.append([(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s])
    return lo, hi, idle


def _overlap(pieces, s: int, e: int) -> dict[str, int]:
    out: dict[str, int] = {}
    for name, ps, pe in pieces:
        o = min(e, pe) - max(s, ps)
        if o > 0:
            out[name] = out.get(name, 0) + o
    return out


def label_gaps(events: dict, spans) -> list[list]:
    """The longest device-idle gaps, each named by the program span whose
    self time overlaps it most; by the request root where only the root
    covers it; else by the client's span, or "client between calls"."""
    _, _, idle = idle_intervals(events)
    gaps = sorted((g for dev in idle for g in dev), key=lambda g: g[0] - g[1])[:GAPS]
    pieces = self_times(spans)
    inner = [p for p in pieces if p[0] != ROOT]
    root = [p for p in pieces if p[0] == ROOT]
    client = [(n, s, s + d) for n, s, d in events["host"] if n != devtrace.WINDOW_SPAN]
    out = []
    for s, e in gaps:
        for group in (inner, root, client):
            o = _overlap(group, s, e)
            if o:
                out.append([max(o, key=o.get), (e - s) / 1e9])
                break
        else:
            out.append(["client between calls", (e - s) / 1e9])
    return out


def idle_by_span(idle, spans) -> dict[str, float]:
    """Every device-idle nanosecond given to one name, with the gap
    labels' precedence: the program spans whose self time runs then
    (shared evenly where several threads run one at once), else the
    request root, else "outside program". Shares in %, summing to 100."""
    pieces = self_times(spans)
    edges = []
    for name, s, e in pieces:
        edges.append((s, 1, name))
        edges.append((e, -1, name))
    for dev in idle:
        for s, e in dev:
            edges.append((s, 2, None))
            edges.append((e, -2, None))
    edges.sort(key=lambda x: x[0])
    active: dict[str, int] = {}
    idle_depth = 0
    got: dict[str, float] = {}
    prev = None
    for t, kind, name in edges:
        if prev is not None and t > prev and idle_depth:
            inner = [n for n, c in active.items() if c and n != ROOT]
            dt = (t - prev) * idle_depth
            if inner:
                for n in inner:
                    got[n] = got.get(n, 0.0) + dt / len(inner)
            else:
                key = ROOT if active.get(ROOT) else "outside program"
                got[key] = got.get(key, 0.0) + dt
        prev = t
        if abs(kind) == 2:
            idle_depth += 1 if kind > 0 else -1
        else:
            active[name] = active.get(name, 0) + kind
    total = sum(got.values())
    return {k: 100.0 * v / total for k, v in sorted(got.items(), key=lambda kv: -kv[1])} if total else {}


def by_role(spans, lo: int, hi: int) -> dict[str, dict[str, float]]:
    """Mean `predicate`, `predicate:decode` and `predicate:encode` ms of
    the requests that start in [lo, hi), by role: a request is an
    executor's when an `executor-lookup` ran inside its root span."""
    lookups = sorted(s for name, _, s, _ in spans if name == "executor-lookup")
    roots = [(line, s, e) for name, line, s, e in spans if name == ROOT and lo <= s < hi]
    role_of = {}
    for line, s, e in roots:
        i = bisect.bisect_left(lookups, s)
        role_of[(line, s, e)] = (
            "executor" if i < len(lookups) and lookups[i] < e else "driver"
        )
    sums: dict[str, dict[str, list[int]]] = {}
    root_at = sorted(role_of)
    for name, line, s, e in spans:
        if name not in (ROOT, "predicate:decode", "predicate:encode"):
            continue
        i = bisect.bisect_right(root_at, (line, s, float("inf"))) - 1
        if i < 0 or root_at[i][0] != line or not root_at[i][1] <= s < root_at[i][2]:
            continue
        role = role_of[root_at[i]]
        sums.setdefault(role, {}).setdefault(name, []).append(e - s)
    return {
        role: {name: statistics.fmean(v) / 1e6 for name, v in sorted(d.items())}
        for role, d in sorted(sums.items())
    }


def solve_split(spans, lo: int, hi: int) -> dict[str, float] | None:
    """A driver window's solve wait along the dispatcher's line, mean ms
    over the windows dispatched in [lo, hi): `prep` (featurize's end to
    the launch: segmented batch, masks), `launch` (`solve-dispatch`),
    `wait` (launch's end to the fetch: handle build, the pull on the
    fetch thread, the serving loop's wake-up), `fetch` (`solve`, with
    `fetch-wait` in it) and `rebuild` (the fetch's end to `commit`:
    reconstruction on the host)."""
    by_line: dict[int, dict[str, list]] = {}
    for name, line, s, e in spans:
        by_line.setdefault(line, {}).setdefault(name, []).append((s, e))
    parts: dict[str, list[int]] = {}
    for names in by_line.values():
        for key in ("featurize", "solve", "commit"):
            names.setdefault(key, []).sort()
        for ds, de in names.get("solve-dispatch", []):
            if not lo <= ds < hi:
                continue
            feat = [e for s, e in names["featurize"] if e <= ds]
            solve = [(s, e) for s, e in names["solve"] if s >= de]
            if not feat or not solve:
                continue
            ss, se = solve[0]
            commit = [s for s, _ in names["commit"] if s >= se]
            if not commit:
                continue
            for key, v in (("prep", ds - feat[-1]), ("launch", de - ds), ("wait", ss - de),
                           ("fetch", se - ss), ("rebuild", commit[0] - se)):
                parts.setdefault(key, []).append(v)
    if not parts:
        return None
    return {k: statistics.fmean(v) / 1e6 for k, v in parts.items()}


def reduce_program(events: dict, spans) -> dict:
    """Program spans of the traced window: the mean of each span that
    starts in it, the gap labels, the share of device-idle time that no
    program span covers, and the idle time by span."""
    lo, hi, idle = idle_intervals(events)
    in_window = [sp for sp in spans if lo <= sp[2] < hi]
    durations: dict[str, list[int]] = {}
    for name, _, s, e in in_window:
        durations.setdefault(name, []).append(e - s)
    covered = devtrace.union(devtrace.clip([(s, e) for _, _, s, e in spans], lo, hi))
    idle_ns = outside_ns = 0
    for dev in idle:
        for s, e in dev:
            idle_ns += e - s
            outside_ns += (e - s) - sum(
                max(0, min(e, ce) - max(s, cs)) for cs, ce in covered
            )
    return {
        "span_ms": {k: statistics.fmean(v) / 1e6 for k, v in sorted(durations.items())},
        "span_count": {k: len(v) for k, v in sorted(durations.items())},
        "idle_outside_program_pct": 100.0 * outside_ns / idle_ns if idle_ns else None,
        "idle_gaps": label_gaps(events, spans),
        "idle_by_span_pct": idle_by_span(idle, spans),
        "by_role_ms": by_role(spans, lo, hi),
        "solve_split_ms": solve_split(spans, lo, hi),
    }


def _mean(vals):
    return statistics.fmean(vals) if vals else None


def _p50(vals):
    return statistics.median(vals) if vals else None


def run_seed(harness, name: str, seed: int, seconds: float, **run_kw) -> dict:
    """One `--trace 1` run of the cell, whose capture holds the program's
    spans, reduced further than its readers go. `run_kw` goes to
    harness.run_cell (a test's small cluster)."""
    ctx: dict = {}
    res = harness.run_cell(name, seed, seconds, True, t_start=time.time(), ctx_out=ctx, **run_kw)
    red, prog, calls, phases = ctx["trace"], ctx["program"], ctx["calls"], ctx["phases"]
    batcher = {k.rsplit(".", 1)[1]: v for k, v in ctx["counters"].items()
               if k.startswith("foundry.spark.scheduler.predicate.batcher.")}
    handoff_ms = None
    if batcher.get("handoffs", 0) > 0:
        handoff_ms = 1e3 * (batcher["queue_wait_s"] + batcher["wake_wait_s"]) / batcher["handoffs"]
    span_ms = prog["span_ms"]
    out = {
        "seed": seed,
        "correct": res["correct"],
        "driver_p50_ms": _p50([ms for role, ms in calls if role == "driver"]),
        "executor_p50_ms": _p50([ms for role, ms in calls if role == "executor"]),
        "calls": len(calls),
        "handoff_ms": handoff_ms,
        "solve_wait_ms": _mean([p["solve_ms"] for p in phases if "solve_ms" in p]),
        "recorder_dispatch_ms": _mean([p["dispatch_ms"] for p in phases if "dispatch_ms" in p]),
        "recorder_fetch_wait_ms": _mean(
            [p["fetch_wait_ms"] for p in phases if "fetch_wait_ms" in p]
        ),
        "window_device_ms": red and red["program_ms_per_run"],
        "device_idle_pct": red and 100.0 * (1.0 - red["busy_s"] / red["window_s"]),
        "client_idle_gaps": red and red["idle_gaps"],
        **prog,
    }
    executor = prog["by_role_ms"].get("executor", {})
    parts = [executor.get("predicate:decode"), span_ms.get("executor-lookup"),
             executor.get("predicate:encode")]
    if None not in parts and handoff_ms is not None:
        out["executor_account_ms"] = sum(parts) + handoff_ms
    if "solve-dispatch" in span_ms and "fetch-wait" in span_ms:
        out["solve_account_ms"] = span_ms["solve-dispatch"] + span_ms["fetch-wait"]
    return out


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=harness.TRACE_SECONDS)
    args = ap.parse_args(argv)
    sys.path.insert(0, harness.ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        line = run_seed(harness, args.workload, seed, args.seconds)
        print("SPANS " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
