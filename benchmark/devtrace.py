"""Reduction of a JAX profiler trace (`.xplane.pb`) to device metrics.

Device planes are the `/device:` planes that hold XLA operations (a TPU
trace also has a `/device:CUSTOM:` plane with none). On each, the "XLA
Ops" and "Async XLA Ops" lines hold one event per operation that ran,
nested ops inside the loop or fusion that runs them, and the "XLA
Modules" line one event per program run. The traced window is the
benchmark's own `bench:window` annotation on the host plane, on the same
clock.
"""

from __future__ import annotations

import glob
import os

WINDOW_SPAN = "bench:window"
HOST_SPANS = ("predicate:driver", "predicate:executor", "watch:apply")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def load(path: str) -> dict:
    """Events of a trace as plain tuples: device ops and programs per
    device plane, and the host spans this benchmark writes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {
                line.name: [(e.name.split(" = ")[0], int(e.start_ns), int(e.duration_ns))
                            for e in line.events]
                for line in plane.lines
            }
            ops = lines.get("XLA Ops", []) + lines.get("Async XLA Ops", [])
            if ops:
                devices[plane.name] = {"ops": ops, "modules": lines.get("XLA Modules", [])}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or e.name in HOST_SPANS:
                        host.append((e.name, int(e.start_ns), int(e.duration_ns)))
    return {"devices": devices, "host": host}


def reduce(events: dict, program: str) -> dict:
    """busy_s and window_s (averaged over the device planes), device time
    of the programs whose name contains `program` per run, the programs
    and Mosaic kernels that took most device time, and the longest idle
    gaps by what the host was doing."""
    spans = [(s, s + d) for n, s, d in events["host"] if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    lo, hi = spans[0]
    window_ns = hi - lo
    busy_ns, prog_ns, prog_runs = [], 0, 0
    op_time: dict[str, int] = {}
    gaps = []
    host = [(n, s, s + d) for n, s, d in events["host"] if n != WINDOW_SPAN]
    for dev in events["devices"].values():
        merged = union(clip([(s, s + d) for _, s, d in dev["ops"]], lo, hi))
        busy_ns.append(sum(e - s for s, e in merged))
        for name, s, d in dev["modules"]:
            if lo <= s < hi:
                op_time[name] = op_time.get(name, 0) + d
                if program in name:
                    prog_ns += d
                    prog_runs += 1
        for name, s, d in dev["ops"]:
            if "pallas" in name and lo <= s < hi:
                op_time[name] = op_time.get(name, 0) + d
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    if not events["devices"]:
        raise ValueError("the trace holds no device plane")
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for s, e in gaps[:10]:
        overlap: dict[str, int] = {}
        for name, hs, he in host:
            o = min(e, he) - max(s, hs)
            if o > 0:
                overlap[name] = overlap.get(name, 0) + o
        doing = max(overlap, key=overlap.get) if overlap else "client between calls"
        idle.append([doing, (e - s) / 1e9])
    n_dev = len(events["devices"])
    return {
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "window_s": window_ns / 1e9,
        "program_ms_per_run": (prog_ns / prog_runs / 1e6) if prog_runs else None,
        "program_runs": prog_runs / n_dev,
        "device_ops": [[n, t / 1e9] for n, t in sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": idle,
    }
