"""One run of one cell: set-up, the measured window, the check, and the
result line. Everything a cell needs is found by name: its entry in
BENCHMARK.json, its configuration under configs/, the configuration's
semantics under semantics/ (see semantics/__init__.py), its traffic
under traffic/, and each per-layer metric's reader under metrics/.

A reader's `read(ctx)` returns its number, or None where it finds
nothing to read. In a `--trace 1` run ctx holds:

  trace       devtrace.reduce of the device trace (None off a chip);
  program     spans.reduce_program of the program's spans in the traced
              window: span means, by role, the solve-wait split, idle
              time by span;
  records     the flight recorder's decision records of the window, of
              every role, oldest first;
  phases      the driver records' phases, each with its queue position;
  calls       (role, ms) of each call of the window, as the client timed it;
  counters    the change of each of served.counters() over the window;
  nodes, executors, rows_mean, device_kind
              the cluster's nodes, executors per app, mean rows of a
              driver window, and the chip.

A reader may name, in `SPANS`, program spans beyond spans.PROGRAM_SPANS
that it reads.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import resource
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

import devtrace
import spans

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".bench_cache")
TRACE_SECONDS = 4.0
WINDOW_PROGRAM = "_window_blob"


class NoChip(RuntimeError):
    """JAX finds no accelerator of the kind, or too few chips."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    return bench, cell, config, traffic


def metrics_of(bench: dict, cell: str, kind: str) -> list[dict]:
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def load_plugin(kind: str, name: str):
    """The module `<kind>/<name>.py` of the benchmark: a metric's reader,
    a configuration's semantics."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", os.path.join(HERE, kind, f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(name: str, ctx: dict):
    return load_plugin("metrics", name).read(ctx)


def semantics_of(config: dict):
    return load_plugin("semantics", config.get("semantics", "static_gang"))


class CompileCounter:
    """Programs compiled or loaded from the persistent cache, and jaxprs
    traced, as jax.monitoring reports them."""

    def __init__(self):
        self.compiles = 0
        self.traces = 0
        self.compile_s = 0.0
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if "backend_compile" in event or "cache_retrieval" in event:
            self.compiles += 1
            self.compile_s += float(duration)
        elif "jaxpr_trace" in event:
            self.traces += 1

    def snap(self) -> tuple[int, int]:
        return self.compiles, self.traces


class HostProbe:
    """What the host did in the window besides serving: garbage
    collections and their pauses, and the process's CPU seconds. Read to
    find why one run is slower than another."""

    def __init__(self):
        self.on = False
        self.pause_s = 0.0
        self.collections = [0, 0, 0]
        self._t0 = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if not self.on:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pause_s += time.perf_counter() - self._t0
            self.collections[info["generation"]] += 1

    def start(self) -> None:
        self._ru0 = resource.getrusage(resource.RUSAGE_SELF)
        self.on = True

    def stop(self, window_s: float) -> dict:
        self.on = False
        gc.callbacks.remove(self._on_gc)
        ru = resource.getrusage(resource.RUSAGE_SELF)
        cpu = (ru.ru_utime - self._ru0.ru_utime) + (ru.ru_stime - self._ru0.ru_stime)
        return {
            "gc_pause_s": self.pause_s, "gc_collections": list(self.collections),
            "cpu_per_wall": cpu / window_s,
        }


def host_windows(paths: dict[str, int]) -> int:
    """Windows the program served on the host, not the device."""
    return sum(v for k, v in paths.items() if "greedy" in k or "fallback" in k)


def log(**fields) -> None:
    print(json.dumps(fields, sort_keys=True), flush=True)


def percentile(vals, q):
    return float(np.percentile(np.asarray(vals, float), q)) if vals else None


def run_cell(name: str, seed: int, seconds: float, trace: bool, *, t_start: float,
             require_tpu: bool = True, overrides: dict | None = None,
             tamper=None, ctx_out: dict | None = None) -> dict:
    """One run. `overrides` replaces keys of the configuration, under
    "traffic" keys of the traffic (a test's small cluster and short
    queue), and under "program" keys of the install that the program
    alone gets, the reference keeping the configuration's (a control);
    `tamper(served)` plants a fault in the booted program; a traced run
    copies its readers' ctx into `ctx_out` (spans.py reads it). Returns
    the result line's object."""
    import jax

    devices = jax.devices()
    bench, cell, config, traffic = load_cell(name)
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX backend is {devices[0].platform}, not tpu")
    if len(devices) < int(cell["chips"]):
        raise NoChip(f"the cell asks for {cell['chips']} chips, JAX sees {len(devices)}")
    dev = devices[0]
    log(phase="device", platform=dev.platform, kind=dev.device_kind, count=len(devices))
    program = {}
    for key, val in (overrides or {}).items():
        if key == "traffic":
            traffic.update(val)
        elif key == "program":
            program = val
        else:
            config[key] = val if not isinstance(val, dict) else {**config.get(key, {}), **val}
    sem = semantics_of(config)
    readers = [(m, load_plugin("metrics", m["name"])) for m in metrics_of(bench, name, "per_layer")]
    counter = CompileCounter()
    t_import = time.time()

    world = sem.build_world(config, traffic, seed, t_start)
    t_world = time.time()

    sys.path.insert(0, ROOT)
    import served as S

    install = {**config["install"], **program}
    install["jax-compilation-cache-dir"] = os.path.join(CACHE_DIR, "jax")
    served = S.Served(install, config.get("runtime", {}), world.nodes, world.pods, world.apps)
    world.nodes = world.pods = None
    t_boot = time.time()
    if tamper is not None:
        tamper(served)

    annotate = None
    if trace:
        annotate = jax.profiler.TraceAnnotation
    gen = sem.make_generator(world, config, traffic, served, annotate)
    try:
        warm = int(traffic["warmup_cycles"])
        cycles = 0
        while True:
            before = counter.snap()
            gen.cycle()
            cycles += 1
            quiet = counter.snap() == before
            if cycles >= warm and quiet or cycles >= 4 * warm + 16:
                break
        c0 = counter.snap()
        calls0 = len(gen.calls)
        errors0 = len(gen.errors)
        seq0 = served.recorder_seq()
        client0, watch0 = gen.client_s, gen.watch_s
        gen.in_window = True
        # The world is the benchmark's data and the reference's packing of
        # it: no change to the program moves it, so set-up leaves it out.
        world_s = t_world - t_import
        setup_s = time.time() - t_start - world_s
        log(phase="setup", setup_s=setup_s, import_s=t_import - t_start,
            world_s=world_s, boot_s=t_boot - t_world,
            warmup_s=time.time() - t_boot, warmup_cycles=cycles, warmup_quiet=quiet,
            running_apps=len(world.running), pending=len(world.pending),
            compiles=c0[0], compile_s=counter.compile_s)

        trace_dir = None
        window = min(seconds, TRACE_SECONDS) if trace else seconds
        with contextlib.ExitStack() as stack:
            if trace:
                counters0 = served.counters()
                trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                # The program's own capture: its spans land in the trace.
                served.start_profile(trace_dir, opts)
                stack.callback(served.stop_profile)
                stack.enter_context(jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN))
            probe = HostProbe()
            probe.start()
            t_w0 = time.perf_counter()
            t_w1 = gen.run_until(t_w0 + window)
        gen.in_window = False
        window_s = t_w1 - t_w0
        host = probe.stop(window_s)
        c1 = counter.snap()
        compiles_in_window, traces_in_window = c1[0] - c0[0], c1[1] - c0[1]
        in_window = [c for c in gen.calls[calls0:] if c[2]]
        log(phase="window", window_s=window_s, calls=len(in_window),
            compiles_in_window=compiles_in_window, traces_in_window=traces_in_window,
            client_share=(gen.client_s - client0) / window_s,
            watch_share=(gen.watch_s - watch0) / window_s,
            cycles=sum(1 for e in gen.log if e[0] == "complete"), **host)

        peak = 0
        for d in devices[: int(cell["chips"])]:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        if trace:
            counters1 = served.counters()
            records, lost = served.records(seq0)
        invariants = served.invariants(gen.expected_reservations())
    finally:
        gen.close()
        served.stop()
    del served

    red = prog = None
    if trace:
        names = set(spans.PROGRAM_SPANS).union(
            getattr(sem, "SPANS", ()), *(getattr(mod, "SPANS", ()) for _, mod in readers))
        try:
            path = devtrace.find_xplane(trace_dir)
            events = devtrace.load(path)
            # The CPU backend writes no device plane (a test's run).
            if events["devices"] or require_tpu:
                red = devtrace.reduce(events, WINDOW_PROGRAM)
            prog = spans.reduce_program(events, spans.load_program(path, names))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        phases = [dict(r["phases"], queue_position=r["queue_position"])
                  for r in records if r["role"] == "driver" and r["phases"]]
        log(phase="trace", program_runs=red and red["program_runs"], recorder_lost=lost,
            records=len(records), driver_records=len(phases),
            program_spans=sum(prog["span_count"].values()))

    t_ref = time.time()
    try:
        out = sem.replay(world, gen.log, gen.bodies)
    except (RuntimeError, ValueError, KeyError) as exc:
        out = {"driver_mismatches": 1, "executor_mismatches": 0, "denials_bad_map": 0,
               "reference_overcommitted_nodes": 0, "replay_error": repr(exc)}
    log(phase="check", reference_s=time.time() - t_ref, **out, **invariants)

    checks = {
        "driver_mismatches": out["driver_mismatches"],
        "executor_mismatches": out["executor_mismatches"],
        "denials_without_full_map": out["denials_bad_map"],
        "overcommitted_nodes": out["reference_overcommitted_nodes"]
        + invariants["bound_overcommitted_nodes"] + invariants["reserved_overcommitted_nodes"],
        "apps_off_reservation": invariants["apps_off_reservation"],
        "error_answers": len(gen.errors),
        # The answers have to come from the device path the window times:
        # degraded mode serves the same answers from the host.
        "degraded_engagements": invariants["degraded_engagements"],
        "windows_off_device": host_windows(invariants["window_paths"]),
        "compiles_in_window": compiles_in_window,
        "traces_in_window": traces_in_window,
    }
    extra = out.get("checks", {})
    if set(extra) & set(checks):
        raise ValueError(f"semantics checks {sorted(set(extra) & set(checks))} shadow the harness's")
    checks.update(extra)
    correct = all(v == 0 for v in checks.values())

    drivers = [ms for role, ms, w in in_window if role == "driver"]
    execs = [ms for role, ms, w in in_window if role == "executor"]
    values = {
        "predicates_per_s": len(in_window) / window_s,
        "driver_p50_ms": percentile(drivers, 50),
        "driver_p95_ms": percentile(drivers, 95),
        "executor_p99_ms": percentile(execs, 99),
        "setup_s": setup_s,
    }
    metrics = {}
    if not trace:
        for m in metrics_of(bench, name, "end_to_end"):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        rows = [p["queue_position"] + 1 for p in phases if p.get("queue_position") is not None]
        ctx = {
            "phases": phases, "trace": red, "program": prog, "records": records,
            "calls": [(role, ms) for role, ms, _ in in_window],
            "counters": {k: v - counters0.get(k, 0) for k, v in counters1.items()},
            "nodes": world.n_nodes, "executors": world.executors,
            "rows_mean": statistics.fmean(rows) if rows else None,
            "device_kind": dev.device_kind,
        }
        if ctx_out is not None:
            ctx_out.update(ctx)
        for m, mod in readers:
            v = mod.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": int(cell["chips"]), "memory_peak_bytes": peak,
    }
    result = {
        "correct": correct,
        "attempted": len(in_window),
        "failed": len(gen.errors) - errors0,
        "metrics": metrics,
        "device": device,
    }
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": prog["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    return result
