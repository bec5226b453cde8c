"""Device solve of an extra executor, from its dispatch to its answer on
the host: the mean of the flight recorder's `solve_ms` over the executor
decisions of the traced window that carry one (the executors that went
through a reschedule solve), as `solve_wait_ms` reads it for drivers."""


def read(ctx):
    vals = [r["phases"]["solve_ms"] for r in ctx["records"]
            if r["role"] == "executor" and "solve_ms" in (r.get("phases") or {})]
    return sum(vals) / len(vals) if vals else None
