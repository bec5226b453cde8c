"""Wait from a driver window's device dispatch to its decisions on the
host: mean of the flight recorder's `solve_ms` over the driver decisions
of the traced window. Host clock: device time plus transfers and wake-up."""


def read(ctx):
    vals = [p["solve_ms"] for p in ctx["phases"] if "solve_ms" in p]
    return sum(vals) / len(vals) if vals else None
