"""Host launch of a driver window's device program: mean of the flight
recorder's `dispatch_ms` (the `solve-dispatch` span) over the driver
decisions of the traced window. Part of `solve_wait_ms`."""


def read(ctx):
    vals = [p["dispatch_ms"] for p in ctx["phases"] if "dispatch_ms" in p]
    return sum(vals) / len(vals) if vals else None
