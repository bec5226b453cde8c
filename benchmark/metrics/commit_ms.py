"""Reservation commit time of a driver window: mean of the flight
recorder's `commit_ms` over the driver decisions of the traced window."""


def read(ctx):
    vals = [p["commit_ms"] for p in ctx["phases"] if "commit_ms" in p]
    return sum(vals) / len(vals) if vals else None
