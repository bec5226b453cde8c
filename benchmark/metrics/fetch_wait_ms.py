"""Blocking wait of a driver window's fetch on its decision pull: mean of
the flight recorder's `fetch_wait_ms` (the `fetch-wait` span inside
`solve`) over the driver decisions of the traced window. Part of
`solve_wait_ms`; the serving loop completes a window once its pull has
landed, so the device time mostly elapses before the fetch, not in it."""


def read(ctx):
    vals = [p["fetch_wait_ms"] for p in ctx["phases"] if "fetch_wait_ms" in p]
    return sum(vals) / len(vals) if vals else None
