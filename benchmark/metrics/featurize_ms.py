"""Host featurize time of a driver window: mean of the flight recorder's
`featurize_ms` over the driver decisions of the traced window."""


def read(ctx):
    vals = [p["featurize_ms"] for p in ctx["phases"] if "featurize_ms" in p]
    return sum(vals) / len(vals) if vals else None
