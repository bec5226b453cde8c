"""Answer encode of a driver call: mean of the program's
`predicate:encode` span over the driver requests of the traced window (a
denial names every offered node in its FailedNodes)."""


def read(ctx):
    return ctx["program"]["by_role_ms"].get("driver", {}).get("predicate:encode")
