"""Body decode of a driver call: mean of the program's `predicate:decode`
span over the driver requests of the traced window (the transport parses
the ExtenderArgs JSON, every offered node name in it)."""


def read(ctx):
    return ctx["program"]["by_role_ms"].get("driver", {}).get("predicate:decode")
