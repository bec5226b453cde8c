"""Device time of one driver window: the window program's runs in the
profiler trace (`jit__window_blob_pallas` and its kind), summed and
divided by the number of runs."""


def read(ctx):
    trace = ctx["trace"]
    return None if trace is None else trace["program_ms_per_run"]
