"""Host time of an extra executor call's reschedule: the mean of the
program's `executor-reschedule` span (the app's context, the candidate
filter, the tensor build, the one-executor device solve and the soft
reservation's write) over the traced window. In this cell only extra
executors reschedule, one span each."""

SPANS = ("executor-reschedule",)


def read(ctx):
    return ctx["program"]["span_ms"].get("executor-reschedule")
