"""A reader a test plugs in by name: decisions the program recorded in
the traced window, by the change of its recorder's counter."""


def read(ctx):
    return ctx["counters"].get("foundry.spark.scheduler.recorder.total_recorded")
