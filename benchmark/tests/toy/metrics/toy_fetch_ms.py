"""A reader a test plugs in by name: the mean of a program span that
spans.PROGRAM_SPANS does not list, which it names in SPANS."""

SPANS = ("toy-fetch",)


def read(ctx):
    return ctx["program"]["span_ms"].get("toy-fetch")
