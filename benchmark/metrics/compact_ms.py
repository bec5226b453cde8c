"""Host time of a compaction pass that had work: the mean of the
program's `compact` span, which opens only where a deleted executor
queued its app, over the traced window (soft-reserved executors taking
over the hard slots their app's dead executors freed, before the next
request is served)."""

SPANS = ("compact",)


def read(ctx):
    return ctx["program"]["span_ms"].get("compact")
