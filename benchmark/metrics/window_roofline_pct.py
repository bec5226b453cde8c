"""The window program's share of its bytes roofline: the least time the
window's bytes take at the chip's peak memory bandwidth, over the device
time it took. The floor counts bytes only, since the window does next to
no arithmetic; but the window is not held by bandwidth. Its kernel walks
the rows one after another, so it is bound by that serial latency, and
the share reads far under 1%: it bounds the kernel from below and shows
a change of bound, not the size of a gain. `window_device_ms` shows that."""

import work


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace["program_ms_per_run"] or not ctx["rows_mean"]:
        return None
    least_s = work.window_bytes(ctx["nodes"], ctx["rows_mean"], ctx["executors"]) / work.peak(
        ctx["device_kind"], "hbm_bytes_per_s"
    )
    return 100.0 * least_s / (trace["program_ms_per_run"] / 1e3)
