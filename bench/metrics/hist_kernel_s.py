"""hist_kernel_s: device seconds of the Pallas histogram kernel per fit,
averaged over the chips the fit runs on: the summed durations of the
kernel's events in the trace of the window, over jobs and chips.  On a v5e
the kernel's ``XLA Ops`` events carry the name of its jitted wrapper,
``%histogram_pallas.<n>``, one op per split level of the fit program."""
from trace_reduce import op_seconds

KERNEL = r"histogram_pallas"


def read(ctx):
    jobs, trace = ctx["counters"].get("jobs"), ctx["trace"]
    if not jobs or not trace or not trace["devices"]:
        return None
    secs, n = op_seconds(trace, KERNEL)
    return secs / trace["devices"] / jobs if n else None
