"""fit_s: host seconds of ``Federation.fit`` per job, from the call to the
fitted trees being ready on the device; the mean over the window's jobs."""


def read(ctx):
    v = ctx["counters"].get("fit_s")
    return sum(v) / len(v) if v else None
