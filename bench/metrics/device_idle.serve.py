"""device_idle.serve: share of the traced serving window in which no op ran
on a device (1 - union of op intervals / window), averaged over the chips."""


def read(ctx):
    trace = ctx["trace"]
    if "serve" not in ctx["counters"] or not trace or not trace["devices"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
