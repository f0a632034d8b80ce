"""predict_device_ms: device milliseconds per served wave: the durations of
the programs (``XLA Modules`` events) executed in the serving window, which
are the bucketed one-round prediction executables, over the waves."""


def read(ctx):
    serve, trace = ctx["counters"].get("serve"), ctx["trace"]
    if not serve or not serve["waves"] or not trace or not trace["devices"]:
        return None
    secs = sum(m["s"] for m in trace["modules"].values())
    return 1e3 * secs / trace["devices"] / serve["waves"]
