"""collective_s: device seconds of all-gather and all-reduce ops per fit,
summed over the chips (the level collectives of the sharded substrate).
Silent where no collective ran, as on one chip under vmap."""


def read(ctx):
    jobs, trace = ctx["counters"].get("jobs"), ctx["trace"]
    if not jobs or not trace or not trace["collective_s"]:
        return None
    return trace["collective_s"] / jobs
