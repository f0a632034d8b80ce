"""ingest_s: host seconds of ``Federation.ingest`` per fit job, the mean over
the window's jobs (hashing and aligning the party blocks, binning)."""


def read(ctx):
    v = ctx["counters"].get("ingest_s")
    return sum(v) / len(v) if v else None
