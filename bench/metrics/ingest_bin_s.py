"""ingest_bin_s: seconds per fit job in the program span ``ingest.bin``:
party-local quantile binning of every party's aligned columns, summed over
the parties."""

import program_spans


def read(ctx):
    return program_spans.per_job_s(ctx, "ingest.bin")
