"""ingest_hash_s: seconds per fit job in the program span ``ingest.hash``:
salted SHA-256 of every party's sample IDs, summed over the parties."""

import program_spans


def read(ctx):
    return program_spans.per_job_s(ctx, "ingest.hash")
