"""ingest_align_s: seconds per fit job in the program span ``ingest.align``:
the per-party uniqueness checks and the hashed-ID intersection
(``crypto.align_ids``)."""

import program_spans


def read(ctx):
    return program_spans.per_job_s(ctx, "ingest.align")
