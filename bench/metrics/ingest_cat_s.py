"""ingest_cat_s: seconds per fit job in the program span
``ingest.categories``: each party's category dictionaries and codes of its
categorical columns, summed over the parties (None where no column is
categorical)."""

import program_spans


def read(ctx):
    return program_spans.per_job_s(ctx, "ingest.categories")
