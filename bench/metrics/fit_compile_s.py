"""fit_compile_s: seconds per fit job in the program span ``fit.compile``:
XLA compilation of the fit program, or its load from the persistent
cache."""

import program_spans


def read(ctx):
    return program_spans.per_job_s(ctx, "fit.compile")
