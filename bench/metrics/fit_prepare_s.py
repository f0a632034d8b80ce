"""fit_prepare_s: seconds per fit job in the program span ``fit.prepare``:
label encoding, label statistics, the master's bootstrap and feature draws
and the inputs' copies to the device."""

import program_spans


def read(ctx):
    return program_spans.per_job_s(ctx, "fit.prepare")
