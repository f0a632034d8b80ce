"""serve_bin_ms: milliseconds per served wave in the program span
``serve.bin``: coalescing pending requests into the wave and binning their
rows."""

import program_spans


def read(ctx):
    return program_spans.per_wave_ms(ctx, "serve.bin")
