"""serve_collect_ms: milliseconds per served wave in the program span
``serve.collect``: waiting for the wave's result, the copy back, stripping
the padding and decoding."""

import program_spans


def read(ctx):
    return program_spans.per_wave_ms(ctx, "serve.collect")
