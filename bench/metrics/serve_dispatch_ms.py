"""serve_dispatch_ms: milliseconds per served wave in the program span
``serve.dispatch``: padding the wave to its bucket, the copy to the device
and the launch."""

import program_spans


def read(ctx):
    return program_spans.per_wave_ms(ctx, "serve.dispatch")
