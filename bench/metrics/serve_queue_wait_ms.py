"""serve_queue_wait_ms: milliseconds per request that the program counts
under ``queue.wait`` (``TRACER.add`` at dispatch): a request's time from its
submission to the dispatch of its first rows."""

import program_spans


def read(ctx):
    return program_spans.per_request_ms(ctx, "queue.wait")
