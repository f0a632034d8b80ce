"""fit_trace_s: seconds per fit that ``jax.monitoring`` reports for jaxpr
tracing and lowering to MLIR, the mean over the window's jobs.  A fit that
builds a new ``jax.jit`` closure pays them again even when its executable
comes from the persistent cache."""


def read(ctx):
    v = ctx["counters"].get("fit_trace_s")
    return sum(v) / len(v) if v else None
