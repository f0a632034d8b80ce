"""fit_mfu: the least time the chips need for the whole fit the algorithm
requires (``work.fit_work``: histograms, routing and node statistics, on the
peaks of ``peaks.json``), as a share of the host-clock fit time ``fit_s``."""
import work


def read(ctx):
    fit_s = ctx["counters"].get("fit_s")
    if not fit_s or ctx["peak"] is None:
        return None
    need, _bound = work.required_seconds(ctx["counters"]["work"]["fit"],
                                         ctx["peak"], ctx["chips"])
    return 100.0 * need / (sum(fit_s) / len(fit_s))
