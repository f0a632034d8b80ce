"""hist_roofline: the least time the chips need for the histograms the
algorithm requires (``work.hist_work``: bytes over HBM bandwidth or adds
over peak, whichever is larger, from ``peaks.json``), as a share of the
kernel's device time per fit (``hist_kernel_s``)."""
import work
from harness import metric_reader


def read(ctx):
    kernel_s = metric_reader("hist_kernel_s")(ctx)
    if not kernel_s or ctx["peak"] is None:
        return None
    need, _bound = work.required_seconds(ctx["counters"]["work"]["hist"],
                                         ctx["peak"], ctx["chips"])
    return 100.0 * need / kernel_s
