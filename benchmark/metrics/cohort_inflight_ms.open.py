"""Mean wait from a fast-path cohort's launch to the end of its readback
over the window, in ms: delta sum / delta count of the histogram
``fastpath.inflight`` from ``GET /_nodes/stats``."""

from benchmark.readers import mean_ms


def read(ctx):
    return mean_ms(ctx, "fastpath.inflight")
