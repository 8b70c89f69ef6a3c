"""Mean wait from the parse stamp of a fast-path query to its cohort's
launch over the window, in ms: delta sum / delta count of the
histogram ``fastpath.queue_wait`` from ``GET /_nodes/stats``."""

from benchmark.readers import mean_ms


def read(ctx):
    return mean_ms(ctx, "fastpath.queue_wait")
