"""Mean wait from the native front's arrival stamp of a request to a
Python fallback worker taking it over the window, in ms: delta sum /
delta count of the histogram ``http.fallback.queue_wait`` from ``GET
/_nodes/stats``."""

from benchmark.readers import mean_ms


def read(ctx):
    return mean_ms(ctx, "http.fallback.queue_wait")
