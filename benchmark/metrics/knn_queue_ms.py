"""Mean wait from a kNN query's enqueue to its cohort's launch over the
window, in ms: delta sum / delta count of the histogram
``knn.queue_wait`` from ``GET /_nodes/stats``."""

from benchmark.readers import mean_ms


def read(ctx):
    return mean_ms(ctx, "knn.queue_wait")
