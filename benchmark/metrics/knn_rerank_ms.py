"""Mean time of a kNN query's float32 host re-rank over the window, in
ms: delta sum / delta count of the histogram ``knn.rerank`` from ``GET
/_nodes/stats``."""

from benchmark.readers import mean_ms


def read(ctx):
    return mean_ms(ctx, "knn.rerank")
