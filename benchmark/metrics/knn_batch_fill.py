"""Queries per kNN cohort launch over the window: delta batched_queries
/ delta launches of the node's KnnBatcher (read in-process)."""

from benchmark.readers import ratio


def read(ctx):
    return ratio(ctx, "knn_batched_queries", "knn_launches")
