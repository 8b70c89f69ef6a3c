"""Queries per fast-path cohort over the window (of q_batch = 32):
delta fast_queries / delta cohorts from ``GET /_kernels`` serving."""

from benchmark.readers import ratio


def read(ctx):
    return ratio(ctx, "fast_queries", "cohorts")
