"""Least time of the kNN work of the window's launches (work/knn.py: one
read of the slab per launch, 2 Q N D flops) over the device time of
the kNN cohort program in the trace, in %."""

from benchmark.bodies.knn import vectors
from benchmark.readers import roofline
from benchmark.work import knn


def read(ctx):
    n, dims = vectors(ctx.data, ctx.params).shape
    flops, nbytes = knn.work(ctx.delta("knn_launches"),
                             ctx.delta("knn_batched_queries"), n, dims)
    return roofline(ctx, knn, flops, nbytes)
