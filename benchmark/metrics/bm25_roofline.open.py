"""Least time of the BM25 work of the queries answered in the window
(work/bm25.py: the real postings of their terms) over the device time
of the fast path's scoring programs in the trace, in %."""

from benchmark.readers import roofline
from benchmark.work import bm25


def read(ctx):
    qs = ctx.queries_done
    postings = sum(ctx.body.postings(ctx.data, ctx.params, q) for q in qs)
    flops, nbytes = bm25.work(postings, len(qs), ctx.params["size"])
    return roofline(ctx, bm25, flops, nbytes)
