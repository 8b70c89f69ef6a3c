"""Least time of the BM25 work of the queries answered in the window
(work/bm25.py: the real postings of their terms) over the device time
of the fast path's scoring programs in the trace, in %."""

from benchmark.readers import bm25_roofline


def read(ctx):
    return bm25_roofline(ctx, ctx.queries_done)
