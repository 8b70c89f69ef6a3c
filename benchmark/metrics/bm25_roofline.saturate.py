"""Least time of the BM25 work of the queries completed within the
window (work/bm25.py: the real postings of their terms) over the device
time of the fast path's scoring programs in the trace, in %. The closed
loop starts the window with nothing in flight, so no completed query's
work lies before it. The queries still in flight at its close (up to
one per connection) are left out of the work, but the device time that
they took inside the window is counted: the share reads low by that
tail, at most one launched cohort per stream (~0.4 s each at 4096
blocks), up to ~6 % of a 25-s window."""

from benchmark.readers import bm25_roofline


def read(ctx):
    return bm25_roofline(ctx, ctx.queries_completed)
