"""Median ``_search`` latency of every request due in the window, timed
from its due time; a failed request counts as missing."""

from benchmark.readers import percentile_ms


def read(ctx):
    return percentile_ms(ctx.latency_s, 50) if ctx.loop == "open" else None
