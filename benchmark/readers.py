"""What the metric files under ``metrics/`` share. Each reader takes the
run's context (harness.context) and returns a number, or None where the
run holds nothing to read."""

from __future__ import annotations

import numpy as np

from benchmark import peaks
from benchmark import trace as trace_mod
from benchmark.work import bm25


def percentile_ms(values, q: float):
    if values is None or len(values) == 0:
        return None
    return float(np.percentile(values, q)) * 1000.0


def ratio(ctx, num: str, den: str):
    d = ctx.delta(den)
    return ctx.delta(num) / d if d > 0 else None


def mean_ms(ctx, histogram: str):
    """Mean of a node histogram's observations (ms) in the window:
    delta sum / delta count (harness.HISTOGRAMS)."""
    return ratio(ctx, histogram + ".sum", histogram + ".count")


def device_idle(ctx):
    """Share of the traced window in which no operation ran on the
    device, in %."""
    red = ctx.trace
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def roofline(ctx, work_mod, flops: float, nbytes: float):
    """Least time of the algorithm's work over the device time of the
    programs that claim it, in %."""
    if ctx.trace is None:
        return None
    spent = trace_mod.program_seconds(ctx.trace, work_mod.PREFIXES)
    if spent <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    return 100.0 * peaks.least_seconds(flops, nbytes,
                                       ctx.device_kind) / spent


def bm25_roofline(ctx, qs):
    """The BM25 work of the queries ``qs`` (the real postings of their
    terms) against the BM25 programs' device time in the window."""
    postings = sum(ctx.body.postings(ctx.data, ctx.params, q) for q in qs)
    flops, nbytes = bm25.work(postings, len(qs), ctx.params["size"])
    return roofline(ctx, bm25, flops, nbytes)
