"""99th percentile of the same latencies as p50_ms."""

from benchmark.readers import percentile_ms


def read(ctx):
    return percentile_ms(ctx.latency_s, 99) if ctx.loop == "open" else None
