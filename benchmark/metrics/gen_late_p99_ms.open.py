"""How late the load generator sent: send time minus due time, 99th
percentile over the window's requests (open loop)."""

from benchmark.readers import percentile_ms


def read(ctx):
    return percentile_ms(ctx.late_s, 99)
