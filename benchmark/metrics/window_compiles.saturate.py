"""First executions (compiles and compile-cache loads) of tracked
kernels inside the window: TRACKER count + cache_hits delta read from
``GET /_kernels``. Should be 0."""


def read(ctx):
    return float(ctx.delta("first_executions"))
