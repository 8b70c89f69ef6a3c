"""Process start to the first due request: data, node start, mount,
fast-path registration, every warm compile or compile-cache load, and
the warm-up traffic."""


def read(ctx):
    return ctx.setup_s
