"""``_search`` requests answered within the window over its length, in
queries/s (closed loop)."""


def read(ctx):
    return ctx.completed / ctx.seconds if ctx.loop == "closed" else None
