"""Output tokens committed in the window, over the window."""


def read(ctx):
    n = sum(1 for r in ctx.reqs for t in r.t_tokens if ctx.inside(t))
    return n / ctx.seconds
