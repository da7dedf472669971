"""Share of the prompt tokens admitted in the window that the prefix
cache served (PagedEngine.last_prefix_hit at each admission), in %."""


def read(ctx):
    total = sum(n for _, n, _ in ctx.admissions)
    if not total:
        return None
    return 100.0 * sum(h for _, _, h in ctx.admissions) / total
