"""Share of the traced window in which no operation ran on the device,
in %."""


def read(ctx):
    if ctx.trace is None or not ctx.trace_span or not ctx.trace.devices:
        return None
    import trace_reduce
    t0, t1 = ctx.trace_span
    busy = trace_reduce.busy_ns(ctx.trace, t0, t1)
    return 100.0 * (1.0 - busy / (t1 - t0))
