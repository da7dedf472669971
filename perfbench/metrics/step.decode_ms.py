"""Device time of the decode program (``jit__paged_decode_step``) in the
traced window, per decode step taken in it."""

import trace_reduce

PROGRAM = trace_reduce.in_program("jit__paged_decode_step")


def read(ctx):
    if ctx.trace is None or not ctx.trace_span or not ctx.decodes:
        return None
    ns = trace_reduce.time_where(ctx.trace, PROGRAM, *ctx.trace_span)
    return ns / 1e6 / len(ctx.decodes) if ns else None
