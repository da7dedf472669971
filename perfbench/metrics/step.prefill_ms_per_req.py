"""Device time of the prefill programs in the traced window -- the cold
prefill (``jit__paged_prefill``) and the suffix prefill over cached
pages (``jit__paged_suffix_prefill``) -- per request admitted in it."""

import trace_reduce

PROGRAMS = trace_reduce.in_program("jit__paged_prefill",
                                   "jit__paged_suffix_prefill")


def read(ctx):
    if ctx.trace is None or not ctx.trace_span or not ctx.admissions:
        return None
    ns = trace_reduce.time_where(ctx.trace, PROGRAMS, *ctx.trace_span)
    return ns / 1e6 / len(ctx.admissions) if ns else None
