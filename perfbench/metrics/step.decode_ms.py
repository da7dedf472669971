"""Device time of the decode program in the traced window, per decode
step taken in it.  The decode program is the one whose paged
decode-attention kernel gets a page table wider than one row's."""

import trace_reduce


def read(ctx):
    if ctx.trace is None or not ctx.trace_span or not ctx.decodes:
        return None
    np_pages = ctx.max_len // ctx.serving["page_size"]
    paged = trace_reduce.pallas_call("s32[")
    mods = trace_reduce.modules_with(
        ctx.trace, lambda op: paged(op)
        and trace_reduce.operand_size(op) > np_pages)
    ns = trace_reduce.time_where(ctx.trace, lambda op: op.module in mods,
                                 *ctx.trace_span)
    return ns / 1e6 / len(ctx.decodes) if ns else None
