"""Device time of the prefill programs in the traced window -- the cold
prefill (the one calling the flash-attention kernel) and the suffix
prefill over cached pages (the one whose paged decode-attention kernel
gets a single row's page table) -- per request admitted in it."""

import trace_reduce


def read(ctx):
    if ctx.trace is None or not ctx.trace_span or not ctx.admissions:
        return None
    np_pages = ctx.max_len // ctx.serving["page_size"]
    paged = trace_reduce.pallas_call("s32[")
    flash = trace_reduce.pallas_call("bf16[")
    mods = trace_reduce.modules_with(
        ctx.trace, lambda op: flash(op) or (
            paged(op) and trace_reduce.operand_size(op) == np_pages))
    ns = trace_reduce.time_where(ctx.trace, lambda op: op.module in mods,
                                 *ctx.trace_span)
    return ns / 1e6 / len(ctx.admissions) if ns else None
