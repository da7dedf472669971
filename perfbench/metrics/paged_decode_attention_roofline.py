"""Share of its roofline that the paged decode-attention kernel reached
in the traced window, in %: the least time for every call (operations
and the live KV bytes each row must read, from the calls' shapes) over
the kernel's device time.  One call per layer for every decode step and
for every token of a suffix prefill admitted in the window."""

import cells
import flops
import trace_reduce

KERNEL = trace_reduce.kernel("paged_decode_attention")


def read(ctx):
    if ctx.trace is None or not ctx.trace_span:
        return None
    ns = trace_reduce.time_where(ctx.trace, KERNEL, *ctx.trace_span)
    if not ns:
        return None
    m = cells.reference(ctx.conf).paged_attention(ctx.conf)
    layers = m["layers"]
    peak = ctx.peak()

    def t(lens):
        ops, byts = flops.paged_decode_attention(lens, m["H"], m["KV"],
                                                 m["Dh"])
        return layers * max(ops / peak["flops"], byts / peak["bytes_per_s"])

    bound = sum(t(lens) for _, lens in ctx.decodes if lens)
    for _, n, hit in ctx.admissions:
        if 0 < hit < n:
            bound += sum(t([p + 1]) for p in range(hit, n))
    if not bound:
        return None
    return 100.0 * bound / (ns / 1e9)
