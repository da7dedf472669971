"""The host's own work per fleet step: the mean, over the ``fleet.step``
spans that start in the traced window, of each span's duration less the
time its nested ``engine.sync`` spans (waits on the device) cover."""

import spans


def read(ctx):
    if ctx.trace is None or not ctx.trace_span:
        return None
    steps = spans.starting_in(ctx.trace, "fleet.step", *ctx.trace_span)
    if not steps:
        return None
    syncs = spans.waits(ctx.trace)
    return sum(h.dur - spans.wait_ns(syncs, h)
               for h in steps) / len(steps) / 1e6
