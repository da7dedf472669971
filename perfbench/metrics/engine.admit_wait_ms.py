"""How long one admission holds the fleet's dispatch loop waiting on the
device: the mean, over the admitted ``engine.admit`` spans that start in
the traced window, of the time their nested ``engine.sync`` spans
cover."""

import spans


def read(ctx):
    if ctx.trace is None or not ctx.trace_span:
        return None
    admits = [h for h in spans.starting_in(ctx.trace, "engine.admit",
                                           *ctx.trace_span)
              if "cached" in h.stats]        # a refused one has only its row
    if not admits:
        return None
    syncs = spans.waits(ctx.trace)
    return sum(spans.wait_ns(syncs, h) for h in admits) / len(admits) / 1e6
