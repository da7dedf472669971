"""Device time of the suffix-prefill program per suffix token, over the
``engine.suffix_prefill`` spans that start in the traced window.

Each span's scan is counted whole, in the window or past its end: the
ops of the modules named ``jit__paged_suffix_prefill`` between the end
of the host's last wait on the device (``engine.sync``) before the span
and the end of its next one after it.  The device had run every earlier
program by the one, and cannot have ended the scan after the other,
since every later program reads the state the scan writes; between the
two the device clock, which runs a little apart from the host's, may
place the scan's first ops just before its span.  A span with no wait
after it in the trace, its scan cut off, is left out with its
``tokens``."""

import spans
import trace_reduce

PROGRAM = "jit__paged_suffix_prefill"


def read(ctx):
    if ctx.trace is None or not ctx.trace_span:
        return None
    syncs = spans.waits(ctx.trace)
    tokens, scans = 0, []
    for h in spans.starting_in(ctx.trace, "engine.suffix_prefill",
                               *ctx.trace_span):
        done = spans.next_wait(syncs, h)
        if done is not None:
            tokens += h.stats.get("tokens", 0)
            scans.append((spans.last_wait_end(syncs, h), done.end))
    ns = sum(trace_reduce.time_where(
        ctx.trace, lambda op: op.module.startswith(PROGRAM), s, e)
        for s, e in spans.union(scans))
    return ns / 1e6 / tokens if ns and tokens else None
