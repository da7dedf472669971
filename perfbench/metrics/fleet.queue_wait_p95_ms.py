"""95th percentile of queue wait: submit to placement on an engine (the
ticket's QUEUED -> PREFILLING transition), over requests placed in the
window."""

import numpy as np


def read(ctx):
    waits = []
    for r in ctx.reqs:
        ev = getattr(r.ticket, "events", None) or []
        t_q = next((e.t for e in ev if e.dst == "queued"), None)
        t_p = next((e.t for e in ev if e.dst == "prefilling"), None)
        if t_q is not None and t_p is not None and ctx.inside(t_p):
            waits.append(t_p - t_q)
    return float(np.percentile(waits, 95) * 1e3) if waits else None
