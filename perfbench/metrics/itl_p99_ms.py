"""99th percentile over every gap between consecutive committed tokens
of a request, both inside the window."""

import numpy as np


def read(ctx):
    gaps = []
    for r in ctx.reqs:
        ts = [t for t in r.t_tokens if ctx.inside(t)]
        gaps.extend(np.diff(ts))
    return float(np.percentile(gaps, 99) * 1e3) if gaps else None
