"""95th percentile of time to first token over every request due in the
window and first served in it: from when the request was due to the host
clock read after the step() that committed its first token."""

import numpy as np


def read(ctx):
    xs = [r.t_tokens[0] - r.t_due for r in ctx.reqs
          if ctx.inside(r.t_due) and r.t_tokens and ctx.inside(r.t_tokens[0])]
    return float(np.percentile(xs, 95) * 1e3) if xs else None
