"""95th percentile of how late the load generator sent each request
after it was due, ms (host clock).  The harness is one thread: a request
that falls due during an engine step is sent when the step returns."""

import numpy as np


def read(ctx):
    v = [r.sent - r.due for r in ctx.requests]
    return float(np.percentile(v, 95)) * 1e3 if v else None
