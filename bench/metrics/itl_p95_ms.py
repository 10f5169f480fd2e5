"""95th percentile of the gaps between successive tokens of a request,
ms, over every gap in the window (host clock at the end of each step)."""

import numpy as np


def read(ctx):
    v = [b - a for r in ctx.requests
         for a, b in zip(r.token_times, r.token_times[1:])]
    return float(np.percentile(v, 95)) * 1e3 if v else None
