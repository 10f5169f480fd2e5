"""Median time to first token, ms: from when each request was due (its
scheduled arrival in an open loop, its send in a closed one) to the end
of the engine step that returned its first token, over every request
whose first token came in the window."""

import numpy as np


def read(ctx):
    v = [r.token_times[0] - r.due for r in ctx.requests if r.token_times]
    return float(np.percentile(v, 50)) * 1e3 if v else None
