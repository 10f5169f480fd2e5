"""90th percentile of the wait from when a request was due to the start
of the engine step that admitted it, ms (host clock): admission,
batching and the host sync that holds the next step back."""

import numpy as np


def read(ctx):
    v = [r.admit_ts - r.due for r in ctx.requests if r.admit_ts is not None]
    return float(np.percentile(v, 90)) * 1e3 if v else None
