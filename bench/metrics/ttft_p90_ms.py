"""90th percentile of time to first token, ms, over the same requests as
ttft_p50_ms (the highest percentile with ten or more samples beyond it
at about a hundred requests)."""

import numpy as np


def read(ctx):
    v = [r.token_times[0] - r.due for r in ctx.requests if r.token_times]
    return float(np.percentile(v, 90)) * 1e3 if v else None
