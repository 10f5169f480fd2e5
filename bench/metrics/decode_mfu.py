"""Model FLOPs of the tokens decoded in the traced window (live rows
only, each at its own context length), over the decode programs' device
time times the chip's peak, %."""

from bench import modelflops as MF
from bench import trace as T

PROGRAM = "_decode_all"


def read(ctx):
    if ctx.trace is None:
        return None
    flops = sum(MF.token_flops(ctx.cfg, ctx.n_params, c)
                for s in ctx.steps if s.traced for c in s.decode_ctx)
    t = sum(e[2] for e in T.program_events(ctx.trace, PROGRAM)) * 1e-9
    if not flops or not t:
        return None
    return 100.0 * flops / (t * ctx.peak["flops_per_s"])
