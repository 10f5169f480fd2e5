"""Model FLOPs of the prompt tokens cached in the traced window, over the
prefill programs' device time times the chip's peak, %."""

from bench import modelflops as MF
from bench import trace as T

PROGRAM = "_prefill_packed"


def read(ctx):
    if ctx.trace is None:
        return None
    flops = sum(MF.prompt_flops(ctx.cfg, ctx.n_params, n)
                for s in ctx.steps if s.traced for n in s.prefill)
    t = sum(e[2] for e in T.program_events(ctx.trace, PROGRAM)) * 1e-9
    if not flops or not t:
        return None
    return 100.0 * flops / (t * ctx.peak["flops_per_s"])
