"""Model FLOPs of every token processed in the window (prompt tokens
cached and tokens decoded, live rows only), over the window's seconds
(host clock) times the chip's peak, %."""

from bench import modelflops as MF


def read(ctx):
    t0, t1 = ctx.window
    flops = sum(MF.prompt_flops(ctx.cfg, ctx.n_params, n)
                for s in ctx.steps for n in s.prefill)
    flops += sum(MF.token_flops(ctx.cfg, ctx.n_params, c)
                 for s in ctx.steps for c in s.decode_ctx)
    if not flops:
        return None
    return 100.0 * flops / ((t1 - t0) * ctx.peak["flops_per_s"])
