"""Output tokens delivered in the window, over the window's seconds
(host clock, from the first request sent to the end of the last step)."""


def read(ctx):
    t0, t1 = ctx.window
    n = sum(len(s.decode_ctx) for s in ctx.steps)
    return n / (t1 - t0) if n else None
