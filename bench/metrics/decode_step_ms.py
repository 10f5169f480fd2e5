"""Device time per decode call (the engine's jitted ``_decode_all`` over
``decode_step``) in the traced window, ms."""

from bench import trace as T

PROGRAM = "_decode_all"


def read(ctx):
    if ctx.trace is None:
        return None
    ev = T.program_events(ctx.trace, PROGRAM)
    if not ev:
        return None
    return sum(e[2] for e in ev) * 1e-6 / len(ev)
