"""Device time of the prefill programs (the engine's bucket executables
over ``nn.model.prefill_cache``) in the traced window, ms, over the
prompt tokens they cached there."""

from bench import trace as T

PROGRAM = "_prefill_packed"


def read(ctx):
    if ctx.trace is None:
        return None
    n = sum(sum(s.prefill) for s in ctx.steps if s.traced)
    ev = T.program_events(ctx.trace, PROGRAM)
    if not n or not ev:
        return None
    return sum(e[2] for e in ev) * 1e-6 / n
