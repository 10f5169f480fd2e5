"""Seconds of set-up spent tracing, lowering and compiling programs or
reading them from the persistent cache: the program's process-wide
compile counter when the window opened (``bench.obs.compile_totals``).
It counts from the engine's construction: the weights made before it
are left out."""

from bench import obs


def read(ctx):
    start = obs.compile_totals(ctx.window[0])
    return None if start is None else start[1]
