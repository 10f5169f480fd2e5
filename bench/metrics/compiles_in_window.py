"""Programs built (backend compiles and persistent-cache reads) while
the window ran: the program's process-wide compile counter at the
window's end minus at its start (``bench.obs.compile_totals``).  0 on a
sound run: a compile in the window is a stall that warm-up should have
taken."""

from bench import obs


def read(ctx):
    start = obs.compile_totals(ctx.window[0])
    end = obs.compile_totals(ctx.window[1])
    if start is None or end is None:
        return None
    return end[0] - start[0]
