"""Share of the (token, expert) pairs routed in the window that went to
an expert held on this chip, %: the program's ``moe.pairs_local`` over
``moe.pairs_routed`` (``repro.obs.trace.counter_totals``), each at the
window's end less at its start, counted over the decode steps' real
rows and summed over layers.

With 8 of 64 experts held and balanced routing it reads near 12.5%.
Far from it, the routing is degenerate: ties that fall to the lowest
indices, or a correction bias that sends the load elsewhere.  None where
the program has no such counters."""

import importlib


def read(ctx):
    try:
        mod = importlib.import_module("repro.obs.trace")
    except ImportError:
        return None
    totals = getattr(mod, "counter_totals", None)
    if totals is None:
        return None
    got = []
    for name in (mod.MOE_PAIRS_ROUTED, mod.MOE_PAIRS_LOCAL):
        t0, t1 = (totals(name, t) for t in ctx.window)
        if t0 is None or t1 is None:
            return None
        got.append(t1 - t0)
    routed, local = got
    return 100.0 * local / routed if routed else None
