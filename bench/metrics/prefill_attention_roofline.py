"""Roofline share of the one-query cached-attention kernel, %: the least
time of its calls in the traced window over its device time there.

One call per layer per decode step and per prefill scan step.  Only live
positions count, whatever the kernel reads: a row with context length L
costs 4 H hd L FLOPs (scores and weighted sum), reads L positions of K
and V (2 L Hkv hd elements) and its query, and writes its output;
padded positions, masked rows and empty slots count for nothing.  Each
call's least time is max(FLOPs / peak, bytes / HBM peak).  The kernel's
device time includes the ops that staged its operands into the chip's
vector memory (layout ``S(1)``) just before the call, such as the slice
of the layer's K and V out of the cache and their relayout
(``trace.staged_seconds``): the share is the same whether XLA or the
kernel reads K and V from HBM.

The kernel is found by its operand signature: a Pallas call on a query,
K, V and an int32 mask."""

from bench import modelflops as MF
from bench import trace as T


def is_kernel(ops):
    return len(ops) == 4 and ops[3][0] == "s32"


def call_cost(cfg, lengths, itemsize=2):
    h, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    flops = sum(4.0 * h * hd * n for n in lengths)
    nbytes = sum(itemsize * (2.0 * hkv * hd * n + 2.0 * h * hd)
                 for n in lengths)
    return flops, nbytes


def read(ctx):
    if ctx.trace is None:
        return None
    ev = T.kernel_events(ctx.trace, is_kernel)
    if not ev:
        return None
    c, peak = ctx.cfg, ctx.peak
    buckets = ctx.cell["engine"]["prefill_buckets"]
    calls = []
    for s in ctx.steps:
        if s.traced:
            if s.prefill:
                calls += list(MF.prefill_steps(buckets, s.prefill))
            calls.append(s.decode_ctx)
    if len(calls) * c["n_layers"] != len(ev):
        raise ValueError(f"{len(ev)} attention kernel events in the trace "
                         f"for {len(calls)} calls x {c['n_layers']} layers")
    t_min = 0.0
    for lengths in calls:
        flops, nbytes = call_cost(c, lengths)
        t_min += max(flops / peak["flops_per_s"],
                     nbytes / peak["hbm_bytes_per_s"])
    t = sum(e[3] for e in ev) * 1e-9 + T.staged_seconds(ctx.trace, ev)
    return 100.0 * c["n_layers"] * t_min / t
