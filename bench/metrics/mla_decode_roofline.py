"""Roofline share of the latent-attention (MLA) decode kernel, %: the
least time of its calls in the traced window over its device time there.

One call per layer per decode step and per prefill scan step.  Only live
positions count, whatever the kernel reads: a row with context length L
costs 2 H W L FLOPs for its scores over the W = rank + rope_dim cached
features and 2 H rank L for its weighted sum, reads its query (H W) and
the L live positions of the latent cache (W L), and writes its output
(H rank); padded positions, masked rows and empty slots count for
nothing.  Each call's least time is max(FLOPs / peak, bytes / HBM peak).
The kernel's device time includes the ops that staged its operands into
the chip's vector memory (layout ``S(1)``) just before the call, such as
the slice of the layer's cache out of the stacked caches
(``trace.staged_seconds``).

The kernel is found by its operand signature: a Pallas call on a 3-D
query, a 3-D cache and an int32 mask (B, 1, S)."""

from bench import modelflops as MF
from bench import trace as T


def is_kernel(ops):
    return (len(ops) == 3 and len(ops[0][1]) == 3 and len(ops[1][1]) == 3
            and ops[2][0] == "s32" and len(ops[2][1]) == 3)


def call_cost(cfg, lengths, itemsize=2):
    h, rank = cfg["n_heads"], cfg["kv_lora_rank"]
    w = rank + cfg["qk_rope_head_dim"]
    flops = sum(2.0 * h * (w + rank) * n for n in lengths)
    nbytes = sum(itemsize * (w * n + h * w + h * rank) for n in lengths)
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
        raise ValueError(f"{len(ev)} MLA kernel events in the trace for "
                         f"{len(calls)} calls x {c['n_layers']} layers")
    t_min = 0.0
    for lengths in calls:
        flops, nbytes = call_cost(c, lengths)
        t_min += max(flops / peak["flops_per_s"],
                     nbytes / peak["hbm_bytes_per_s"])
    t = sum(e[3] for e in ev) * 1e-9 + T.staged_seconds(ctx.trace, ev)
    return 100.0 * c["n_layers"] * t_min / t
