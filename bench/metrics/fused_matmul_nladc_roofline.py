"""Roofline share of the fused matmul + NL-ADC kernel, %: for each call,
the least time its shapes allow, max(FLOPs / peak, bytes / HBM peak),
summed over the calls in the traced window, over the kernel's device
time there.

Each call is the MLP gate projection of one layer at one engine step:
x (M, K) times W (K, N), digitized, out (M, N), with M the engine's
max_batch rows (the kernel pads them to its block; padding is not work)
and K, N read from the weight operand in the trace.  FLOPs 2 M K N.
Bytes: x, W and the output once each, in the weight's dtype, wherever
they are read or written from.  The kernel's device time includes the
ops that staged its operands into the chip's vector memory (layout
``S(1)``) just before the call, such as the dynamic slice of the layer's
weight out of the stacked weights (``trace.staged_seconds``): the share
is the same whether XLA or the kernel reads W from HBM.

The kernel is found by its operand signature: a Pallas call on two
matrices and one threshold vector (P,) or table (N, P)."""

from bench import trace as T

SIZE = {"bf16": 2, "f32": 4, "f16": 2}


def is_kernel(ops):
    return (len(ops) == 3 and len(ops[0][1]) == 2 and len(ops[1][1]) == 2
            and ops[2][0] == "f32" and len(ops[2][1]) in (1, 2))


def call_cost(m, k, n, itemsize):
    """FLOPs and bytes of one call."""
    return 2.0 * m * k * n, float(itemsize) * (m * k + k * n + m * n)


def read(ctx):
    if ctx.trace is None:
        return None
    ev = T.kernel_events(ctx.trace, is_kernel)
    if not ev:
        return None
    m, t_min = ctx.cell["engine"]["max_batch"], 0.0
    for ops, *_ in ev:
        (k, n), size = ops[1][1], SIZE[ops[1][0]]
        flops, nbytes = call_cost(m, k, n, size)
        t_min += max(flops / ctx.peak["flops_per_s"],
                     nbytes / ctx.peak["hbm_bytes_per_s"])
    t = sum(e[3] for e in ev) * 1e-9 + T.staged_seconds(ctx.trace, ev)
    return 100.0 * t_min / t
