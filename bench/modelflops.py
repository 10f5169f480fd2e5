"""Model FLOPs of served tokens, shared by the MFU metrics.

A token at context length ``c`` (itself and the ``c - 1`` positions
before it) costs ``2 * params`` FLOPs in the matrix multiplications,
the tied LM head included (each weight is one multiply and one add),
plus ``4 * heads * head_dim * c`` in each attention layer (scores and
the weighted sum over the live context).  Padding, masked rows and
recomputed work count for nothing.
"""

from __future__ import annotations


def attention_layers(cfg: dict) -> int:
    return cfg["n_layers"] if cfg.get("n_heads", 0) else 0


def token_flops(cfg: dict, n_params: int, context: int) -> float:
    attn = 4.0 * cfg.get("n_heads", 0) * cfg.get("head_dim", 0) * context
    return 2.0 * n_params + attention_layers(cfg) * attn


def prompt_flops(cfg: dict, n_params: int, length: int) -> float:
    """FLOPs of prefilling positions 0 .. length - 1 of one prompt."""
    attn = 4.0 * cfg.get("n_heads", 0) * cfg.get("head_dim", 0) \
        * attention_layers(cfg)
    return 2.0 * n_params * length + attn * length * (length + 1) / 2.0


def prefill_steps(buckets, lens):
    """The scan steps of one packed prefill wave, as the engine runs it:
    the wave's longest prompt rounded up to a bucket (chunked by the
    largest bucket when longer), one masked decode step per column.
    Yields, per step, the context lengths of the rows live in it (a row
    is live while the step's position is inside its prompt)."""
    l_max, pos = max(lens), 0
    while pos < l_max:
        rest = l_max - pos
        b = next((x for x in buckets if x >= rest), buckets[-1])
        for t in range(pos, pos + b):
            yield [t + 1 for n in lens if t < n]
        pos += b
