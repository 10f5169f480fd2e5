"""Pallas kernel: one-query attention against a latent (MLA) decode cache.

Latent attention caches, per position, one ``rank + rope_dim`` wide row
``[c, k_r]`` shared by every head (``nn.attention.mla_decode_attend``).  With
``W_UK`` absorbed into the query and ``W_UV`` into the output, a decode
step's attention is multi-query attention over that cache: scores over
all ``rank + rope_dim`` features, values over the first ``rank``.  The
cache is position-minor, (B, rank + rope_dim, S), so positions fill the
lanes.  This kernel computes it with one grid step per batch row: the
row's query (H, rank + rope_dim) and cache (rank + rope_dim, S) in VMEM,
then the scale, both dots, the mask and the softmax fused in one pass.  The
element-wise sequence is ``nn.attention.mla_attend``'s (same scale
cast, f32 logits, -1e30 fill, softmax, probabilities cast back to the
cache dtype), so in interpret mode the output is that path's.

Operands: the query, the cache and an int32 (B, 1, S) validity mask.
The benchmark's ``mla_decode_roofline`` finds the kernel by that
signature.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, c_ref, m_ref, o_ref, *, scale, rank):
    q = q_ref[...]                                       # (H, W)
    qs = q * jnp.asarray(scale, q.dtype)
    c = c_ref[...]                                       # (W, S)
    mask = m_ref[...] != 0                               # (1, S)
    s = jnp.dot(qs, c, preferred_element_type=jnp.float32)  # (H, S)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jax.lax.dot_general(p.astype(c.dtype), c[:rank],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (H, rank)
    o_ref[...] = o.astype(o_ref.dtype)


def mla_decode_attention_pallas(q, ckv, mask, *, rank: int, scale: float,
                                interpret: bool = True):
    """q: (B, H, W), ckv: (B, W, S), mask: (B, S) nonzero-where-valid
    -> (B, H, rank), W = rank + rope_dim.  Every block's last two dims
    are whole array dims, as the TPU tiling requires; ``rank`` is a
    multiple of the sublane tile, so the value slice is aligned."""
    b, h, w = q.shape
    s_len = ckv.shape[2]
    kern = functools.partial(_kernel, scale=scale, rank=rank)
    return pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, h, w), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, w, s_len), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, s_len), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, h, rank), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
        interpret=interpret,
    )(q, ckv, mask.reshape(b, 1, s_len).astype(jnp.int32))
