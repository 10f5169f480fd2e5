"""Pallas kernel: batched one-query attention against a decode cache.

Bucketed prefill (PR 7) runs the prompt through a masked scan of
``decode_step`` — so its attention is the one-token-vs-cache pattern of
``nn.attention.attend_full`` with a (1, 1, S) validity mask, evaluated
once per prompt position.  This kernel lifts exactly that pattern out
of XLA: one grid step per batch row, the row's (H, D)
query and (S, Hkv*D) cache tiles in VMEM, GQA grouping + scale + mask +
softmax + weighted sum fused in one pass.  The cache row arrives
lane-dense as (S, Hkv*D) and is regrouped in VMEM into (Hkv, S, D) from
per-head lane slices, so both matmuls are 3-D einsums batched over the KV
heads, which the TPU compiler lowers (the 5-D einsums of ``attend_full``
it does not).  They are the same batched dots XLA makes of
``attend_full``, and the element-wise sequence is its own (same scale
cast, f32 logits, -1e30 mask fill, softmax, probabilities cast back to
the query dtype), so in interpret mode the output is bitwise the XLA
path's for f32 and bf16 — the serve stream/checkpoint contract survives
backend switches.  Compiled for the TPU, the two agree to rounding.

Decode shares the kernel: ``decode_self_attention`` dispatches its
non-int8 paths through ``core.backend.prefill_attention``, so on the
pallas backend every cached-attention call (bucketed prefill, legacy scan
prefill, per-token decode) lands here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, m_ref, o_ref, *, scale, hkv, d):
    h = q_ref.shape[0]
    q = q_ref[...]                                       # (H, D)
    qs = q * jnp.asarray(scale, q.dtype)
    # (Hkv, G, D) query groups; the reshape runs in f32 (exact round trip)
    # so the grouping never splits a packed bf16 sublane tile
    qg = qs.astype(jnp.float32).reshape(hkv, h // hkv, d).astype(q.dtype)
    # (Hkv, S, D): each KV head's lane slice of the (S, Hkv*D) row
    k = jnp.stack([k_ref[:, j * d:(j + 1) * d] for j in range(hkv)])
    v = jnp.stack([v_ref[:, j * d:(j + 1) * d] for j in range(hkv)])
    mask = m_ref[...] != 0                               # (1, S)
    s = jnp.einsum("hgd,hsd->hgs", qg, k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgs,hsd->hgd", p.astype(q.dtype), v,
                   preferred_element_type=jnp.float32)
    o_ref[...] = o.astype(jnp.float32).reshape(h, d).astype(o_ref.dtype)


def prefill_attention_pallas(q, k, v, mask, *, scale=None,
                             interpret: bool = True):
    """One-query cached attention.  q: (B, H, D), k/v: (B, S, Hkv, D),
    mask: (B, S) nonzero-where-valid -> (B, H, D).

    H must be a multiple of Hkv (GQA grouping, as in ``attend_full``).
    K/V are viewed as (B, S, Hkv*D) and the mask as (B, 1, S), so every
    block's last two dims are lane-dense or whole-array, as the TPU tiling
    requires.
    """
    b, h, d = q.shape
    s_len, hkv = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"{h} query heads not grouped over {hkv} KV heads")
    kern = functools.partial(
        _kernel, scale=1.0 / math.sqrt(d) if scale is None else scale,
        hkv=hkv, d=d)
    kv_spec = pl.BlockSpec((None, s_len, hkv * d), lambda i: (i, 0, 0))
    return pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, h, d), lambda i: (i, 0, 0)),
            kv_spec,
            kv_spec,
            pl.BlockSpec((None, 1, s_len), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, h, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(q, k.reshape(b, s_len, hkv * d), v.reshape(b, s_len, hkv * d),
      mask.reshape(b, 1, s_len))
