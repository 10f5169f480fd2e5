"""Pallas TPU kernel: flash-decode with fused int8-KV dequantization.

The §Perf B-cell analysis showed decode is bound by KV-cache bytes; int8
storage (§Perf B3) halves them, but an XLA-level dequantize still
materializes a bf16 copy of the cache.  This kernel removes that copy: the
int8 K/V tiles are dequantized **in VMEM, per tile, inside the attention
loop** — HBM sees exactly 1 byte/element of cache traffic.

Grid: (batch, kv_blocks).  Each step loads one (block_s, H_kv*D) int8 tile
+ its (block_s, H_kv) scales, dequantizes in VMEM, and accumulates the
online softmax state (m, l, acc) for all query heads of one batch row in
VMEM scratch that persists across the kv_blocks axis.  The per-row valid
lengths arrive by scalar prefetch (SMEM), and the body is written in 3-D
einsums batched over the KV heads, the form the TPU compiler lowers.

This is the TPU analogue of the paper's thesis one level up: keep the
cheap-to-recreate value (the dequantized cache / the activation) out of
HBM and pay only the irreducible storage traffic.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_S = 256
NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
            m_ref, l_ref, acc_ref, *, n_blocks, block_s, hkv, scale):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    h, d = q_ref.shape
    q = q_ref[...].astype(jnp.float32)             # (H, D)
    qg = q.reshape(hkv, h // hkv, d) * scale       # (Hkv, G, D)
    k8 = k_ref[...].astype(jnp.float32)            # (S_blk, Hkv*D) int8
    ks = ks_ref[...].astype(jnp.float32)           # (S_blk, Hkv)
    v8 = v_ref[...].astype(jnp.float32)
    vs = vs_ref[...].astype(jnp.float32)
    # dequant IN VMEM, regrouped per KV head: (Hkv, S_blk, D)
    k = jnp.stack([k8[:, c * d:(c + 1) * d] * ks[:, c:c + 1]
                   for c in range(hkv)])
    v = jnp.stack([v8[:, c * d:(c + 1) * d] * vs[:, c:c + 1]
                   for c in range(hkv)])
    s = jnp.einsum("hgd,hsd->hgs", qg, k)          # (Hkv, G, S_blk)
    # causal/validity mask: absolute slot id < the row's current length
    slot = j * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(slot < len_ref[i], s, NEG_INF)

    m_prev = m_ref[...]                            # (Hkv, G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.einsum("hgs,hsd->hgd", p, v)
    m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _final():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = o.reshape(h, d)


def flash_decode_int8(q, k8, k_scale, v8, v_scale, length, *,
                      block_s: int = DEFAULT_BLOCK_S,
                      interpret: bool = True):
    """One-token attention over an int8 KV cache.

    q: (B, H, D); k8/v8: (B, S, H_kv, D) int8; scales: (B, S, H_kv);
    length: (B,) int32 valid-slot counts.  Returns (B, H, D) f32.
    """
    b, h, d = q.shape
    s_len, hkv = k8.shape[1], k8.shape[2]
    g = h // hkv
    n_blocks = pl.cdiv(s_len, block_s)
    kernel = functools.partial(_kernel, n_blocks=n_blocks, block_s=block_s,
                               hkv=hkv, scale=1.0 / (d ** 0.5))
    kv_spec = pl.BlockSpec((None, block_s, hkv * d),
                           lambda i, j, n: (i, j, 0))
    sc_spec = pl.BlockSpec((None, block_s, hkv), lambda i, j, n: (i, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_blocks),
        in_specs=[
            pl.BlockSpec((None, h, d), lambda i, j, n: (i, 0, 0)),
            kv_spec, sc_spec, kv_spec, sc_spec,
        ],
        out_specs=pl.BlockSpec((None, h, d), lambda i, j, n: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),      # running max m
            pltpu.VMEM((hkv, g, 1), jnp.float32),      # running sum l
            pltpu.VMEM((hkv, g, d), jnp.float32),      # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), jnp.float32),
        interpret=interpret,
    )(length.astype(jnp.int32), q, k8.reshape(b, s_len, hkv * d), k_scale,
      v8.reshape(b, s_len, hkv * d), v_scale)
