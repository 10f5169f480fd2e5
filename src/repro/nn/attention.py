"""Attention: GQA/MQA/MHA, chunked online-softmax, KV caches, cross-attn.

Design notes
------------
* **Chunked (flash-style) attention**: full ``S_q x S_kv`` score tensors are
  never materialized — a ``lax.scan`` over KV chunks carries the online
  softmax state ``(m, l, acc)``.  This is what lets the 32k-prefill cells
  compile inside the per-device memory budget (and is the TPU-idiomatic
  equivalent of flash attention at the XLA level; the Pallas fused variant
  is a §Perf iteration).
* **GQA** is computed in grouped layout ``(B, S, H_kv, G, D)`` so that the
  KV tensors are never repeated in memory.
* **Caches**: standard append cache for global attention;
  **rolling-window** cache for local attention (recurrentgemma) so the
  long_500k decode cell holds a 2048-slot buffer, not 524288.  RoPE is
  applied *before* caching, so rolling slots need no position bookkeeping.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn import layers as L

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def attn_init(key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
              *, qkv_bias: bool = False, dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    q_dim, kv_dim = n_heads * head_dim, n_kv_heads * head_dim
    return {
        "wq": L.dense_init(kq, d_model, q_dim, bias=qkv_bias, dtype=dtype),
        "wk": L.dense_init(kk, d_model, kv_dim, bias=qkv_bias, dtype=dtype),
        "wv": L.dense_init(kv, d_model, kv_dim, bias=qkv_bias, dtype=dtype),
        "wo": L.dense_init(ko, q_dim, d_model, bias=False, dtype=dtype),
    }


def _split_heads(x, n_heads, head_dim):
    return x.reshape(x.shape[:-1] + (n_heads, head_dim))


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------

def _grouped(q, n_kv_heads):
    """(B, S, H, D) -> (B, S, H_kv, G, D)."""
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv_heads, h // n_kv_heads, d)


def attend_chunked(q, k, v, *, mask_fn, kv_chunk: int = 1024,
                   scale: Optional[float] = None, unroll: bool = False):
    """Online-softmax attention scanning over KV chunks.

    q: (B, Sq, H, D); k, v: (B, Skv, H_kv, D).
    ``mask_fn(kv_start, kv_len) -> (Sq, kv_len) bool`` builds the mask for one
    chunk (True = attend).  Returns (B, Sq, H, D) in q.dtype.

    ``unroll=True`` replaces the lax.scan with a Python loop — used by the
    dry-run analysis pass so XLA cost_analysis sees every chunk (while-loop
    bodies are otherwise counted once, not x trip-count).
    """
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    g = h // hkv
    # Stay in q.dtype (bf16): the MXU accumulates in f32 via
    # preferred_element_type without materializing f32 copies of K/V —
    # measured ~2x on decode HLO bytes (§Perf B1).
    qg = _grouped(q, hkv) * jnp.asarray(scale, q.dtype)  # (B,Sq,Hkv,G,D)

    n_chunks = math.ceil(skv / kv_chunk)
    pad = n_chunks * kv_chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # (n_chunks, B, C, Hkv, D)
    kc = k.reshape(b, n_chunks, kv_chunk, hkv, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, kv_chunk, hkv, d).transpose(1, 0, 2, 3, 4)

    def body(carry, inputs):
        m, l, acc = carry
        ci, kci, vci = inputs
        kv_start = ci * kv_chunk
        # scores: (B, Hkv, G, Sq, C) — bf16 operands, f32 accumulation
        s = jnp.einsum("bqhgd,bchd->bhgqc", qg, kci,
                       preferred_element_type=jnp.float32)
        mask = mask_fn(kv_start, kv_chunk)                 # (Sq, C)
        if pad:
            in_range = (kv_start + jnp.arange(kv_chunk)) < skv
            mask = mask & in_range[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqc,bchd->bhgqd", p.astype(q.dtype), vci,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    if unroll:
        carry = (m0, l0, a0)
        for ci in range(n_chunks):
            carry, _ = body(carry, (jnp.asarray(ci), kc[ci], vc[ci]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc)
        )
    out = acc / jnp.maximum(l, 1e-30)[..., None]           # (B,Hkv,G,Sq,D)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)
    return out.astype(q.dtype)


def attend_full(q, k, v, mask, *, scale: Optional[float] = None):
    """Unchunked attention (decode / tests). mask: broadcast to (B,.,Sq,Skv)."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = _grouped(q, hkv) * jnp.asarray(scale, q.dtype)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(mask[:, None, None] if mask.ndim == 3 else mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(q.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Self-attention layer (train / prefill / decode)
# ---------------------------------------------------------------------------

def self_attention(p, x, *, n_heads, n_kv_heads, head_dim, rope_theta,
                   window: int = 0, positions=None, kv_chunk: int = 1024,
                   return_kv: bool = False, unroll: bool = False):
    """Causal (optionally windowed) self-attention over a full sequence."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q = _split_heads(L.dense_apply(p["wq"], x), n_heads, head_dim)
    k = _split_heads(L.dense_apply(p["wk"], x), n_kv_heads, head_dim)
    v = _split_heads(L.dense_apply(p["wv"], x), n_kv_heads, head_dim)
    q = L.apply_rope(q, positions, rope_theta)
    k = L.apply_rope(k, positions, rope_theta)

    def mask_fn(kv_start, kv_len):
        q_pos = jnp.arange(s)[:, None]
        k_pos = kv_start + jnp.arange(kv_len)[None, :]
        m = k_pos <= q_pos
        if window > 0:
            m = m & (k_pos > q_pos - window)
        return m

    out = attend_chunked(q, k, v, mask_fn=mask_fn, kv_chunk=kv_chunk,
                         unroll=unroll)
    y = L.dense_apply(p["wo"], out.reshape(b, s, n_heads * head_dim))
    if return_kv:
        return y, (k, v)
    return y


def init_cache(batch: int, max_len: int, n_kv_heads: int, head_dim: int,
               *, window: int = 0, dtype=jnp.bfloat16,
               quantized: bool = False):
    """Decode cache for one layer. Rolling buffer if window > 0.

    ``quantized``: int8 storage with per-(token, head) symmetric scales
    (§Perf B3) — halves cache residency and read bytes; the dequant fuses
    into the attention dot on TPU.
    """
    slots = min(max_len, window) if window > 0 else max_len
    if quantized:
        return {
            "k": jnp.zeros((batch, slots, n_kv_heads, head_dim), jnp.int8),
            "v": jnp.zeros((batch, slots, n_kv_heads, head_dim), jnp.int8),
            "k_scale": jnp.zeros((batch, slots, n_kv_heads), jnp.bfloat16),
            "v_scale": jnp.zeros((batch, slots, n_kv_heads), jnp.bfloat16),
        }
    return {
        "k": jnp.zeros((batch, slots, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, slots, n_kv_heads, head_dim), dtype),
    }


def _quant_kv(x):
    """(B, 1, H, D) -> int8 codes + (B, 1, H) scales."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _dequant(q, scale, dtype):
    return q.astype(dtype) * scale[..., None].astype(dtype)


def decode_self_attention(p, x, cache, index, *, n_heads, n_kv_heads,
                          head_dim, rope_theta, window: int = 0,
                          analog_backend: str = ""):
    """One-token decode step. ``index`` = absolute position of the new token.

    Returns (y, new_cache).  RoPE is applied before caching; for windowed
    attention the cache is a rolling buffer indexed ``index % window``.

    int8 caches attend through the analog backend's fused decode primitive
    (``analog_backend`` selects it): the ref path is the dequantize-all
    oracle; the pallas path is the flash-decode kernel that dequantizes
    per KV tile in VMEM (1 byte/element of HBM cache traffic).  Rolling
    (windowed) int8 caches keep the dequantize-all fallback.  Every other
    cache layout attends through ``backend.prefill_attention`` — ref is
    ``attend_full`` itself, pallas the one-query cached-attention kernel
    (bitwise equal), so bucketed prefill (a masked scan of this step) and
    per-token decode stop being pure-XLA on the pallas backend.
    """
    b = x.shape[0]
    q = _split_heads(L.dense_apply(p["wq"], x), n_heads, head_dim)
    k = _split_heads(L.dense_apply(p["wk"], x), n_kv_heads, head_dim)
    v = _split_heads(L.dense_apply(p["wv"], x), n_kv_heads, head_dim)
    pos = jnp.full((1, 1), index, dtype=jnp.int32)
    q = L.apply_rope(q, pos, rope_theta)
    k = L.apply_rope(k, pos, rope_theta)

    slots = cache["k"].shape[1]
    slot = index % slots if window > 0 else index
    quantized = "k_scale" in cache
    new_cache = dict(cache)
    if quantized:
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        new_cache["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], kq, slot, axis=1)
        new_cache["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], vq, slot, axis=1)
        new_cache["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_scale"], ks, slot, axis=1)
        new_cache["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v_scale"], vs, slot, axis=1)
        if window == 0:
            from repro.core import backend as BK

            length = jnp.full((b,), index + 1, jnp.int32)
            out = BK.get_backend(analog_backend).decode_attention_int8(
                q[:, 0], new_cache["k"], new_cache["k_scale"],
                new_cache["v"], new_cache["v_scale"], length)
            out = out[:, None].astype(x.dtype)       # (B, 1, H, D)
            y = L.dense_apply(p["wo"],
                              out.reshape(b, 1, n_heads * head_dim))
            return y, new_cache
        k_att = _dequant(new_cache["k"], new_cache["k_scale"], x.dtype)
        v_att = _dequant(new_cache["v"], new_cache["v_scale"], x.dtype)
    else:
        new_cache["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        new_cache["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        k_att, v_att = new_cache["k"], new_cache["v"]

    slot_ids = jnp.arange(slots)
    if window > 0:
        valid = slot_ids < jnp.minimum(index + 1, slots)
    else:
        valid = slot_ids <= index
    mask = valid[None, None, :]                     # (1, Sq=1, Skv)
    # one-query cached attention through the backend seam: ref IS
    # attend_full; pallas runs the prefill_attention kernel (bitwise equal
    # — bucketed prefill scans this very step, so prefill is covered too)
    from repro.core import backend as BK

    out = BK.get_backend(analog_backend).prefill_attention(
        q, k_att, v_att, mask)
    y = L.dense_apply(p["wo"], out.reshape(b, 1, n_heads * head_dim))
    return y, new_cache


# ---------------------------------------------------------------------------
# Latent attention (MLA: DeepSeek-V2/V3, Moonlight)
# ---------------------------------------------------------------------------
#
# Per token, kv_a_proj_with_mqa gives a latent c (rank wide, RMS-normed) and
# one RoPE key k_r (rope_dim wide) shared by every head; kv_b_proj expands c
# into each head's key part W_UK c and value W_UV c.  q_proj gives each head
# a query [q_nope (head_dim), q_r (rope_dim)].  The decode cache holds only
# [c, k_r] per position: one (rank + rope_dim)-wide leaf, shared by all
# heads, stored position-minor, (B, rank + rope_dim, S): at published widths
# 576 lanes would pad to 640, and the chip's default layout then puts the
# 768 positions minor anyway, so each step would relayout the whole cache
# for the kernel.  Decode absorbs W_UK into the query (q_c = q_nope W_UK^T,
# scores q_c c^T + q_r k_r^T) and W_UV into the output (o = (p c) W_UV), so
# it attends over the latent cache as multi-query attention.  The
# full-sequence path computes keys and values as published.

def mla_init(key, d_model: int, n_heads: int, head_dim: int, rope_dim: int,
             rank: int, *, dtype=jnp.float32):
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {
        "q_proj": L.dense_init(kq, d_model, n_heads * (head_dim + rope_dim),
                               dtype=dtype),
        "kv_a_proj_with_mqa": L.dense_init(ka, d_model, rank + rope_dim,
                                           dtype=dtype),
        "kv_a_layernorm": L.rmsnorm_init(rank),
        "kv_b_proj": L.dense_init(kb, rank, n_heads * 2 * head_dim,
                                  dtype=dtype),
        "o_proj": L.dense_init(ko, n_heads * head_dim, d_model, dtype=dtype),
    }


def _mla_query(p, x, positions, n_heads, head_dim, rope_theta):
    """-> q_nope (B, S, H, head_dim), q_r (B, S, H, rope_dim) roped."""
    q = L.dense_apply(p["q_proj"], x)
    q = _split_heads(q, n_heads, q.shape[-1] // n_heads)
    return q[..., :head_dim], L.apply_rope(q[..., head_dim:], positions,
                                           rope_theta)


def _mla_latent(p, x, positions, rank, rope_theta, eps):
    """-> [c, k_r] (B, S, rank + rope_dim): the latent, normed, beside the
    roped shared key — what the decode cache holds per position."""
    kv = L.dense_apply(p["kv_a_proj_with_mqa"], x)
    c = L.rmsnorm_apply(p["kv_a_layernorm"], kv[..., :rank], eps)
    k_r = L.apply_rope(kv[..., None, rank:], positions, rope_theta)
    return jnp.concatenate([c, k_r[..., 0, :]], axis=-1)


def mla_scale(head_dim: int, rope_dim: int) -> float:
    return 1.0 / math.sqrt(head_dim + rope_dim)


def mla_attention(p, x, *, n_heads, head_dim, rank, rope_theta, eps,
                  positions=None, kv_chunk: int = 1024, unroll: bool = False):
    """Causal MLA over a full sequence, keys and values expanded per head."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q_nope, q_r = _mla_query(p, x, positions, n_heads, head_dim, rope_theta)
    ckv = _mla_latent(p, x, positions, rank, rope_theta, eps)
    kv = _split_heads(L.dense_apply(p["kv_b_proj"], ckv[..., :rank]),
                      n_heads, 2 * head_dim)
    rope_dim = q_r.shape[-1]
    k_r = jnp.broadcast_to(ckv[:, :, None, rank:], (b, s, n_heads, rope_dim))
    q = jnp.concatenate([q_nope, q_r], axis=-1)
    k = jnp.concatenate([kv[..., :head_dim], k_r], axis=-1)
    # values are head_dim wide, queries and keys head_dim + rope_dim: pad
    # the values with zeros to one width and drop those lanes after
    v = jnp.pad(kv[..., head_dim:], ((0, 0),) * 3 + ((0, rope_dim),))

    def mask_fn(kv_start, kv_len):
        return kv_start + jnp.arange(kv_len)[None, :] \
            <= jnp.arange(s)[:, None]

    out = attend_chunked(q, k, v, mask_fn=mask_fn, kv_chunk=kv_chunk,
                         scale=mla_scale(head_dim, rope_dim), unroll=unroll)
    return L.dense_apply(p["o_proj"],
                         out[..., :head_dim].reshape(b, s, -1))


def init_mla_cache(batch: int, max_len: int, width: int,
                   dtype=jnp.bfloat16):
    """Latent decode cache of one layer: [c, k_r] per position,
    (B, width, S)."""
    return {"ckv": jnp.zeros((batch, width, max_len), dtype)}


def mla_attend(q, ckv, mask, *, rank: int, scale: float):
    """One-query attention over a latent cache (the absorbed form).

    q: (B, H, rank + rope_dim) = [q_c, q_r]; ckv: (B, rank + rope_dim, S);
    mask: (B, S) bool.  Scores over all rank + rope_dim features, values
    the first ``rank`` -> (B, H, rank).  The ref backend's
    ``mla_decode_attention``; the Pallas kernel computes the same
    sequence (scale cast, f32 logits, -1e30 fill, softmax, probabilities
    cast back)."""
    qs = q * jnp.asarray(scale, q.dtype)
    s = jnp.einsum("bhk,bks->bhs", qs, ckv,
                   preferred_element_type=jnp.float32)
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhs,brs->bhr", pr.astype(ckv.dtype), ckv[:, :rank],
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def mla_decode(p, x, cache, index, *, n_heads, head_dim, rank, rope_theta,
               eps, analog_backend: str = "", commit=None):
    """One-token MLA decode step at position ``index`` -> (y, new_cache).

    ``commit`` (B,) bool, from a masked prefill: rows that do not commit
    write zeros at ``index``, which is what the fresh state it fills holds
    there, so no step selects rows over the whole cache.  (Reading each
    row's old entry back instead would make the compiler relayout the
    whole position-minor cache at every step.)"""
    from repro.core import backend as BK

    b = x.shape[0]
    pos = jnp.full((1, 1), index, dtype=jnp.int32)
    q_nope, q_r = _mla_query(p, x, pos, n_heads, head_dim, rope_theta)
    new = _mla_latent(p, x, pos, rank, rope_theta, eps)
    entry = jnp.swapaxes(new, 1, 2).astype(cache["ckv"].dtype)
    if commit is not None:
        entry = jnp.where(commit[:, None, None], entry, 0)
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], entry, index,
                                              axis=2)
    w = p["kv_b_proj"]["w"].reshape(rank, n_heads, 2 * head_dim) \
        .astype(x.dtype)
    q_c = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w[..., :head_dim])
    q = jnp.concatenate([q_c, q_r[:, 0]], axis=-1).astype(ckv.dtype)
    mask = jnp.broadcast_to(jnp.arange(ckv.shape[2]) <= index,
                            (b, ckv.shape[2]))
    o_c = BK.get_backend(analog_backend).mla_decode_attention(
        q, ckv, mask, rank=rank,
        scale=mla_scale(head_dim, q_r.shape[-1]))
    o = jnp.einsum("bhr,rhd->bhd", o_c.astype(x.dtype), w[..., head_dim:])
    y = L.dense_apply(p["o_proj"], o.reshape(b, 1, n_heads * head_dim))
    return y, {"ckv": ckv}


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_attn_init(key, d_model, n_heads, n_kv_heads, head_dim,
                    *, qkv_bias=False, dtype=jnp.float32):
    return attn_init(key, d_model, n_heads, n_kv_heads, head_dim,
                     qkv_bias=qkv_bias, dtype=dtype)


def cross_kv(p, enc_out, *, n_kv_heads, head_dim):
    """Precompute K/V from encoder output (cached once per request)."""
    k = _split_heads(L.dense_apply(p["wk"], enc_out), n_kv_heads, head_dim)
    v = _split_heads(L.dense_apply(p["wv"], enc_out), n_kv_heads, head_dim)
    return k, v


def cross_attention(p, x, kv: Tuple, *, n_heads, head_dim,
                    kv_chunk: int = 1024, unroll: bool = False):
    """Encoder-decoder attention; no mask (all frames visible)."""
    b, s, _ = x.shape
    k, v = kv
    q = _split_heads(L.dense_apply(p["wq"], x), n_heads, head_dim)
    mask_fn = lambda kv_start, kv_len: jnp.ones((s, kv_len), bool)
    out = attend_chunked(q, k, v, mask_fn=mask_fn, kv_chunk=kv_chunk,
                         unroll=unroll)
    return L.dense_apply(p["wo"], out.reshape(b, s, n_heads * head_dim))


def bidirectional_attention(p, x, *, n_heads, n_kv_heads, head_dim,
                            kv_chunk: int = 1024, unroll: bool = False):
    """Encoder self-attention (whisper): full visibility, no RoPE."""
    b, s, _ = x.shape
    q = _split_heads(L.dense_apply(p["wq"], x), n_heads, head_dim)
    k = _split_heads(L.dense_apply(p["wk"], x), n_kv_heads, head_dim)
    v = _split_heads(L.dense_apply(p["wv"], x), n_kv_heads, head_dim)
    mask_fn = lambda kv_start, kv_len: jnp.ones((s, kv_len), bool)
    out = attend_chunked(q, k, v, mask_fn=mask_fn, kv_chunk=kv_chunk,
                         unroll=unroll)
    return L.dense_apply(p["wo"], out.reshape(b, s, n_heads * head_dim))
