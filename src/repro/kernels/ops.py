"""Public jit'd wrappers for the Pallas kernels.

Handles: leading-dim flattening, padding to block multiples, and the
interpret-mode switch (compiled on a TPU; on the CPU ``interpret=True``
executes the kernel bodies with XLA ops for correctness validation).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

import numpy as np

from repro.core.nladc import BankedThresholds, Ramp
from repro.kernels import crossbar_mac as _cb
from repro.kernels import flash_decode as _fd
from repro.kernels import fused_matmul_nladc as _fm
from repro.kernels import lstm_cell as _lc
from repro.kernels import nladc_kernel as _nk
from repro.kernels import prefill_attention as _pa
from repro.kernels import tune
from repro.kernels.common import BlockRowThresholds


def interpret_mode() -> bool:
    """True when the kernels run in Pallas interpret mode.

    Decided by the platform alone: compiled on a TPU, interpreted
    everywhere else (the CPU test path).
    """
    return jax.default_backend() != "tpu"


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fastpath_enabled() -> bool:
    """``REPRO_KERNEL_FASTPATH=0`` disables the (P,) bank-row fast path
    (bisection aid — the dense (bn, P) layout is the reference)."""
    return os.environ.get("REPRO_KERNEL_FASTPATH", "") \
        not in ("0", "false", "False")


def _resolve_thr(thresholds, n_cols: int, mult: int, *,
                 allow_fastpath: bool = True):
    """Banked thresholds -> a padded (N, P) per-column matrix, or — when
    every ``mult``-wide lane block maps to one bank (``bank_cols`` a
    multiple of the block width, the aligned common case) — a
    :class:`BlockRowThresholds` carrying one (P,) bank row per block, so
    the kernel skips the (bn, P) VMEM operand entirely.

    The column→bank gather happens HERE, at trace time — the kernels see a
    dense per-column threshold operand (or the per-block row table) and
    never gather on the VPU.  Plain (P,)/None thresholds pass through
    untouched.  Padded columns replicate the last row (their outputs are
    sliced away; the compare just needs finite values).
    """
    if not isinstance(thresholds, BankedThresholds):
        return thresholds
    idx = thresholds.bank_map.idx
    if idx.shape[0] != n_cols:
        raise ValueError(
            f"bank map covers {idx.shape[0]} columns but the operand has "
            f"{n_cols}")
    if allow_fastpath and _fastpath_enabled():
        idx_np = np.asarray(idx)
        starts = np.arange(-(-n_cols // mult)) * mult
        if all(np.all(idx_np[s:s + mult] == idx_np[s]) for s in starts):
            # padded tail columns inherit the last block's bank — same
            # finite-compare contract as the dense edge pad below
            return BlockRowThresholds(
                thr=thresholds.thr.astype(jnp.float32)[
                    jnp.asarray(idx_np[starts])])
    thr_cols = thresholds.thr.astype(jnp.float32)[jnp.asarray(idx)]
    pad = (-n_cols) % mult
    if pad:
        thr_cols = jnp.pad(thr_cols, ((0, pad), (0, 0)), mode="edge")
    return thr_cols


def nladc(x, ramp: Ramp, *, thresholds=None, block=None):
    """Elementwise NL-ADC of any-shaped x (flattened to 2D tiles).

    ``thresholds`` may be a :class:`BankedThresholds` — each column of the
    last axis then compares against its own bank's programmed ramp.
    """
    shape = x.shape
    flat = x.reshape(-1, shape[-1]) if x.ndim > 1 else x.reshape(1, -1)
    m0, n0 = flat.shape
    blk = block or tune.resolve_blocks("nladc", (m0, n0), x.dtype)
    thr = _resolve_thr(thresholds, n0, blk[1])
    flat = _pad_to(_pad_to(flat, blk[0], 0), blk[1], 1)
    out = _nk.nladc_pallas(flat, ramp, thresholds=thr, block=blk,
                           interpret=interpret_mode())
    return out[:m0, :n0].reshape(shape)


def fused_matmul_nladc(x, w, ramp: Ramp, bias=None, *, thresholds=None,
                       blocks=None):
    """NLADC(x @ w + bias) with batch-dims flattened into M.

    ``thresholds`` may be a :class:`BankedThresholds` over w's output
    columns (one ramp per crossbar col-tile).  Rows are padded to the
    resolved bm: below 256 rows that is the skinny plan's, the rows
    rounded up to x's sublane tile (``fused_matmul_nladc.plan_blocks``).
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    xf = x.reshape(-1, k)
    m0 = xf.shape[0]
    blk = blocks or tune.resolve_blocks(
        "fused_matmul_nladc", (m0, k, n), x.dtype,
        fallback=_fm.plan_blocks(m0, k, n, x.dtype, w.dtype))
    thr = _resolve_thr(thresholds, n, blk[1])
    xf = _pad_to(_pad_to(xf, blk[0], 0), blk[2], 1)
    wp = _pad_to(_pad_to(w, blk[2], 0), blk[1], 1)
    bp = None
    if bias is not None:
        bp = _pad_to(bias, blk[1], 0)
    out = _fm.fused_matmul_nladc_pallas(xf, wp, ramp, bp,
                                        thresholds=thr, blocks=blk,
                                        interpret=interpret_mode())
    return out[:m0, :n].reshape(lead + (n,))


def analog_tile(x, w, ramp: Ramp, *, input_bits: Optional[int] = None,
                input_clip: float = 1.0, w_noise=None, blocks=None):
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    xf = x.reshape(-1, k)
    m0 = xf.shape[0]
    blk = blocks or tune.resolve_blocks("analog_tile", (m0, k, n), x.dtype)
    xf = _pad_to(_pad_to(xf, blk[0], 0), blk[2], 1)
    wp = _pad_to(_pad_to(w, blk[2], 0), blk[1], 1)
    nz = None
    if w_noise is not None:
        nz = _pad_to(_pad_to(w_noise, blk[2], 0), blk[1], 1)
    out = _cb.analog_tile_pallas(xf, wp, ramp, input_bits=input_bits,
                                 input_clip=input_clip, w_noise=nz,
                                 blocks=blk, interpret=interpret_mode())
    return out[:m0, :n].reshape(lead + (n,))


def lstm_gates(gates, c, sig_ramp: Ramp, tanh_ramp: Ramp, *,
               sig_thresholds=None, tanh_thresholds=None, block=None):
    """Fused LSTM tail. gates: (B, 4H), c: (B, H) -> (h', c').

    Threshold args may be :class:`BankedThresholds` over the hidden dim —
    every gate (and the cell tanh) of hidden unit h then uses the ramp of
    h's col-tile bank.
    """
    b0, h4 = gates.shape
    h0 = h4 // 4
    blk = block or tune.resolve_blocks("lstm_gates", (b0, h0), gates.dtype)
    # the LSTM tail kernel keeps the dense (bn, P) banked layout (its
    # four-gate packing reads two ramps per tile — fast-path rows would
    # double the spec surface for a kernel that is VPU-, not VMEM-, bound)
    sig_thresholds = _resolve_thr(sig_thresholds, h0, blk[1],
                                  allow_fastpath=False)
    tanh_thresholds = _resolve_thr(tanh_thresholds, h0, blk[1],
                                   allow_fastpath=False)
    # pad batch and hidden separately (gates padded per-gate inside kernel
    # wrapper: split, pad, re-concat keeps the [f|a|i|o] packing intact)
    gf, ga, gi, go = jnp.split(gates, 4, axis=-1)
    parts = [_pad_to(_pad_to(g, blk[0], 0), blk[1], 1)
             for g in (gf, ga, gi, go)]
    gp = jnp.concatenate(parts, axis=-1)
    cp = _pad_to(_pad_to(c, blk[0], 0), blk[1], 1)
    h, c_new = _lc.lstm_gates_pallas(gp, cp, sig_ramp, tanh_ramp,
                                     sig_thresholds=sig_thresholds,
                                     tanh_thresholds=tanh_thresholds,
                                     block=blk, interpret=interpret_mode())
    return h[:b0, :h0], c_new[:b0, :h0]


def moe_fused_matmul(x, w, ramp: Ramp, *, thresholds=None, blocks=None):
    """Per-expert fused gate einsum: NLADC(x[e] @ w[e]) for every expert.

    x: (E, C, d) dispatched expert buffers, w: (E, d, f) expert weights
    -> (E, C, f).  ``fused_matmul_nladc`` vmapped over the expert axis —
    one fused MXU+NL-ADC kernel per expert instead of the XLA einsum +
    separate quantize.  ``thresholds`` (shared across experts, like the
    deployed col-tile periphery) may be banked; block resolution uses the
    per-expert (C, d, f) shape.
    """
    def one(xe, we):
        return fused_matmul_nladc(xe, we, ramp, thresholds=thresholds,
                                  blocks=blocks)

    return jax.vmap(one)(x, w)


def prefill_attention(q, k, v, mask):
    """Batched one-query cached attention (the bucketed-prefill /decode
    pattern).  q: (B, 1, H, D), k/v: (B, S, Hkv, D), mask broadcastable
    to (B, 1, S) bool -> (B, 1, H, D), matching ``attend_full`` bitwise.
    """
    b, q_len, h, d = q.shape
    if q_len != 1:
        raise ValueError(f"prefill_attention is one-query; got q_len="
                         f"{q_len}")
    s_len = k.shape[1]
    mask2 = jnp.broadcast_to(mask, (b, 1, s_len))[:, 0, :].astype(jnp.int32)
    out = _pa.prefill_attention_pallas(q[:, 0], k, v, mask2,
                                       interpret=interpret_mode())
    return out[:, None]


def flash_decode_int8(q, k8, k_scale, v8, v_scale, length, *, block_s=None):
    """One-token flash attention over an int8 KV cache (fused dequant)."""
    bs = block_s or _fd.DEFAULT_BLOCK_S
    s_len = k8.shape[1]
    pad = (-s_len) % bs
    if pad:
        k8 = _pad_to(k8, bs, 1)
        v8 = _pad_to(v8, bs, 1)
        k_scale = _pad_to(k_scale, bs, 1)
        v_scale = _pad_to(v_scale, bs, 1)
    return _fd.flash_decode_int8(q, k8, k_scale, v8, v_scale, length,
                                 block_s=bs, interpret=interpret_mode())
