"""Pallas TPU kernel: MXU-tiled matmul with fused NL-ADC epilogue.

This is the paper's core insight restated for TPU: the activation costs
nothing beyond the MAC digitization.  On the crossbar the ramp comparator
runs at the column periphery; on TPU the ramp quantizer runs on the matmul
accumulator **while it is still in VMEM**, so the activation adds zero HBM
round-trips (vs. matmul -> write 16 GB/s-bound activations -> read -> act).

Grid (i, j, k) over (M/bm, N/bn, K/bk); the f32 accumulator tile lives in
VMEM scratch across the k-steps; the NL-ADC epilogue (thermometer compare
+ affine decode + optional bias) fires on the last k-step and writes the
only output.  bf16 x and W feed the MXU as stored, one pass, into the f32
accumulator (the products are exact in f32); f32 or mixed operands are
dotted in f32, so nothing stored in f32 is rounded to bf16.

Blocks (``plan_blocks``, unless an override or the tune cache names
them): ``DEFAULT_BLOCKS`` from 256 rows up (prefill, training); below, a
skinny plan for decode-sized calls, rows fitted to the sublane tile and
the whole of K in one grid step.

Every operand block is 2-D (or an untiled (P,) table) so the TPU tiling
accepts it: the bias is a (1, bn) row of a (1, N) array, and the
fast-path bank rows a (1, P) slice of an (n_blocks, 1, P) table.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.nladc import Ramp
from repro.kernels import tune
from repro.kernels.common import BlockRowThresholds
from repro.kernels.ref import (closed_form_decode, decode_mode, decode_params,
                               thermometer_count)

DEFAULT_BLOCKS = (256, 256, 512)   # (bm, bn, bk)
# the skinny plan's largest (bk, bn) weight tile: K in one step up to here
SKINNY_W_TILE_BYTES = 4 * 1024 * 1024


def _round_up(a: int, mult: int) -> int:
    return -(-a // mult) * mult


def plan_blocks(m: int, k: int, n: int, x_dtype,
                w_dtype) -> Tuple[int, int, int]:
    """The blocks of one (m, k, n) call when neither an override nor the
    tune cache names them.

    ``DEFAULT_BLOCKS`` from ``DEFAULT_BLOCKS[0]`` rows up.  Fewer rows (a
    decode step's batch, an expert's capacity) take the skinny plan: bm is
    the rows rounded up to x's sublane tile (8 for f32, 16 for bf16), so no
    MXU pass or epilogue runs on padding rows, and bk is the whole of K
    (rounded up to 128 lanes) while a (bk, bn) weight tile fits
    ``SKINNY_W_TILE_BYTES``, so each lane block is one grid step.  bn stays
    ``DEFAULT_BLOCKS[1]``.
    """
    bm, bn, bk = DEFAULT_BLOCKS
    if m >= bm:
        return DEFAULT_BLOCKS
    sublanes = 32 // jnp.dtype(x_dtype).itemsize
    k_all = _round_up(k, 128)
    if k_all * bn * jnp.dtype(w_dtype).itemsize <= SKINNY_W_TILE_BYTES:
        bk = k_all
    return _round_up(max(m, 1), sublanes), bn, bk


def _kernel(*refs, n_k: int, y0, lsb_l, lsb_r, m, mode, has_bias,
            bank_fast):
    if has_bias:
        x_ref, w_ref, thr_ref, b_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, thr_ref, o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x, w = x_ref[...], w_ref[...]
    if not x.dtype == w.dtype == jnp.bfloat16:
        # f32 or mixed storage: an f32 dot, nothing rounded below its dtype
        x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...]
        if has_bias:
            acc = acc + b_ref[...].astype(jnp.float32)   # (1, bn) row
        # thr: (P,) shared ramp, (bn, P) per-column (threshold banks), or —
        # fast path — the block's single (1, P) bank row, register-resident
        # through the broadcast compare
        thr = thr_ref[0] if bank_fast else thr_ref[...]
        n = thermometer_count(acc, thr)
        y = closed_form_decode(n, mode, y0, lsb_l, lsb_r, m)
        o_ref[...] = y.astype(o_ref.dtype)


def fused_matmul_nladc_pallas(
        x, w, ramp: Ramp, bias: Optional[jax.Array] = None, *,
        thresholds: Optional[jax.Array] = None,
        blocks: Tuple[int, int, int] = DEFAULT_BLOCKS,
        interpret: bool = True):
    """y = NLADC(x @ w + bias).  x: (M, K), w: (K, N) -> (M, N).

    ``thresholds`` overrides the programmed comparator levels — a traced
    (P,) array, an (N, P) per-column matrix for the banked layout (the
    col-tile ADC periphery), or a :class:`BlockRowThresholds` carrier (one
    (P,) bank row per lane block — the register-resident fast path); the
    closed-form decode params stay the ramp's.
    """
    m_dim, k_dim = x.shape
    k2, n_dim = w.shape
    assert k_dim == k2, (x.shape, w.shape)
    bm = min(blocks[0], m_dim)
    bn = min(blocks[1], n_dim)
    bk = min(blocks[2], k_dim)
    if (bm, bn, bk) != tuple(blocks):
        tune.warn_clamp("fused_matmul_nladc", (m_dim, k_dim, n_dim),
                        blocks, (bm, bn, bk), dtype=x.dtype)
    grid = (pl.cdiv(m_dim, bm), pl.cdiv(n_dim, bn), pl.cdiv(k_dim, bk))
    y0, lsb_l, lsb_r, mm = decode_params(ramp)
    bank_fast = isinstance(thresholds, BlockRowThresholds)
    if bank_fast:
        thr = thresholds.thr.astype(jnp.float32)
        if thr.shape[0] != grid[1]:
            raise ValueError(
                f"BlockRowThresholds has {thr.shape[0]} rows for "
                f"{grid[1]} lane blocks (bn={bn})")
        thr = thr[:, None, :]                        # (n_blocks, 1, P)
        thr_spec = pl.BlockSpec((None, 1, thr.shape[2]),
                                lambda i, j, k: (j, 0, 0))
    else:
        thr = jnp.asarray(ramp.thresholds, jnp.float32) \
            if thresholds is None else thresholds.astype(jnp.float32)
        if thr.ndim == 2:
            thr_spec = pl.BlockSpec((bn, thr.shape[1]),
                                    lambda i, j, k: (j, 0))
        else:
            thr_spec = pl.BlockSpec((thr.shape[0],), lambda i, j, k: (0,))
    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        thr_spec,
    ]
    operands = [x, w, thr]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        operands.append(bias.reshape(1, n_dim))
    kernel = functools.partial(
        _kernel, n_k=grid[2], y0=y0, lsb_l=lsb_l, lsb_r=lsb_r, m=mm,
        mode=decode_mode(ramp), has_bias=has_bias, bank_fast=bank_fast)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_dim, n_dim), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*operands)
