"""Pallas TPU kernel: elementwise NL-ADC (thermometer compare + affine decode).

The paper's NL-ADC is a bank of 2^b comparators against a programmed ramp.
On TPU this maps to a VPU-friendly compare-and-sum against a (2^b,)-entry
threshold table resident in VMEM next to the data tile, followed by the
closed-form decode (the ramp's y-levels are uniform by construction, so no
gather is needed — gathers are the thing to avoid on the TPU vector unit):

    n(x)  = sum_k [x > V_k]                  (thermometer count)
    y(x)  = y0 + n * lsb                     (monotonic)
    y(x)  = y0 + |n - m| * lsb_{left/right}  (extremum split, Supp. S12)

Tiling: (block_m, block_n) VMEM tiles of the input; the threshold table is
small (<= 2^12 entries) and broadcast to every grid step.  Lane-dim blocks
are multiples of 128 to match the VPU/VREG layout.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.nladc import Ramp
from repro.kernels import tune
from repro.kernels.common import BlockRowThresholds
from repro.kernels.ref import (closed_form_decode, decode_mode, decode_params,
                               thermometer_count)

DEFAULT_BLOCK = (256, 512)


def _nladc_kernel(x_ref, thr_ref, o_ref, *, y0, lsb_l, lsb_r, m, mode,
                  bank_fast):
    x = x_ref[...].astype(jnp.float32)
    # thr: (P,) shared ramp in VMEM, (bn, P) per-column (banked layout,
    # the column->bank gather resolved at trace time by ops.nladc), or —
    # fast path — the block's single (1, P) bank row of an (n_blocks, 1, P)
    # table (a (1, P) block of an (n_blocks, P) array breaks the tiling).
    thr = thr_ref[0] if bank_fast else thr_ref[...]
    n = thermometer_count(x, thr)
    y = closed_form_decode(n, mode, y0, lsb_l, lsb_r, m)
    o_ref[...] = y.astype(o_ref.dtype)


def _thr_spec_2d(thr, bn):
    """BlockSpec for the threshold operand: broadcast (P,) table, or the
    (bn, P) per-column slice tracking the lane-dim grid step (banked)."""
    if thr.ndim == 2:
        return pl.BlockSpec((bn, thr.shape[1]), lambda i, j: (j, 0))
    return pl.BlockSpec((thr.shape[0],), lambda i, j: (0,))


def nladc_pallas(x, ramp: Ramp, *, thresholds=None,
                 block: Tuple[int, int] = DEFAULT_BLOCK,
                 interpret: bool = True):
    """2D-tiled elementwise NL-ADC.  x: (M, N) -> (M, N).

    ``thresholds`` overrides the programmed comparator levels — a traced
    (P,) array (NL-ADC-aware training perturbs the ramp per step) or an
    (N, P) per-column matrix (threshold banks: each output column compares
    against its own col-tile's programmed ramp); the decode stays the
    ramp's closed form (y-levels are fixed by design).
    """
    m_dim, n_dim = x.shape
    bm, bn = min(block[0], m_dim), min(block[1], n_dim)
    if (bm, bn) != tuple(block):
        tune.warn_clamp("nladc", (m_dim, n_dim), block, (bm, bn),
                        dtype=x.dtype)
    grid = (pl.cdiv(m_dim, bm), pl.cdiv(n_dim, bn))
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
                                lambda i, j: (j, 0, 0))
    else:
        thr = jnp.asarray(ramp.thresholds, jnp.float32) \
            if thresholds is None else thresholds.astype(jnp.float32)
        thr_spec = _thr_spec_2d(thr, bn)
    kernel = functools.partial(
        _nladc_kernel, y0=y0, lsb_l=lsb_l, lsb_r=lsb_r, m=mm,
        mode=decode_mode(ramp), bank_fast=bank_fast)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            thr_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_dim, n_dim), x.dtype),
        interpret=interpret,
    )(x, thr)
