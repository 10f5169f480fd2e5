"""NL-ADC quantized activations for the plain references, from the paper.

The configurations serve with ``analog.mode = "exact"``: no device noise,
but every NL-ADC'd activation is still quantized by its ideal ramp
(Eq. 3 of the paper for a monotonic activation, Supp. Note S12's
extremum split for silu).  This module builds those ramps from the
paper's definition and the domains of Supp. Tab. S2, in float64 numpy,
and quantizes in float32 with the strict comparator ``n = #{V_k < x}``.
It shares no code and no table with the program.

    P = 2**bits comparator levels; output levels uniform in y.
    monotonic g on [x_lo, x_hi]:  y_k = g(x_lo) + k (g(x_hi) - g(x_lo)) / P,
                                  V_k = g^-1(y_k), k = 1..P
    silu (minimum at x_m):        one LSB shared by both branches,
                                  m codes on the left branch, P - m on
                                  the right; decode y = y(x_m) + LSB |n - m|
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

# Domains of Supp. Tab. S2 (the ramp's saturation points).
DOMAINS = {"silu": (-6.0, 6.0), "softplus": (-2.634, 2.179)}


def _silu(x):
    x = np.asarray(x, np.float64)
    return x / (1.0 + np.exp(-x))


def _softplus(x):
    return np.logaddexp(0.0, np.asarray(x, np.float64))


def _softplus_inv(y):
    y = np.asarray(y, np.float64)
    return y + np.log(-np.expm1(-y))


def _bisect(f, y, lo, hi, increasing):
    """Branch inverse of f on [lo, hi] by bisection (float64)."""
    y = np.asarray(y, np.float64)
    a, b = np.full_like(y, lo), np.full_like(y, hi)
    for _ in range(100):
        mid = 0.5 * (a + b)
        left = f(mid) >= y if increasing else f(mid) <= y
        a, b = np.where(left, a, mid), np.where(left, mid, b)
    return 0.5 * (a + b)


def _silu_minimum(lo=-4.0, hi=0.0):
    """x where silu'(x) = 0, by bisection on the derivative."""
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        s = 1.0 / (1.0 + math.exp(-mid))
        if s + mid * s * (1.0 - s) < 0:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def ramp(name: str, bits: int):
    """-> (thresholds (P,), y_table (P + 1,)) in float64."""
    p = 1 << bits
    lo, hi = DOMAINS[name]
    if name == "softplus":
        y = np.linspace(_softplus(lo), _softplus(hi), p + 1)
        v = _softplus_inv(y)
        v[0], v[-1] = lo, hi
        return v[1:].copy(), y
    if name != "silu":
        raise KeyError(f"no reference ramp for {name!r}")
    xm = _silu_minimum()
    y0, y_left, y_right = float(_silu(xm)), float(_silu(lo)), float(_silu(hi))
    lsb = ((y_left - y0) + (y_right - y0)) / p
    m = min(max(int(round((y_left - y0) / lsb)), 1), p - 1)
    x_left = _bisect(_silu, y0 + np.arange(m, 0, -1) * lsb, lo, xm, False)
    x_right = _bisect(_silu, y0 + np.arange(1, p - m + 1) * lsb, xm, hi,
                      True)
    v = np.concatenate([x_left, [xm], x_right])
    v[0], v[-1] = min(v[0], lo), max(v[-1], hi)
    n = np.arange(p + 1, dtype=np.float64)
    y = np.where(n <= m, y0 + (m - n) * lsb, y0 + (n - m) * lsb)
    return v[1:].copy(), y


class Quantizer:
    """x -> y_table[#{V_k < x}] in float32, as a sum of the steps crossed
    (no gathers, so it stays fast on the TPU)."""

    def __init__(self, name: str, bits: int):
        thr, y = ramp(name, bits)
        self.thresholds = [float(np.float32(t)) for t in thr]
        self.y0 = float(np.float32(y[0]))
        self.steps = [float(d) for d in np.diff(y)]

    def __call__(self, x):
        x = x.astype(jnp.float32)
        y = jnp.full(x.shape, self.y0, jnp.float32)
        for t, d in zip(self.thresholds, self.steps):
            y = y + jnp.where(x > t, jnp.float32(d), jnp.float32(0.0))
        return y
