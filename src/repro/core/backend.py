"""Pluggable analog-execution backends — the single dispatch seam.

Every model family (lstm / rglru / ssd / transformer / mlp / moe) and the
serving engine reach the analog compute primitives through this module, so
the whole config grid runs on either implementation:

* ``"ref"``    — the pure-jnp reference simulation (the former inline
  quantize -> matmul -> NL-ADC sequences, with the STE gradients from
  :mod:`repro.core.nladc`);
* ``"pallas"`` — the fused Pallas kernels (:mod:`repro.kernels`): the
  NL-ADC epilogue runs on the matmul accumulator in VMEM, the LSTM tail is
  one elementwise pass, decode attention dequantizes int8 KV per-tile, the
  MoE gate einsum is the fused matmul vmapped over experts, and the
  non-int8 cached-attention path (bucketed prefill + decode) is one Pallas
  pass per batch row, as is latent (MLA) attention over its cache.  The kernels run compiled on a TPU and in interpret
  mode elsewhere (``repro.kernels.interpret_mode``).
  Block sizes resolve per kernel x shape through the
  :mod:`repro.kernels.tune` cache at trace time; on a cache miss each
  kernel's ``DEFAULT_BLOCKS``, except the fused matmul's skinny plan below
  256 rows (:func:`repro.kernels.fused_matmul_nladc.plan_blocks`).

The Pallas kernels are forward-only; each is wrapped in ``jax.custom_vjp``
whose backward re-derives the reference path's straight-through gradients
with plain jnp ops (the STE formula itself is shared:
:func:`repro.core.nladc.nladc_ste`), so Alg. 1 training works identically
on both backends.  The backwards are hand-written rather than
``jax.vjp``-of-ref because the ref path is itself a custom_vjp, and
nesting one inside another's bwd does not survive scan transposition.

Selection: ``AnalogConfig.backend`` (empty string = auto), the
``REPRO_ANALOG_BACKEND`` env var, or the ``--backend`` train/serve CLI flag.
Third-party backends can be added with :func:`register_backend`.

All quantizing primitives accept explicit comparator ``thresholds``
overrides so
the NL-ADC-aware training noise (perturbed ramp steps) is drawn once in
shared orchestration code and both backends consume identical draws.  The
override may be a :class:`repro.core.nladc.BankedThresholds` — the
``(n_col_tiles, P)`` per-col-tile layout — in which case the ref path
bank-gathers a per-column ``searchsorted`` and the Pallas path feeds the
kernels a per-column threshold operand gathered at trace time; the STE
backwards are shared and bank-agnostic (they depend only on the input).

The circuit-level stages (``LineResistance`` / ``NonlinearIV``) never
appear here: the IR effective-weight correction and the I-V input
distortion are folded into the shared weight/input preparation seam
upstream (``analog_layer._noisy_weights`` / ``analog_matmul_act``), so
both backends consume identical corrected operands and their bitwise
ADC-code parity is preserved without per-backend duplication.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.nladc import (NLADC, BankedThresholds, BankMap, Ramp,
                              _nladc_apply, _nladc_banked_apply,
                              _nladc_banked_fwd_impl, _nladc_fwd_impl,
                              nladc_ste)

DEFAULT_BACKEND = "ref"


def resolve_backend(name: str = "") -> str:
    """Explicit name, else the ``REPRO_ANALOG_BACKEND`` env var, else ref."""
    if name:
        return name
    return os.environ.get("REPRO_ANALOG_BACKEND", "") or DEFAULT_BACKEND


# ---------------------------------------------------------------------------
# Reference backend (pure jnp, differentiable with STE)
# ---------------------------------------------------------------------------

class RefBackend:
    """The jnp simulation path; semantics define the contract."""

    name = "ref"

    def nladc(self, x, adc: NLADC, thresholds=None):
        """Elementwise NL-ADC (thermometer code + table decode, STE bwd).

        ``thresholds`` may be a :class:`BankedThresholds` — the banked
        ``(n_col_tiles, P)`` layout where each column of x's last axis
        compares against its own col-tile's programmed ramp.
        """
        thr = adc.thresholds if thresholds is None else thresholds
        if isinstance(thr, BankedThresholds):
            return _nladc_banked_apply(x, thr.thr, adc.y_table,
                                       adc.ramp.name, thr.bank_map)
        return _nladc_apply(x, thr, adc.y_table, adc.ramp.name)

    def matmul_nladc(self, x, w, adc: NLADC, bias=None, thresholds=None,
                     preferred_dtype=None):
        """NLADC(x @ w + bias).

        ``preferred_dtype`` set (crossbar path): accumulate there;
        unset (LM dense path): matmul in x's compute dtype.
        """
        if preferred_dtype is not None:
            y = jnp.matmul(x, w, preferred_element_type=preferred_dtype)
        else:
            y = x @ w.astype(x.dtype)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return self.nladc(y, adc, thresholds).astype(x.dtype)

    def lstm_gates(self, gates, c, sig_adc: NLADC, tanh_adc: NLADC,
                   sig_thr=None, tanh_thr=None):
        """The LSTM elementwise tail (Eq. 5): 5 NL-ADCs + cell update.

        gates: (B, 4H) raw MAC results in [f|a|i|o] order; c: (B, H).
        """
        hf, ha, hi, ho = jnp.split(gates, 4, axis=-1)
        f = self.nladc(hf, sig_adc, sig_thr)
        a = self.nladc(ha, tanh_adc, tanh_thr)
        i = self.nladc(hi, sig_adc, sig_thr)
        o = self.nladc(ho, sig_adc, sig_thr)
        c_new = f * c + i * a
        h_new = o * self.nladc(c_new, tanh_adc, tanh_thr)
        return h_new, c_new

    def decode_attention_int8(self, q, k8, k_scale, v8, v_scale, length):
        """One-token attention over an int8 KV cache (dequantize-all ref).

        q: (B, H, D); k8/v8: (B, S, H_kv, D) int8; scales (B, S, H_kv);
        length: (B,) valid-slot counts.  Returns (B, H, D) f32.
        """
        from repro.kernels import ref as kref

        return kref.flash_decode_int8(q, k8, k_scale, v8, v_scale, length)

    def moe_matmul_nladc(self, x, w, adc: NLADC, thresholds=None):
        """Per-expert fused gate: NLADC(x[e] @ w[e]) for every expert.

        x: (E, C, d) dispatched expert buffers, w: (E, d, f) expert
        weights -> (E, C, f).  The ref path is exactly the historical
        ``act(einsum("ecd,edf->ecf", ...))`` MoE gate sequence — einsum
        then the elementwise NL-ADC — so swapping ``nn.moe`` onto this
        primitive changes nothing bitwise on the ref backend.
        """
        h = jnp.einsum("ecd,edf->ecf", x, w.astype(x.dtype))
        return self.nladc(h, adc, thresholds)

    def prefill_attention(self, q, k, v, mask):
        """One-query cached attention (bucketed prefill / decode step).

        q: (B, 1, H, D); k/v: (B, S, H_kv, D); mask broadcastable to
        (B, 1, S).  The ref path IS ``nn.attention.attend_full`` — the
        import is deferred to keep core free of nn at import time.
        """
        from repro.nn.attention import attend_full

        return attend_full(q, k, v, mask)

    def mla_decode_attention(self, q, ckv, mask, *, rank: int,
                             scale: float):
        """One-query attention over a latent (MLA) decode cache.

        q: (B, H, W) absorbed queries [q_c, q_r]; ckv: (B, W, S) cached
        [c, k_r]; mask: (B, S) bool -> (B, H, rank).  The ref path IS
        ``nn.attention.mla_attend``."""
        from repro.nn.attention import mla_attend

        return mla_attend(q, ckv, mask, rank=rank, scale=scale)


# ---------------------------------------------------------------------------
# Pallas backend (fused kernels fwd, ref-VJP bwd)
# ---------------------------------------------------------------------------

def _ramp_key(ramp: Ramp):
    from repro.kernels.ref import decode_mode, decode_params

    return (ramp.name, ramp.bits, ramp.split_index, ramp.monotonic_split,
            decode_params(ramp), decode_mode(ramp))


_FN_CACHE: Dict = {}


def _cached(kind, key, build):
    full = (kind,) + key
    fn = _FN_CACHE.get(full)
    if fn is None:
        fn = _FN_CACHE[full] = build()
    return fn


def _pallas_nladc_fn(ramp: Ramp, bank_map: Optional[BankMap] = None):
    def build():
        def raw(x, thr):
            from repro.kernels import ops

            if bank_map is not None:
                thr = BankedThresholds(thr, bank_map)
            return ops.nladc(x, ramp, thresholds=thr)

        def fwd(x, thr):
            return raw(x, thr), x

        def bwd(res, ct):
            return (nladc_ste(ramp.name, res, ct), None)

        fn = jax.custom_vjp(raw)
        fn.defvjp(fwd, bwd)
        return fn

    return _cached("nladc", _ramp_key(ramp) + (bank_map,), build)


def _pallas_matmul_fn(ramp: Ramp, has_bias: bool, preferred_dtype,
                      bank_map: Optional[BankMap] = None):
    pd_key = None if preferred_dtype is None \
        else jnp.dtype(preferred_dtype).name

    def build():
        def _pre(x, w, b):
            """The pre-activation accumulator, ref semantics."""
            if preferred_dtype is not None:
                y = jnp.matmul(x, w, preferred_element_type=preferred_dtype)
            else:
                y = x @ w.astype(x.dtype)
            if has_bias:
                y = y + b.astype(y.dtype)
            return y

        def raw(x, w, b, thr):
            from repro.kernels import ops

            if bank_map is not None:
                thr = BankedThresholds(thr, bank_map)
            return ops.fused_matmul_nladc(
                x, w, ramp, bias=(b if has_bias else None), thresholds=thr)

        def fwd(x, w, b, thr):
            return raw(x, w, b, thr), (x, w, b)

        def bwd(res, ct):
            x, w, b = res
            pre = _pre(x, w, b)           # rematerialized accumulator
            d_pre = nladc_ste(ramp.name, pre, ct.astype(pre.dtype))
            w_used = w if preferred_dtype is not None else w.astype(x.dtype)
            dx = jnp.einsum("...n,kn->...k", d_pre, w_used).astype(x.dtype)
            dw = jnp.einsum("...k,...n->kn", x, d_pre).astype(w.dtype)
            db = None
            if has_bias:
                axes = tuple(range(d_pre.ndim - 1))
                db = jnp.sum(d_pre, axis=axes).astype(b.dtype)
            else:
                db = jnp.zeros_like(b)
            return (dx, dw, db, None)

        fn = jax.custom_vjp(raw)
        fn.defvjp(fwd, bwd)
        return fn

    return _cached("matmul",
                   _ramp_key(ramp) + (has_bias, pd_key, bank_map), build)


def _pallas_lstm_fn(sig_ramp: Ramp, tanh_ramp: Ramp,
                    bank_map: Optional[BankMap] = None):
    def build():
        # NUMPY (not jnp) constants: build() may run inside an active trace
        # and the closure is cached — a jnp.asarray here would capture a
        # tracer of that trace and leak it into later traces.
        import numpy as np

        sig_y = np.asarray(sig_ramp.y_table, np.float32)
        tanh_y = np.asarray(tanh_ramp.y_table, np.float32)

        def raw(gates, c, sig_thr, tanh_thr):
            from repro.kernels import ops

            if bank_map is not None:
                sig_thr = BankedThresholds(sig_thr, bank_map)
                tanh_thr = BankedThresholds(tanh_thr, bank_map)
            return ops.lstm_gates(gates, c, sig_ramp, tanh_ramp,
                                  sig_thresholds=sig_thr,
                                  tanh_thresholds=tanh_thr)

        def fwd(gates, c, sig_thr, tanh_thr):
            return raw(gates, c, sig_thr, tanh_thr), \
                (gates, c, sig_thr, tanh_thr)

        def bwd(res, ct):
            # Rematerialize the quantized tail, then chain the STEs exactly
            # as autodiff does through the ref implementation.
            gates, c, sig_thr, tanh_thr = res
            ct_h, ct_c = ct
            hf, ha, hi, ho = jnp.split(gates, 4, axis=-1)

            def sq(v):
                if bank_map is not None:
                    return _nladc_banked_fwd_impl(v, sig_thr, sig_y,
                                                  bank_map)
                return _nladc_fwd_impl(v, sig_thr, sig_y)

            def tq(v):
                if bank_map is not None:
                    return _nladc_banked_fwd_impl(v, tanh_thr, tanh_y,
                                                  bank_map)
                return _nladc_fwd_impl(v, tanh_thr, tanh_y)

            f, a, i, o = sq(hf), tq(ha), sq(hi), sq(ho)
            c_new = f * c + i * a
            tc = tq(c_new)
            d_o = nladc_ste(sig_ramp.name, ho, ct_h * tc)
            d_cnew = ct_c + nladc_ste(tanh_ramp.name, c_new, ct_h * o)
            d_f = nladc_ste(sig_ramp.name, hf, d_cnew * c)
            d_i = nladc_ste(sig_ramp.name, hi, d_cnew * a)
            d_a = nladc_ste(tanh_ramp.name, ha, d_cnew * i)
            d_gates = jnp.concatenate([d_f, d_a, d_i, d_o], axis=-1)
            return (d_gates, d_cnew * f, None, None)

        fn = jax.custom_vjp(raw)
        fn.defvjp(fwd, bwd)
        return fn

    return _cached("lstm",
                   _ramp_key(sig_ramp) + _ramp_key(tanh_ramp) + (bank_map,),
                   build)


def _pallas_moe_fn(ramp: Ramp, bank_map: Optional[BankMap] = None):
    def build():
        def raw(x, w, thr):
            from repro.kernels import ops

            if bank_map is not None:
                thr = BankedThresholds(thr, bank_map)
            return ops.moe_fused_matmul(x, w, ramp, thresholds=thr)

        def fwd(x, w, thr):
            return raw(x, w, thr), (x, w)

        def bwd(res, ct):
            x, w = res
            pre = jnp.einsum("ecd,edf->ecf", x, w.astype(x.dtype))
            d_pre = nladc_ste(ramp.name, pre, ct.astype(pre.dtype))
            dx = jnp.einsum("ecf,edf->ecd", d_pre,
                            w.astype(x.dtype)).astype(x.dtype)
            dw = jnp.einsum("ecd,ecf->edf", x, d_pre).astype(w.dtype)
            return (dx, dw, None)

        fn = jax.custom_vjp(raw)
        fn.defvjp(fwd, bwd)
        return fn

    return _cached("moe_matmul", _ramp_key(ramp) + (bank_map,), build)


def _pallas_prefill_attention_fn():
    def build():
        def raw(q, k, v, mask):
            from repro.kernels import ops

            return ops.prefill_attention(q, k, v, mask)

        def fwd(q, k, v, mask):
            return raw(q, k, v, mask), (q, k, v, mask)

        def bwd(res, ct):
            # attend_full is plain jnp (no nested custom_vjp), so jax.vjp
            # of the ref math is safe under scan transposition here
            from repro.nn.attention import attend_full

            q, k, v, mask = res
            _, vjp = jax.vjp(
                lambda q_, k_, v_: attend_full(q_, k_, v_, mask), q, k, v)
            dq, dk, dv = vjp(ct)
            return (dq, dk, dv, None)

        fn = jax.custom_vjp(raw)
        fn.defvjp(fwd, bwd)
        return fn

    return _cached("prefill_attention", (), build)


def _pallas_mla_decode_fn(rank: int, scale: float):
    def build():
        def raw(q, ckv, mask):
            from repro.kernels import ops

            return ops.mla_decode_attention(q, ckv, mask, rank=rank,
                                            scale=scale)

        def fwd(q, ckv, mask):
            return raw(q, ckv, mask), (q, ckv, mask)

        def bwd(res, ct):
            from repro.nn.attention import mla_attend

            q, ckv, mask = res
            _, vjp = jax.vjp(lambda q_, c_: mla_attend(
                q_, c_, mask, rank=rank, scale=scale), q, ckv)
            dq, dc = vjp(ct)
            return (dq, dc, None)

        fn = jax.custom_vjp(raw)
        fn.defvjp(fwd, bwd)
        return fn

    return _cached("mla_decode_attention", (rank, scale), build)


class PallasBackend(RefBackend):
    """Fused Pallas kernels; falls back to ref only where no kernel exists
    (the raw activation-less crossbar MAC — by design the upstream GEMM
    stays a single wide matmul and the fused tails do the NL-ADC work)."""

    name = "pallas"

    def nladc(self, x, adc: NLADC, thresholds=None):
        thr = adc.thresholds if thresholds is None else thresholds
        if isinstance(thr, BankedThresholds):
            return _pallas_nladc_fn(adc.ramp, thr.bank_map)(x, thr.thr)
        return _pallas_nladc_fn(adc.ramp)(x, thr)

    def matmul_nladc(self, x, w, adc: NLADC, bias=None, thresholds=None,
                     preferred_dtype=None):
        thr = adc.thresholds if thresholds is None else thresholds
        bank_map = thr.bank_map if isinstance(thr, BankedThresholds) \
            else None
        fn = _pallas_matmul_fn(adc.ramp, bias is not None, preferred_dtype,
                               bank_map)
        b = bias if bias is not None \
            else jnp.zeros((w.shape[-1],), jnp.float32)
        return fn(x, w, b, thr.thr if bank_map is not None else thr)

    def lstm_gates(self, gates, c, sig_adc: NLADC, tanh_adc: NLADC,
                   sig_thr=None, tanh_thr=None):
        st = sig_adc.thresholds if sig_thr is None else sig_thr
        tt = tanh_adc.thresholds if tanh_thr is None else tanh_thr
        s_banked = isinstance(st, BankedThresholds)
        if s_banked != isinstance(tt, BankedThresholds):
            # both come from one AnalogConfig, so one banking geometry
            raise ValueError("lstm_gates: sigmoid and tanh thresholds must "
                             "both be banked or both be flat")
        if s_banked:
            if st.bank_map != tt.bank_map:
                raise ValueError("lstm_gates: sigmoid/tanh bank maps differ")
            fn = _pallas_lstm_fn(sig_adc.ramp, tanh_adc.ramp, st.bank_map)
            return fn(gates, c, st.thr, tt.thr)
        fn = _pallas_lstm_fn(sig_adc.ramp, tanh_adc.ramp)
        return fn(gates, c, st, tt)

    def decode_attention_int8(self, q, k8, k_scale, v8, v_scale, length):
        from repro.kernels import ops

        return ops.flash_decode_int8(q, k8, k_scale, v8, v_scale, length)

    def moe_matmul_nladc(self, x, w, adc: NLADC, thresholds=None):
        thr = adc.thresholds if thresholds is None else thresholds
        bank_map = thr.bank_map if isinstance(thr, BankedThresholds) \
            else None
        fn = _pallas_moe_fn(adc.ramp, bank_map)
        return fn(x, w, thr.thr if bank_map is not None else thr)

    def prefill_attention(self, q, k, v, mask):
        return _pallas_prefill_attention_fn()(q, k, v, mask)

    def mla_decode_attention(self, q, ckv, mask, *, rank: int,
                             scale: float):
        return _pallas_mla_decode_fn(rank, scale)(q, ckv, mask)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, object] = {}


def register_backend(name: str, impl) -> None:
    """Register an analog backend implementation under ``name``."""
    _REGISTRY[name] = impl


register_backend("ref", RefBackend())
register_backend("pallas", PallasBackend())


def get_backend(name: str = ""):
    """Resolve (explicit / env / default) and return the backend object."""
    resolved = resolve_backend(name)
    try:
        return _REGISTRY[resolved]
    except KeyError:
        raise KeyError(
            f"unknown analog backend {resolved!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def backend_names():
    return tuple(sorted(_REGISTRY))
