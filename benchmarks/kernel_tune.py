"""Kernel block-size autotune sweep + bitwise kernel digests.

    PYTHONPATH=src python -m benchmarks.run --only kernel_tune

Three jobs in one module:

* run the :mod:`repro.kernels.tune` sweep over a representative kernel x
  shape grid (deterministic proxy scoring in interpret mode, measured wall
  time on a TPU, where the kernels compile) and print the chosen blocks
  per shape;
* per tuned shape, record the jnp-ref wall time (the CPU-visible
  throughput proxy — NEVER gated), the interpret-mode correctness of the
  Pallas kernel vs its jnp oracle, and a crc32 digest of the kernel output
  bytes on seeded inputs (bitwise-gated by ``benchmarks.kernel_gate``);
* record the new-path parity section: threshold fast path vs the dense
  banked layout (bitwise), the fused MoE expert einsum vs the ref backend
  (ADC codes within LSB/2 + STE grads), and the Pallas cached-attention
  kernel vs ``attend_full`` (bitwise, output AND gradient).

The result (tune cache + digests + parity) is committed as
``benchmarks/BENCH_kernels.json``; re-record on real TPU to replace the
proxy-selected blocks with measured ones (see README "Kernel autotuning").
"""

from __future__ import annotations

import json
import os
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as BK
from repro.core.nladc import NLADC, BankedThresholds, bank_map_for, build_ramp
from repro.kernels import ops, ref, tune

OUT_PATH = os.path.join(os.path.dirname(__file__), "BENCH_kernels.json")

# kernel -> shapes swept and digested; bank_cols = 128 makes the threshold
# fast path eligible at lane blocks of 128 (bank_cols % bn == 0)
SHAPES_QUICK = {
    "fused_matmul_nladc": [(64, 128, 256), (128, 256, 512)],
    "nladc": [(128, 512)],
    "lstm_gates": [(32, 128)],
}
SHAPES_FULL = {
    "fused_matmul_nladc": [(64, 128, 256), (128, 256, 512),
                           (512, 1024, 1024)],
    "analog_tile": [(128, 256, 256)],
    "nladc": [(128, 512), (1024, 2048)],
    "lstm_gates": [(32, 128), (128, 512)],
}
BANK_COLS = 128


def _digest(*arrays) -> str:
    crc = 0
    for a in arrays:
        crc = zlib.crc32(np.ascontiguousarray(
            np.asarray(a, np.float32)).tobytes(), crc)
    return f"{crc:08x}"


def _ref_us(fn, *args, n: int = 3) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return round((time.perf_counter() - t0) / n * 1e6, 1)


def _shape_cell(kernel, shape, blocks, ramp, sig, tnh, rng):
    """Digest + oracle error + jnp-ref wall time for one tuned shape."""
    if kernel in ("fused_matmul_nladc", "analog_tile"):
        m, k, n = shape
        x = jnp.asarray(rng.normal(0, 0.4, (m, k)).astype(np.float32))
        w = jnp.asarray(rng.normal(0, 0.2, (k, n)).astype(np.float32))
        if kernel == "fused_matmul_nladc":
            got = ops.fused_matmul_nladc(x, w, ramp, blocks=blocks)
            want = ref.fused_matmul_nladc(x, w, ramp)
            us = _ref_us(jax.jit(
                lambda a, b: ref.fused_matmul_nladc(a, b, ramp)), x, w)
        else:
            got = ops.analog_tile(x, w, ramp, blocks=blocks)
            want = ref.analog_tile(x, w, ramp)
            us = _ref_us(jax.jit(
                lambda a, b: ref.analog_tile(a, b, ramp)), x, w)
    elif kernel == "nladc":
        m, n = shape
        x = jnp.asarray(rng.normal(0, 2, (m, n)).astype(np.float32))
        got = ops.nladc(x, ramp, block=blocks)
        want = ref.nladc(x, ramp)
        us = _ref_us(jax.jit(lambda a: ref.nladc(a, ramp)), x)
    else:  # lstm_gates
        b, h = shape
        g = jnp.asarray(rng.normal(0, 1.5, (b, 4 * h)).astype(np.float32))
        c = jnp.asarray(rng.normal(0, 0.5, (b, h)).astype(np.float32))
        got = ops.lstm_gates(g, c, sig, tnh, block=blocks)
        want = ref.lstm_gates(g, c, sig, tnh)
        got = jnp.concatenate(got, axis=-1)
        want = jnp.concatenate(want, axis=-1)
        us = _ref_us(jax.jit(
            lambda a, b2: jnp.concatenate(
                ref.lstm_gates(a, b2, sig, tnh), axis=-1)), g, c)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    return {"blocks": list(blocks), "digest": _digest(got),
            "max_err_vs_ref": err, "ref_us": us}


def _parity_section(rng):
    """The new-path parity cells the gate enforces bitwise / in LSB."""
    ramp = build_ramp("swish", 5)
    adc = NLADC(ramp)
    lsb = float(ramp.lsb)
    out = {}

    # --- threshold fast path vs dense banked layout (bitwise) ---
    n, p_len = 256, int(np.asarray(ramp.thresholds).shape[0])
    bm = bank_map_for(n, BANK_COLS)
    thr = jnp.asarray(np.sort(rng.normal(0, 1, (bm.n_banks, p_len)),
                              axis=1).astype(np.float32))
    bt = BankedThresholds(thr, bm)
    x = jnp.asarray(rng.normal(0, 1.5, (32, n)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.3, (64, n)).astype(np.float32))
    xm = jnp.asarray(rng.normal(0, 0.5, (16, 64)).astype(np.float32))
    blocks = (256, BANK_COLS, 512)
    from repro.kernels.common import BlockRowThresholds
    assert isinstance(ops._resolve_thr(bt, n, BANK_COLS),
                      BlockRowThresholds), \
        "fast-path carrier not selected for the aligned bank layout"
    fast_n = ops.nladc(x, ramp, thresholds=bt, block=(256, BANK_COLS))
    fast_m = ops.fused_matmul_nladc(xm, w, ramp, thresholds=bt,
                                    blocks=blocks)
    os.environ["REPRO_KERNEL_FASTPATH"] = "0"
    try:
        dense_n = ops.nladc(x, ramp, thresholds=bt, block=(256, BANK_COLS))
        dense_m = ops.fused_matmul_nladc(xm, w, ramp, thresholds=bt,
                                         blocks=blocks)
    finally:
        del os.environ["REPRO_KERNEL_FASTPATH"]
    out["fastpath"] = {
        "bitwise_equal": bool(jnp.array_equal(fast_n, dense_n))
        and bool(jnp.array_equal(fast_m, dense_m)),
        "digest": _digest(fast_n, fast_m),
    }

    # --- fused MoE expert einsum vs ref backend (codes + STE grads) ---
    e_dim, c_dim, d_dim, f_dim = 4, 8, 64, n
    xe = jnp.asarray(rng.normal(0, 0.5,
                                (e_dim, c_dim, d_dim)).astype(np.float32))
    we = jnp.asarray(rng.normal(0, 0.3,
                                (e_dim, d_dim, f_dim)).astype(np.float32))
    pb, rb = BK.get_backend("pallas"), BK.get_backend("ref")
    y_p = pb.moe_matmul_nladc(xe, we, adc, bt)
    y_r = rb.moe_matmul_nladc(xe, we, adc, bt)
    g_p = jax.grad(lambda a: jnp.sum(pb.moe_matmul_nladc(a, we, adc,
                                                         bt)))(xe)
    g_r = jax.grad(lambda a: jnp.sum(rb.moe_matmul_nladc(a, we, adc,
                                                         bt)))(xe)
    out["moe_einsum"] = {
        "max_err_lsb": float(jnp.max(jnp.abs(y_p - y_r))) / lsb,
        "grad_max_err": float(jnp.max(jnp.abs(g_p - g_r))),
        "digest": _digest(y_p),
    }

    # --- Pallas cached attention vs attend_full (bitwise + grads) ---
    b, h, hkv, d, s = 3, 8, 2, 16, 24
    q = jnp.asarray(rng.normal(0, 1, (b, 1, h, d)).astype(np.float32))
    kc = jnp.asarray(rng.normal(0, 1, (b, s, hkv, d)).astype(np.float32))
    vc = jnp.asarray(rng.normal(0, 1, (b, s, hkv, d)).astype(np.float32))
    mask = (jnp.arange(s) < 17)[None, None, :]
    o_p = pb.prefill_attention(q, kc, vc, mask)
    o_r = rb.prefill_attention(q, kc, vc, mask)
    gq_p = jax.grad(lambda a: jnp.sum(pb.prefill_attention(a, kc, vc,
                                                           mask)))(q)
    gq_r = jax.grad(lambda a: jnp.sum(rb.prefill_attention(a, kc, vc,
                                                           mask)))(q)
    out["attention"] = {
        "bitwise_equal": bool(jnp.array_equal(o_p, o_r)),
        "grad_max_err": float(jnp.max(jnp.abs(gq_p - gq_r))),
        "digest": _digest(o_p),
    }
    return out


def run(quick=True):
    shapes = SHAPES_QUICK if quick else SHAPES_FULL
    ramp = build_ramp("sigmoid", 5)
    sig, tnh = build_ramp("sigmoid", 5), build_ramp("tanh", 5)
    print("=== kernel autotune sweep "
          f"({tune.platform()}/{tune.backend_mode()}) ===")
    cache = tune.autotune(shapes)
    cells = {}
    for kernel, shape_list in sorted(shapes.items()):
        for shape in shape_list:
            rng = np.random.default_rng(0)
            blocks = cache.lookup(kernel, shape)
            cell = _shape_cell(kernel, shape, blocks, ramp, sig, tnh, rng)
            key = f"{kernel}|" + "x".join(map(str, shape))
            cells[key] = cell
            print(f"  {key:42} blocks={tuple(blocks)}  "
                  f"err={cell['max_err_vs_ref']:.2e}  "
                  f"ref {cell['ref_us']:8.1f} us  "
                  f"digest {cell['digest']}")

    parity = _parity_section(np.random.default_rng(7))
    print(f"  fastpath bitwise: {parity['fastpath']['bitwise_equal']}   "
          f"moe err {parity['moe_einsum']['max_err_lsb']:.3f} LSB "
          f"(grad {parity['moe_einsum']['grad_max_err']:.1e})   "
          f"attention bitwise: {parity['attention']['bitwise_equal']}")

    results = {"quick": quick, "platform": tune.platform(),
               "backend_mode": tune.backend_mode(),
               "tune": cache.to_dict(), "shapes": cells, "parity": parity}
    if not quick or not os.path.exists(OUT_PATH):
        with open(OUT_PATH, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"  baseline written to {OUT_PATH}")
    return results


if __name__ == "__main__":
    run(quick=False)
