"""Pallas kernels vs pure-jnp oracles: shape/dtype/activation sweeps."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import core

from repro.core.nladc import build_ramp, nladc_reference
from repro.kernels import ops, ref

SHAPES_2D = [(8, 8), (70, 130), (256, 512), (257, 513), (1, 640)]
ACTS = ["sigmoid", "tanh", "softplus", "elu", "selu", "gelu", "swish"]


@pytest.mark.parametrize("name", ACTS)
@pytest.mark.parametrize("shape", SHAPES_2D[:3])
def test_nladc_kernel_sweep(name, shape, rng):
    ramp = build_ramp(name, 5)
    x = jnp.asarray(rng.normal(0, 2, shape).astype(np.float32))
    np.testing.assert_allclose(ops.nladc(x, ramp), ref.nladc(x, ramp),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits", [3, 4, 5, 8])
def test_nladc_kernel_bits(bits, rng):
    ramp = build_ramp("sigmoid", bits)
    x = jnp.asarray(rng.normal(0, 2, (64, 257)).astype(np.float32))
    np.testing.assert_allclose(ops.nladc(x, ramp), ref.nladc(x, ramp),
                               rtol=1e-5, atol=1e-5)


def test_nladc_kernel_matches_table_oracle(rng):
    """Closed-form kernel decode == y_table-lookup core oracle."""
    for name in ACTS:
        ramp = build_ramp(name, 5)
        x = rng.normal(0, 2, (33, 65)).astype(np.float32)
        got = np.asarray(ops.nladc(jnp.asarray(x), ramp))
        want = nladc_reference(x, ramp)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mkn", [(16, 32, 8), (37, 100, 67), (256, 512, 256),
                                 (129, 300, 140)])
def test_fused_matmul_sweep(mkn, dtype, rng):
    m, k, n = mkn
    ramp = build_ramp("swish", 5)
    x = jnp.asarray(rng.normal(0, 0.4, (m, k)).astype(np.float32), dtype)
    w = jnp.asarray(rng.normal(0, 0.2, (k, n)).astype(np.float32), dtype)
    got = ops.fused_matmul_nladc(x, w, ramp)
    want = ref.fused_matmul_nladc(x, w, ramp)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2, atol=2e-2)


def _pallas_eqn(fn, *args):
    """The one ``pallas_call`` in ``fn``'s jaxpr."""
    eqns = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
            if e.primitive.name == "pallas_call"]
    assert len(eqns) == 1, eqns
    return eqns[0]


def _kernel_dot_dtypes(eqn):
    """Operand dtypes of every dot in the kernel body."""
    out, todo = [], [eqn.params["jaxpr"]]
    while todo:
        jaxpr = todo.pop()
        for e in jaxpr.eqns:
            if e.primitive.name == "dot_general":
                out.append(tuple(v.aval.dtype for v in e.invars))
            todo.extend(core.jaxprs_in_params(e.params))
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m", [1, 16, 32, 37])
def test_fused_matmul_decode_shapes(m, dtype, rng):
    """Decode-sized calls take the skinny plan: rows padded to x's sublane
    tile (not to 256), all of K in one step, bf16 operands dotted as
    stored; the result matches the oracle at the sweep's tolerance."""
    k, n = 2048, 640
    ramp = build_ramp("swish", 5)
    x = jnp.asarray(rng.normal(0, 0.4, (m, k)).astype(np.float32), dtype)
    w = jnp.asarray(rng.normal(0, 0.2, (k, n)).astype(np.float32), dtype)
    got = ops.fused_matmul_nladc(x, w, ramp)
    want = ref.fused_matmul_nladc(x, w, ramp)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2, atol=2e-2)
    sublanes = 16 if dtype == jnp.bfloat16 else 8
    eqn = _pallas_eqn(lambda a, b: ops.fused_matmul_nladc(a, b, ramp), x, w)
    assert eqn.invars[0].aval.shape == (-(-m // sublanes) * sublanes, k)
    assert eqn.params["grid_mapping"].grid == (1, 3, 1)   # n 640, bn 256
    assert _kernel_dot_dtypes(eqn) == [(jnp.dtype(dtype),) * 2]


def test_fused_matmul_plan_blocks():
    """From 256 rows the historical DEFAULT_BLOCKS tile the call; below,
    the skinny plan (the decode call: 32 rows, all of K in one step)."""
    fm = importlib.import_module("repro.kernels.fused_matmul_nladc")
    assert fm.plan_blocks(256, 2048, 11008, jnp.bfloat16, jnp.bfloat16) \
        == fm.DEFAULT_BLOCKS
    assert fm.plan_blocks(4096, 2048, 11008, jnp.float32, jnp.float32) \
        == fm.DEFAULT_BLOCKS
    assert fm.plan_blocks(32, 2048, 11008, jnp.bfloat16, jnp.bfloat16) \
        == (32, 256, 2048)
    # a (K, bn) tile past the budget keeps the default bk
    assert fm.plan_blocks(32, 16384, 256, jnp.float32, jnp.float32)[2] \
        == fm.DEFAULT_BLOCKS[2]


def test_fused_matmul_mixed_dtypes_keep_f32_dot(rng):
    """A bf16 x against an f32 W is dotted in f32: W is never rounded to
    bf16, so the result is the f32 oracle's."""
    ramp = build_ramp("swish", 5)
    x = jnp.asarray(rng.normal(0, 0.4, (32, 512)).astype(np.float32),
                    jnp.bfloat16)
    w = jnp.asarray(rng.normal(0, 0.2, (512, 256)).astype(np.float32))
    eqn = _pallas_eqn(lambda a, b: ops.fused_matmul_nladc(a, b, ramp), x, w)
    assert _kernel_dot_dtypes(eqn) == [(jnp.dtype(jnp.float32),) * 2]
    np.testing.assert_allclose(
        np.asarray(ops.fused_matmul_nladc(x, w, ramp), np.float32),
        np.asarray(ref.fused_matmul_nladc(x, w, ramp), np.float32),
        rtol=1e-2, atol=2e-2)


def test_fused_matmul_batch_dims(rng):
    ramp = build_ramp("sigmoid", 5)
    x = jnp.asarray(rng.normal(0, 0.4, (2, 3, 40)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.2, (40, 24)).astype(np.float32))
    got = ops.fused_matmul_nladc(x, w, ramp)
    want = ref.fused_matmul_nladc(x.reshape(-1, 40), w, ramp).reshape(2, 3, 24)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits_in", [3, 5, None])
def test_analog_tile_sweep(bits_in, rng):
    ramp = build_ramp("tanh", 5)
    x = jnp.asarray(rng.normal(0, 0.5, (50, 72)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.2, (72, 128)).astype(np.float32))
    nz = jnp.asarray(rng.normal(0, 2.67 / 75, (72, 128)).astype(np.float32))
    got = ops.analog_tile(x, w, ramp, input_bits=bits_in, w_noise=nz)
    want = ref.analog_tile(x, w, ramp, input_bits=bits_in, w_noise=nz)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bh", [(4, 32), (33, 50), (64, 2016)])
def test_lstm_gates_sweep(bh, rng):
    b, h = bh
    sig, tnh = build_ramp("sigmoid", 5), build_ramp("tanh", 5)
    g = jnp.asarray(rng.normal(0, 1.5, (b, 4 * h)).astype(np.float32))
    c = jnp.asarray(rng.normal(0, 0.5, (b, h)).astype(np.float32))
    h1, c1 = ops.lstm_gates(g, c, sig, tnh)
    h2, c2 = ref.lstm_gates(g, c, sig, tnh)
    np.testing.assert_allclose(h1, h2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(c1, c2, rtol=1e-5, atol=1e-5)


def test_lstm_gates_matches_analog_lstm_cell(rng):
    """Kernel tail == nn.lstm cell (exact mode) given identical gates."""
    import jax
    from repro.core.analog_layer import AnalogConfig
    from repro.nn import lstm as NN

    spec = NN.LSTMSpec(n_in=8, n_hidden=16,
                       analog=AnalogConfig(enabled=True, adc_bits=5,
                                           input_bits=None, mode="exact"))
    acts = NN.make_gate_acts(spec.analog)
    p = NN.lstm_init(jax.random.PRNGKey(0), spec)
    x = jnp.asarray(rng.normal(0, 1, (4, 8)).astype(np.float32))
    hprev = jnp.zeros((4, 16), jnp.float32)
    c = jnp.asarray(rng.normal(0, 0.5, (4, 16)).astype(np.float32))
    h_nn, c_nn = NN.lstm_cell(p, x, hprev, c, spec, acts)
    gates = jnp.concatenate([x, hprev], -1) @ p["w_gates"]
    sig, tnh = build_ramp("sigmoid", 5), build_ramp("tanh", 5)
    h_k, c_k = ops.lstm_gates(gates, c, sig, tnh)
    np.testing.assert_allclose(h_nn, h_k, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(c_nn, c_k, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("cfg", [(2, 8, 2, 32, 100), (1, 16, 1, 128, 513),
                                 (3, 4, 4, 64, 256)])
def test_flash_decode_int8_sweep(cfg, rng):
    """Flash-decode kernel (fused int8 dequant) vs the dequantize-all oracle."""
    b, h, hkv, d, s_len = cfg
    q = jnp.asarray(rng.normal(0, 1, (b, h, d)).astype(np.float32))
    k8 = jnp.asarray(rng.integers(-127, 128, (b, s_len, hkv, d)), jnp.int8)
    v8 = jnp.asarray(rng.integers(-127, 128, (b, s_len, hkv, d)), jnp.int8)
    ks = jnp.asarray(rng.uniform(1e-3, 2e-2, (b, s_len, hkv))
                     .astype(np.float32))
    vs = jnp.asarray(rng.uniform(1e-3, 2e-2, (b, s_len, hkv))
                     .astype(np.float32))
    ln = jnp.asarray(rng.integers(1, s_len, (b,)), jnp.int32)
    got = ops.flash_decode_int8(q, k8, ks, v8, vs, ln)
    want = ref.flash_decode_int8(q, k8, ks, v8, vs, ln)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# PR 10 kernels: threshold fast path, fused MoE einsum, cached attention
# ---------------------------------------------------------------------------

def _aligned_banked(rng, n_cols, bank_cols, p_len):
    from repro.core.nladc import BankedThresholds, bank_map_for

    bm = bank_map_for(n_cols, bank_cols)
    thr = np.sort(rng.normal(0, 1, (bm.n_banks, p_len)), axis=1)
    return BankedThresholds(jnp.asarray(thr, jnp.float32), bm)


@pytest.mark.parametrize("bank_cols,bn", [(128, 128), (256, 128), (128, 64)])
def test_threshold_fastpath_bitwise(bank_cols, bn, rng):
    """(P,) bank-row fast path == dense (bn, P) banked layout, BITWISE,
    whenever bank_cols is a multiple of the lane block."""
    import os

    from repro.kernels.common import BlockRowThresholds

    ramp = build_ramp("swish", 5)
    n = 512
    bt = _aligned_banked(rng, n, bank_cols,
                         int(np.asarray(ramp.thresholds).shape[0]))
    assert isinstance(ops._resolve_thr(bt, n, bn), BlockRowThresholds)
    x = jnp.asarray(rng.normal(0, 1.5, (24, n)).astype(np.float32))
    xm = jnp.asarray(rng.normal(0, 0.5, (16, 48)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.3, (48, n)).astype(np.float32))

    fast_n = ops.nladc(x, ramp, thresholds=bt, block=(128, bn))
    fast_m = ops.fused_matmul_nladc(xm, w, ramp, thresholds=bt,
                                    blocks=(128, bn, 64))
    os.environ["REPRO_KERNEL_FASTPATH"] = "0"
    try:
        assert not isinstance(ops._resolve_thr(bt, n, bn),
                              BlockRowThresholds)
        dense_n = ops.nladc(x, ramp, thresholds=bt, block=(128, bn))
        dense_m = ops.fused_matmul_nladc(xm, w, ramp, thresholds=bt,
                                         blocks=(128, bn, 64))
    finally:
        del os.environ["REPRO_KERNEL_FASTPATH"]
    np.testing.assert_array_equal(np.asarray(fast_n), np.asarray(dense_n))
    np.testing.assert_array_equal(np.asarray(fast_m), np.asarray(dense_m))


def test_threshold_fastpath_requires_alignment(rng):
    """bank_cols NOT a multiple of the lane block -> dense layout (the
    fast path must never trigger on misaligned banks)."""
    from repro.kernels.common import BlockRowThresholds

    ramp = build_ramp("sigmoid", 5)
    bt = _aligned_banked(rng, 512, 96,
                         int(np.asarray(ramp.thresholds).shape[0]))
    resolved = ops._resolve_thr(bt, 512, 128)
    assert not isinstance(resolved, BlockRowThresholds)


def test_moe_fused_matmul_vs_expert_loop(rng):
    """Vmapped fused MoE einsum == per-expert fused_matmul_nladc calls."""
    ramp = build_ramp("swish", 5)
    e, c, d, f = 3, 8, 32, 48
    x = jnp.asarray(rng.normal(0, 0.5, (e, c, d)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.3, (e, d, f)).astype(np.float32))
    got = ops.moe_fused_matmul(x, w, ramp)
    want = jnp.stack([ops.fused_matmul_nladc(x[i], w[i], ramp)
                      for i in range(e)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prefill_attention_vs_attend_full(rng):
    """Pallas cached-attention kernel == attend_full, bitwise."""
    from repro.nn.attention import attend_full

    b, h, hkv, d, s = 2, 8, 2, 16, 20
    q = jnp.asarray(rng.normal(0, 1, (b, 1, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (b, s, hkv, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (b, s, hkv, d)).astype(np.float32))
    for valid in (1, 7, s):
        mask = (jnp.arange(s) < valid)[None, None, :]
        got = ops.prefill_attention(q, k, v, mask)
        want = attend_full(q, k, v, mask)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prefill_attention_mha_no_gqa(rng):
    """h == h_kv (no grouping) also matches bitwise."""
    from repro.nn.attention import attend_full

    b, h, d, s = 1, 4, 8, 9
    q = jnp.asarray(rng.normal(0, 1, (b, 1, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (b, s, h, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (b, s, h, d)).astype(np.float32))
    mask = (jnp.arange(s) < 5)[None, None, :]
    np.testing.assert_array_equal(
        np.asarray(ops.prefill_attention(q, k, v, mask)),
        np.asarray(attend_full(q, k, v, mask)))
