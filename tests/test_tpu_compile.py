"""The main-path Pallas kernels compile for a TPU v5e at qwen2.5-3b widths.

Nothing runs: each kernel is lowered through its ``repro.kernels.ops``
wrapper and compiled by the TPU compiler for a described (not attached)
``v5e:2x2`` chip, which refuses what interpret mode accepts (blocks that
break the tiling, layouts Mosaic cannot match, too much VMEM).  Widths are
qwen2.5-3b's: d_model 2048, d_ff 11008, 16 query heads over 2 KV heads of
128, bf16 activations, a 5-bit ramp (P = 32 comparator levels).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every xdist
worker imports this file.  These are the only tests that describe it.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.nladc import BankedThresholds, bank_map_for, build_ramp
from repro.kernels import ops

D_MODEL, D_FF = 2048, 11008
N_HEADS, N_KV, HEAD_DIM = 16, 2, 128
DECODE_ROWS = 4                 # a small decode batch
# fused matmul rows: small and the benchmark engine's max_batch (32) take
# the skinny block plan, 4096 (prefill, training) the 256-row default
MATMUL_ROWS = [4, 32, 4096]
BITS = 5


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled(monkeypatch, one_chip):
    """Steer the wrappers to the TPU lowering (this process's platform is
    the CPU, where they would pick interpret mode) and return a
    compile-for-the-chip helper: (fn, *shapes) -> Compiled."""
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        c = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in c.as_text()    # a Mosaic kernel, not XLA
        return c

    return compile_


def _ramp():
    return build_ramp("silu", BITS)


def _p():
    return int(np.asarray(_ramp().thresholds).shape[0])


@pytest.mark.parametrize("rows", MATMUL_ROWS)
@pytest.mark.parametrize("w_dtype", [jnp.float32, jnp.bfloat16])
def test_fused_matmul_nladc_shared_ramp(compiled, w_dtype, rows):
    """The MLP gate with the NL-ADC epilogue, one shared ramp."""
    ramp = _ramp()
    compiled(lambda x, w: ops.fused_matmul_nladc(x, w, ramp),
             ((rows, D_MODEL), jnp.bfloat16),
             ((D_MODEL, D_FF), w_dtype))


@pytest.mark.parametrize("rows", MATMUL_ROWS)
def test_fused_matmul_nladc_bias(compiled, rows):
    ramp = _ramp()
    compiled(lambda x, w, b: ops.fused_matmul_nladc(x, w, ramp, bias=b),
             ((rows, D_MODEL), jnp.bfloat16),
             ((D_MODEL, D_FF), jnp.float32), ((D_FF,), jnp.float32))


@pytest.mark.parametrize("rows", MATMUL_ROWS)
@pytest.mark.parametrize("bank_cols", [512, 96],
                         ids=["fast_path", "dense_banked"])
def test_fused_matmul_nladc_banked(compiled, bank_cols, rows):
    """Threshold banks: 512-column col-tiles take the (P,) bank-row fast
    path; 96 does not divide the lane block and keeps the (bn, P) layout."""
    ramp = _ramp()
    bm = bank_map_for(D_FF, bank_cols)

    def fn(x, w, thr):
        return ops.fused_matmul_nladc(x, w, ramp,
                                      thresholds=BankedThresholds(thr, bm))

    compiled(fn, ((rows, D_MODEL), jnp.bfloat16),
             ((D_MODEL, D_FF), jnp.float32), ((bm.n_banks, _p()), jnp.float32))


@pytest.mark.parametrize("bank_cols", [0, 512], ids=["shared", "fast_path"])
def test_nladc(compiled, bank_cols):
    ramp = _ramp()
    if not bank_cols:
        compiled(lambda x: ops.nladc(x, ramp), ((256, D_FF), jnp.bfloat16))
        return
    bm = bank_map_for(D_FF, bank_cols)
    compiled(lambda x, thr: ops.nladc(x, ramp,
                                      thresholds=BankedThresholds(thr, bm)),
             ((256, D_FF), jnp.bfloat16), ((bm.n_banks, _p()), jnp.float32))


@pytest.mark.parametrize("s_len", [128, 4096])
def test_prefill_attention(compiled, s_len):
    """One-query cached attention, as every decode step calls it."""
    compiled(lambda q, k, v, m: ops.prefill_attention(q, k, v, m),
             ((DECODE_ROWS, 1, N_HEADS, HEAD_DIM), jnp.bfloat16),
             ((DECODE_ROWS, s_len, N_KV, HEAD_DIM), jnp.bfloat16),
             ((DECODE_ROWS, s_len, N_KV, HEAD_DIM), jnp.bfloat16),
             ((1, 1, s_len), jnp.bool_))


def test_flash_decode_int8(compiled):
    s_len = 4096
    compiled(ops.flash_decode_int8,
             ((DECODE_ROWS, N_HEADS, HEAD_DIM), jnp.bfloat16),
             ((DECODE_ROWS, s_len, N_KV, HEAD_DIM), jnp.int8),
             ((DECODE_ROWS, s_len, N_KV), jnp.bfloat16),
             ((DECODE_ROWS, s_len, N_KV, HEAD_DIM), jnp.int8),
             ((DECODE_ROWS, s_len, N_KV), jnp.bfloat16),
             ((DECODE_ROWS,), jnp.int32))


def test_lstm_gates(compiled):
    sig, tanh = build_ramp("sigmoid", BITS), build_ramp("tanh", BITS)
    compiled(lambda g, c: ops.lstm_gates(g, c, sig, tanh),
             ((256, 4 * 512), jnp.float32), ((256, 512), jnp.float32))
