"""The FLOP and byte counts behind the share metrics, worked by hand."""

import pytest

from bench import modelflops as MF
from bench import run as R

QWEN = {"n_layers": 36, "n_heads": 16, "head_dim": 128, "n_kv_heads": 2}
MAMBA = {"n_layers": 48, "n_heads": 0, "head_dim": 0}


def test_token_flops_dense_and_ssm():
    # 2 per weight, plus 4 H hd c per attention layer
    assert MF.token_flops(QWEN, 1000, 10) == 2000 + 36 * 4 * 16 * 128 * 10
    assert MF.token_flops(MAMBA, 1000, 10) == 2000


def test_prompt_flops_sum_token_flops():
    n = 37
    want = sum(MF.token_flops(QWEN, 10**9, c) for c in range(1, n + 1))
    assert MF.prompt_flops(QWEN, 10**9, n) == pytest.approx(want)


def test_prefill_steps_mask_rows_and_chunk():
    steps = list(MF.prefill_steps([4, 8], [3, 1]))
    # bucket 4 covers length 3: four scan steps, rows live while t < len
    assert steps == [[1, 1], [2], [3], []]
    # longer than every bucket: a largest-bucket chunk, then the rest
    steps = list(MF.prefill_steps([4, 8], [10]))
    assert len(steps) == 8 + 4 and steps[9] == [10] and steps[10] == []


def test_fused_matmul_cost():
    m = R.load_module("metrics", "fused_matmul_nladc_roofline")
    flops, nbytes = m.call_cost(32, 2048, 11008, 2)
    assert flops == 2 * 32 * 2048 * 11008
    assert nbytes == 2 * (32 * 2048 + 2048 * 11008 + 32 * 11008)
    # bound by bytes on a v5e: 45 MB at 819 GB/s is about 56 us
    assert nbytes / 819e9 > flops / 197e12
    assert nbytes / 819e9 == pytest.approx(56.3e-6, rel=0.01)


def test_attention_cost_counts_live_positions_only():
    m = R.load_module("metrics", "prefill_attention_roofline")
    flops, nbytes = m.call_cost(QWEN, [100, 28])
    assert flops == 4 * 16 * 128 * 128
    assert nbytes == 2 * (2 * 128 * 2 * 128 + 2 * 2 * 16 * 128)
    assert m.call_cost(QWEN, []) == (0.0, 0.0)
