"""Trace reduction on a trace recorded on a TPU v5e: three decode steps
of qwen2.5-3b.decode-heavy (32 rows, context 129 to 131)."""

import os

import pytest

from bench import run as R
from bench import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "decode_steps.xplane.pb")
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def red():
    return T.reduce_file(DATA)


def _ctx(red, cell_name="qwen2.5-3b.decode-heavy"):
    cell = R.load_json("workloads", cell_name + ".json")
    config = R.load_json("configs", cell["config"] + ".json")
    steps = [R.StepRec(0.0, 0.0, [], [c] * 32, True) for c in (129, 130,
                                                                131)]
    return R.Context(cell=cell, cfg=R.reference_config(config), peak=PEAK,
                     n_params=3_085_938_688, setup_s=0.0,
                     window=(0.0, 1.0), requests=[], steps=steps, trace=red)


def test_window_and_busy(red):
    assert red["devices"] == ["/device:TPU:0"]
    assert red["window_s"] == pytest.approx(0.171364742)
    assert 0 < red["busy_s"] < red["window_s"]
    assert [p[0].split("(")[0] for p in red["programs"]] == \
        ["jit__decode_all"] * 3
    gaps = sum(g[1] for g in red["gaps"]) * 1e-9
    assert gaps == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert {g[2] for g in red["gaps"]} <= {"engine.step", "none"}


def test_breakdown_is_bounded(red):
    b = T.breakdown(red)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert sum(v for _, v in b["device_ops"]) <= red["busy_s"] + 1e-9


def test_kernels_found_by_signature(red):
    fused = R.load_module("metrics", "fused_matmul_nladc_roofline")
    attn = R.load_module("metrics", "prefill_attention_roofline")
    assert len(T.kernel_events(red, fused.is_kernel)) == 3 * 36
    assert len(T.kernel_events(red, attn.is_kernel)) == 3 * 36
    ops, res, *_ = T.kernel_events(red, fused.is_kernel)[0]
    # XLA staged every operand into vector memory before the call
    assert ops == [("bf16", (256, 2048), True), ("bf16", (2048, 11008), True),
                   ("f32", (32,), True)]
    assert res == ("bf16", (256, 11008), True)


def test_staging_ops_are_charged_to_their_kernel(red):
    fused = R.load_module("metrics", "fused_matmul_nladc_roofline")
    attn = R.load_module("metrics", "prefill_attention_roofline")
    # the first MLP gate call: its layer's weight sliced out of the
    # stacked weights, the padded rows, the thresholds' copy; not the
    # norm that computed the rows
    first = T.kernel_events(red, fused.is_kernel)[:1]
    names = [n for n, _ in T.staged_ops(red, first)]
    assert sorted(names) == sorted(["dynamic-slice_bitcast_fusion.8",
                                    "pad.35", "copy-done.1",
                                    "copy-start.1"])
    assert T.staged_seconds(red, first) == pytest.approx(60.7e-6, rel=0.02)
    # the first attention call: K and V sliced out of the cache, the new
    # position written, relaid out; the query's copy and the mask
    first = T.kernel_events(red, attn.is_kernel)[:1]
    names = {n for n, _ in T.staged_ops(red, first)}
    assert {"dynamic-slice_bitcast_fusion.6", "reshape.316",
            "dynamic-slice_bitcast_fusion.7", "reshape.317",
            "broadcast.222"} <= names
    assert not {"bitcast_add_fusion.7", "fusion.122"} & names
    # every call of a step is charged once
    ev = T.kernel_events(red, fused.is_kernel)
    assert len(T.staged_ops(red, ev)) == 4 * len(ev)


@pytest.mark.parametrize("name", ["fused_matmul_nladc_roofline",
                                  "prefill_attention_roofline",
                                  "decode_step_ms", "decode_mfu",
                                  "idle_share.throughput"])
def test_trace_metrics_read(red, name):
    value = R.load_module("metrics", name).read(_ctx(red))
    assert value is not None and value > 0
    if name.endswith(("_roofline", "_mfu")) or name.startswith("idle"):
        assert value < 100


def test_prefill_metrics_find_nothing_here(red):
    ctx = _ctx(red)
    for name in ("prefill_ms_per_token", "prefill_mfu"):
        assert R.load_module("metrics", name).read(ctx) is None


def test_attention_call_count_must_match(red):
    ctx = _ctx(red)
    ctx.steps = ctx.steps[:2]
    with pytest.raises(ValueError):
        R.load_module("metrics", "prefill_attention_roofline").read(ctx)
