"""The Moonlight cell at CPU size: its reference against the engine, the
control and the planted faults failing the check, the configuration's
catalog numbers against the program's, the reference's sigmoid ramp,
and the cell's two readers on a hand-built trace and counter log."""

import contextlib
import copy
import time

import pytest

from bench import run as R
from bench.tests import faults

NAME = "moonlight-16b-a3b.decode-heavy"
SECONDS = 3.0
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# the program's smoke widths, 2 of 8 experts held from the third on;
# larger weights than the chip's, as for the other cells at CPU size
SMOKE_MODEL = {"n_layers": 3, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
               "head_dim": 16, "kv_lora_rank": 32, "qk_rope_head_dim": 8,
               "d_ff": 32, "dense_d_ff": 96, "n_experts": 8,
               "experts_held": 2, "expert_offset": 2, "top_k": 2,
               "n_shared_experts": 1, "vocab": 256, "vocab_pad_multiple": 8}
SMOKE_CELL = {
    "traffic": {"clients": 4, "prompt_len": 16, "max_new_tokens": 24},
    "engine": {"max_batch": 4, "max_len": 48, "prefill_buckets": [16]},
    "check": {"requests": 4, "min_tokens": 48, "top_k": 1,
              "min_agree": 0.6}}


def load():
    cell = R.load_json("workloads", NAME + ".json")
    config = copy.deepcopy(R.load_json("configs", cell["config"] + ".json"))
    config["model"].update(SMOKE_MODEL)
    config["weights"].update({"init_std": 0.25})
    for key, upd in SMOKE_CELL.items():
        cell[key].update(upd)
    cell["trace"] = {"start_s": 0.0, "seconds": 1.0, "phase": "wave"}
    return cell, config


def _run(seed, fault=None):
    cell, config = load()
    ctx = faults.FAULTS[fault]() if fault else contextlib.nullcontext()
    with ctx:
        return R.run(NAME, cell, config, seed, SECONDS, False,
                     t_start=time.perf_counter(), log=lambda m: None)


def test_served_tokens_agree_with_reference():
    res = _run(11)
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}


def test_control_fails_the_check():
    cell, config = load()
    cell["check"]["requests"] = 16
    s = R.Session(cell, config, 11, log=lambda m: None)
    traffic, records, _, _, _ = s.window(11, SECONDS, False)
    _, got = s.check(records, 11, traffic, control="fp8")
    chk = cell["check"]
    assert R.check_numbers(got["fp8@bf16"], chk)["agree_min"]["value"] \
        < chk["min_agree"] \
        <= R.check_numbers(got["bf16"], chk)["agree_min"]["value"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_fails_the_check(fault):
    res = _run(11, fault)
    assert not res["correct"], res["check"]


def test_catalog_numbers_are_the_programs():
    """The configuration file's catalog keys hold what the program runs;
    the one key cut, the experts held, states the published count."""
    config = R.load_json("configs", "moonlight-16b-a3b.json")
    cfg = R.program_config(config)
    assert config["reduced"] == ["n_routed_experts"]
    assert config["published"]["n_routed_experts"] == cfg.n_experts == 64
    want = {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "qk_nope_head_dim": cfg.head_dim, "v_head_dim": cfg.head_dim,
            "intermediate_size": cfg.dense_d_ff,
            "moe_intermediate_size": cfg.d_ff,
            "first_k_dense_replace": cfg.n_dense_layers,
            "n_routed_experts": cfg.experts_held,
            "n_shared_experts": cfg.n_shared_experts,
            "num_experts_per_tok": cfg.top_k,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "scoring_func": cfg.router_score, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "vocab_size": cfg.vocab,
            "tie_word_embeddings": cfg.tie_embeddings,
            "hidden_act": cfg.hidden_act}
    assert {k: config[k] for k in want} == want
    assert config["q_lora_rank"] is None and config["n_group"] == 1
    assert config["topk_method"] == "noaux_tc" and config["norm_topk_prob"]


def test_reference_sigmoid_ramp_is_the_programs():
    import numpy as np

    from bench.reference import moonlight as REF
    from repro.core.nladc import build_ramp

    thr, y = REF.sigmoid_ramp(5)
    ramp = build_ramp("sigmoid", 5)
    np.testing.assert_allclose(thr, ramp.thresholds, rtol=0, atol=1e-12)
    np.testing.assert_allclose(y, ramp.y_table, rtol=0, atol=1e-12)


def _op(i, rows, heads, width, rank, positions):
    return (f"%mla_attention.{i} = bf16[{rows},{heads},{rank}]"
            f"{{2,1,0:T(8,128)(2,1)}} custom-call(bf16[{rows},{heads},"
            f"{width}]{{2,1,0}} %q.{i}, bf16[{rows},{width},{positions}]"
            f"{{2,1,0}} %c.{i}, s32[{rows},1,{positions}]{{2,1,0}} %m.{i}),"
            f' custom_call_target="tpu_custom_call"')


def _ctx(ops, steps, window=(0.0, 1.0)):
    cell = R.load_json("workloads", NAME + ".json")
    config = R.load_json("configs", cell["config"] + ".json")
    red = {"ops": ops, "programs": [], "annotations": []}
    return R.Context(cell=cell, cfg=R.reference_config(config), peak=PEAK,
                     n_params=3_364_615_296, setup_s=0.0, window=window,
                     requests=[], steps=steps, trace=red)


def test_mla_roofline_by_hand():
    """One decode step of two live rows (contexts 100 and 28) over 27
    layers, each call 10 us: the bytes bound it (q, the live latent rows
    and the output), 265 ns a call, so the share is 2.65%."""
    m = R.load_module("metrics", "mla_decode_roofline")
    ops = [(_op(i, 64, 16, 576, 512, 768), 1000.0 * i, 10_000.0, "tpu0")
           for i in range(27)]
    steps = [R.StepRec(0.0, 0.0, [], [100, 28], True),
             R.StepRec(0.0, 0.0, [], [7] * 64, False)]
    flops = 2 * 16 * (576 + 512) * 128
    nbytes = 2 * (576 * 128 + 2 * (16 * 576 + 16 * 512))
    cfg = _ctx([], []).cfg
    assert m.call_cost(cfg, [100, 28]) == (flops, nbytes)
    want = 100.0 * max(flops / 197e12, nbytes / 819e9) / 10e-6
    assert m.read(_ctx(ops, steps)) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(2.6506, rel=1e-4)
    with pytest.raises(ValueError):
        m.read(_ctx(ops[:26], steps))


def test_kernel_signatures_stay_apart():
    """The MLA kernel is not the one-query GQA kernel nor the fused
    matmul, and they are not it."""
    from bench import trace as T

    mla = R.load_module("metrics", "mla_decode_roofline").is_kernel
    gqa = R.load_module("metrics", "prefill_attention_roofline").is_kernel
    fused = R.load_module("metrics", "fused_matmul_nladc_roofline").is_kernel
    ops = T.custom_call_operands(_op(0, 64, 16, 576, 512, 768))
    assert mla(ops) and not gqa(ops) and not fused(ops)
    four = ops + [("s32", (64, 1, 768), False)]
    two_d = [("bf16", (32, 2048), False), ("bf16", (2048, 11008), False),
             ("f32", (32,), False)]
    assert not mla(four) and not mla(two_d)


def test_local_share_reads_the_window():
    from repro.obs import MetricsRegistry
    from repro.obs import trace as otrace

    m = R.load_module("metrics", "moe_local_share")
    reg = MetricsRegistry()
    otrace.count(reg, **{otrace.MOE_PAIRS_ROUTED: 60,
                         otrace.MOE_PAIRS_LOCAL: 30})
    t0 = time.perf_counter()
    for local in (70, 80):
        otrace.count(reg, **{otrace.MOE_PAIRS_ROUTED: 600,
                             otrace.MOE_PAIRS_LOCAL: local})
    t1 = time.perf_counter()
    otrace.count(reg, **{otrace.MOE_PAIRS_ROUTED: 60,
                         otrace.MOE_PAIRS_LOCAL: 60})
    assert m.read(_ctx([], [], window=(t0, t1))) == pytest.approx(12.5)
    assert m.read(_ctx([], [], window=(t1, t1))) is None
    assert reg.find(otrace.MOE_PAIRS_ROUTED).value == 1320
