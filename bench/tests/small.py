"""The benchmark's cells cut to CPU size for its tests: the same
harness, generators, references and checks, at the programs' smoke
widths and a few requests."""

from __future__ import annotations

import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOKE_MODEL = {
    "qwen2.5-3b": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                   "n_kv_heads": 2, "head_dim": 16, "d_ff": 160,
                   "vocab": 256, "vocab_pad_multiple": 8},
    "mamba2-370m": {"n_layers": 2, "d_model": 64, "ssm_state": 16,
                    "ssm_headdim": 16, "ssm_chunk": 16, "vocab": 256,
                    "vocab_pad_multiple": 8},
}

SMOKE_CELL = {
    "qwen2.5-3b.decode-heavy": {
        "traffic": {"clients": 4, "prompt_len": 16, "max_new_tokens": 12},
        "engine": {"max_batch": 4, "max_len": 32, "prefill_buckets": [16]},
        "check": {"requests": 4, "min_tokens": 24,
                  "top_k": 1, "min_agree": 0.75}},
    "qwen2.5-3b.long-prompt": {
        "traffic": {"clients": 2, "prompt_len": 24, "max_new_tokens": 8},
        "engine": {"max_batch": 2, "max_len": 32, "prefill_buckets": [16]},
        "check": {"requests": 4, "min_tokens": 32,
                  "top_k": 1, "min_agree": 0.75}},
    "mamba2-370m.chat-bursty": {
        "traffic": {"rate_rps": 40.0, "pool_size": 64,
                    "prompt": {"median": 6, "sigma": 0.8, "min": 2,
                               "max": 16},
                    "output": {"median": 10, "sigma": 0.3, "min": 8,
                               "max": 14}},
        "engine": {"max_batch": 4, "max_len": 32,
                   "prefill_buckets": [4, 8, 16]},
        "check": {"requests": 4, "min_tokens": 32,
                  "top_k": 1, "min_agree": 0.75}},
}


# Larger weights than the chip's cells: at d_model 64 the 0.02 of a
# published init leaves the context almost no say in the next token, and
# a fault that loses the context would go unseen.
SMOKE_WEIGHTS = {"init_std": 0.25, "bias_std": 0.25}


def load(cell_name: str):
    """-> (cell, config) of a benchmark cell at CPU size."""
    with open(os.path.join(BENCH, "workloads", cell_name + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(BENCH, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    config = copy.deepcopy(config)
    config["model"].update(SMOKE_MODEL[cell["config"]])
    config["weights"].update(SMOKE_WEIGHTS)
    for key, upd in SMOKE_CELL[cell_name].items():
        cell[key].update(upd)
    cell["trace"] = {"start_s": 0.0, "seconds": 1.0, "phase": "wave"}
    return cell, config
