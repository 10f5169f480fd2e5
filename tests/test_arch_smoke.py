"""Per-architecture smoke tests (deliverable f): reduced same-family config,
one forward + one train step on CPU, asserting shapes + no NaNs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.steps import build_all, make_optimizer
from repro.nn.frontends import audio_frame_stub, vision_patch_stub

ARCHS = list(configs.ARCH_NAMES[:10])


def _batch_for(cfg, b, s, rng):
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
    }
    if cfg.modality == "vision":
        batch["patch_embeds"] = vision_patch_stub(
            jax.random.PRNGKey(5), b, cfg.n_patches, cfg.d_model)
    if cfg.modality == "audio":
        batch["frames"] = audio_frame_stub(
            jax.random.PRNGKey(5), b, cfg.enc_len, cfg.d_model)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_train_step(arch):
    rng = np.random.default_rng(0)
    cfg = configs.get_smoke(arch)
    model, train_step, prefill_step, serve_step = build_all(cfg)
    opt = make_optimizer(cfg, total_steps=10)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    b, s = 2, 16
    batch = _batch_for(cfg, b, s, rng)

    # forward
    extra = {k: v for k, v in batch.items() if k not in ("tokens", "labels")}
    logits = model.forward(params, batch["tokens"], extra or None)
    assert logits.shape == (b, s, cfg.padded_vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))

    # one jitted train step
    new_params, new_opt, metrics = jax.jit(train_step)(
        params, opt_state, batch, 0)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, x: a + float(jnp.sum(jnp.abs(x[0] - x[1]))),
        jax.tree.map(lambda a, b_: (a, b_), params, new_params), 0.0)
    assert delta > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_serve_step(arch):
    rng = np.random.default_rng(1)
    cfg = configs.get_smoke(arch)
    model, _, _, serve_step = build_all(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = 2
    state = model.init_decode_state(b, max_len=32)
    if cfg.family == "encdec":
        frames = audio_frame_stub(jax.random.PRNGKey(5), b, cfg.enc_len,
                                  cfg.d_model)
        state = model.start_decode(params, state, frames)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, (b, 1)), jnp.int32)
    nxt, state = jax.jit(serve_step)(params, state, tok)
    assert nxt.shape == (b,)
    assert int(state["index"]) == 1
    nxt2, state = jax.jit(serve_step)(params, state, nxt[:, None])
    assert int(state["index"]) == 2


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_numbers_match_assignment(arch):
    """The FULL configs carry the exact published numbers."""
    cfg = configs.get(arch)
    expected = {
        "pixtral-12b": (40, 5120, 32, 8, 14336, 131072),
        "whisper-base": (12, 512, 8, 8, 2048, 51865),
        "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
        "granite-34b": (88, 6144, 48, 1, 24576, 49152),
        "granite-3-8b": (40, 4096, 32, 8, 12800, 49155),
        "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151936),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 1408, 163840),
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "mamba2-370m": (48, 1024, 0, 0, 0, 50280),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab)
    assert got == expected, (got, expected)
    if arch in ("moonlight-16b-a3b", "deepseek-moe-16b"):
        assert cfg.n_experts == 64 and cfg.top_k == 6
    if arch == "moonlight-16b-a3b":
        assert (cfg.kv_lora_rank, cfg.qk_rope_head_dim) == (512, 64)
        assert (cfg.n_dense_layers, cfg.dense_d_ff) == (1, 11264)
        assert cfg.routed_scaling_factor == 2.446
    if arch == "mamba2-370m":
        assert cfg.ssm_state == 128
    if arch == "recurrentgemma-9b":
        assert cfg.block_pattern == ("rec", "rec", "attn")
