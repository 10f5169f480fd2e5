"""Serving engine: continuous batching, slot reuse, greedy consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import AnalogSpec
from repro.nn.model import build
from repro.serve.engine import Request, ServingEngine, serving_params


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32", analog=AnalogSpec(enabled=False))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_engine_generates(smoke_model):
    cfg, model, params = smoke_model
    engine = ServingEngine(model, params, max_batch=2, max_len=64)
    rng = np.random.default_rng(0)
    for uid in range(3):
        engine.submit(Request(uid=uid,
                              prompt=rng.integers(0, cfg.vocab, 5)
                              .astype(np.int32),
                              max_new_tokens=4))
    reqs = {r.uid: r for r in engine.queue}
    for _ in range(40):
        engine.step()
        if not engine.queue and all(engine.slot_free):
            break
    assert all(engine.slot_free)
    for r in reqs.values():
        assert len(r.generated) == 4


def test_continuous_batching_slot_reuse(smoke_model):
    cfg, model, params = smoke_model
    engine = ServingEngine(model, params, max_batch=1, max_len=64)
    rng = np.random.default_rng(1)
    r1 = Request(uid=1, prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32),
                 max_new_tokens=2)
    r2 = Request(uid=2, prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32),
                 max_new_tokens=2)
    engine.submit(r1)
    engine.submit(r2)
    for _ in range(20):
        engine.step()
        if not engine.queue and all(engine.slot_free):
            break
    assert len(r1.generated) == 2 and len(r2.generated) == 2


def test_engine_emits_only_vocabulary_ids():
    """The embedding table is padded to vocab_pad_multiple; those padded
    logit columns are not tokens, even where they score highest."""
    cfg = configs.get_smoke("qwen2.5-3b").replace(
        vocab=250, dtype="float32", analog=AnalogSpec(enabled=False))
    assert cfg.padded_vocab > cfg.vocab
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    decode_step = model.decode_step

    def padding_wins(params, state, tokens, *, key=None):
        logits, state = decode_step(params, state, tokens, key=key)
        return logits.at[..., cfg.vocab:].set(1e9), state

    model.decode_step = padding_wins
    engine = ServingEngine(model, params, max_batch=2, max_len=32)
    rng = np.random.default_rng(2)
    reqs = [Request(uid=u, prompt=rng.integers(0, cfg.vocab, 5)
                    .astype(np.int32), max_new_tokens=3) for u in range(2)]
    for r in reqs:
        engine.submit(r)
    engine.run_to_completion()
    toks = [t for r in reqs for t in r.generated]
    assert len(toks) == 6
    assert all(0 <= t < cfg.vocab for t in toks)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_serving_params_are_init_in_serve_dtype(dtype):
    """The serve path's params are ``model.init``'s values with the
    float32 leaves stored in ``serve_params_dtype``."""
    cfg = configs.get_smoke("qwen2.5-3b").replace(serve_params_dtype=dtype)
    model = build(cfg)
    key = jax.random.PRNGKey(3)
    want = jax.tree.map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a,
        model.init(key))
    got = serving_params(model, key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def test_engine_greedy_matches_manual(smoke_model):
    """Single request: the engine reproduces manual greedy decode."""
    cfg, model, params = smoke_model
    prompt = np.asarray([3, 7, 11, 2], np.int32)
    n_new = 5

    # manual greedy with decode_step
    state = model.init_decode_state(1, max_len=64)
    toks = list(prompt)
    for t in toks[:-1]:
        _, state = model.decode_step(
            params, state, jnp.asarray([[t]], jnp.int32))
    cur = toks[-1]
    manual = []
    for _ in range(n_new):
        logits, state = model.decode_step(
            params, state, jnp.asarray([[cur]], jnp.int32))
        cur = int(jnp.argmax(logits[0, -1]))
        manual.append(cur)

    engine = ServingEngine(model, params, max_batch=1, max_len=64)
    req = Request(uid=0, prompt=prompt, max_new_tokens=n_new)
    engine.submit(req)
    for _ in range(20):
        engine.step()
        if all(engine.slot_free) and not engine.queue:
            break
    assert req.generated == manual


def test_engine_with_recurrent_state_model():
    """Continuous batching works for attention-free (SSM) archs too —
    the engine's slot merge handles (B, H, P, N) recurrent states."""
    cfg = configs.get_smoke("mamba2-370m").replace(
        dtype="float32", analog=AnalogSpec(enabled=False))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_batch=2, max_len=64)
    rng = np.random.default_rng(2)
    reqs = [Request(uid=u, prompt=rng.integers(0, cfg.vocab, 5)
                    .astype(np.int32), max_new_tokens=3) for u in range(3)]
    for r in reqs:
        engine.submit(r)
    for _ in range(30):
        engine.step()
        if not engine.queue and all(engine.slot_free):
            break
    for r in reqs:
        assert len(r.generated) == 3
