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


# ---------------------------------------------------------------------------
# The donated decode state
# ---------------------------------------------------------------------------


def _xs_ys_decode_step(model):
    """``model.decode_step`` with the stacked latent cache as the layer
    scan's xs and ys, each layer's (B, W, S) slice written and returned
    whole: the path the carried cache replaced."""

    def decode_step(params, state, tokens, *, key=None, commit=None):
        kinds, index = model.layer_kinds(), state["index"]
        x = model.embed(params, tokens)
        new = {"index": index + 1, "lead": []}
        for p, c, kind in zip(params["lead"], state["lead"], kinds):
            x, c, _ = model._decode_block(p, c, x, kind, index, key=key,
                                          commit=commit)
            new["lead"].append(c)

        def body(x, lp_cl):
            x, cl, pairs = model._decode_block(*lp_cl, x, kinds[-1], index,
                                               key=key, commit=commit)
            return x, (cl, pairs)

        x, (new["layers"], pairs) = jax.lax.scan(
            body, x, (params["layers"], state["layers"]))
        pairs = jnp.sum(pairs, axis=0)
        if commit is not None:
            pairs = jnp.where(commit[:, None], pairs, state["moe_pairs"])
        new["moe_pairs"] = pairs
        return model.logits(params, x), new

    return decode_step


def _serve_two_waves(model, params, ckpt_root, *, donated):
    """Warm-up, then two waves of four requests through bucketed packed
    admission (the slot scatter), each on a fresh decode state (the
    engine's own, then a new one), with a checkpoint taken two steps into
    the second wave.
    ``donated`` False builds the decode program without donation.
    -> (each wave's streams, the checkpoint's directory)."""
    eng = ServingEngine(model, params, max_batch=4, max_len=32,
                        prefill_buckets=(8,))
    if not donated:
        eng._jit_decode = jax.jit(eng._decode_all)
    eng.warmup()
    fresh = jax.jit(lambda: model.init_decode_state(4, 32))
    rng = np.random.default_rng(7)
    waves, ckpt = [], None
    for wave in range(2):
        if wave:        # the first wave serves the state warm-up left
            eng.state = fresh()
        reqs = [Request(uid=10 * wave + i, max_new_tokens=6,
                        prompt=rng.integers(0, model.cfg.vocab, n)
                        .astype(np.int32))
                for i, n in enumerate((5, 9, 3, 7))]
        for r in reqs:
            eng.submit(r)
        if wave == 1:
            eng.step()
            eng.step()
            ckpt = eng.save(ckpt_root, 1)
        eng.run_to_completion()
        waves.append({r.uid: r.generated for r in reqs})
    return waves, ckpt


@pytest.mark.parametrize("arch", ["moonlight-16b-a3b", "qwen2.5-3b"])
def test_donated_decode_state_serves_the_undonated_tokens(arch, tmp_path):
    """Where the engine donates the decode state (the latent model),
    warm-up, fresh states per wave, the slot scatter and a checkpoint of
    the state touch no deleted buffer; for both models the tokens are
    those of an undonated decode program (for the latent model, over the
    xs/ys cache path), also after a restore."""
    cfg = configs.get_smoke(arch).replace(
        dtype="float32", analog=AnalogSpec(enabled=False))
    params = build(cfg).init(jax.random.PRNGKey(0))
    before = build(cfg)
    if cfg.kv_lora_rank:
        before.decode_step = _xs_ys_decode_step(before)
    want, _ = _serve_two_waves(before, params, str(tmp_path / "before"),
                               donated=False)
    model = build(cfg)
    got, ckpt = _serve_two_waves(model, params, str(tmp_path / "after"),
                                 donated=True)
    assert got == want
    assert all(len(t) == 6 for wave in got for t in wave.values())
    eng = ServingEngine.restore(model, str(tmp_path / "after"),
                                prefill_buckets=(8,))
    resumed = [r for r in eng.slot_req if r is not None]
    assert len(resumed) == 4 and ckpt.endswith("step_00000001")
    eng.run_to_completion()
    assert {r.uid: r.generated for r in resumed} == got[1]


@pytest.mark.parametrize("arch,donated", [("moonlight-16b-a3b", True),
                                          ("qwen2.5-3b", False),
                                          ("mamba2-370m", False)])
def test_decode_state_donated_only_where_written_in_place(arch, donated):
    """The engine donates the decode state to a model whose decode step
    writes it in place (the carried latent stack).  Where the layer scan
    returns caches as its ys, a donated state would cost a copy of the
    whole stack into the donated buffer every step, so it is not."""
    cfg = configs.get_smoke(arch).replace(
        dtype="float32", analog=AnalogSpec(enabled=False))
    model = build(cfg)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                        max_batch=2, max_len=16)
    header = eng._jit_decode.lower(
        eng.params, eng.state, jnp.zeros((2, 1), jnp.int32),
        jnp.zeros((2,), jnp.int32), None).compile().as_text() \
        .split("\n", 1)[0]
    assert ("input_output_alias" in header) == donated


def test_detok_worker_reads_pairs_after_the_state_is_donated():
    """The detokenize worker reads a step's ``moe_pairs`` when it gets to
    them, possibly after later steps were given the donated state.  The
    decode step reads no pairs, so its program takes none and donates
    none: what the worker was handed stays readable, each step's counts."""
    cfg = configs.get_smoke("moonlight-16b-a3b").replace(
        dtype="float32", analog=AnalogSpec(enabled=False))
    model = build(cfg)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                        max_batch=2, max_len=16, detok_thread=True)
    held = []
    eng._detok.put = lambda tok, snap, pairs=None, on_pairs=None: \
        held.append(pairs)
    for uid in range(2):
        eng.submit(Request(uid=uid, prompt=np.arange(3 + uid,
                                                     dtype=np.int32),
                           max_new_tokens=4))
    for _ in range(4):
        eng.step()
    assert len(held) >= 3
    n_moe = cfg.n_layers - cfg.n_dense_layers
    for pairs in held:
        routed = np.asarray(pairs)[:, 0]
        assert (routed == n_moe * cfg.top_k).all(), routed
