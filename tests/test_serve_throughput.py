"""Serving admission: bucketed AOT prefill of packed waves, chunked
prefill, and the background detokenize pipeline.

The reference is each request decoded alone through ``model.decode_step``
on a batch-1 state (:func:`_alone`).  The engine's token streams equal it
exactly; its decode caches agree within ``CACHE_ATOL``, because a packed
row's batched contractions sum in another order than a batch-1 step's.
Under the CI pallas job (``REPRO_ANALOG_BACKEND=pallas``, kernels in
interpret mode on the CPU) the same assertions run against the kernel
backend.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import AnalogSpec
from repro.core.device import get_device
from repro.nn.model import build, decode_state_batch_axes
from repro.serve.engine import Request, ServingEngine
from repro.serve.lifecycle import RecalPolicy

PROMPTS = [np.arange(1, 6, dtype=np.int32),        # short
           np.arange(2, 15, dtype=np.int32),       # medium
           np.asarray([7], np.int32),              # degenerate (no prefill)
           np.arange(3, 25, dtype=np.int32)]       # long

# float32 caches: the engine's against the batch-1 reference's differ by
# at most 2.03e-6 on the smoke qwen (chunked or not), 1.37e-6 on the
# smoke mamba2
CACHE_ATOL = 1e-5


@pytest.fixture(scope="module")
def exact_model():
    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32", analog=AnalogSpec(enabled=False))
    model = build(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def noisy_model():
    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32",
        analog=AnalogSpec(enabled=True, mode="infer", device="aged-1day"))
    model = build(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _run(model, params, prompts=PROMPTS, *, max_batch=2, max_len=48,
         max_new=6, eos_id=-1, **kw):
    eng = ServingEngine(model, params, max_batch=max_batch, max_len=max_len,
                        **kw)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new, eos_id=eos_id)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    n = eng.run_to_completion()
    return n, [list(r.generated) for r in reqs], eng


def _assert_state_bitwise(e0, e1, tag):
    for a, b in zip(jax.tree.leaves(e0.state), jax.tree.leaves(e1.state)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"decode-state leaf mismatch ({tag})"


def _starts(prompts, max_batch, max_new):
    """The position each request's first decode step runs at, when every
    request runs ``max_new`` tokens: waves of ``max_batch`` in submission
    order, each decoded at one shared index, the larger of where the last
    wave left it and the wave's longest prefill."""
    starts, index = [], 0
    for w in range(0, len(prompts), max_batch):
        wave = prompts[w:w + max_batch]
        index = max([index] + [len(p) - 1 for p in wave])
        starts += [index] * len(wave)
        index += max_new
    return starts


def _alone(step, model, params, prompt, *, start, max_new, max_len):
    """One request decoded alone through ``model.decode_step`` on a
    ``model.init_decode_state(1, max_len)`` state: the prompt minus its
    last token, then ``max_new`` greedy tokens from position ``start``
    (the cache between the prompt's end and ``start`` stays as the fresh
    state holds it).  Returns (tokens, final state)."""
    state = model.init_decode_state(1, max_len)
    for tok in prompt[:-1]:
        _, state = step(params, state, jnp.full((1, 1), tok, jnp.int32))
    state["index"] = jnp.asarray(start, state["index"].dtype)
    tok, out = int(prompt[-1]), []
    for _ in range(max_new):
        logits, state = step(params, state, jnp.full((1, 1), tok, jnp.int32))
        tok = int(jnp.argmax(logits[0, -1, :model.cfg.vocab]))
        out.append(tok)
    return out, state


def _assert_matches_alone(model, params, *, atol=CACHE_ATOL, max_batch=2,
                          max_len=48, max_new=6, **kw):
    """Serve ``PROMPTS`` and hold the engine to :func:`_alone`: every
    stream exactly, and the caches of the requests still in their slots
    at the end within ``atol``.  Returns the engine."""
    eng = ServingEngine(model, params, max_batch=max_batch, max_len=max_len,
                        **kw)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(PROMPTS)]
    for r in reqs:
        eng.submit(r)
    last_in_slot = {}
    while eng.queue or not all(eng.slot_free):
        for slot, r in enumerate(eng.slot_req):
            if r is not None:
                last_in_slot[slot] = r.uid
        eng.step()
    step = jax.jit(model.decode_step)
    axes = decode_state_batch_axes(model)
    starts = _starts(PROMPTS, max_batch, max_new)
    for r, start in zip(reqs, starts):
        want, alone = _alone(step, model, params, r.prompt, start=start,
                             max_new=max_new, max_len=max_len)
        assert r.generated == want, f"uid {r.uid} stream"
        slots = [s for s, uid in last_in_slot.items() if uid == r.uid]
        if not slots:
            continue                       # its slot went to a later wave
        for a, b, ax in zip(jax.tree.leaves(eng.state),
                            jax.tree.leaves(alone), jax.tree.leaves(axes)):
            a, b = np.asarray(a), np.asarray(b)
            if ax < 0:
                assert np.array_equal(a, b), f"uid {r.uid} shared leaf"
                continue
            np.testing.assert_allclose(
                np.take(a, slots[0], axis=ax).astype(np.float64),
                np.take(b, 0, axis=ax).astype(np.float64),
                rtol=0, atol=atol, err_msg=f"uid {r.uid} cache")
    assert set(last_in_slot.values()) == {2, 3}    # the last wave checked
    return eng


# ---------------------------------------------------------------------------
# Parity: packed / chunked / detok against the batch-1 reference
# ---------------------------------------------------------------------------

def test_bucketed_parity_exact(exact_model):
    """An engine built with no prefill keywords serves through the packed
    bucketed path, and matches each request decoded alone."""
    _, model, params = exact_model
    eng = _assert_matches_alone(model, params)
    # waves of longest prefill 12 and 21: the buckets 16 and 32
    assert sorted(eng._prefill_exec) == [16, 32]


def test_chunked_prefill_parity_exact(exact_model):
    """A prompt longer than every bucket runs as repeated largest-bucket
    chunks carrying the state, and still matches the reference."""
    _, model, params = exact_model
    eng = _assert_matches_alone(model, params, prefill_buckets=(4, 8))
    assert sorted(eng._prefill_exec) == [4, 8]


def test_bucketed_parity_noisy(noisy_model, monkeypatch):
    """Under noise (infer mode, aged device) the wave-shared key, folded
    in at each global position, makes a chunked wave bitwise the
    unchunked one: streams and caches.

    The LM families' ramps draw step noise only in training
    (``DeviceModel.ramp_sigma_us``), so an infer-mode key would change
    nothing here; the test gives the ramp steps a read-noise sigma, and
    checks that the draws reach the caches (another seed, other caches).
    """
    from repro.core.device import DeviceModel

    monkeypatch.setattr(DeviceModel, "ramp_sigma_us",
                        lambda self, mode: 3.5)
    _, model, params = noisy_model
    dev = get_device("aged-1day")
    kw0 = dict(device=dev, noise_seed=3)
    n0, s0, e0 = _run(model, params, **kw0)
    n1, s1, e1 = _run(model, params, **kw0, prefill_buckets=(4, 8))
    assert (n1, s1) == (n0, s0), "noisy stream mismatch (chunked)"
    _assert_state_bitwise(e0, e1, "chunked")
    _, _, e2 = _run(model, params, device=dev, noise_seed=4)
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(e0.state),
                               jax.tree.leaves(e2.state)))


def test_bucketed_parity_recurrent_arch():
    """Batch-axis inference generalizes past KV caches: the SSM arch's
    (B, H, P, N) recurrent states route through the packed path too, and
    match each request decoded alone."""
    cfg = configs.get_smoke("mamba2-370m").replace(
        dtype="float32", analog=AnalogSpec(enabled=False))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _assert_matches_alone(model, params)


def test_detok_thread_parity(exact_model):
    """The background detokenize pipeline lands the same streams (lag is
    drained by run_to_completion's flush) and the same token count."""
    _, model, params = exact_model
    n0, s0, _ = _run(model, params)
    n1, s1, _ = _run(model, params, detok_thread=True)
    assert (n1, s1) == (n0, s0)
    n2, s2, _ = _run(model, params, prefill_buckets=(4, 8),
                     detok_thread=True)
    assert (n2, s2) == (n0, s0)


def test_detok_eos_truncation(exact_model):
    """EOS detection lags one step on the worker, but the emitted stream
    is truncated exactly like the synchronous path."""
    _, model, params = exact_model
    prompts = PROMPTS[:2]                      # one wave, no slot reuse
    _, s0, _ = _run(model, params, prompts, max_new=8)
    eos = s0[0][2]                             # a token that DOES occur
    _, sync, _ = _run(model, params, prompts, max_new=8, eos_id=eos)
    _, detok, _ = _run(model, params, prompts, max_new=8, eos_id=eos,
                       detok_thread=True)
    assert sync == detok
    assert sync[0][-1] == eos and len(sync[0]) <= len(s0[0])


# ---------------------------------------------------------------------------
# AOT warmup + bucket-aware invalidation
# ---------------------------------------------------------------------------

def test_warmup_precompiles_every_bucket(exact_model):
    _, model, params = exact_model
    eng = ServingEngine(model, params, max_batch=2, max_len=48)
    assert eng.prefill_buckets == (8, 16, 32, 47)
    info = eng.warmup()
    assert info["prefill_buckets"] == [8, 16, 32, 47]
    assert sorted(eng._prefill_exec) == [8, 16, 32, 47]
    # a served burst only reuses the warm executables
    for i, p in enumerate(PROMPTS):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    eng.run_to_completion()
    assert sorted(eng._prefill_exec) == [8, 16, 32, 47]


def test_bucket_validation(exact_model):
    _, model, params = exact_model
    for kw in (dict(prefill="scan"), dict(pack_prefill=False),
               dict(prefill="eager")):
        with pytest.raises(ValueError, match="one prefill path"):
            ServingEngine(model, params, max_batch=2, max_len=48, **kw)
    with pytest.raises(ValueError, match="strictly increasing"):
        ServingEngine(model, params, max_batch=2, max_len=48,
                      prefill_buckets=(8, 8))
    # the values the benchmark's workload files pass are the one path
    eng = ServingEngine(model, params, max_batch=2, max_len=48,
                        prefill="bucketed", pack_prefill=True)
    assert eng.prefill_buckets == (8, 16, 32, 47)


def test_schedulerless_drain_keeps_buckets(exact_model):
    """A forced drain window on a chip whose thresholds never moved must
    keep every warm bucket executable AND the compiled decode step."""
    _, model, params = exact_model
    eng = ServingEngine(model, params, max_batch=2, max_len=48,
                        external_maintenance=True)
    eng.warmup()
    execs = dict(eng._prefill_exec)
    eng.begin_drain()
    eng.step()                                 # drain point: re-program
    assert eng.last_invalidation == {
        "kept_buckets": [8, 16, 32, 47], "dropped_buckets": [],
        "decode_rebuilt": False}
    # the executables are literally the same objects — nothing recompiled
    assert all(eng._prefill_exec[b] is execs[b] for b in execs)


def test_recal_drain_invalidates_dirty_buckets(noisy_model):
    """A threshold-moving re-program (recal under drain_before_rejit)
    drops the stale bucket executables, re-AOTs them eagerly, and
    rebuilds the decode step."""
    _, model, params = noisy_model
    dev = get_device("aged-1day")
    pol = RecalPolicy(age_per_step_s=3600.0, check_every=2,
                      inl_threshold_lsb=0.05)
    eng = ServingEngine(model, params, max_batch=2, max_len=48, device=dev,
                        noise_seed=3, recal=pol, drain_before_rejit=True)
    eng.warmup()
    for i, p in enumerate(PROMPTS):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
    eng.run_to_completion()
    inval = eng.last_invalidation
    assert inval is not None and inval["decode_rebuilt"]
    assert inval["dropped_buckets"] == [8, 16, 32, 47]
    # dropped buckets were re-AOT'd at the drain point, not lazily
    assert sorted(eng._prefill_exec) == [8, 16, 32, 47]
    # the fresh executables serve the post-recal chip: a second burst
    # still streams tokens
    eng.submit(Request(uid=99, prompt=PROMPTS[0], max_new_tokens=3))
    assert eng.run_to_completion() >= 3


# ---------------------------------------------------------------------------
# Checkpoint mid-stream
# ---------------------------------------------------------------------------

def test_ckpt_midstream_restore_into_bucketed(noisy_model, tmp_path):
    """A deployment checkpointed mid-stream resumes bitwise with the
    detokenize thread on — one state layout, so the restored engine
    admits the checkpointed queue through the AOT bucket path and still
    reproduces the uninterrupted run."""
    _, model, params = noisy_model
    dev = get_device("aged-1day")

    def fresh():
        eng = ServingEngine(model, params, max_batch=2, max_len=48,
                            device=dev, noise_seed=5)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(PROMPTS)]
        for r in reqs:
            eng.submit(r)
        return eng, reqs

    ref_eng, ref_reqs = fresh()
    ref_eng.run_to_completion()
    ref_streams = [list(r.generated) for r in ref_reqs]

    eng, _ = fresh()
    for _ in range(4):                         # mid-stream: slots + queue
        eng.step()
    assert eng.queue and not all(eng.slot_free)
    root = str(tmp_path / "deploy")
    eng.save(root, step=4)

    res = ServingEngine.restore(model, root, params_like=params,
                                detok_thread=True)
    # grab the restored Request objects BEFORE running — finished
    # requests leave the slot table
    restored = {r.uid: r for r in list(res.slot_req) + res.queue
                if r is not None}
    assert sorted(restored) == [0, 1, 2, 3]
    res.run_to_completion()
    for uid, ref in enumerate(ref_streams):
        assert list(restored[uid].generated) == ref, \
            f"uid {uid} diverged after restore"
