"""Throughput serving path: bucketed AOT prefill, prompt packing, chunked
prefill, and the background detokenize pipeline.

The correctness anchor for every knob is **bitwise parity** with the
legacy scan-prefill path: identical token streams AND identical final
decode caches, noiseless and noisy.  Under the CI pallas job
(``REPRO_ANALOG_BACKEND=pallas``, kernels in interpret mode on the CPU)
the same assertions run against the kernel backend.
"""

import jax
import numpy as np
import pytest

from repro import configs
from repro.configs.base import AnalogSpec
from repro.core.device import get_device
from repro.nn.model import build
from repro.serve.engine import Request, ServingEngine
from repro.serve.lifecycle import RecalPolicy

PROMPTS = [np.arange(1, 6, dtype=np.int32),        # short
           np.arange(2, 15, dtype=np.int32),       # medium
           np.asarray([7], np.int32),              # degenerate (no prefill)
           np.arange(3, 25, dtype=np.int32)]       # long


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
            f"decode-state leaf mismatch vs scan path ({tag})"


# ---------------------------------------------------------------------------
# Bitwise parity: bucketed / packed / chunked / detok vs the scan path
# ---------------------------------------------------------------------------

def test_bucketed_parity_exact(exact_model):
    """Bucketed and packed prefill reproduce the scan path bitwise —
    token streams AND the final decode caches."""
    _, model, params = exact_model
    n0, s0, e0 = _run(model, params)
    for tag, kw in [("bucketed", dict(prefill="bucketed")),
                    ("packed", dict(prefill="bucketed", pack_prefill=True))]:
        n1, s1, e1 = _run(model, params, **kw)
        assert (n1, s1) == (n0, s0), f"stream mismatch ({tag})"
        _assert_state_bitwise(e0, e1, tag)


def test_chunked_prefill_parity_exact(exact_model):
    """A prompt longer than every bucket runs as repeated largest-bucket
    chunks carrying the state — still bitwise the scan path."""
    _, model, params = exact_model
    n0, s0, e0 = _run(model, params)
    n1, s1, e1 = _run(model, params, prefill="bucketed",
                      prefill_buckets=(4, 8), pack_prefill=True)
    assert (n1, s1) == (n0, s0)
    _assert_state_bitwise(e0, e1, "chunked")


def test_bucketed_parity_noisy(noisy_model):
    """Under read noise (infer mode, aged device) the wave-shared key +
    fold_in-at-global-position schedule keeps all prefill paths bitwise
    interchangeable."""
    _, model, params = noisy_model
    dev = get_device("aged-1day")
    kw0 = dict(device=dev, noise_seed=3)
    n0, s0, e0 = _run(model, params, **kw0)
    for tag, kw in [("bucketed", dict(prefill="bucketed")),
                    ("packed", dict(prefill="bucketed", pack_prefill=True)),
                    ("chunked", dict(prefill="bucketed", pack_prefill=True,
                                     prefill_buckets=(4, 8)))]:
        n1, s1, e1 = _run(model, params, **kw0, **kw)
        assert (n1, s1) == (n0, s0), f"noisy stream mismatch ({tag})"
        _assert_state_bitwise(e0, e1, tag)


def test_bucketed_parity_recurrent_arch():
    """Batch-axis inference generalizes past KV caches: the SSM arch's
    (B, H, P, N) recurrent states route through the bucketed path too.

    Unpacked (pack rows = 1) is bitwise the scan path.  Packing changes
    the SSM einsums' batch extent, and XLA:CPU's batched contraction
    accumulates in a different order there — token streams stay
    identical, recurrent-state leaves agree to float32 accumulation
    error (~1e-9; the transformer family is bitwise even packed, see
    :func:`test_bucketed_parity_exact`)."""
    cfg = configs.get_smoke("mamba2-370m").replace(
        dtype="float32", analog=AnalogSpec(enabled=False))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n0, s0, e0 = _run(model, params)
    n1, s1, e1 = _run(model, params, prefill="bucketed")
    assert (n1, s1) == (n0, s0)
    _assert_state_bitwise(e0, e1, "ssm unpacked")
    n2, s2, e2 = _run(model, params, prefill="bucketed", pack_prefill=True)
    assert (n2, s2) == (n0, s0)
    for a, b in zip(jax.tree.leaves(e0.state), jax.tree.leaves(e2.state)):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64),
                                   rtol=0, atol=1e-6)


def test_detok_thread_parity(exact_model):
    """The background detokenize pipeline lands the same streams (lag is
    drained by run_to_completion's flush) and the same token count."""
    _, model, params = exact_model
    n0, s0, _ = _run(model, params)
    n1, s1, _ = _run(model, params, detok_thread=True)
    assert (n1, s1) == (n0, s0)
    n2, s2, _ = _run(model, params, prefill="bucketed", pack_prefill=True,
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
    eng = ServingEngine(model, params, max_batch=2, max_len=48,
                        prefill="bucketed", pack_prefill=True)
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
    with pytest.raises(ValueError, match="require prefill='bucketed'"):
        ServingEngine(model, params, max_batch=2, max_len=48,
                      pack_prefill=True)
    with pytest.raises(ValueError, match="strictly increasing"):
        ServingEngine(model, params, max_batch=2, max_len=48,
                      prefill="bucketed", prefill_buckets=(8, 8))
    with pytest.raises(ValueError, match="prefill must be"):
        ServingEngine(model, params, max_batch=2, max_len=48,
                      prefill="eager")


def test_schedulerless_drain_keeps_buckets(exact_model):
    """A forced drain window on a chip whose thresholds never moved must
    keep every warm bucket executable AND the compiled decode step."""
    _, model, params = exact_model
    eng = ServingEngine(model, params, max_batch=2, max_len=48,
                        prefill="bucketed", pack_prefill=True,
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
                        noise_seed=3, recal=pol, drain_before_rejit=True,
                        prefill="bucketed", pack_prefill=True)
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
# Checkpoint mid-stream across prefill modes
# ---------------------------------------------------------------------------

def test_ckpt_midstream_restore_into_bucketed(noisy_model, tmp_path):
    """A scan-mode deployment checkpointed mid-stream resumes bitwise in
    bucketed+packed(+detok) mode — the modes share one state layout, so
    the restored engine admits the checkpointed queue through the AOT
    path and still reproduces the uninterrupted run."""
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
                                prefill="bucketed", pack_prefill=True,
                                detok_thread=True)
    # grab the restored Request objects BEFORE running — finished
    # requests leave the slot table
    restored = {r.uid: r for r in list(res.slot_req) + res.queue
                if r is not None}
    assert sorted(restored) == [0, 1, 2, 3]
    res.run_to_completion()
    for uid, ref in enumerate(ref_streams):
        assert list(restored[uid].generated) == ref, \
            f"uid {uid} diverged after restore into the bucketed path"
