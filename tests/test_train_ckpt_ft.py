"""Training loop + checkpointing + fault tolerance integration."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.ckpt import CheckpointManager, load_checkpoint, save_checkpoint
from repro.ckpt.checkpoint import list_checkpoints
from repro.data.pipeline import CharCorpus, SyntheticKWS, SyntheticLM
from repro.ft.executor import (RetryingExecutor, StragglerPolicy,
                               TransientFailure, WorkerFailure,
                               HeartbeatMonitor)
from repro.launch.steps import build_all, make_optimizer
from repro.train import optim
from repro.train.loop import TrainState, Trainer


@pytest.mark.parametrize("grad_comm", ["gspmd", "psum"])
def test_make_trainer_matches_plain_loop(grad_comm):
    """``launch.train``'s set-up on a one-device mesh takes the same steps
    as a plain jitted step on ``model.init`` params."""
    from jax.sharding import Mesh

    from repro.launch.steps import make_train_step
    from repro.launch.train import fit, make_trainer
    from repro.nn.model import build

    cfg = configs.get_smoke("qwen2.5-3b")
    model = build(cfg)
    opt = make_optimizer(cfg, total_steps=3)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    trainer, state = make_trainer(
        model, opt, mesh, grad_comm,
        SyntheticLM(cfg.vocab, 16, 4, seed=1), seed=2, log_every=1)
    fit(trainer, state, 3, mesh, grad_comm)
    got = [h["loss"] for h in trainer.history]

    params = model.init(jax.random.PRNGKey(2))
    plain = Trainer(model, opt, make_train_step(model, opt),
                    SyntheticLM(cfg.vocab, 16, 4, seed=1),
                    put_batch=lambda b: {k: jnp.asarray(v)
                                         for k, v in b.items()},
                    log_every=1)
    plain.fit(TrainState(params, opt.init(params)), 3)
    want = [h["loss"] for h in plain.history]
    assert len(got) == 3 and np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-3)


def test_loss_decreases_small_lm(tmp_path):
    from repro.launch.steps import make_train_step
    from repro.nn.model import build

    cfg = configs.get_smoke("qwen2.5-3b")
    model = build(cfg)
    opt = optim.Adam(lr=3e-3, grad_clip_norm=1.0)
    train_step = make_train_step(model, opt)
    params = model.init(jax.random.PRNGKey(0))
    state = TrainState(params, opt.init(params))
    pipe = SyntheticLM(cfg.vocab, seq_len=32, global_batch=8, seed=3)
    trainer = Trainer(model, opt, train_step, pipe,
                      put_batch=lambda b: {k: jnp.asarray(v)
                                           for k, v in b.items()},
                      log_every=5)
    state = trainer.fit(state, 30)
    losses = [h["loss"] for h in trainer.history]
    assert len(losses) >= 4
    assert losses[-1] < losses[0] - 0.1, losses


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    save_checkpoint(str(tmp_path), 7, tree, metadata={"note": "x"})
    like = jax.tree.map(jnp.zeros_like, tree)
    restored, step, meta = load_checkpoint(str(tmp_path), like)
    assert step == 7 and meta["note"] == "x"
    np.testing.assert_array_equal(restored["a"], tree["a"])
    np.testing.assert_array_equal(np.asarray(restored["b"]["c"], np.float32),
                                  np.ones(4, np.float32))


def test_checkpoint_keep_k_and_tmp_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, save_interval=1)
    tree = {"x": jnp.zeros((2,))}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree, blocking=True)
    assert list_checkpoints(str(tmp_path)) == [3, 4]
    # a stale tmp dir is ignored and GC'd on next manager init
    os.makedirs(tmp_path / "step_00000099.tmp")
    mgr2 = CheckpointManager(str(tmp_path), keep=2)
    assert mgr2.latest_step() == 4
    assert not (tmp_path / "step_00000099.tmp").exists()


def test_checkpoint_tree_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"a": jnp.zeros((2,))})
    with pytest.raises(ValueError):
        load_checkpoint(str(tmp_path), {"zz": jnp.zeros((2,))})


def test_executor_retries_transient():
    calls = {"n": 0}

    def step_fn(state, step):
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientFailure("flaky link")
        return state + 1

    ex = RetryingExecutor(step_fn, backoff_s=0.0)
    out, nxt = ex.run_step(0, 0)
    assert out == 1 and nxt == 1
    assert ex.stats.retries == 2


def test_executor_restore_on_worker_failure(tmp_path):
    events = []

    def step_fn(state, step):
        if step == 3 and not events:
            events.append("fail")
            raise WorkerFailure("host lost")
        return state + 1

    def restore_fn(step):
        return 100, 2   # rewind to checkpointed step 2

    ex = RetryingExecutor(step_fn, restore_fn=restore_fn)
    state, step = 0, 0
    while step < 5:
        state, step = ex.run_step(state, step)
    assert ex.stats.restores == 1
    assert state == 100 + 3   # replayed 2->5 from the restored state


def test_straggler_policy():
    pol = StragglerPolicy(multiplier=2.0, min_deadline_s=0.0)
    for _ in range(10):
        pol.observe(1.0)
    assert pol.observe(5.0) is True
    assert pol.observe(1.0) is False


def test_heartbeat_monitor():
    t = {"now": 0.0}
    mon = HeartbeatMonitor(3, timeout_s=5.0, clock=lambda: t["now"])
    t["now"] = 3.0
    mon.beat(0)
    mon.beat(1)
    t["now"] = 7.0
    assert mon.dead_workers() == [2]
    assert not mon.healthy()


def test_trainer_resume_exact(tmp_path):
    """Restart mid-run == uninterrupted run (deterministic pipeline)."""
    cfg = configs.get_smoke("qwen2.5-3b")
    model, train_step, _, _ = build_all(cfg)
    opt = make_optimizer(cfg, total_steps=12)

    def fresh():
        params = model.init(jax.random.PRNGKey(0))
        return TrainState(params, opt.init(params))

    def put(b):
        return {k: jnp.asarray(v) for k, v in b.items()}

    pipe = SyntheticLM(cfg.vocab, seq_len=16, global_batch=4, seed=5)

    # uninterrupted 8 steps
    t_full = Trainer(model, opt, train_step, pipe, put_batch=put,
                     log_every=100)
    s_full = t_full.fit(fresh(), 8)

    # 4 steps -> checkpoint -> new trainer resumes to 8
    ck = str(tmp_path / "ck")
    t_a = Trainer(model, opt, train_step, pipe, ckpt_dir=ck, ckpt_every=4,
                  log_every=100)
    t_a.fit(fresh(), 4)
    t_b = Trainer(model, opt, train_step, pipe, ckpt_dir=ck, ckpt_every=100,
                  log_every=100)
    s_resumed = t_b.fit(fresh(), 8)

    for a, b in zip(jax.tree.leaves(s_full.params),
                    jax.tree.leaves(s_resumed.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


def test_optim_schedules():
    sched = optim.cosine_schedule(1.0, warmup_steps=10, total_steps=100)
    assert float(sched(jnp.asarray(0))) == 0.0
    assert abs(float(sched(jnp.asarray(10))) - 1.0) < 1e-6
    assert float(sched(jnp.asarray(100))) < 0.2
    wsd = optim.wsd_schedule(1.0, warmup_steps=10, total_steps=100)
    assert abs(float(wsd(jnp.asarray(50))) - 1.0) < 1e-6
    assert float(wsd(jnp.asarray(100))) < 0.2


def test_grad_clip():
    tree = {"a": jnp.full((10,), 100.0)}
    clipped = optim.clip_by_global_norm(tree, 1.0)
    assert abs(float(optim.global_norm(clipped)) - 1.0) < 1e-5


def test_data_pipelines_shapes():
    lm = SyntheticLM(vocab=50, seq_len=8, global_batch=6, n_hosts=2,
                     host_id=1)
    b = lm.next_batch()
    assert b["tokens"].shape == (3, 8)
    cc = CharCorpus(seq_len=16, batch=4, corpus_len=2000)
    b = cc.next_batch()
    assert b["tokens"].shape == (4, 16) and b["tokens"].max() < 50
    assert cc.embeddings().shape == (50, 128)
    # orthogonality (paper: Gram-Schmidt)
    e = cc.embeddings()
    np.testing.assert_allclose(e @ e.T, np.eye(50), atol=1e-5)
    kws = SyntheticKWS()
    (xtr, ytr), (xte, yte) = kws.splits(64, 32)
    assert xtr.shape == (64, 49, 40) and set(ytr) <= set(range(12))


def test_grad_accum_equivalent_to_full_batch():
    """Averaged-microbatch grads + one update == the monolithic step."""
    from repro.launch.steps import make_train_step
    from repro.nn.model import build
    from repro.train.loop import grad_accum_step
    from repro.configs.base import AnalogSpec

    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32", analog=AnalogSpec(enabled=False))
    model = build(cfg)
    opt = optim.Adam(lr=1e-3)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)

    pipe = SyntheticLM(cfg.vocab, seq_len=16, global_batch=8, seed=1)
    big = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    micro = jax.tree.map(lambda x: x.reshape(2, 4, *x.shape[1:]), big)

    full_step = make_train_step(model, opt)
    p_full, _, m_full = jax.jit(full_step)(params, opt_state, big, 0)

    accum = grad_accum_step(model, opt, n_micro=2)
    p_acc, _, m_acc = jax.jit(accum)(params, opt_state, micro, 0)

    # same loss (token-mean over the same tokens) and near-identical params
    np.testing.assert_allclose(float(m_full["loss"]), float(m_acc["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_acc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
