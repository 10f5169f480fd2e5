#!/usr/bin/env python3
"""Chip smoke test: the qwen2.5-3b serving path on one TPU, end to end.

    python chip_smoke.py             # one chip: the serving phase
    python chip_smoke.py --chips 4   # four chips: the multi-chip phase only

One process holds the chip(s) for the whole run and starts no other.

Serving phase (one chip).  qwen2.5-3b at its published widths and full
depth (``configs.get`` -> ``nn.model.build``, random weights from a seed
made by ``serve.engine.serving_params`` as ``launch.serve`` makes them,
stored in the config's bf16 ``serve_params_dtype``) is served by
``serve.engine.ServingEngine`` with ``backend="pallas"``, so the fused
matmul + NL-ADC and cached-attention kernels run compiled for the TPU.
Four seeded prompts of mixed length (8-64 tokens) ask for 16 new tokens
each and are drained.  Checks: every request got its tokens, every token
is a vocabulary id, no logit is NaN or Inf, the logits of the pallas
backend agree with the ``ref`` backend on the same params (same arrays,
no second copy) at the longest prompt's last position and the first
decode steps after it, and there the tokens the engine served are the
row maxima of the pallas logits.

Multi-chip phase (``--chips 4``).  qwen2.5-3b at full width cut to 2
layers (about 0.47B parameters, about 7.5 GB with gradients and Adam
state) takes 3 training steps through ``launch.train.make_trainer`` and
``launch.train.fit``, the set-up ``launch.train`` runs: GSPMD on the
(1, 4) host mesh (megatron model-axis layout), then ``--grad-comm psum``
data parallelism over 4 chips.  Both are compared with the same steps on one
chip, run first in the same process and freed before the others start.
Training runs the ``ref`` backend: GSPMD cannot partition a Pallas call.

The script exits non-zero, and prints no result line, when JAX finds no
TPU or any check fails; no phase's failure is caught.  Its last line is
one JSON object: ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``.  The phases are functions of a config, so they can be
rehearsed on the CPU at a smoke config (``JAX_PLATFORMS=cpu``, kernels in
interpret mode) by calling them directly; ``main`` refuses any platform
but the TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PROMPT_LENS = (8, 24, 40, 64)
MAX_NEW = 16
MAX_BATCH = 4
MAX_LEN = 128
PREFILL_BUCKETS = (16, 64)
N_COMPARE_DECODE = 4        # decode steps compared after the prompt

# Largest |logit(pallas) - logit(ref)| allowed, as a fraction of the
# largest |logit(ref)| at the compared positions.  The two backends run
# the same bf16 model but round differently: the fused kernel accumulates
# the MLP gate in f32 and digitizes that, where ref digitizes the bf16
# matmul output, so an accumulator within bf16 rounding of a ramp
# threshold lands one NL-ADC code apart, and the attention kernel's
# softmax rounds in another order than XLA's.  These code flips enter
# every layer and compound over depth: in interpret mode on the CPU, at
# qwen2.5-3b's depth and heads with d_model 256, the ratio is 0.09 from
# rounding alone, while a kernel that drops the attention mask, doubles
# the attention scale or shifts every code by one gives 0.5 to 1.2.
LOGIT_REL_TOL = 0.25

# Largest |loss(n chips) - loss(1 chip)| allowed per training step.  The
# partitioned programs reduce matmuls and gradients in another order, in
# bf16 activations; the loss is ln(vocab) ~ 11.9 at random init, so this
# is ~0.2% of it, while a wrong sharding or a missing reduction moves the
# loss by far more.
LOSS_TOL = 2e-2
TRAIN_STEPS = 3
TRAIN_BATCH = 8
TRAIN_SEQ = 128


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {msg}")


def _peak_bytes(device) -> str:
    stats = device.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return str(stats["peak_bytes_in_use"])


def _with_backend(cfg, backend: str):
    return cfg.replace(analog=dataclasses.replace(cfg.analog,
                                                  backend=backend))


def _teacher_forced_logits(model, max_len: int):
    """jit(params, tokens (T,)) -> (T, vocab) f32 logits of one request
    fed token by token through the model's decode step (the serving
    seam)."""
    import jax
    import jax.numpy as jnp

    def run(params, tokens):
        state = model.init_decode_state(1, max_len)

        def body(st, tok):
            logits, st = model.decode_step(params, st, tok[None, None])
            return st, logits[0, 0, :model.cfg.vocab].astype(jnp.float32)

        _, logits = jax.lax.scan(body, state, tokens)
        return logits

    return jax.jit(run)


def serve_phase(cfg, *, seed: int = 0) -> dict:
    """Serve seeded requests with the pallas backend; check the tokens and
    the logits against the ref backend.  Raises RuntimeError on a failed
    check; returns what it measured."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.nn.model import build
    from repro.serve.engine import Request, ServingEngine, serving_params

    device = jax.devices()[0]
    model = build(_with_backend(cfg, "pallas"))
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        serving_params(model, jax.random.PRNGKey(seed)))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    _log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
         f"d_ff {cfg.d_ff}, {n_params} parameters "
         f"({jax.tree.leaves(params)[0].dtype}); init (compile + run) "
         f"{time.perf_counter() - t0:.1f} s")

    engine = ServingEngine(model, params, max_batch=MAX_BATCH,
                           max_len=MAX_LEN, prefill_buckets=PREFILL_BUCKETS)
    t0 = time.perf_counter()
    engine.warmup()
    _log(f"compile seconds, pallas engine (decode step + prefill buckets "
         f"{PREFILL_BUCKETS}): {time.perf_counter() - t0:.1f}")

    rng = np.random.default_rng(seed)
    reqs = [Request(uid=i, max_new_tokens=MAX_NEW,
                    prompt=rng.integers(0, cfg.vocab, size=n)
                    .astype(np.int32))
            for i, n in enumerate(PROMPT_LENS)]
    for r in reqs:
        engine.submit(r)
    steps = 0
    while engine.queue or not all(engine.slot_free):
        engine.step()
        steps += 1
        _check(steps <= len(reqs) * (MAX_NEW + 1),
               f"engine did not drain after {steps} steps")
    streams = [list(r.generated) for r in reqs]
    n_tokens = sum(len(s) for s in streams)
    _log(f"tokens served: {n_tokens} for {len(reqs)} requests "
         f"(prompt lengths {PROMPT_LENS}, {steps} engine steps)")
    for r, s in zip(reqs, streams):
        _check(len(s) == MAX_NEW,
               f"request {r.uid} got {len(s)} tokens, wanted {MAX_NEW}")
    bad = [t for s in streams for t in s if not 0 <= t < cfg.vocab]
    _check(not bad, f"tokens outside the vocabulary [0, {cfg.vocab}): "
                    f"{bad[:8]}")
    _log(f"check: all {n_tokens} tokens are ids in [0, {cfg.vocab})")

    # pallas vs ref on the same params, one request fed token by token:
    # the longest prompt plus the first tokens the engine served for it
    longest = int(np.argmax(PROMPT_LENS))
    prompt = reqs[longest].prompt
    seq = np.concatenate([prompt, streams[longest][:N_COMPARE_DECODE]])
    tokens = jnp.asarray(seq, jnp.int32)
    first = len(prompt) - 1               # the prompt's last position
    logits = {}
    for backend in ("pallas", "ref"):
        m = model if backend == "pallas" \
            else build(_with_backend(cfg, backend))
        t0 = time.perf_counter()
        fn = _teacher_forced_logits(m, MAX_LEN).lower(params, tokens) \
            .compile()
        _log(f"compile seconds, {backend} teacher-forced decode "
             f"({len(seq)} tokens): {time.perf_counter() - t0:.1f}")
        out = np.asarray(fn(params, tokens))[first:]
        _check(bool(np.all(np.isfinite(out))),
               f"{backend} logits hold NaN or Inf")
        logits[backend] = out
    _log(f"check: no NaN or Inf in the {logits['pallas'].size} pallas and "
         f"ref logits compared")
    delta = np.abs(logits["pallas"] - logits["ref"])
    scale = float(np.max(np.abs(logits["ref"])))
    per_pos = [float(d) for d in delta.max(axis=1)]
    _log("max |logit(pallas) - logit(ref)| at the prompt's last position "
         "then each decode step: " + ", ".join(f"{d:.6g}" for d in per_pos)
         + f"; max |logit(ref)| {scale:.6g}")
    worst = max(per_pos)
    _check(worst <= LOGIT_REL_TOL * scale,
           f"pallas and ref logits differ by {worst:.6g}, more than "
           f"{LOGIT_REL_TOL} x max|logit| = {LOGIT_REL_TOL * scale:.6g}")
    _log(f"check: logit delta {worst:.6g} <= {LOGIT_REL_TOL} x "
         f"{scale:.6g}")

    # The engine's own tokens for that request: each must be the argmax of
    # the pallas teacher-forced row up to the rounding the two programs
    # may differ by (the same bound as pallas vs ref).  Only the longest
    # request has a per-request reference: the decode state keeps one
    # shared index, the longest slot's position, so shorter requests
    # decode at a position that is not their own (ROADMAP Reach 1).
    pl = logits["pallas"]
    served = np.asarray(streams[longest][:len(pl)])
    row_max = pl.max(axis=1)
    gaps = row_max - pl[np.arange(len(pl)), served]
    tol = LOGIT_REL_TOL * scale
    n_near = (pl >= (row_max - tol)[:, None]).sum(axis=1)
    _log("request of the longest prompt: max logit - logit of the token "
         "the engine served, at the prompt's last position then each "
         "decode step: " + ", ".join(f"{g:.6g}" for g in gaps)
         + f"; vocabulary ids within {tol:.6g} of each row max: "
         + ", ".join(str(int(c)) for c in n_near))
    _check(bool(np.all(gaps <= tol)),
           f"the engine served tokens {gaps.max():.6g} below the row max "
           f"of the teacher-forced pallas logits, more than {tol:.6g}")
    _log(f"check: served tokens within {tol:.6g} of the row max")
    _log(f"peak_bytes_in_use: {_peak_bytes(device)}")
    return {"tokens": n_tokens, "max_abs_dlogit": worst,
            "max_abs_logit_ref": scale, "max_served_gap": float(gaps.max())}


def _check_placement(grad_comm: str, n: int, params, opt_state):
    """Every param and optimizer-state array spans all ``n`` devices, and
    under gspmd each device holds well under the whole model; -> (param
    bytes on one device, param bytes in all)."""
    import jax

    for what, tree in (("param", params), ("optimizer state", opt_state)):
        for leaf in jax.tree.leaves(tree):
            _check(len(leaf.sharding.device_set) == n,
                   f"{grad_comm}: {what} of shape {leaf.shape} lives on "
                   f"{len(leaf.sharding.device_set)} of {n} devices")
    leaves = jax.tree.leaves(params)
    total = sum(p.nbytes for p in leaves)
    per_dev = sum(p.addressable_shards[0].data.nbytes for p in leaves)
    if grad_comm == "gspmd" and n > 1:
        # megatron layout: the model axis splits the big matrices, so no
        # device holds the whole model
        _check(per_dev < total // 2,
               f"gspmd holds {per_dev} of {total} param bytes per device")
    return per_dev, total


def _train_losses(model, opt, mesh, grad_comm: str, *, seed: int) -> list:
    """TRAIN_STEPS steps of ``launch.train``'s set-up on ``mesh``;
    -> losses."""
    import jax

    from repro.data.pipeline import SyntheticLM
    from repro.launch.train import fit, make_trainer

    n = mesh.devices.size
    trainer, state = make_trainer(
        model, opt, mesh, grad_comm,
        SyntheticLM(model.cfg.vocab, TRAIN_SEQ, TRAIN_BATCH, seed=seed),
        seed=seed, log_every=1)
    per_dev, total = _check_placement(grad_comm, n, state.params,
                                      state.opt_state)
    _log(f"{grad_comm} on {n} device(s): params {per_dev} of {total} bytes "
         f"on each device")
    t0 = time.perf_counter()
    state = fit(trainer, state, TRAIN_STEPS, mesh, grad_comm)
    jax.block_until_ready(state.params)
    _log(f"{grad_comm} on {n} device(s): {TRAIN_STEPS} steps in "
         f"{time.perf_counter() - t0:.1f} s (compile included)")
    _check_placement(grad_comm, n, state.params, state.opt_state)
    losses = [h["loss"] for h in trainer.history]
    del state, trainer
    return losses


def multichip_phase(cfg, *, n_devices: int = 4, seed: int = 0) -> dict:
    """TRAIN_STEPS training steps on one device, then GSPMD on the (1, n)
    host mesh, then psum data parallelism on n devices; the losses of
    both must match the one-device run within LOSS_TOL."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.ft.elastic import build_mesh, plan_for_devices
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_optimizer
    from repro.nn.model import build

    _check(len(jax.devices()) == n_devices,
           f"need {n_devices} devices, JAX sees {len(jax.devices())}")
    cfg = _with_backend(cfg, "ref")
    model = build(cfg)
    opt = make_optimizer(cfg, total_steps=TRAIN_STEPS)
    _log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
         f"d_ff {cfg.d_ff}, ~{cfg.n_params() / 1e9:.2f}B parameters; "
         f"batch {TRAIN_BATCH} x {TRAIN_SEQ} tokens")

    one = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
               ("data", "model"))
    base = _train_losses(model, opt, one, "gspmd", seed=seed)
    _log(f"1 device losses: {base}")
    _log(f"peak_bytes_in_use after the 1-device run (device 0): "
         f"{_peak_bytes(jax.devices()[0])}")

    out = {"loss_1": base}
    host = make_host_mesh()
    plan = plan_for_devices(n_devices, global_batch=TRAIN_BATCH,
                            model_parallel=1)
    dp = build_mesh(plan)
    for name, mesh in (("gspmd", host), ("psum", dp)):
        losses = _train_losses(model, opt, mesh, name, seed=seed)
        worst = max(abs(a - b) for a, b in zip(losses, base))
        _log(f"{name} losses on mesh {dict(mesh.shape)}: {losses}; "
             f"max |loss - loss(1 device)| {worst:.6g}")
        _check(len(losses) == TRAIN_STEPS and np.all(np.isfinite(losses)),
               f"{name}: losses {losses}")
        _check(worst <= LOSS_TOL,
               f"{name} loss differs from one device by {worst:.6g} > "
               f"{LOSS_TOL}")
        out[f"loss_{name}"] = losses
    for i, d in enumerate(jax.devices()):
        _log(f"peak_bytes_in_use device {i}: {_peak_bytes(d)}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving phase; 4: only the multi-chip "
                         "training phase")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); this check runs only on a TPU",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    _log(f"platform {devices[0].platform}, device_kind {kind!r}, "
         f"device count {len(devices)}; compile cache {cache}")

    from repro import configs

    cfg = configs.get("qwen2.5-3b")
    if args.chips == 1:
        serve_phase(cfg)
    else:
        multichip_phase(cfg.replace(n_layers=2), n_devices=args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
