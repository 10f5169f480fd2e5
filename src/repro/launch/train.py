"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Runs a real (CPU-sized or cluster-sized) training job with the full stack:
deterministic pipeline -> jitted sharded train step -> checkpoints -> FT
executor.  On this container use ``--smoke`` for the reduced configs.

Distribution is wired through :mod:`repro.dist`:

* ``--grad-comm gspmd`` (default) — params/optimizer state are placed with
  the megatron ``param_specs`` layout, the batch with ``batch_specs``, and
  the jitted step lets the GSPMD partitioner insert collectives;
* ``--grad-comm psum|hierarchical|int8`` — a shard_map data-parallel step
  with the explicit gradient-reduction path from
  :mod:`repro.dist.collectives` / :mod:`repro.dist.compress`; the mesh is
  sized by :func:`repro.ft.elastic.plan_for_devices` so the data axis
  always divides the global batch (elastic shrink/grow reuses the same
  plan + ``reshard`` round-trip on restore).
"""

from __future__ import annotations

import argparse
import contextlib

import jax
import jax.numpy as jnp

import dataclasses

from repro import configs
from repro.core.backend import backend_names
from repro.core.device import device_names
from repro.data.pipeline import SyntheticLM
from repro.dist import sharding as SH
from repro.ft.elastic import build_mesh, init_sharded, plan_for_devices
from repro.kernels import tune
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import (make_dp_opt_state, make_dp_train_step,
                                make_optimizer, make_train_step)
from repro.nn.frontends import audio_frame_stub, vision_patch_stub
from repro.nn.model import build
from repro.train.loop import TrainState, Trainer

GRAD_COMM_MODES = ("gspmd", "psum", "hierarchical", "int8")


def make_trainer(model, opt, mesh, grad_comm: str, data, *, seed: int = 0,
                 ckpt_dir=None, log_every: int = 10):
    """The training set-up on ``mesh``: the train step for ``grad_comm``,
    params made in place with the megatron layout (all replicated for SSM
    configs), optimizer state laid out like them, and batches placed by
    ``batch_specs``.  ``data`` is the pipeline (``batch_at(step)``);
    -> ``(Trainer, TrainState)``."""
    cfg = model.cfg
    if grad_comm == "gspmd":
        train_step = make_train_step(model, opt)
    else:
        train_step = make_dp_train_step(model, opt, mesh,
                                        grad_comm=grad_comm)
    params = init_sharded(model.init, jax.random.PRNGKey(seed), mesh,
                          replicate_all=cfg.family == "ssm")
    # int8 grad-comm carries per-replica error-feedback residuals alongside
    # the Adam state (see make_dp_opt_state); other modes get plain state.
    opt_state = make_dp_opt_state(opt, params, mesh, grad_comm=grad_comm)
    batch_sh = None

    def put_batch(b):
        nonlocal batch_sh
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        n = batch["tokens"].shape[0]
        if cfg.modality == "vision":
            batch["patch_embeds"] = vision_patch_stub(
                jax.random.PRNGKey(7), n, cfg.n_patches, cfg.d_model)
        if cfg.modality == "audio":
            batch["frames"] = audio_frame_stub(
                jax.random.PRNGKey(7), n, cfg.enc_len, cfg.d_model)
        if batch_sh is None:
            batch_sh = SH.shardings_for(SH.batch_specs(batch, mesh), mesh)
        return jax.tree.map(jax.device_put, batch, batch_sh)

    trainer = Trainer(model, opt, train_step, data, ckpt_dir=ckpt_dir,
                      put_batch=put_batch, log_every=log_every)
    return trainer, TrainState(params, opt_state)


def fit(trainer, state, n_steps: int, mesh, grad_comm: str):
    """``trainer.fit`` under the mesh context its step needs.  GSPMD traces
    under the mesh so mesh-aware model branches (sequence parallelism,
    ``moe_impl="ep_shardmap"``) see it, same as dryrun's lowering; the
    explicit-collective DP step must trace *outside* any mesh context (see
    ``make_dp_train_step``)."""
    ctx = (jax.set_mesh(mesh) if grad_comm == "gspmd"
           else contextlib.nullcontext())
    with ctx:
        return trainer.fit(state, n_steps)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--production-mesh", action="store_true",
                    help="16x16 mesh (needs 256 devices)")
    ap.add_argument("--grad-comm", choices=GRAD_COMM_MODES, default="gspmd",
                    help="gradient-reduction path (see repro.dist)")
    ap.add_argument("--backend", choices=("",) + backend_names(), default="",
                    help="analog execution backend (default: "
                         "REPRO_ANALOG_BACKEND env or 'ref'); composes "
                         "with any --grad-comm mode")
    ap.add_argument("--device", choices=("",) + device_names(), default="",
                    help="device-model preset (repro.core.device; default: "
                         "REPRO_DEVICE env or 'paper'); composes with any "
                         "--backend / --grad-comm")
    ap.add_argument("--analog-mode", choices=("", "exact", "train", "infer"),
                    default="", help="override AnalogSpec.mode (most LM "
                    "configs default to 'exact'; pass 'train' for Alg. 1 "
                    "nonideality-aware training so --device actually acts)")
    ap.add_argument("--kernel-cache", default="",
                    help="path to a kernel tune-cache JSON "
                         "(benchmarks.kernel_tune output); Pallas block "
                         "sizes then resolve per shape from it (also: "
                         "REPRO_KERNEL_CACHE env)")
    ap.add_argument("--kernel-blocks", default="",
                    help="force per-kernel Pallas blocks, e.g. "
                         "'fused_matmul_nladc=128x128x512,nladc=256x512' "
                         "— overrides the tune cache (also: "
                         "REPRO_KERNEL_BLOCKS env)")
    args = ap.parse_args()
    enable_compile_cache()

    try:
        tune.configure(args.kernel_blocks, args.kernel_cache)
    except (ValueError, OSError) as e:
        ap.error(f"--kernel-blocks/--kernel-cache: {e}")

    if args.production_mesh and args.grad_comm != "gspmd":
        ap.error("--production-mesh requires --grad-comm gspmd: the "
                 "explicit-collective DP path builds its own data-parallel "
                 "(model=1) mesh and would silently drop the 16x16 layout")

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get(args.arch)
    spec_kw = {}
    if args.backend:
        spec_kw["backend"] = args.backend
    if args.device:
        spec_kw["device"] = args.device
    if args.analog_mode:
        spec_kw["mode"] = args.analog_mode
    if spec_kw:
        cfg = cfg.replace(analog=dataclasses.replace(cfg.analog, **spec_kw))
    if args.device and cfg.analog.mode == "exact":
        print(f"[train] note: --device {args.device} is inert in "
              "analog mode 'exact' (no noise stages act); pass "
              "--analog-mode train|infer")
    # One optimizer instance (scheduled over --steps) for every grad-comm
    # mode, so gspmd vs psum/hierarchical/int8 differ only in the gradient
    # path, not the LR schedule.
    model = build(cfg)
    opt = make_optimizer(cfg, total_steps=args.steps)

    if args.grad_comm == "gspmd":
        mesh = (make_production_mesh() if args.production_mesh
                else make_host_mesh())
    else:
        # Explicit-collective DP: the elastic planner picks the largest
        # (data, model=1) mesh whose data axis divides the global batch.
        plan = plan_for_devices(len(jax.devices()),
                                global_batch=args.batch, model_parallel=1)
        mesh = build_mesh(plan)
        used = plan.new_shape["data"] * plan.new_shape["model"]
        if used < len(jax.devices()):
            print(f"[train] note: data axis must divide --batch "
                  f"{args.batch}; using {used} of {len(jax.devices())} "
                  "devices")

    trainer, state = make_trainer(
        model, opt, mesh, args.grad_comm,
        SyntheticLM(cfg.vocab, args.seq, args.batch), seed=0,
        ckpt_dir=args.ckpt_dir)
    fit(trainer, state, args.steps, mesh, args.grad_comm)
    print("[train] done; final loss:",
          trainer.history[-1]["loss"] if trainer.history else "n/a")


if __name__ == "__main__":
    main()
