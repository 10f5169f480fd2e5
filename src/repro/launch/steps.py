"""Step factories: train_step / prefill_step / serve_step for any arch.

These are the functions the dry-run lowers and the real drivers execute.
All randomness is derived from an int32 ``seed`` input so steps take only
arrays (ShapeDtypeStruct-friendly).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.nn.model import build
from repro.train import optim


def make_optimizer(cfg: ModelConfig, total_steps: int = 10000) -> optim.Adam:
    return optim.Adam(
        lr=optim.cosine_schedule(3e-4, warmup_steps=min(500, total_steps // 10 + 1),
                                 total_steps=total_steps),
        weight_decay=0.1,
        grad_clip_norm=1.0,
    )


def make_train_step(model, optimizer: optim.Adam,
                    *, remat: bool = True) -> Callable:
    def train_step(params, opt_state, batch, seed):
        key = jax.random.PRNGKey(seed)

        def loss_fn(p):
            return model.loss(p, batch, key=key, remat=remat)

        grads, metrics = jax.grad(loss_fn, has_aux=True)(params)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        metrics = dict(metrics, grad_norm=optim.global_norm(grads))
        return new_params, new_opt, metrics

    return train_step


def make_dp_train_step(model, optimizer: optim.Adam, mesh,
                       *, grad_comm: str = "psum",
                       remat: bool = True) -> Callable:
    """Data-parallel train step with *explicit* gradient collectives.

    The GSPMD train step leaves gradient reduction to the partitioner; this
    variant shard_maps the whole step over the mesh's data-like axes so the
    reduction path is chosen by ``grad_comm``:

    * ``psum``         — flat all-reduce (the GSPMD-equivalent baseline);
    * ``hierarchical`` — pod-local reduce-scatter -> cross-pod all-reduce ->
      all-gather (:mod:`repro.dist.collectives`);
    * ``int8``         — shared-scale int8 wire format with **error
      feedback** (:func:`repro.dist.compress.compressed_psum_ef`): the
      per-replica quantization residual rides in the optimizer state
      (``opt_state = {"opt": adam, "ef": residuals}``, leading dim =
      replica, sharded over the data-like axes — build it with
      :func:`make_dp_opt_state`), so the time-averaged reduced gradient
      is unbiased over long runs.

    Params/optimizer state are replicated (the int8 EF residual is the one
    per-replica exception); the batch is sharded on dim 0 over the
    data-like axes (the caller guarantees divisibility — see
    :func:`repro.ft.elastic.plan_for_devices`).  Trace this step *outside*
    any mesh context: inside the shard_map body the model must not emit
    sharding constraints.

    Equivalence to the plain (GSPMD) step: exact for the CE term under any
    label masking (per-shard gradients are valid-token-share weighted, see
    ``tests/test_dist_edges``); the MoE router aux loss is the uniform
    average of per-shard aux over local tokens — the standard DP
    approximation of the global statistic.
    """
    from jax.sharding import PartitionSpec as P

    from repro.dist.collectives import grad_allreduce, replica_index
    from repro.dist.compress import compressed_psum_ef

    pod_axis = "pod" if "pod" in mesh.axis_names else None
    axes = (pod_axis, "data") if pod_axis else ("data",)
    use_ef = grad_comm == "int8"

    def local_step(params, opt_state, batch, seed):
        if use_ef:
            inner_opt = opt_state["opt"]
            # local residual shard: (1, ...) -> (...)
            ef_res = jax.tree.map(lambda r: r[0], opt_state["ef"])
        else:
            inner_opt = opt_state
        # Per-replica key: fold in the linearized replica index so model
        # noise is independent across shards (matching the GSPMD step's
        # one-key-over-the-global-batch draws in distribution).
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 replica_index(axes))
        n_rep = jax.lax.psum(1, axes)
        # GSPMD equivalence on masked data: the plain step normalizes the
        # CE term by the GLOBAL valid-token count, so each shard's mean CE
        # is weighted by its valid-token share before the sum (exact; the
        # share depends only on the labels, not on params).  The MoE
        # router aux loss is different: every token routes regardless of
        # label masking and the loss is a *nonlinear* global statistic, so
        # it gets the standard DP treatment — per-shard aux over local
        # tokens, averaged uniformly (1/n_rep) — which approximates (not
        # reproduces) the GSPMD-global aux.
        n_valid = jnp.sum(batch["labels"] >= 0).astype(jnp.float32)
        share = n_valid / jnp.maximum(jax.lax.psum(n_valid, axes), 1.0)

        def loss_fn(p):
            total, m = model.loss(p, batch, key=key, remat=remat)
            obj = share * m["loss"]
            if "aux_loss" in m:
                obj = obj + model.cfg.router_aux_coef * m["aux_loss"] / n_rep
            return obj, m

        grads, metrics = jax.grad(loss_fn, has_aux=True)(params)
        if use_ef:
            grads, new_res = compressed_psum_ef(grads, ef_res, axes)
        else:
            grads = grad_allreduce(grads, mode=grad_comm, data_axis="data",
                                   pod_axis=pod_axis)
        metrics = {k: (jax.lax.psum(v, axes) if k == "tokens"
                       else jax.lax.pmean(v, axes) if k == "aux_loss"
                       else jax.lax.psum(v * share, axes))
                   for k, v in metrics.items()}
        new_params, new_opt = optimizer.update(grads, inner_opt, params)
        metrics = dict(metrics, grad_norm=optim.global_norm(grads))
        if use_ef:
            new_opt = {"opt": new_opt,
                       "ef": jax.tree.map(lambda r: r[None], new_res)}
        return new_params, new_opt, metrics

    opt_spec = {"opt": P(), "ef": P(axes)} if use_ef else P()
    return jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), opt_spec, P(axes), P()),
        out_specs=(P(), opt_spec, P()),
        check_vma=False)


def make_dp_opt_state(optimizer: optim.Adam, params, mesh,
                      *, grad_comm: str = "gspmd"):
    """Optimizer state for a train step, shaped for the grad-comm mode.

    ``int8`` appends the per-replica error-feedback residual pytree
    (``{"opt": adam_state, "ef": residuals}``; residual leaves are stacked
    ``(n_replicas, *param_shape)`` f32, sharded over the data-like axes by
    the step's in_specs).  Every other mode returns plain Adam state.

    The state is made in place on ``mesh``: each Adam moment takes its
    param's layout (replicated where the param is not laid out on a mesh)
    and the step count is replicated.  Zero-filled state depends on no
    input's values, so a plain ``jit`` would put all of it on the first
    device.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    replicated = NamedSharding(mesh, P())
    moments = jax.tree.map(
        lambda p: p.sharding if isinstance(p.sharding, NamedSharding)
        else replicated, params)
    opt_state = jax.jit(optimizer.init, out_shardings=optim.OptState(
        count=replicated, mu=moments, nu=moments))(params)
    if grad_comm != "int8":
        return opt_state
    axes = tuple(ax for ax in ("pod", "data") if ax in mesh.axis_names)
    n_rep = math.prod(mesh.shape[ax] for ax in axes)
    ef = jax.jit(
        lambda ps: jax.tree.map(
            lambda p: jnp.zeros((n_rep,) + p.shape, jnp.float32), ps),
        out_shardings=NamedSharding(mesh, P(axes)))(params)
    return {"opt": opt_state, "ef": ef}


def make_prefill_step(model) -> Callable:
    def prefill_step(params, batch):
        tokens = batch["tokens"]
        extra = {k: v for k, v in batch.items() if k != "tokens"}
        logits = model.prefill(params, tokens, extra or None)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits

    return prefill_step


def make_serve_step(model) -> Callable:
    """One decode step: token in, greedy next token + updated state out."""

    def serve_step(params, state, tokens):
        logits, new_state = model.decode_step(params, state, tokens)
        next_token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_token, new_state

    return serve_step


def build_all(cfg: ModelConfig):
    """(model, train_step, prefill_step, serve_step) for one config."""
    model = build(cfg)
    opt = make_optimizer(cfg)
    return (model, make_train_step(model, opt), make_prefill_step(model),
            make_serve_step(model))
