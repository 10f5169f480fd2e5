"""Expert-parallel MoE via shard_map + all-to-all (§Perf iteration).

The GSPMD path (:func:`repro.nn.moe.moe_apply`) routes *globally*: every
device materializes the full (E, C, d) dispatch buffer and the partitioner
turns the expert einsum into whatever collectives it likes.  This module is
the hand-scheduled equivalent: tokens stay sharded (batch over the data-like
axes, sequence over ``model``), each device routes only its local tokens,
and two ``all_to_all`` exchanges move exactly the routed activations to the
expert-owner shards and back — the DeepSpeed-MoE / Switch dispatch pattern.

Numerics match the GSPMD path to float tolerance whenever nothing is
capacity-dropped (the per-(expert, sender) capacity differs from the global
per-expert capacity only under overflow), which ``tests/test_dist.py``
asserts at rel < 2e-4 on an 8-device mesh.

Falls back to the GSPMD path when no mesh with a >1 ``model`` axis is
visible at trace time, or when shapes don't divide the mesh (e.g. the S=1
decode step), so ``moe_impl="ep_shardmap"`` configs stay runnable on a
single host.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.analog_layer import AnalogActivation, moe_gate_nladc
from repro.dist import collectives as COLL
from repro.dist import sharding as SH
from repro.nn import moe as MOE


def _mesh_info(ep_axis: str):
    """(mesh, model_size, data_axes) if an EP-capable mesh is visible."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or ep_axis not in mesh.axis_names:
        return None
    return mesh, dict(mesh.shape)[ep_axis], SH.data_axes(mesh)


def moe_apply_ep(p, x, *, top_k: int, capacity_factor: float,
                 act: AnalogActivation, router_score: str = "softmax",
                 router_act: Optional[AnalogActivation] = None,
                 key=None, ep_axis: str = "model",
                 return_aux: bool = False, expert_offset: int = 0,
                 routed_scaling: float = 1.0):
    """Drop-in for :func:`repro.nn.moe.moe_apply` with explicit all-to-all.

    x: (B, S, d).  Requires every expert held here, E % model, S % model,
    B % data to all be 0 for the shard_map path; otherwise delegates to
    the GSPMD implementation.
    """
    info = _mesh_info(ep_axis)
    n_experts = p["router"].shape[-1]
    usable = (info is not None and x.ndim == 3)
    if usable:
        mesh, m_size, baxes = info
        sizes = dict(mesh.shape)
        d_size = 1
        for ax in baxes:
            d_size *= sizes[ax]
        usable = (m_size > 1
                  and p["w_gate"].shape[0] == n_experts
                  and n_experts % m_size == 0
                  and x.shape[1] % m_size == 0
                  and x.shape[0] % d_size == 0)
    if not usable:
        return MOE.moe_apply(
            p, x, top_k=top_k, capacity_factor=capacity_factor, act=act,
            router_score=router_score, router_act=router_act, key=key,
            return_aux=return_aux, expert_offset=expert_offset,
            routed_scaling=routed_scaling)

    tok_axes = baxes + (ep_axis,)          # axes that partition the tokens

    def body(xl, pl, kl):
        b, s, d = xl.shape
        xf = xl.reshape(-1, d)
        n = xf.shape[0]
        key_l = kl[0] if kl else None
        if key_l is not None:
            # Per-shard key so analog-activation noise is independent
            # across shards, matching the GSPMD path's one-draw-over-the-
            # global-buffer distribution.
            key_l = jax.random.fold_in(key_l,
                                       COLL.replica_index(tok_axes))

        gates, idx, probs_f32 = MOE.route(
            pl, xf, top_k=top_k, router_score=router_score,
            router_act=router_act, scaling=routed_scaling)

        capacity = MOE.expert_capacity(n, top_k, n_experts, capacity_factor)
        st, sg, dest, valid = MOE.dispatch_plan(idx, gates, n, n_experts,
                                                capacity)
        x_buf = MOE.gather_expert_buffer(xf, st, dest, valid, n_experts,
                                         capacity)                # (E, C, d)

        # --- all-to-all: slots travel to their expert-owner shard ---
        e_loc = pl["w_gate"].shape[0]
        send = x_buf.reshape(m_size, e_loc, capacity, d)
        recv = jax.lax.all_to_all(send, ep_axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        # (peer, E_loc, C, d) -> (E_loc, peer*C, d): per-expert batches over
        # every sender's slots.
        xb = recv.transpose(1, 0, 2, 3).reshape(e_loc,
                                                m_size * capacity, d)

        # --- local expert SwiGLU on the owned experts (gate einsum +
        # NL-ADC fused per expert on pallas, same helper as nn.moe) ---
        gate_h = moe_gate_nladc(xb, pl["w_gate"], act, key=key_l)
        up_h = jnp.einsum("end,edf->enf", xb, pl["w_up"].astype(xb.dtype))
        h = jnp.einsum("enf,efd->end", gate_h * up_h,
                       pl["w_down"].astype(xb.dtype))

        # --- return trip + local combine ---
        hb = h.reshape(e_loc, m_size, capacity, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(hb, ep_axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        h_full = back.reshape(n_experts, capacity, d)
        out = MOE.combine_expert_buffer(h_full, xf, st, sg, dest, valid)

        if "shared" in pl:
            from repro.nn.mlp import mlp_apply

            out = out + mlp_apply(pl["shared"], xf, "swiglu", act, key=key_l)
        out = out.reshape(b, s, d)
        if not return_aux:
            return out, jnp.zeros((), jnp.float32)

        # Global load-balance loss: reduce the per-shard count/importance
        # sums over every token-partitioning axis, then form the Switch
        # loss exactly as the GSPMD path does over the full token set.
        load = jnp.zeros((n_experts,), jnp.float32) \
            .at[idx.reshape(-1)].add(1.0)
        load = jax.lax.psum(load, tok_axes)
        load = load / jnp.maximum(jnp.sum(load), 1.0)
        imp = jax.lax.psum(jnp.sum(probs_f32, axis=0), tok_axes) \
            / jax.lax.psum(jnp.float32(n), tok_axes)
        aux = n_experts * jnp.sum(imp * load)
        return out, aux

    # Expert stacks shard over the model axis, everything else — router,
    # shared experts — replicates; derived from the same rule table as the
    # parameter layout so the two cannot drift.
    param_specs = SH.ep_param_specs(p, ep_axis)
    x_spec = P(baxes, ep_axis, None)
    # ``key`` rides in a length-0/1 tuple so specs stay pytree-shaped.
    key_tuple = (key,) if key is not None else ()
    key_specs = tuple(P(*(None,) * jnp.asarray(k).ndim) for k in key_tuple)

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, param_specs, key_specs),
        out_specs=(x_spec, P()),
        check_vma=False)
    out, aux = mapped(x, p, key_tuple)
    if return_aux:
        return out, aux
    return out
