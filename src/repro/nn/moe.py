"""Mixture-of-Experts: token-choice top-k routing with capacity + shared experts.

Deepseek-MoE / Moonlight style fine-grained MoE: 64 routed experts (top-6)
plus always-on shared experts.  Dispatch uses the sort-based capacity
formulation (no (N, E, C) one-hot tensors):

  1. top-k per token -> (token, expert, gate) slot triples;
  2. stable-sort slots by expert; position-within-expert via exclusive
     cumsum of expert counts; slots beyond capacity C are dropped;
  3. tokens gathered into an (E, C, d) buffer (explicitly sharded
     ``experts -> model`` = expert parallelism), per-expert SwiGLU einsum,
     weighted scatter-add back.

Router logits are float32 over every expert.  The routing softmax stays
full precision (not an elementwise bijection — see DESIGN
§Arch-applicability); the expert gate activation is NL-ADC'd.  A sigmoid
router (``router_score='sigmoid'``: DeepSeek-V3's ``noaux_tc``, Moonlight)
*is* elementwise and gets the NL-ADC treatment; a per-expert correction
bias is added for the choice only, and the chosen scores, without it, are
normalised and scaled by ``routed_scaling_factor``.

A layer may hold only its chip's share of an expert-parallel deployment
(``w_gate`` and friends stack ``n_held`` experts, ``expert_offset``
onward): it routes over all experts and computes the part of the result
its own experts give; pairs routed elsewhere contribute nothing here.

Decode (:func:`moe_decode`, the serving path) is dropless: every held
expert takes every token of the call, weighted by its routing weight (0
where not routed), so no (token, expert) pair is dropped at any batch or
skew.  An expert takes a token at most once, so the call's tokens are the
capacity that never drops; the decode call's few rows cost no more than
the routed ones, since the expert FFN is bound by reading its weights.

The pjit/GSPMD version here is the paper-faithful baseline; the shard_map
all-to-all expert-parallel variant lives in repro.dist.ep and is a §Perf
iteration.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.analog_layer import AnalogActivation, moe_gate_nladc
from repro.nn import layers as L
from repro.obs import trace as otrace


def _maybe_shard(x, spec: P):
    """Apply a sharding constraint only when a mesh with the axes exists.

    Keeps the layer usable in single-device smoke tests while pinning the
    expert-parallel layout under the production mesh.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)
    if any(ax not in names for ax in jax.tree.leaves(tuple(spec)) or []):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def moe_init(key, d_model: int, d_ff: int, n_experts: int,
             n_shared: int, kind: str = "swiglu", dtype=jnp.float32, *,
             n_held: int = 0, correction_bias: bool = False):
    """Router over ``n_experts``; ``n_held`` (0: all) routed experts
    stacked (E, d, ff) / (E, ff, d); the noaux_tc correction bias when
    ``correction_bias``; the shared experts as one MLP."""
    kr, ke, ks = jax.random.split(key, 3)
    scale = 1.0 / math.sqrt(d_model)
    n_held = n_held or n_experts
    p = {
        "router": L.trunc_normal(kr, (d_model, n_experts), 1.0, dtype),
        "w_gate": scale * jax.random.normal(ke, (n_held, d_model, d_ff),
                                            dtype),
        "w_up": scale * jax.random.normal(
            jax.random.fold_in(ke, 1), (n_held, d_model, d_ff), dtype),
        "w_down": scale * jax.random.normal(
            jax.random.fold_in(ke, 2), (n_held, d_ff, d_model), dtype) \
            / math.sqrt(d_ff / d_model),
    }
    if correction_bias:
        p["e_score_correction_bias"] = jnp.zeros((n_experts,), dtype)
    if n_shared > 0:
        from repro.nn.mlp import mlp_init
        p["shared"] = mlp_init(ks, d_model, n_shared * d_ff, kind, dtype)
    return p


def router_gates(logits, top_k: int, score: str,
                 router_act: Optional[AnalogActivation], bias=None,
                 scaling: float = 1.0):
    """Top-k gates. softmax: probs then top-k; sigmoid (noaux_tc): NL-ADC'd
    scores, top-k of the scores plus ``bias`` (ties to the lowest index),
    the chosen scores without it normalised and scaled by ``scaling``
    (DeepSeek-V3/Moonlight convention).

    Public: shared by the GSPMD path below and the expert-parallel
    shard_map path (:mod:`repro.dist.ep`), which must route identically."""
    if score == "sigmoid":
        probs = (router_act(logits) if router_act is not None
                 else jax.nn.sigmoid(logits))
        choice = probs if bias is None else probs + bias.astype(probs.dtype)
        _, idx = jax.lax.top_k(choice, top_k)
        gates = jnp.take_along_axis(probs, idx, axis=-1)
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20) \
            * scaling
    else:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gates, idx = jax.lax.top_k(probs, top_k)
    return gates.astype(logits.dtype), idx, (
        probs if score != "sigmoid" else
        jax.nn.softmax(logits.astype(jnp.float32), axis=-1))


def route(p, xf, *, top_k: int, router_score: str,
          router_act: Optional[AnalogActivation], scaling: float = 1.0):
    """xf (N, d) -> (gates, idx, probs): float32 router logits over every
    expert, then :func:`router_gates`."""
    with jax.named_scope(otrace.SCOPE_ROUTER):
        logits = jnp.matmul(xf.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        return router_gates(logits, top_k, router_score, router_act,
                            bias=p.get("e_score_correction_bias"),
                            scaling=scaling)


def aux_load_balance_loss(probs_f32, idx, n_experts: int):
    """Switch-style load-balance auxiliary loss (mean prob x mean load)."""
    n = probs_f32.shape[0]
    load = jnp.zeros((n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    load = load / jnp.maximum(jnp.sum(load), 1.0)
    imp = jnp.mean(probs_f32, axis=0)
    return n_experts * jnp.sum(imp * load)


def dispatch_plan(idx, gates, n_tokens: int, n_experts: int, capacity: int):
    """Sort-based slot assignment shared by the GSPMD and EP paths.

    Returns (st, sg, dest, valid): source token, gate weight, destination
    slot in the flattened (E*C [+1 overflow]) buffer, and the
    within-capacity mask, one entry per (token, expert) routing slot.
    Expert id ``n_experts`` (an expert held elsewhere) gets no slot.
    """
    top_k = idx.shape[-1]
    slot_expert = idx.reshape(-1)                       # (N*k,)
    slot_token = jnp.repeat(jnp.arange(n_tokens), top_k)  # (N*k,)
    slot_gate = gates.reshape(-1)
    order = jnp.argsort(slot_expert, stable=True)
    se = slot_expert[order]
    st = slot_token[order]
    sg = slot_gate[order]
    counts = jnp.zeros((n_experts + 1,), jnp.int32).at[se].add(1)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(se.shape[0], dtype=jnp.int32) - offsets[se]
    valid = (pos_in_e < capacity) & (se < n_experts)
    dump = n_experts * capacity                          # overflow slot
    dest = jnp.where(valid, se * capacity + jnp.minimum(pos_in_e,
                                                        capacity - 1), dump)
    return st, sg, dest, valid


def gather_expert_buffer(xf, st, dest, valid, n_experts: int, capacity: int):
    """Gather routed tokens into the (E, C, d) expert input buffer."""
    d = xf.shape[-1]
    token_for_slot = jnp.full((n_experts * capacity + 1,), 0, jnp.int32)
    token_for_slot = token_for_slot.at[dest].set(st)
    slot_used = jnp.zeros((n_experts * capacity + 1,), xf.dtype)
    slot_used = slot_used.at[dest].set(
        jnp.where(valid, 1.0, 0.0).astype(xf.dtype))
    x_buf = xf[token_for_slot[:-1]] * slot_used[:-1, None]
    return x_buf.reshape(n_experts, capacity, d)


def combine_expert_buffer(h, xf, st, sg, dest, valid):
    """Weighted scatter-add of expert outputs back onto the tokens."""
    n_slots = h.shape[0] * h.shape[1]
    h_flat = h.reshape(n_slots, h.shape[-1])
    contrib = h_flat[jnp.minimum(dest, n_slots - 1)] \
        * (sg * valid.astype(sg.dtype))[:, None].astype(h_flat.dtype)
    return jnp.zeros_like(xf).at[st].add(contrib.astype(xf.dtype))


def expert_capacity(n_tokens: int, top_k: int, n_experts: int,
                    capacity_factor: float) -> int:
    return max(int(math.ceil(n_tokens * top_k / n_experts
                             * capacity_factor)), top_k)


def _held_ids(idx, offset: int, n_held: int):
    """Routed expert ids -> ids among the held experts; ``n_held`` for
    those held elsewhere."""
    local = idx - offset
    return jnp.where((local >= 0) & (local < n_held), local, n_held)


def expert_ffn(p, x_buf, act: AnalogActivation, *, key=None):
    """SwiGLU of each held expert over its buffer: (E, C, d) -> (E, C, d).
    The gate einsum + NL-ADC pair is one fused vmapped kernel on pallas."""
    gate_h = moe_gate_nladc(x_buf, p["w_gate"], act, key=key)
    up_h = jnp.einsum("ecd,edf->ecf", x_buf, p["w_up"].astype(x_buf.dtype))
    return jnp.einsum("ecf,efd->ecd", gate_h * up_h,
                      p["w_down"].astype(x_buf.dtype))


def _shared(p, xf, act, key):
    from repro.nn.mlp import mlp_apply

    with jax.named_scope(otrace.SCOPE_SHARED_EXPERTS):
        return mlp_apply(p["shared"], xf, "swiglu", act, key=key)


def moe_apply(p, x, *, top_k: int, capacity_factor: float,
              act: AnalogActivation, router_score: str = "softmax",
              router_act: Optional[AnalogActivation] = None,
              key=None, ep_axis: Optional[str] = "model",
              return_aux: bool = False, expert_offset: int = 0,
              routed_scaling: float = 1.0):
    """x: (..., d) -> (..., d).  Flattens leading dims for routing; each
    held expert takes at most its capacity of the call's pairs (the
    full-sequence path)."""
    orig_shape = x.shape
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    n_experts = p["router"].shape[-1]
    n_held = p["w_gate"].shape[0]

    gates, idx, probs_f32 = route(p, xf, top_k=top_k,
                                  router_score=router_score,
                                  router_act=router_act,
                                  scaling=routed_scaling)

    # --- slot assignment (sort by expert, capacity-crop) ---
    capacity = expert_capacity(n, top_k, n_experts, capacity_factor)
    st, sg, dest, valid = dispatch_plan(
        _held_ids(idx, expert_offset, n_held), gates, n, n_held, capacity)

    with jax.named_scope(otrace.SCOPE_HELD_EXPERTS):
        # --- dispatch: gather tokens into the (E, C, d) expert buffer ---
        x_buf = gather_expert_buffer(xf, st, dest, valid, n_held, capacity)
        if ep_axis is not None:
            x_buf = _maybe_shard(x_buf, P(ep_axis, None, None))
        # --- expert FFN (EP einsum over the sharded expert axis) ---
        h = expert_ffn(p, x_buf, act, key=key)
        if ep_axis is not None:
            h = _maybe_shard(h, P(ep_axis, None, None))

    # --- combine: weighted scatter-add back to tokens ---
    with jax.named_scope(otrace.SCOPE_COMBINE):
        out = combine_expert_buffer(h, xf, st, sg, dest, valid)

    # --- shared experts (always-on) ---
    if "shared" in p:
        out = out + _shared(p, xf, act, key)

    out = out.reshape(orig_shape)
    if return_aux:
        aux = aux_load_balance_loss(probs_f32, idx, n_experts)
        return out, aux
    return out


def moe_decode(p, x, *, top_k: int, act: AnalogActivation,
               router_score: str = "softmax",
               router_act: Optional[AnalogActivation] = None, key=None,
               expert_offset: int = 0, routed_scaling: float = 1.0):
    """Dropless MoE of a decode call: x (..., d) -> (out (..., d), pairs
    (N, 2) int32), ``pairs`` holding each token's routed pairs and those
    routed to a held expert.

    Every held expert takes every token and the combine weighs each
    expert's output by the token's routing weight for it, 0 where the
    token was not routed there: nothing is dropped, whatever the batch or
    the skew."""
    orig_shape = x.shape
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    n_held = p["w_gate"].shape[0]

    gates, idx, _ = route(p, xf, top_k=top_k, router_score=router_score,
                          router_act=router_act, scaling=routed_scaling)
    held = _held_ids(idx, expert_offset, n_held)
    # (N, E_held): the routing weight of each token for each held expert
    weight = jnp.sum(jnp.where(held[..., None] == jnp.arange(n_held),
                               gates[..., None], 0.0), axis=1)
    with jax.named_scope(otrace.SCOPE_HELD_EXPERTS):
        h = expert_ffn(p, jnp.broadcast_to(xf, (n_held,) + xf.shape), act,
                       key=key)
    with jax.named_scope(otrace.SCOPE_COMBINE):
        out = jnp.einsum("etd,te->td", h.astype(jnp.float32), weight)
    out = out.astype(xf.dtype)
    if "shared" in p:
        out = out + _shared(p, xf, act, key)
    pairs = jnp.stack([jnp.full(held.shape[:1], top_k, jnp.int32),
                       jnp.sum(held < n_held, axis=-1, dtype=jnp.int32)],
                      axis=-1)
    return out.reshape(orig_shape), pairs
