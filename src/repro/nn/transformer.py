"""Decoder-only LM assembly: scan-over-layers, prefill/decode, all families.

One :class:`LM` object covers the dense / moe / hybrid / ssm families (the
enc-dec whisper model lives in :mod:`repro.nn.encdec`):

* **scan-over-layers** with stacked params keeps HLO size and compile time
  independent of depth (granite-34b is 88 layers);
* per-family block bodies: ``attn+mlp``, ``attn+moe``, ``rec+mlp`` (RG-LRU),
  ``ssd``;  the hybrid 1-attn:2-recurrent pattern scans over (rec,rec,attn)
  groups with the remainder layers unscanned; a MoE model's leading dense
  layers (``n_dense_layers``, MLP width ``dense_d_ff``) run unscanned
  before its scanned MoE stack;
* attention is GQA, or latent attention (MLA) where ``kv_lora_rank`` is
  set, with a latent decode cache;
* a single NL-ADC activation object (host-precomputed ramp) is shared by all
  layers — it is a closure constant, not a traced param;
* decode carries a stacked per-layer cache pytree through the same scan.

The remat policy is applied by the caller (train step) via ``jax.checkpoint``
around :meth:`LM.loss`'s per-layer body — exposed as ``remat`` here.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.analog_layer import AnalogActivation, AnalogConfig
from repro.nn import attention as A
from repro.nn import layers as L
from repro.nn import moe as MOE
from repro.nn import rglru as RG
from repro.nn import ssd as SSD
from repro.nn.mlp import make_activation, mlp_apply, mlp_init, mlp_type_for
from repro.obs import trace as otrace


class LM:
    """A decoder-only language model for one :class:`ModelConfig`."""

    def __init__(self, cfg: ModelConfig):
        assert cfg.family in ("dense", "moe", "hybrid", "ssm"), cfg.family
        self.cfg = cfg
        self.compute_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" \
            else jnp.float32
        self.mlp_kind = mlp_type_for(cfg)
        self.act = make_activation(cfg)                     # hidden NL-ADC
        # One resolved AnalogConfig (backend + device model) shared by every
        # auxiliary NL-ADC: ramps are programmed once per deployment here,
        # not per layer — all layers read the same simulated chip.
        acfg = AnalogConfig.from_spec(cfg.analog)
        self.sigmoid_act = AnalogActivation("sigmoid", acfg)
        self.softplus_act = AnalogActivation("softplus", acfg)
        self.silu_act = AnalogActivation("silu", acfg)
        # Eagerly realize the hidden activation's per-col-tile threshold
        # bank (width = d_ff, the MLP gate output) so lifecycle consumers
        # (RecalScheduler) see the bank inventory before the first trace;
        # other widths realize lazily at trace time (same keyed draws).
        self.act.bank_for(cfg.d_ff)
        # kv_chunk for flash-style attention; smaller for huge sequences.
        self.kv_chunk = 1024
        # Analysis mode: unroll layer/kv scans into Python loops so XLA
        # cost_analysis counts every iteration (dry-run §Roofline only).
        self.unroll = False

    def _maybe_scan(self, body, carry, xs):
        if not self.unroll:
            return jax.lax.scan(body, carry, xs)
        n = jax.tree.leaves(xs)[0].shape[0]
        ys = []
        for i in range(n):
            xi = jax.tree.map(lambda a: a[i], xs)
            carry, y = body(carry, xi)
            ys.append(y)
        if ys and all(y is None for y in ys):
            return carry, None
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
        return carry, ys

    # -- sequence parallelism (§Perf C5) --------------------------------

    def _sp_axes(self):
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty or "model" not in mesh.axis_names:
            return None
        return tuple(ax for ax in ("pod", "data") if ax in mesh.axis_names)

    def _sp_shard(self, x):
        """Residual layout: (batch->(pod,data), seq->model, d)."""
        baxes = self._sp_axes()
        if baxes is None or not self.cfg.sequence_parallel or x.ndim != 3:
            return x
        from jax.sharding import PartitionSpec as P

        return jax.lax.with_sharding_constraint(
            x, P(baxes, "model", None))

    def _sp_full(self, x):
        """Gather the sequence before token-mixing blocks (AG)."""
        baxes = self._sp_axes()
        if baxes is None or not self.cfg.sequence_parallel or x.ndim != 3:
            return x
        from jax.sharding import PartitionSpec as P

        return jax.lax.with_sharding_constraint(x, P(baxes, None, None))

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------

    def _block_init(self, key, kind: str):
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        d = cfg.d_model
        if kind == "ssd":
            return {
                "norm": L.rmsnorm_init(d),
                "ssd": SSD.ssd_init(ks[0], d, expand=cfg.ssm_expand,
                                    headdim=cfg.ssm_headdim,
                                    d_state=cfg.ssm_state,
                                    conv_width=cfg.conv_width),
            }
        if kind == "rec":
            return {
                "norm1": L.rmsnorm_init(d),
                "rec": RG.rglru_init(ks[0], d, cfg.lru_width or d,
                                     cfg.conv_width,
                                     gate_blocks=cfg.lru_gate_blocks),
                "norm2": L.rmsnorm_init(d),
                "mlp": mlp_init(ks[1], d, cfg.d_ff, self.mlp_kind),
            }
        if cfg.kv_lora_rank:
            attn = A.mla_init(ks[0], d, cfg.n_heads, cfg.head_dim,
                              cfg.qk_rope_head_dim, cfg.kv_lora_rank)
        else:
            attn = A.attn_init(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim, qkv_bias=cfg.qkv_bias)
        blk = {"norm1": L.rmsnorm_init(d), "attn": attn,
               "norm2": L.rmsnorm_init(d)}
        if kind == "moe_attn":
            blk["moe"] = MOE.moe_init(
                ks[1], d, cfg.d_ff, cfg.n_experts, cfg.n_shared_experts,
                self.mlp_kind, n_held=cfg.n_held,
                correction_bias=cfg.router_score == "sigmoid")
        else:
            d_ff = cfg.dense_d_ff if cfg.family == "moe" else cfg.d_ff
            blk["mlp"] = mlp_init(ks[1], d, d_ff, self.mlp_kind)
        return blk

    def act_param_leaves(self) -> Dict[str, Tuple[str, ...]]:
        """NL-ADC activation -> keystr substrings of the param leaves whose
        crossbar columns feed it.

        Only the hidden activation (``act``) maps cleanly: it digitizes the
        MLP gate projection's output (width d_ff), so the gate/up matrices
        of every family's MLP — and the MoE expert / shared-expert
        equivalents — are the crossbars behind its threshold banks.  The
        auxiliary sigmoid/softplus/silu activations ride inside recurrence
        cells at assorted widths and are deliberately unmapped: a weight
        refresh they trigger falls back to the chip-wide re-program.
        Consumed by ``ServingEngine`` for per-tile weight refresh.
        """
        return {"act": ("['mlp']['wi_gate']['w']", "['mlp']['wi']['w']",
                        "['moe']['w_gate']", "['moe']['w_up']",
                        "['mlp']['wi_up']['w']",
                        "['moe']['shared']['wi_gate']['w']",
                        "['moe']['shared']['wi_up']['w']",
                        "['moe']['shared']['wi']['w']")}

    def layer_kinds(self) -> Tuple[str, ...]:
        cfg = self.cfg
        if cfg.family == "ssm":
            return ("ssd",) * cfg.n_layers
        if cfg.family == "moe":
            n = cfg.n_dense_layers
            return ("attn",) * n + ("moe_attn",) * (cfg.n_layers - n)
        if cfg.family == "hybrid":
            return cfg._pattern()
        return ("attn",) * cfg.n_layers

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        k_embed, k_layers, k_head = jax.random.split(key, 3)
        params: Dict[str, Any] = {
            "embed": L.embedding_init(k_embed, cfg.padded_vocab, cfg.d_model),
            "final_norm": L.rmsnorm_init(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(k_head, cfg.d_model,
                                             cfg.padded_vocab)
        kinds = self.layer_kinds()
        if cfg.family == "hybrid":
            pat = cfg.block_pattern
            n_groups = cfg.n_layers // len(pat)
            tail = kinds[n_groups * len(pat):]
            gkeys = jax.random.split(k_layers, n_groups)

            def group_init(k):
                sub = jax.random.split(k, len(pat))
                return {f"b{i}_{kind}": self._block_init(sub[i], kind)
                        for i, kind in enumerate(pat)}

            params["groups"] = jax.vmap(group_init)(gkeys)
            tkeys = jax.random.split(jax.random.fold_in(k_layers, 7),
                                     max(len(tail), 1))
            params["tail"] = [self._block_init(tkeys[i], kind)
                              for i, kind in enumerate(tail)]
        else:
            n_lead = cfg.n_dense_layers
            lkeys = jax.random.split(k_layers, cfg.n_layers)
            if n_lead:
                params["lead"] = [self._block_init(lkeys[i], kinds[i])
                                  for i in range(n_lead)]
            params["layers"] = jax.vmap(
                lambda k: self._block_init(k, kinds[-1]))(lkeys[n_lead:])
        return params

    # ------------------------------------------------------------------
    # block bodies (full sequence)
    # ------------------------------------------------------------------

    def _apply_block(self, p, x, kind: str, *, positions, key=None,
                     collect_aux: bool = False):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        if kind == "ssd":
            h = L.rmsnorm_apply(p["norm"], x, cfg.norm_eps)
            x = x + SSD.ssd_apply(
                p["ssd"], h, expand=cfg.ssm_expand, headdim=cfg.ssm_headdim,
                d_state=cfg.ssm_state, chunk=cfg.ssm_chunk,
                dt_act=self.softplus_act, gate_act=self.silu_act, key=key)
            return x, aux
        if kind == "rec":
            h = self._sp_full(L.rmsnorm_apply(p["norm1"], x, cfg.norm_eps))
            x = x + self._sp_shard(RG.rglru_apply(
                p["rec"], h, self.sigmoid_act, self.act, key=key,
                scan_dtype=(jnp.bfloat16 if cfg.lru_scan_dtype == "bfloat16"
                            else jnp.float32),
                chunk=cfg.lru_chunk))
            h = self._sp_full(L.rmsnorm_apply(p["norm2"], x, cfg.norm_eps))
            x = x + self._sp_shard(
                mlp_apply(p["mlp"], h, self.mlp_kind, self.act, key=key))
            return x, aux
        # attention block (global or windowed)
        window = cfg.window if (cfg.family == "hybrid" and kind == "attn") \
            else 0
        h = self._sp_full(L.rmsnorm_apply(p["norm1"], x, cfg.norm_eps))
        if cfg.kv_lora_rank:
            with jax.named_scope(otrace.SCOPE_MLA):
                y = A.mla_attention(
                    p["attn"], h, n_heads=cfg.n_heads,
                    head_dim=cfg.head_dim, rank=cfg.kv_lora_rank,
                    rope_theta=cfg.rope_theta, eps=cfg.norm_eps,
                    positions=positions, kv_chunk=self.kv_chunk,
                    unroll=self.unroll)
        else:
            y = A.self_attention(
                p["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                window=window, positions=positions, kv_chunk=self.kv_chunk,
                unroll=self.unroll)
        x = x + self._sp_shard(y)
        h = self._sp_full(L.rmsnorm_apply(p["norm2"], x, cfg.norm_eps))
        if kind == "moe_attn":
            moe_fn = MOE.moe_apply
            if cfg.moe_impl == "ep_shardmap":
                from repro.dist.ep import moe_apply_ep as moe_fn
            out = moe_fn(
                p["moe"], h, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor, act=self.act,
                router_score=cfg.router_score, router_act=self.sigmoid_act,
                key=key, return_aux=collect_aux,
                expert_offset=cfg.expert_offset,
                routed_scaling=cfg.routed_scaling_factor)
            if collect_aux:
                out, aux = out
            x = x + out
        else:
            x = x + self._sp_shard(
                mlp_apply(p["mlp"], h, self.mlp_kind, self.act, key=key))
        return x, aux

    # ------------------------------------------------------------------
    # forward (train / prefill logits)
    # ------------------------------------------------------------------

    def embed(self, params, tokens, extra: Optional[Dict] = None):
        cfg = self.cfg
        x = L.embedding_apply(params["embed"], tokens,
                              compute_dtype=self.compute_dtype)
        if cfg.modality == "vision" and extra and "patch_embeds" in extra:
            pe = extra["patch_embeds"].astype(x.dtype)      # (B, n_patch, d)
            n_patch = pe.shape[1]
            pad = x.shape[1] - n_patch
            pe_full = jnp.pad(pe, ((0, 0), (0, pad), (0, 0)))
            is_patch = (jnp.arange(x.shape[1]) < n_patch)[None, :, None]
            x = jnp.where(is_patch, pe_full, x)
        return x

    def logits(self, params, x):
        cfg = self.cfg
        x = L.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            return L.embedding_attend(params["embed"], x)
        return L.dense_apply(params["lm_head"], x,
                             compute_dtype=self.compute_dtype) \
            .astype(jnp.float32)

    def forward(self, params, tokens, extra: Optional[Dict] = None,
                *, key=None, collect_aux: bool = False, remat: bool = False):
        """Full-sequence logits. tokens: (B, S) -> (B, S, padded_vocab)."""
        cfg = self.cfg
        x = self._sp_shard(self.embed(params, tokens, extra))
        positions = jnp.arange(tokens.shape[1])[None, :]
        total_aux = jnp.zeros((), jnp.float32)

        def scan_blocks(x, stacked, kinds_in_group):
            def body(carry, lp):
                xc, auxc, k = carry
                k_layer = None
                if k is not None:
                    k, k_layer = jax.random.split(k)
                for i, kind in enumerate(kinds_in_group):
                    sub = lp if len(kinds_in_group) == 1 \
                        else lp[f"b{i}_{kind}"]
                    xc, aux = self._apply_block(
                        sub, xc, kind, positions=positions, key=k_layer,
                        collect_aux=collect_aux)
                    auxc = auxc + aux
                return (xc, auxc, k), None

            if remat and cfg.remat_policy != "none":
                policy = (jax.checkpoint_policies.dots_saveable
                          if cfg.remat_policy == "dots"
                          else jax.checkpoint_policies.nothing_saveable)
                body = jax.checkpoint(body, policy=policy)
            (x, aux, _), _ = self._maybe_scan(
                body, (x, jnp.zeros((), jnp.float32), key), stacked)
            return x, aux

        if cfg.family == "hybrid":
            x, aux = scan_blocks(x, params["groups"], cfg.block_pattern)
            total_aux += aux
            kinds = self.layer_kinds()
            n_scanned = (cfg.n_layers // len(cfg.block_pattern)) \
                * len(cfg.block_pattern)
            for p_tail, kind in zip(params["tail"], kinds[n_scanned:]):
                x, aux = self._apply_block(p_tail, x, kind,
                                           positions=positions, key=key,
                                           collect_aux=collect_aux)
                total_aux += aux
        else:
            kinds = self.layer_kinds()
            for p_lead, kind in zip(params.get("lead", []), kinds):
                x, aux = self._apply_block(p_lead, x, kind,
                                           positions=positions, key=key,
                                           collect_aux=collect_aux)
                total_aux += aux
            x, aux = scan_blocks(x, params["layers"], (kinds[-1],))
            total_aux += aux

        logits = self.logits(params, x)
        if collect_aux:
            return logits, total_aux
        return logits

    def loss(self, params, batch: Dict, *, key=None, remat: bool = True):
        """Next-token CE loss (labels = batch['labels'], -1 = masked)."""
        cfg = self.cfg
        extra = {k: v for k, v in batch.items()
                 if k not in ("tokens", "labels")}
        out = self.forward(params, batch["tokens"], extra or None, key=key,
                           collect_aux=(cfg.family == "moe"), remat=remat)
        aux = jnp.zeros((), jnp.float32)
        if cfg.family == "moe":
            logits, aux = out
        else:
            logits = out
        labels = batch["labels"]
        valid = labels >= 0
        safe = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        nll = jnp.where(valid, nll, 0.0)
        # 'tokens' reports the TRUE valid count (0 for an all-masked batch);
        # the clamp guards only the division.  The DP train step relies on
        # this to weight shards by token share without counting phantoms.
        n_valid = jnp.sum(valid)
        loss = jnp.sum(nll) / jnp.maximum(n_valid, 1)
        total = loss + cfg.router_aux_coef * aux
        metrics = {"loss": loss, "aux_loss": aux,
                   "tokens": n_valid.astype(jnp.float32)}
        return total, metrics

    # ------------------------------------------------------------------
    # decode path
    # ------------------------------------------------------------------

    def _block_cache(self, kind: str, batch: int, max_len: int):
        cfg = self.cfg
        if kind == "ssd":
            return SSD.ssd_init_state(
                batch, cfg.d_model, expand=cfg.ssm_expand,
                headdim=cfg.ssm_headdim, d_state=cfg.ssm_state,
                conv_width=cfg.conv_width, dtype=self.compute_dtype)
        if kind == "rec":
            return RG.rglru_init_state(batch, cfg.lru_width or cfg.d_model,
                                       cfg.conv_width,
                                       dtype=self.compute_dtype)
        if cfg.kv_lora_rank:
            return A.init_mla_cache(
                batch, max_len, cfg.kv_lora_rank + cfg.qk_rope_head_dim,
                dtype=self.compute_dtype)
        window = cfg.window if (cfg.family == "hybrid" and kind == "attn") \
            else 0
        return A.init_cache(batch, max_len, cfg.n_kv_heads, cfg.head_dim,
                            window=window, dtype=self.compute_dtype,
                            quantized=(cfg.kv_cache_dtype == "int8"))

    def init_decode_state(self, batch: int, max_len: int) -> Dict:
        cfg = self.cfg
        kinds = self.layer_kinds()
        state: Dict[str, Any] = {"index": jnp.zeros((), jnp.int32)}
        if cfg.family == "hybrid":
            pat = cfg.block_pattern
            n_groups = cfg.n_layers // len(pat)

            def one_group(_):
                return {f"b{i}_{kind}": self._block_cache(kind, batch,
                                                          max_len)
                        for i, kind in enumerate(pat)}

            state["groups"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape).copy(),
                one_group(None))
            state["tail"] = [self._block_cache(kind, batch, max_len)
                             for kind in kinds[n_groups * len(pat):]]
        else:
            n_lead = cfg.n_dense_layers
            if n_lead:
                state["lead"] = [self._block_cache(kind, batch, max_len)
                                 for kind in kinds[:n_lead]]
            one = self._block_cache(kinds[-1], batch, max_len)
            n = cfg.n_layers - n_lead
            state["layers"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), one)
        if cfg.family == "moe":
            # the last step's (token, expert) pairs per row: routed, and
            # routed to a held expert (summed over layers); the engine
            # reads them with the step's tokens
            state["moe_pairs"] = jnp.zeros((batch, 2), jnp.int32)
        return state

    def _decode_block(self, p, cache_l, x, kind: str, index, *, key=None,
                      commit=None):
        """-> (x, new cache, the MoE layer's per-row pairs or None).
        ``commit``: as in :meth:`decode_step` (MLA layers only)."""
        cfg = self.cfg
        if kind == "ssd":
            h = L.rmsnorm_apply(p["norm"], x, cfg.norm_eps)
            y, new = SSD.ssd_decode(
                p["ssd"], h, cache_l, expand=cfg.ssm_expand,
                headdim=cfg.ssm_headdim, d_state=cfg.ssm_state,
                dt_act=self.softplus_act, gate_act=self.silu_act, key=key)
            return x + y, new, None
        if kind == "rec":
            h = L.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
            y, new = RG.rglru_decode(p["rec"], h, cache_l, self.sigmoid_act,
                                     self.act, key=key)
            x = x + y
            h = L.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
            x = x + mlp_apply(p["mlp"], h, self.mlp_kind, self.act, key=key)
            return x, new, None
        window = cfg.window if (cfg.family == "hybrid" and kind == "attn") \
            else 0
        h = L.rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
        if cfg.kv_lora_rank:
            with jax.named_scope(otrace.SCOPE_MLA):
                y, new = A.mla_decode(
                    p["attn"], h, cache_l, index, n_heads=cfg.n_heads,
                    head_dim=cfg.head_dim, rank=cfg.kv_lora_rank,
                    rope_theta=cfg.rope_theta, eps=cfg.norm_eps,
                    analog_backend=cfg.analog.backend, commit=commit)
        else:
            y, new = A.decode_self_attention(
                p["attn"], h, cache_l, index, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                rope_theta=cfg.rope_theta, window=window,
                analog_backend=cfg.analog.backend)
        x = x + y
        h = L.rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
        if kind == "moe_attn":
            out, pairs = MOE.moe_decode(
                p["moe"], h, top_k=cfg.top_k, act=self.act,
                router_score=cfg.router_score, router_act=self.sigmoid_act,
                key=key, expert_offset=cfg.expert_offset,
                routed_scaling=cfg.routed_scaling_factor)
            return x + out, new, pairs
        x = x + mlp_apply(p["mlp"], h, self.mlp_kind, self.act, key=key)
        return x, new, None

    @property
    def commits_rows(self) -> bool:
        """Latent-attention models take a masked prefill's per-row commit
        mask into :meth:`decode_step`; the others are row-selected after
        each step (``nn.model.prefill_cache``)."""
        return bool(self.cfg.kv_lora_rank)

    def decode_step(self, params, state: Dict, tokens, *, key=None,
                    commit=None):
        """One decode step. tokens: (B, 1) -> (logits (B, 1, V), new state).

        ``commit`` (B,) bool, for models that ``commits_rows``: rows that
        do not commit leave their latent caches as a fresh state holds them
        (zeros at the step's position; ``nn.attention.mla_decode``) and
        their pair counts as they were.  Masked prefill passes it, so that
        no step copies the whole cache to select rows."""
        cfg = self.cfg
        index = state["index"]
        x = self.embed(params, tokens)
        if cfg.family == "hybrid":
            pat = cfg.block_pattern

            def body(x, lp_cache):
                lp, cl = lp_cache
                new_cl = {}
                for i, kind in enumerate(pat):
                    name = f"b{i}_{kind}"
                    x, new_cl[name], _ = self._decode_block(
                        lp[name], cl[name], x, kind, index, key=key)
                return x, new_cl

            x, new_groups = self._maybe_scan(
                body, x, (params["groups"], state["groups"]))
            new_state = {"index": index + 1, "groups": new_groups,
                         "tail": []}
            kinds = self.layer_kinds()
            n_scanned = (cfg.n_layers // len(pat)) * len(pat)
            for p_tail, c_tail, kind in zip(params["tail"], state["tail"],
                                            kinds[n_scanned:]):
                x, new_c, _ = self._decode_block(p_tail, c_tail, x, kind,
                                                 index, key=key)
                new_state["tail"].append(new_c)
        else:
            kinds = self.layer_kinds()
            new_state = {"index": index + 1}
            if "lead" in params:
                new_state["lead"] = []
                for p_lead, c_lead, kind in zip(params["lead"],
                                                state["lead"], kinds):
                    x, new_c, _ = self._decode_block(p_lead, c_lead, x, kind,
                                                     index, key=key,
                                                     commit=commit)
                    new_state["lead"].append(new_c)

            def body(x, lp_cache):
                lp, cl = lp_cache
                x, new_cl, pairs = self._decode_block(lp, cl, x, kinds[-1],
                                                      index, key=key,
                                                      commit=commit)
                return x, (new_cl, pairs)

            x, (new_state["layers"], pairs) = self._maybe_scan(
                body, x, (params["layers"], state["layers"]))
            if pairs is not None:
                pairs = jnp.sum(pairs, axis=0)
                if commit is not None:
                    pairs = jnp.where(commit[:, None], pairs,
                                      state["moe_pairs"])
                new_state["moe_pairs"] = pairs
        logits = self.logits(params, x)
        return logits, new_state

    def prefill(self, params, tokens, extra: Optional[Dict] = None,
                *, key=None):
        """Forward a prompt, returning last-position logits.

        The baseline prefill recomputes no cache fill (the dry-run cell
        measures the forward FLOPs); cache-filling prefill for the serving
        engine is :meth:`prefill_cache`.
        """
        logits = self.forward(params, tokens, extra, key=key)
        return logits[:, -1:]

    def prefill_cache(self, params, state, tokens, valid_len, *, key=None,
                      batch_axes=None):
        """Cache-writing chunked/batched prefill (see
        :func:`repro.nn.model.prefill_cache` — exact w.r.t. the decode
        path, per-row length masking, shared global index)."""
        from repro.nn import model as M

        return M.prefill_cache(self, params, state, tokens, valid_len,
                               key=key, batch_axes=batch_axes)
