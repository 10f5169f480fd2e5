"""ModelConfig schema, the shape table, and the arch registry plumbing.

Every assigned architecture is one ``<id>.py`` module in this package exposing
``CONFIG`` (exact published numbers) and ``SMOKE`` (reduced same-family
variant).  The registry imports them lazily so that importing
:mod:`repro.configs` never touches jax device state.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class AnalogSpec:
    """Per-model NL-ADC deployment knobs (the paper's technique)."""

    enabled: bool = True
    adc_bits: int = 5
    input_bits: Optional[int] = None   # PWM input quantization off for LMs
    mode: str = "exact"                # exact | train | infer
    # Which nonlinearity gets the NL-ADC treatment (must be in the registry).
    # Empty string -> use the model's hidden_act.
    activation: str = ""
    # Analog execution backend: "" = auto (REPRO_ANALOG_BACKEND env, else
    # "ref"); "ref" = jnp simulation; "pallas" = fused Pallas kernels
    # (repro.core.backend).
    backend: str = ""
    # Device-model preset name (repro.core.device registry: "ideal",
    # "paper", "paper-infer", "aged-1day", "stressed", or custom-registered).
    # "" = auto (REPRO_DEVICE env, else "paper").  Kept as a *name* here so
    # ModelConfig stays a plain published-numbers record; AnalogConfig
    # resolves it to the DeviceModel tree.
    device: str = ""
    # Threshold banks: output columns served by one physical NL-ADC ramp
    # (one ramp generator per crossbar col-tile).  0 = single shared ramp
    # per activation (legacy (P,) layout); e.g. 512 = the paper's tile
    # width, giving a (n_col_tiles, P) bank for matrices wider than a tile.
    bank_cols: int = 0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One architecture. Published numbers only — no silent rescaling."""

    name: str
    family: str                 # dense | moe | hybrid | ssm | encdec | lstm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 128
    qkv_bias: bool = False
    hidden_act: str = "silu"
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    # --- MoE ---
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.0
    router_aux_coef: float = 0.001
    # softmax | sigmoid: DeepSeek-V3's noaux_tc router (a per-expert
    # correction bias added for the choice only, weights normalised)
    router_score: str = "softmax"
    routed_scaling_factor: float = 1.0     # scales the routed experts' sum
    moe_impl: str = "gspmd"         # gspmd | ep_shardmap (§Perf iteration)
    # The chip's share of an expert-parallel deployment: the router spans
    # all ``n_experts``; this layer holds ``experts_held`` of them (0 =
    # all), experts ``expert_offset`` onward, and computes their part only.
    experts_held: int = 0
    expert_offset: int = 0
    # Leading dense layers in front of the MoE stack, and their MLP width.
    n_dense_layers: int = 0
    dense_d_ff: int = 0
    # --- latent attention (MLA; DeepSeek-V2/V3) ---
    # kv_lora_rank > 0 selects MLA: keys and values come from a cached
    # latent of this width plus a shared RoPE key of qk_rope_head_dim.
    # head_dim is both qk_nope_head_dim and v_head_dim (equal in every
    # published MLA model).
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    # --- hybrid (recurrentgemma) ---
    block_pattern: Tuple[str, ...] = ()   # e.g. ("rec", "rec", "attn")
    window: int = 0                        # local-attention window (0 = global)
    lru_width: int = 0
    # §Perf C2/C3: recurrence-scan precision and chunking (0 = plain scan)
    lru_scan_dtype: str = "float32"
    lru_chunk: int = 0
    # Griffin's gates are BLOCK-DIAGONAL (one block per head); 0 = dense
    # (the unfaithful ablation kept for the §Perf before/after).
    lru_gate_blocks: int = 0
    # §Perf C5: Megatron-style sequence parallelism — the residual stream
    # is sequence-sharded over the model axis between blocks (AG -> block
    # -> RS replaces the partial-sum all-reduce; norms/elementwise run on
    # 1/model_degree of the tokens).
    sequence_parallel: bool = False
    # Activation-checkpoint policy for the layer scan: "full" recomputes
    # everything (min memory), "dots" saves matmul outputs, "none" saves all.
    remat_policy: str = "full"
    # --- ssm (mamba2 SSD) ---
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_width: int = 4
    # --- enc-dec (whisper) ---
    n_enc_layers: int = 0
    n_dec_layers: int = 0
    enc_len: int = 1500                    # stub frontend frames
    max_position: int = 32768              # learned-pos-table size (encdec)
    # --- modality frontend stub ---
    modality: str = "text"                 # text | audio | vision
    n_patches: int = 0                     # vision: patch-embedding positions
    # --- lstm (the paper's own models) ---
    lstm_hidden: int = 0
    lstm_proj: int = 0
    n_input_features: int = 0
    n_classes: int = 0
    # --- analog / NL-ADC ---
    analog: AnalogSpec = dataclasses.field(default_factory=AnalogSpec)
    # --- numerics / padding ---
    dtype: str = "bfloat16"
    # Serving-time param storage: cast-at-load for decode/prefill (standard
    # deployment practice; f32 master weights exist only in training).
    serve_params_dtype: str = "float32"
    # §Perf B3: KV-cache storage dtype ("int8" = per-token-per-head
    # symmetric quantization with bf16 scales; dequant fuses into the
    # attention dot on TPU).
    kv_cache_dtype: str = "bfloat16"
    vocab_pad_multiple: int = 512

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return math.ceil(self.vocab / m) * m

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def n_held(self) -> int:
        """Routed experts held by this chip's MoE layers."""
        return self.experts_held or self.n_experts

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic archs run the 524k-token decode cell."""
        return self.family in ("ssm", "hybrid")

    @property
    def has_decoder(self) -> bool:
        return True  # every assigned arch decodes (whisper via its decoder)

    def n_params(self) -> int:
        """Approximate parameter count (embedding + blocks), for roofline."""
        d, ff, v = self.d_model, self.d_ff, self.padded_vocab
        if self.family == "lstm":
            n_in = self.n_input_features + (self.lstm_proj or self.lstm_hidden)
            p = n_in * 4 * self.lstm_hidden
            if self.lstm_proj:
                p += self.lstm_hidden * self.lstm_proj
            p += (self.lstm_proj or self.lstm_hidden) * self.n_classes
            return p
        emb = v * d * (1 if self.tie_embeddings else 2)
        if self.family == "encdec":
            att = 2 * d * (self.q_dim + self.kv_dim + self.q_dim)  # self+x-attn q,o
            blk = att + 2 * d * ff  # gelu mlp (2 mats)
            return emb + (self.n_enc_layers + self.n_dec_layers) * blk
        att = self._attn_params()
        mlp = 3 * d * ff
        if self.family == "moe":
            return self._moe_params(emb, att, self.n_held)
        if self.family == "ssm":
            # in_proj packs [z, x] (2*din) plus B/C/dt rows (d_state- and
            # head-sized, negligible at these widths); out_proj din*d;
            # per-head dt_bias/a_log/d_skip ~ din/headdim.
            din = self.ssm_expand * d
            blk = 2 * d * din + din * d + d * (din // self.ssm_headdim)
            return emb + self.n_layers * blk
        if self.family == "hybrid":
            w = self.lru_width or d
            rec = d * w * 3 + w * d + 2 * w  # gates + in/out proj + lru params
            n_rec = sum(1 for b in self._pattern() if b == "rec")
            n_att = self.n_layers - n_rec
            return emb + n_att * (att + mlp) + n_rec * (rec + mlp)
        return emb + self.n_layers * (att + mlp)

    def n_active_params(self) -> int:
        """Active (per-token) params — differs from n_params for MoE."""
        if self.family != "moe":
            return self.n_params()
        emb = self.padded_vocab * self.d_model \
            * (1 if self.tie_embeddings else 2)
        return self._moe_params(emb, self._attn_params(), self.top_k)

    def _attn_params(self) -> int:
        d = self.d_model
        if self.kv_lora_rank:
            # MLA: q_proj, kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj
            # (a key and a value per head), o_proj
            h, r = self.n_heads, self.kv_lora_rank
            hd, rd = self.head_dim, self.qk_rope_head_dim
            return (d * h * (hd + rd) + d * (r + rd) + r + r * h * 2 * hd
                    + h * hd * d)
        return d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d

    def _moe_params(self, emb: int, att: int, n_experts: int) -> int:
        """Embedding, attention, ``n_experts`` routed experts and the shared
        ones in each MoE layer, the router (and its correction bias), and
        the leading dense layers.  MLA models are counted exactly, norm
        scales included, against their published totals."""
        d, ff = self.d_model, self.d_ff
        router = d * self.n_experts
        if self.router_score == "sigmoid":
            router += self.n_experts
        moe = (n_experts + self.n_shared_experts) * 3 * d * ff + router
        n_moe = self.n_layers - self.n_dense_layers
        total = emb + self.n_layers * att + n_moe * moe \
            + self.n_dense_layers * 3 * d * self.dense_d_ff
        if self.kv_lora_rank:
            total += (2 * self.n_layers + 1) * d
        return total

    def _pattern(self) -> Tuple[str, ...]:
        """Full per-layer block-type sequence."""
        if self.family == "hybrid" and self.block_pattern:
            reps = math.ceil(self.n_layers / len(self.block_pattern))
            return (self.block_pattern * reps)[: self.n_layers]
        return ("attn",) * self.n_layers

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode | long_decode

    @property
    def lowers(self) -> str:
        return "train_step" if self.kind == "train" else (
            "prefill_step" if self.kind == "prefill" else "serve_step")


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "long_decode"),
}

ARCH_NAMES = (
    "pixtral-12b",
    "whisper-base",
    "qwen2.5-32b",
    "granite-34b",
    "granite-3-8b",
    "qwen2.5-3b",
    "moonlight-16b-a3b",
    "deepseek-moe-16b",
    "recurrentgemma-9b",
    "mamba2-370m",
    "kws_lstm",
    "ptb_lstm",
)

_MODULE_FOR = {n: "repro.configs." + n.replace("-", "_").replace(".", "_")
               for n in ARCH_NAMES}


def _load(name: str):
    if name not in _MODULE_FOR:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_NAMES}")
    return importlib.import_module(_MODULE_FOR[name])


def get(name: str) -> ModelConfig:
    return _load(name).CONFIG


def get_smoke(name: str) -> ModelConfig:
    return _load(name).SMOKE


def cells(include_skips: bool = False):
    """All (arch, shape) dry-run cells, honoring the documented skips.

    Skips (DESIGN.md §Arch-applicability): ``long_500k`` needs sub-quadratic
    attention -> only ssm/hybrid run it.  The paper's LSTM workloads are extra
    (not part of the 40 assigned cells) and are exercised by their own
    benchmarks, not the dry-run grid.
    """
    out = []
    for arch in ARCH_NAMES[:10]:
        cfg = get(arch)
        for sname, shape in SHAPES.items():
            skip = (shape.kind == "long_decode"
                    and not cfg.supports_long_context)
            if skip and not include_skips:
                continue
            out.append((arch, sname, skip))
    return out
