"""Plain float32 reference of the DeepSeek-V3 block (Moonlight-16B-A3B),
at one chip's share of an expert-parallel deployment.

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * s1
                [q_nope, q_r] = h Wq                         (per head)
                [c, k_r] = h Wkva;  c = rmsnorm(c) * s_kv
                q_r, k_r = rope(q_r), rope(k_r)   (k_r one head, shared)
                [k_nope, v] = c Wkvb                         (per head)
                x += softmax([q_nope, q_r] [k_nope, k_r]^T / sqrt(192)
                             + causal) v Wo
                h = rmsnorm(x) * s2
      layer 0:  x += (silu_nladc(h Wg) * (h Wu)) Wd            (dense MLP)
      others:   s = sigmoid_nladc(h Wr)            (float32 logits, 64 wide)
                chosen = top-6 of s + b            (ties: lowest index)
                w = s[chosen] / sum(s[chosen]) * 2.446
                x += sum over chosen experts e held here of
                         w_e (silu_nladc(h Wg_e) * (h Wu_e)) Wd_e
                     + shared experts' MLP(h)
    logits = (rmsnorm(x) * s_f) W_head                        (untied)

The attention is the published, non-absorbed form: each head's key and
value are expanded from the latent.  The layer holds ``experts_held``
routed experts, ``expert_offset`` onward, as the program's layer does;
pairs routed to experts held elsewhere contribute nothing.

``silu_nladc`` and ``sigmoid_nladc`` are the paper's 5-bit NL-ADC
functions (``ramps``; the sigmoid ramp is built here from the paper's
definition over Supp. Tab. S2's domain), the configuration's
``analog.mode = "exact"``.  RoPE rotates adjacent pairs: DeepSeek-V3
de-interleaves the rope lanes of q and k alike before its rotate-half,
which pairs lanes (2i, 2i + 1), so the scores are the published ones.
Everything is float32 with matmuls at ``highest`` precision; the weights
are the served values, read as exact numbers.  Layers run one at a time.

``mode="bf16"`` keeps the configuration's stated precision: every tensor
between two operations is held in bfloat16, while matmuls accumulate and
norms, RoPE, softmax and the router (logits, scores, weights) compute in
float32.  ``mode="fp8"`` is the control: every projection's operands are
rounded to float8 e4m3 with one scale per tensor.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import ramps

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
SIGMOID_DOMAIN = (-3.496, 3.496)        # Supp. Tab. S2


def sigmoid_ramp(bits: int):
    """-> (thresholds (P,), y_table (P + 1,)) in float64: output levels
    uniform in y over the domain, thresholds the sigmoid's inverse."""
    lo, hi = SIGMOID_DOMAIN
    g = lambda x: 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64)))
    y = np.linspace(g(lo), g(hi), (1 << bits) + 1)
    v = np.log(y) - np.log1p(-y)
    v[0], v[-1] = lo, hi
    return v[1:].copy(), y


class SigmoidQuantizer(ramps.Quantizer):
    def __init__(self, bits: int):
        thr, y = sigmoid_ramp(bits)
        self.thresholds = [float(np.float32(t)) for t in thr]
        self.y0 = float(np.float32(y[0]))
        self.steps = [float(d) for d in np.diff(y)]


def padded_vocab(cfg: dict) -> int:
    m = cfg["vocab_pad_multiple"]
    return -(-cfg["vocab"] // m) * m


def n_held(cfg: dict) -> int:
    return cfg.get("experts_held") or cfg["n_experts"]


def init_params(cfg: dict, key, dtype):
    """Seeded weights in the served layout and dtype (jit this)."""
    d, nh, hd = cfg["d_model"], cfg["n_heads"], cfg["head_dim"]
    rd, r = cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    ff, ne, ns = cfg["d_ff"], cfg["n_experts"], cfg["n_shared_experts"]
    n_lead = cfg["n_dense_layers"]
    nl = cfg["n_layers"] - n_lead
    std, sstd = cfg["init_std"], cfg["norm_std"]
    vp = padded_vocab(cfg)
    ks = iter(jax.random.split(key, 64))

    def normal(shape, s=std):
        return (s * jax.random.normal(next(ks), shape, F32)).astype(dtype)

    def scale(shape):
        return (1.0 + sstd * jax.random.normal(next(ks), shape, F32)) \
            .astype(dtype)

    def block(lead, moe):
        """One block; ``lead`` () for an unstacked layer, (nl,) stacked."""
        p = {
            "norm1": {"scale": scale(lead + (d,))},
            "attn": {
                "q_proj": {"w": normal(lead + (d, nh * (hd + rd)))},
                "kv_a_proj_with_mqa": {"w": normal(lead + (d, r + rd))},
                "kv_a_layernorm": {"scale": scale(lead + (r,))},
                "kv_b_proj": {"w": normal(lead + (r, nh * 2 * hd))},
                "o_proj": {"w": normal(lead + (nh * hd, d))},
            },
            "norm2": {"scale": scale(lead + (d,))},
        }
        if moe:
            e = lead + (n_held(cfg),)
            p["moe"] = {
                "router": normal(lead + (d, ne)),
                "e_score_correction_bias": normal(lead + (ne,),
                                                  cfg["bias_std"]),
                "w_gate": normal(e + (d, ff)),
                "w_up": normal(e + (d, ff)),
                "w_down": normal(e + (ff, d)),
                "shared": {"wi_gate": {"w": normal(lead + (d, ns * ff))},
                           "wi_up": {"w": normal(lead + (d, ns * ff))},
                           "wo": {"w": normal(lead + (ns * ff, d))}},
            }
        else:
            dff = cfg["dense_d_ff"]
            p["mlp"] = {"wi_gate": {"w": normal(lead + (d, dff))},
                        "wi_up": {"w": normal(lead + (d, dff))},
                        "wo": {"w": normal(lead + (dff, d))}}
        return p

    params = {
        "embed": {"table": normal((vp, d))},
        "final_norm": {"scale": scale((d,))},
        "lm_head": {"w": normal((d, vp))},
        "layers": block((nl,), True),
    }
    if n_lead:
        params["lead"] = [block((), False) for _ in range(n_lead)]
    return params


def _fp8(a):
    """Round to float8 e4m3 with one per-tensor scale, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def rounder(mode):
    """The rounding between operations: bfloat16 in ``bf16`` mode."""
    if mode == "bf16":
        return lambda a: a.astype(jnp.bfloat16).astype(F32)
    return lambda a: a


def _mm(a, b, mode, spec="...k,kn->...n"):
    a, b = a.astype(F32), b.astype(F32)
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rmsnorm(x, s, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * s.astype(F32)


def _rope(x, theta):
    """x: (B, L, heads, rd); rotate pairs (2i, 2i + 1) by
    pos * theta^(-2i/rd)."""
    rd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, rd, 2, dtype=F32) / rd)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


class Reference:
    def __init__(self, cfg: dict, mode: str = "f32"):
        self.cfg, self.mode = cfg, mode
        self.act = ramps.Quantizer(cfg["analog_activation"],
                                   cfg["adc_bits"])
        self.router_act = SigmoidQuantizer(cfg["adc_bits"])
        self._layer = jax.jit(self._layer_fn)
        self._lead = jax.jit(self._lead_fn)
        self._head = jax.jit(self._head_fn)

    # -- pieces ------------------------------------------------------------

    def attention(self, p, x):
        c, mode = self.cfg, self.mode
        r = rounder(mode)
        b, length, _ = x.shape
        nh, hd = c["n_heads"], c["head_dim"]
        rd, rank = c["qk_rope_head_dim"], c["kv_lora_rank"]
        h = r(_rmsnorm(x, p["norm1"]["scale"], c["norm_eps"]))
        a = p["attn"]
        q = r(_mm(h, a["q_proj"]["w"], mode)).reshape(b, length, nh,
                                                      hd + rd)
        kv = r(_mm(h, a["kv_a_proj_with_mqa"]["w"], mode))
        lat = r(_rmsnorm(kv[..., :rank], a["kv_a_layernorm"]["scale"],
                         c["norm_eps"]))
        k_r = r(_rope(kv[..., None, rank:], c["rope_theta"]))
        kvb = r(_mm(lat, a["kv_b_proj"]["w"], mode)).reshape(
            b, length, nh, 2 * hd)
        q = jnp.concatenate([q[..., :hd],
                             r(_rope(q[..., hd:], c["rope_theta"]))], -1)
        k = jnp.concatenate([kvb[..., :hd],
                             jnp.broadcast_to(k_r, (b, length, nh, rd))], -1)
        q = r(q * r(jnp.float32(1.0 / math.sqrt(hd + rd))))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI)
        causal = jnp.tril(jnp.ones((length, length), bool))
        pr = r(jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1))
        o = r(jnp.einsum("bhqk,bkhd->bqhd", pr, kvb[..., hd:], precision=HI))
        return r(x + r(_mm(o.reshape(b, length, nh * hd), a["o_proj"]["w"],
                           mode)))

    def mlp(self, p, h, experts: bool = False):
        """SwiGLU with the NL-ADC'd gate; with ``experts``, the weights
        carry a leading expert axis and every held expert runs at once
        (-> (E, ..., d))."""
        r, mode = rounder(self.mode), self.mode
        up, down = (("...k,ekn->e...n", "e...k,ekn->e...n") if experts
                    else ("...k,kn->...n",) * 2)
        g = r(self.act(_mm(h, p["wi_gate"], mode, up)))
        u = r(_mm(h, p["wi_up"], mode, up))
        return r(_mm(r(g * u), p["wo"], mode, down))

    def route(self, p, h):
        """-> (N, n_experts) routing weights, 0 where not chosen."""
        c = self.cfg
        logits = _mm(h, p["router"], self.mode)
        s = self.router_act(logits)
        choice = s + p["e_score_correction_bias"].astype(F32)
        _, idx = jax.lax.top_k(choice, c["top_k"])
        chosen = jnp.sum(jax.nn.one_hot(idx, c["n_experts"], dtype=F32), -2)
        w = s * chosen
        return w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
            * c["routed_scaling_factor"]

    def moe(self, p, h):
        """The routed experts held here, weighed, plus the shared ones."""
        c, r = self.cfg, rounder(self.mode)
        held = n_held(c)
        off = c.get("expert_offset", 0)
        w = self.route(p, h)[..., off:off + held]              # (.., E)
        experts = {"wi_gate": p["w_gate"], "wi_up": p["w_up"],
                   "wo": p["w_down"]}
        y = self.mlp(experts, h, experts=True)                  # (E, .., d)
        routed = r(jnp.einsum("e...d,...e->...d", y, w, precision=HI))
        return r(routed + self.mlp({k: v["w"] for k, v in
                                    p["shared"].items()}, h))

    # -- layers --------------------------------------------------------------

    def _lead_fn(self, x, p):
        r = rounder(self.mode)
        x = self.attention(p, x)
        h = r(_rmsnorm(x, p["norm2"]["scale"], self.cfg["norm_eps"]))
        return r(x + self.mlp({k: v["w"] for k, v in p["mlp"].items()}, h))

    def _layer_fn(self, x, layers, i):
        p = jax.tree.map(lambda a: a[i], layers)
        r = rounder(self.mode)
        x = self.attention(p, x)
        h = r(_rmsnorm(x, p["norm2"]["scale"], self.cfg["norm_eps"]))
        return r(x + self.moe(p["moe"], h))

    def _head_fn(self, x, rows, pos, final_scale, w):
        r = rounder(self.mode)
        h = r(_rmsnorm(x[rows, pos], final_scale, self.cfg["norm_eps"]))
        return _mm(h, r(w.astype(F32)), self.mode)[:, :self.cfg["vocab"]]

    def hidden(self, params, tokens):
        """tokens (B, L) int; -> the last layer's output (B, L, d_model),
        float32, one jitted layer at a time."""
        table = params["embed"]["table"]
        x = rounder(self.mode)(
            jnp.take(table, jnp.asarray(tokens), axis=0).astype(F32))
        for p in params.get("lead", []):
            x = self._lead(x, p)
        n = self.cfg["n_layers"] - self.cfg["n_dense_layers"]
        for i in range(n):
            x = self._layer(x, params["layers"], jnp.int32(i))
        return x

    def head(self, params, x, rows, pos):
        """float32 logits (len(rows), vocab) of the untied head at
        positions ``pos`` of batch rows ``rows`` of ``hidden``'s output."""
        return self._head(x, jnp.asarray(rows), jnp.asarray(pos),
                          params["final_norm"]["scale"],
                          params["lm_head"]["w"])
