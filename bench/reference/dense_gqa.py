"""Plain float32 reference of a dense GQA decoder (the Qwen2.5 block).

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * s1
                q, k, v = h Wq + bq, h Wk + bk, h Wv + bv      (GQA heads)
                q, k = rope(q), rope(k)         (theta; pairs (2i, 2i+1))
                x += softmax(q k^T / sqrt(hd) + causal) v Wo
                h = rmsnorm(x) * s2
                x += (silu_nladc(h Wg) * (h Wu)) Wd
    logits = (rmsnorm(x) * s_f) E^T                       (tied head)

``silu_nladc`` is the 5-bit NL-ADC silu of the paper (``ramps``), the
configuration's ``analog.mode = "exact"``.  RoPE rotates adjacent pairs,
as the served model lays out its heads; with seeded weights the pairing
is a fixed permutation of Wq and Wk columns, so this is Qwen2.5's
attention.  Everything is float32 with matmuls at ``highest`` precision;
the weights are the served values, read as exact numbers.  Layers run
one at a time so that only one layer is upcast on the device at once.

``mode="bf16"`` keeps the configuration's stated precision: every tensor
between two operations is held in bfloat16 (weights, activations, the
residual stream, the KV cache, the attention probabilities), while
matmuls accumulate and norms, RoPE and softmax compute in float32; the
NL-ADC digitizes the float32 accumulator.  ``mode="fp8"`` is the
control: every projection's operands are rounded to float8 e4m3 with one
scale per tensor, the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import ramps

F32 = jnp.float32


def init_params(cfg: dict, key, dtype):
    """Seeded weights in the served layout and dtype (jit this)."""
    d, nl = cfg["d_model"], cfg["n_layers"]
    hd, nh, nkv, ff = (cfg["head_dim"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["d_ff"])
    std, bstd, sstd = cfg["init_std"], cfg["bias_std"], cfg["norm_std"]
    vp = padded_vocab(cfg)
    ks = iter(jax.random.split(key, 16))

    def normal(shape, s):
        return (s * jax.random.normal(next(ks), shape, F32)).astype(dtype)

    def scale(shape):
        return (1.0 + sstd * jax.random.normal(next(ks), shape, F32)) \
            .astype(dtype)

    def dense(n_in, n_out, bias):
        p = {"w": normal((nl, n_in, n_out), std)}
        if bias:
            p["b"] = normal((nl, n_out), bstd)
        return p

    bias = cfg["qkv_bias"]
    return {
        "embed": {"table": normal((vp, d), std)},
        "final_norm": {"scale": scale((d,))},
        "layers": {
            "norm1": {"scale": scale((nl, d))},
            "attn": {"wq": dense(d, nh * hd, bias),
                     "wk": dense(d, nkv * hd, bias),
                     "wv": dense(d, nkv * hd, bias),
                     "wo": dense(nh * hd, d, False)},
            "norm2": {"scale": scale((nl, d))},
            "mlp": {"wi_gate": dense(d, ff, False),
                    "wi_up": dense(d, ff, False),
                    "wo": dense(ff, d, False)},
        },
    }


def padded_vocab(cfg: dict) -> int:
    m = cfg["vocab_pad_multiple"]
    return -(-cfg["vocab"] // m) * m


def _fp8(a):
    """Round to float8 e4m3 with one per-tensor scale, back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _bf16(a):
    return a.astype(jnp.bfloat16).astype(F32)


def rounder(mode):
    """The rounding between operations: bfloat16 in ``bf16`` mode."""
    return _bf16 if mode == "bf16" else (lambda a: a)


def _mm(a, b, mode):
    a, b = a.astype(F32), b.astype(F32)
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, s, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * s.astype(F32)


def _rope(x, theta):
    """x: (B, L, heads, hd); rotate pairs (2i, 2i + 1) by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv      # (L, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


class Reference:
    def __init__(self, cfg: dict, mode: str = "f32"):
        self.cfg, self.mode = cfg, mode
        self.act = ramps.Quantizer(cfg["analog_activation"],
                                   cfg["adc_bits"])
        self._layer = jax.jit(self._layer_fn)
        self._head = jax.jit(self._head_fn)

    def _layer_fn(self, x, layers, i):
        p = jax.tree.map(lambda a: a[i], layers)
        c, mode = self.cfg, self.mode
        b, length, _ = x.shape
        nh, nkv, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]

        r = rounder(mode)
        hi = jax.lax.Precision.HIGHEST

        def proj(name, h):
            y = r(_mm(h, p["attn"][name]["w"], mode))
            if "b" in p["attn"][name]:
                y = r(y + p["attn"][name]["b"].astype(F32))
            return y

        h = r(_rmsnorm(x, p["norm1"]["scale"], c["norm_eps"]))
        q = proj("wq", h).reshape(b, length, nkv, nh // nkv, hd)
        k = proj("wk", h).reshape(b, length, nkv, hd)
        v = proj("wv", h).reshape(b, length, nkv, hd)
        q = r(_rope(q.reshape(b, length, nh, hd), c["rope_theta"])) \
            .reshape(q.shape)
        k = r(_rope(k, c["rope_theta"]))
        q = r(q * r(jnp.float32(1.0 / math.sqrt(hd))))
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, precision=hi)
        causal = jnp.tril(jnp.ones((length, length), bool))
        s = jnp.where(causal, s, -jnp.inf)
        pr = r(jax.nn.softmax(s, axis=-1))
        o = r(jnp.einsum("bhgqk,bkhd->bqhgd", pr, v, precision=hi))
        x = r(x + r(_mm(o.reshape(b, length, nh * hd),
                        p["attn"]["wo"]["w"], mode)))
        h = r(_rmsnorm(x, p["norm2"]["scale"], c["norm_eps"]))
        g = r(self.act(_mm(h, p["mlp"]["wi_gate"]["w"], mode)))
        u = r(_mm(h, p["mlp"]["wi_up"]["w"], mode))
        return r(x + r(_mm(r(g * u), p["mlp"]["wo"]["w"], mode)))

    def _head_fn(self, x, rows, pos, final_scale, table):
        r = rounder(self.mode)
        h = r(_rmsnorm(x[rows, pos], final_scale, self.cfg["norm_eps"]))
        return _mm(h, r(table.astype(F32)).T, self.mode)[
            :, :self.cfg["vocab"]]

    def hidden(self, params, tokens):
        """tokens (B, L) int; -> the last layer's output (B, L, d_model),
        float32, one jitted layer at a time."""
        table = params["embed"]["table"]
        x = rounder(self.mode)(
            jnp.take(table, jnp.asarray(tokens), axis=0).astype(F32))
        for i in range(self.cfg["n_layers"]):
            x = self._layer(x, params["layers"], jnp.int32(i))
        return x

    def head(self, params, x, rows, pos):
        """float32 logits (len(rows), vocab) of the tied head at positions
        ``pos`` of batch rows ``rows`` of ``hidden``'s output."""
        return self._head(x, jnp.asarray(rows), jnp.asarray(pos),
                          params["final_norm"]["scale"],
                          params["embed"]["table"])

