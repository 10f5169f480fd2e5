"""Plain float32 reference of the Mamba-2 SSD language model.

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * s
                z, u, B, C, dt_raw = split(h W_in)
                u, B, C = silu(causal_conv([u, B, C]))     (depthwise, width K)
                dt = softplus_nladc(dt_raw + dt_bias)          (per head)
                S_t = exp(-dt_t A) S_{t-1} + dt_t u_t B_t^T     A = exp(a_log)
                y_t = S_t C_t + D u_t
                x += (rmsnorm(y * silu_nladc(z)) * s_g) W_out
    logits = (rmsnorm(x) * s_f) E^T                       (tied head)

The recurrence runs token by token (``lax.scan``), the plain form of the
SSD recurrence, with no chunking.  As served, the block has one B/C
group and no conv or projection biases, and the dt softplus and the z
gate silu are the 5-bit NL-ADC ramps of the paper (``ramps``).
Everything is float32 with matmuls at ``highest`` precision; layers run
one at a time.  ``mode="bf16"`` keeps the configuration's stated
precision: weights used in bfloat16, every activation between two
operations held in bfloat16, while matmuls, the conv, the recurrence
(state in float32) and norms compute in float32.  ``mode="fp8"`` (the
control) rounds every projection's operands to float8 e4m3 with one
scale per tensor.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dense_gqa, ramps
from bench.reference.dense_gqa import _mm, _rmsnorm, padded_vocab, rounder

F32 = jnp.float32


def _sizes(cfg):
    d_inner = cfg["ssm_expand"] * cfg["d_model"]
    return d_inner, d_inner // cfg["ssm_headdim"], cfg["ssm_state"]


def init_params(cfg: dict, key, dtype):
    """Seeded weights in the served layout and dtype (jit this).

    Matrices N(0, init_std); A ~ U(1, 16) and dt ~ logU(1e-3, 1e-1) with
    dt_bias = softplus^-1(dt), as Mamba-2 initialises them; D = 1 plus
    noise; conv taps U(-1/sqrt(K), 1/sqrt(K)); norm scales 1 plus noise.
    """
    d, nl, k = cfg["d_model"], cfg["n_layers"], cfg["conv_width"]
    din, nh, n = _sizes(cfg)
    std, sstd = cfg["init_std"], cfg["norm_std"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, s):
        return s * jax.random.normal(next(ks), shape, F32)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, F32, lo, hi)

    dt = jnp.exp(uniform((nl, nh), np.log(1e-3), np.log(1e-1)))
    tree = {
        "embed": {"table": normal((padded_vocab(cfg), d), std)},
        "final_norm": {"scale": 1.0 + normal((d,), sstd)},
        "layers": {
            "norm": {"scale": 1.0 + normal((nl, d), sstd)},
            "ssd": {
                "in_proj": {"w": normal((nl, d, 2 * din + 2 * n + nh), std)},
                "conv": uniform((nl, k, din + 2 * n), -k ** -0.5, k ** -0.5),
                "a_log": jnp.log(uniform((nl, nh), 1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "d_skip": 1.0 + normal((nl, nh), sstd),
                "out_proj": {"w": normal((nl, din, d), std)},
                "norm": {"scale": 1.0 + normal((nl, din), sstd)},
            },
        },
    }
    return jax.tree.map(lambda a: a.astype(dtype), tree)


class Reference:
    def __init__(self, cfg: dict, mode: str = "f32"):
        self.cfg, self.mode = cfg, mode
        self.gate = ramps.Quantizer(cfg["analog_activation"],
                                    cfg["adc_bits"])
        self.dt_act = ramps.Quantizer("softplus", cfg["adc_bits"])
        self._layer = jax.jit(self._layer_fn)
        self._head = jax.jit(self._head_fn)

    def _layer_fn(self, x, layers, i):
        lp = jax.tree.map(lambda a: a[i].astype(F32), layers)
        p, c = lp["ssd"], self.cfg
        r = rounder(self.mode)
        din, nh, n = _sizes(c)
        hp = c["ssm_headdim"]
        b, length, _ = x.shape
        h = r(_rmsnorm(x, lp["norm"]["scale"], c["norm_eps"]))
        zxbcdt = r(_mm(h, r(p["in_proj"]["w"]), self.mode))
        z = zxbcdt[..., :din]
        xbc = zxbcdt[..., din:2 * din + 2 * n]
        dt_raw = zxbcdt[..., 2 * din + 2 * n:]
        w = p["conv"]                                      # (K, C)
        kw = w.shape[0]
        pad = jnp.pad(xbc, ((0, 0), (kw - 1, 0), (0, 0)))
        conv = sum(pad[:, kw - 1 - j:kw - 1 - j + length] * w[j]
                   for j in range(kw))                     # sum_j u_{t-j} w_j
        xbc = r(jax.nn.silu(r(conv)))
        u = xbc[..., :din].reshape(b, length, nh, hp)
        bm, cm = xbc[..., din:din + n], xbc[..., din + n:]
        dt = r(self.dt_act(r(dt_raw + r(p["dt_bias"]))))    # (b, L, nh)
        a = jnp.exp(p["a_log"])

        def step(s, inp):
            u_t, b_t, c_t, dt_t = inp
            s = s * jnp.exp(-dt_t * a)[..., None, None] \
                + dt_t[..., None, None] * u_t[..., None] * b_t[:, None, None]
            y = jnp.einsum("bhpn,bn->bhp", s, c_t,
                           precision=jax.lax.Precision.HIGHEST)
            return s, y

        s0 = jnp.zeros((b, nh, hp, n), F32)
        _, y = jax.lax.scan(step, s0, (u.swapaxes(0, 1), bm.swapaxes(0, 1),
                                       cm.swapaxes(0, 1), dt.swapaxes(0, 1)))
        y = r(y.swapaxes(0, 1) + p["d_skip"][:, None] * u)
        y = r(y.reshape(b, length, din) * r(self.gate(z)))
        y = r(_rmsnorm(y, p["norm"]["scale"], c["norm_eps"]))
        return r(x + r(_mm(y, r(p["out_proj"]["w"]), self.mode)))

    _head_fn = dense_gqa.Reference._head_fn
    hidden = dense_gqa.Reference.hidden
    head = dense_gqa.Reference.head
