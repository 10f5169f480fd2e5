"""Moonlight (DeepSeek-V3 block) against its plain reference at smoke width:
latent attention through the cache, the MLA decode kernel, the chip's
share of the experts, dropless routing and the noaux_tc router.

The reference is the benchmark's (``bench/reference/moonlight.py``), which
imports nothing of the program.  Both sides compute in float32.  With the
NL-ADC on, a value within float rounding of a ramp threshold may land one
level apart on the two sides, so the logit comparisons run with exact
activations (the program's analog spec disabled, the reference's
quantizers swapped for the exact functions); the NL-ADC'd path is held to
the reference by the benchmark's check and by the shares test below,
whose two sides quantize the same numbers.
"""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import AnalogSpec
from repro.nn import attention as A
from repro.nn import moe as MOE
from repro.nn.model import build
from repro.serve.engine import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.reference import moonlight as REF  # noqa: E402

# f32 through three layers of a width-64 model: the program and the
# reference differ only in the order of float32 sums, 1.4e-6 of the
# largest logit.  Rounding the latent cache to bfloat16 moves the logits
# by 1.0e-2 of it, and the router's logits by 3.9e-4 (both checked
# below): 2e-5 lies 14x above the first and 20x under the second.
RTOL = 2e-5


def smoke_cfg(**kw):
    return configs.get_smoke("moonlight-16b-a3b").replace(
        dtype="float32", serve_params_dtype="float32", **kw)


def ref_cfg(cfg) -> dict:
    """The reference's configuration dict for a program config."""
    out = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
           if f.name != "analog"}
    out.update(init_std=0.25, norm_std=0.1, bias_std=0.05,
               analog_activation="silu", adc_bits=5)
    return out


def exact_reference(rc):
    """The reference with its NL-ADC quantizers swapped for the exact
    functions (matching a program whose analog spec is disabled)."""
    ref = REF.Reference(rc, "f32")
    ref.act = jax.nn.silu
    ref.router_act = jax.nn.sigmoid
    return ref


def params_for(cfg, rc, seed=0):
    params = REF.init_params(rc, jax.random.PRNGKey(seed), jnp.float32)
    want = jax.tree.map(lambda a: a.shape,
                        jax.eval_shape(build(cfg).init,
                                       jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a: a.shape, params) == want
    return params


def prefill_then_decode(model, params, tokens, n_prefill):
    """Logits of positions n_prefill - 1 .. end: the prompt's first
    ``n_prefill - 1`` tokens written by the masked prefill, the rest fed
    through ``decode_step``."""
    b, s = tokens.shape
    state = model.init_decode_state(b, 32)
    state = model.prefill_cache(params, state, tokens[:, :n_prefill - 1],
                                jnp.full((b,), n_prefill - 1, jnp.int32))
    out = []
    for t in range(n_prefill - 1, s):
        logits, state = model.decode_step(params, state, tokens[:, t:t + 1])
        out.append(logits[:, 0, :model.cfg.vocab])
    return jnp.stack(out, axis=1)


def reference_logits(ref, params, tokens, first):
    b, s = tokens.shape
    x = ref.hidden(params, tokens)
    rows = np.repeat(np.arange(b), s - first)
    pos = np.tile(np.arange(first, s), b)
    return ref.head(params, x, rows, pos).reshape(b, s - first, -1)


def rel_err(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def test_prefill_and_decode_match_the_reference_forward():
    cfg = smoke_cfg(experts_held=4, expert_offset=2,
                    analog=AnalogSpec(enabled=False))
    rc = ref_cfg(cfg)
    params = params_for(cfg, rc)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 14), 0, cfg.vocab)
    want = reference_logits(exact_reference(rc), params, tokens, 7)
    got = prefill_then_decode(build(cfg), params, tokens, 8)
    assert rel_err(got, want) < RTOL


@pytest.mark.parametrize("fault", ["cache_bf16", "router_bf16"])
def test_bf16_cache_or_router_fails_the_tolerance(fault, monkeypatch):
    """The tolerance above is tight enough to see a bfloat16 latent cache
    or bfloat16 router logits."""
    cfg = smoke_cfg(experts_held=4, expert_offset=2,
                    analog=AnalogSpec(enabled=False))
    rc = ref_cfg(cfg)
    params = params_for(cfg, rc)
    if fault == "cache_bf16":
        init = A.init_mla_cache
        monkeypatch.setattr(A, "init_mla_cache", lambda b, s, w, dtype=None:
                            init(b, s, w, dtype=jnp.bfloat16))
    else:
        route = MOE.route

        def bf16_route(p, xf, **kw):
            p = dict(p, router=p["router"].astype(jnp.bfloat16)
                     .astype(jnp.float32))
            return route(p, xf.astype(jnp.bfloat16).astype(jnp.float32),
                         **kw)
        monkeypatch.setattr(MOE, "route", bf16_route)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 14), 0, cfg.vocab)
    want = reference_logits(exact_reference(rc), params, tokens, 7)
    got = prefill_then_decode(build(cfg), params, tokens, 8)
    assert rel_err(got, want) > 10 * RTOL


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mla_kernel_matches_the_jnp_path(dtype):
    """The Pallas kernel (interpret mode here) computes mla_attend's
    element-wise sequence: equal to it bitwise."""
    from repro.kernels import ops

    b, h, rank, rd, s = 3, 4, 32, 8, 24
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    q = jax.random.normal(k1, (b, h, rank + rd), jnp.float32).astype(dtype)
    ckv = jax.random.normal(k2, (b, rank + rd, s), jnp.float32).astype(dtype)
    mask = jnp.arange(s)[None, :] < jnp.asarray([[5], [24], [1]])
    got = ops.mla_decode_attention(q, ckv, mask, rank=rank, scale=0.125)
    want = A.mla_attend(q, ckv, mask, rank=rank, scale=0.125)
    assert got.shape == (b, h, rank) and got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _dus_updates(text, shape):
    """The update shapes of every dynamic-update-slice into a buffer of
    ``shape`` in compiled HLO text (fusion bodies included)."""
    dims = dict(re.findall(r"%(\S+) = \w+\[([\d,]*)\]", text))
    into = r"= \w+\[" + ",".join(map(str, shape)) \
        + r"\]\S* dynamic-update-slice\(%[^,]+, %([^,]+),"
    return [tuple(int(d) for d in dims[u].split(","))
            for u in re.findall(into, text)]


@pytest.mark.parametrize("program", ["decode", "masked_prefill"])
def test_latent_stack_takes_one_position_per_layer(program):
    """The stacked latent cache rides the layer scan as a carry: each layer
    writes its (B, W, 1) entry into the stack, and no op writes a whole
    (B, W, S) layer back (the decode step, and the masked prefill that
    scans it)."""
    cfg = smoke_cfg()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 4, 32
    state = model.init_decode_state(b, s)
    stack = state["layers"]["ckv"].shape
    _, _, w, _ = stack
    if program == "decode":
        lowered = jax.jit(model.decode_step).lower(
            params, state, np.zeros((b, 1), np.int32))
    else:
        lowered = jax.jit(model.prefill_cache).lower(
            params, state, np.zeros((b, 8), np.int32),
            np.full((b,), 8, np.int32))
    updates = _dus_updates(lowered.compile().as_text(), stack)
    assert updates and set(updates) == {(1, b, w, 1)}, updates


@pytest.mark.parametrize("index", [0, 17, 39])
@pytest.mark.parametrize("stacked", [False, True])
def test_latent_cache_write_is_the_one_position_write(index, stacked):
    """The cache comes back with only the entry written at ``index`` (of
    layer 1 of a stack, or of one layer's cache), every other position
    and layer as it was; the view returned is the layer's."""
    rng = np.random.default_rng(index)
    cache = jnp.asarray(rng.standard_normal((3, 2, 5, 40)), jnp.bfloat16)
    entry = jnp.asarray(rng.standard_normal((2, 5, 1)), jnp.float32)
    layer = 1 if stacked else None
    ckv = cache if stacked else cache[1]
    got, view = jax.jit(A.mla_cache_write)(ckv, entry, index, layer)
    want = cache.at[1, :, :, index].set(entry[..., 0].astype(jnp.bfloat16))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want if stacked else want[1]))
    np.testing.assert_array_equal(np.asarray(view), np.asarray(want[1]))


def test_engine_decode_aliases_the_latent_stack():
    """The engine donates the decode state: the compiled decode program
    writes the stacked latent cache's output over its input."""
    cfg = smoke_cfg()
    model = build(cfg)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                        max_batch=4, max_len=32)
    text = eng._jit_decode.lower(
        eng.params, eng.state, jnp.zeros((4, 1), jnp.int32),
        jnp.zeros((4,), jnp.int32), None).compile().as_text()
    (param,) = re.findall(
        r"parameter\((\d+)\), metadata=\{op_name=\"state\[\\'layers\\'\]"
        r"\[\\'ckv\\'\]", text)
    header = text.split("\n", 1)[0]
    assert re.search(r"input_output_alias=\{[^\n]*: \(" + param + r", \{\}",
                     header), header[:300]


def _moe_layer(cfg, rc, params, share):
    """One MoE layer's params for experts ``share`` (a slice)."""
    p = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    return dict(p, **{k: p[k][share] for k in ("w_gate", "w_up", "w_down")})


def test_shares_sum_to_the_uncut_reference_layer():
    """Eight chips' shares (one expert each) of a layer, with the shared
    expert counted once, add up to the reference's layer holding all."""
    cfg = smoke_cfg(analog=AnalogSpec(backend="ref"))
    rc = ref_cfg(cfg)
    params = params_for(cfg, rc)
    model = build(cfg)
    h = jax.random.normal(jax.random.PRNGKey(3), (16, cfg.d_model))
    total, shared = 0.0, None
    for e in range(cfg.n_experts):
        p = _moe_layer(cfg, rc, params, slice(e, e + 1))
        out, pairs = MOE.moe_decode(
            p, h, top_k=cfg.top_k, act=model.act,
            router_score=cfg.router_score, router_act=model.sigmoid_act,
            expert_offset=e, routed_scaling=cfg.routed_scaling_factor)
        shared = MOE._shared(p, h, model.act, None)
        total = total + (out - shared)
    got = total + shared
    ref = REF.Reference(rc, "f32")
    want = ref.moe(jax.tree.map(lambda a: a[0], params["layers"]["moe"]), h)
    assert rel_err(got, want) < RTOL


def test_router_forced_onto_one_expert_drops_nothing():
    """Every token routed to expert 3: the dropless decode layer keeps
    every pair (equal to the reference), where a capacity of one even
    share would drop most of them."""
    cfg = smoke_cfg(analog=AnalogSpec(enabled=False))
    rc = ref_cfg(cfg)
    params = params_for(cfg, rc)
    p = _moe_layer(cfg, rc, params, slice(None))
    p["e_score_correction_bias"] = jnp.zeros((cfg.n_experts,)).at[3].set(9.)
    model = build(cfg)
    h = jax.random.normal(jax.random.PRNGKey(4), (32, cfg.d_model))
    out, pairs = MOE.moe_decode(
        p, h, top_k=cfg.top_k, act=model.act, router_score="sigmoid",
        router_act=model.sigmoid_act,
        routed_scaling=cfg.routed_scaling_factor)
    assert np.all(np.asarray(pairs) == cfg.top_k)
    ref = exact_reference(rc)
    assert bool(jnp.all(ref.route(p, h)[:, 3] > 0))
    want = ref.moe(p, h)
    assert rel_err(out, want) < RTOL
    capped = MOE.moe_apply(
        p, h, top_k=cfg.top_k, capacity_factor=1.0, act=model.act,
        router_score="sigmoid", router_act=model.sigmoid_act, ep_axis=None,
        routed_scaling=cfg.routed_scaling_factor)
    assert rel_err(capped, want) > 1e-2


def test_noaux_tc_weights_by_hand():
    """Choice by score + bias, weights the chosen scores without it,
    normalised and scaled; ties go to the lowest index."""
    logits = jnp.asarray([[0.0, 1.0, 2.0, -1.0],
                          [0.0, 0.0, 0.0, 0.0]])
    bias = jnp.asarray([0.0, 0.0, -5.0, 3.0])
    gates, idx, _ = MOE.router_gates(logits, 2, "sigmoid", None, bias=bias,
                                     scaling=2.446)
    s = 1.0 / (1.0 + np.exp(-np.asarray([1.0, -1.0])))      # experts 1, 3
    np.testing.assert_array_equal(np.asarray(idx[0]), [3, 1])
    np.testing.assert_allclose(np.asarray(gates[0]),
                               2.446 * s[::-1] / s.sum(), rtol=1e-6)
    # all scores equal: bias alone orders 3 first, then the tie between
    # 0 and 1 goes to 0
    np.testing.assert_array_equal(np.asarray(idx[1]), [3, 0])
    np.testing.assert_allclose(np.asarray(gates[1]), [1.223, 1.223],
                               rtol=1e-6)
