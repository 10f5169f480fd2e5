"""ref-vs-pallas analog backend parity: every family, every AnalogConfig
mode, outputs AND straight-through gradients.

Outputs must be quantization-exact: the two backends may differ only in the
floating-point arithmetic of the decode (closed-form vs table lookup) and
the matmul accumulation, both far below the ramp LSB — so we assert
max|diff| < LSB/2, which implies **bitwise-equal ADC codes** (a single code
flip shifts the output by a full LSB).  Codes are additionally compared
bitwise where the raw thermometer count is recoverable.

Runs in Pallas interpret mode on CPU (the kernels' correctness-validation
mode); on a TPU host the same tests exercise the compiled kernels.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as BK
from repro.core.analog_layer import (AnalogActivation, AnalogConfig,
                                     analog_matmul_act, dense_nladc)
from repro.core.nladc import NLADC, build_ramp

MODES = ["exact", "train", "infer"]
BACKENDS = ["ref", "pallas"]


def _cfg(mode, be, **kw):
    kw.setdefault("input_bits", None)
    return AnalogConfig(enabled=True, adc_bits=5, mode=mode, backend=be, **kw)


def _lsb(act: AnalogActivation) -> float:
    return act.ramp.lsb


def _key(mode):
    return jax.random.PRNGKey(3) if mode != "exact" else None


# ---------------------------------------------------------------------------
# Primitive-level parity (bitwise codes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["sigmoid", "tanh", "softplus", "gelu",
                                  "swish", "selu"])
def test_elementwise_codes_bitwise(name, rng):
    """Same input -> the two backends produce bitwise-identical ADC codes."""
    from repro.kernels import nladc as k_nladc

    ramp = build_ramp(name, 5)
    adc = NLADC(ramp)
    x = jnp.asarray(rng.normal(0, 2, (37, 65)).astype(np.float32))
    ref_codes = np.asarray(adc.codes(x))
    # recover kernel codes from the closed-form output
    from repro.kernels.ref import decode_mode, decode_params, MODE_AFFINE

    y = np.asarray(k_nladc(x, ramp), np.float64)
    y0, lsb_l, lsb_r, m = decode_params(ramp)
    if decode_mode(ramp) == MODE_AFFINE:
        got_codes = np.rint((y - y0) / lsb_l).astype(np.int64)
        np.testing.assert_array_equal(got_codes, ref_codes)
    else:
        # split decodes are not code-injective; assert value equality at
        # sub-LSB tolerance instead (implies equal |n - m|)
        want = np.asarray(NLADC(ramp)(x), np.float64)
        assert np.max(np.abs(y - want)) < ramp.lsb / 2


def test_fused_matmul_codes_bitwise(rng):
    """Ref codes of the accumulator == codes recovered from the kernel."""
    from repro.kernels import fused_matmul_nladc as k_mm
    from repro.kernels.ref import decode_params

    ramp = build_ramp("sigmoid", 5)
    adc = NLADC(ramp)
    x = jnp.asarray(rng.normal(0, 0.4, (33, 40)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.2, (40, 24)).astype(np.float32))
    acc = jnp.matmul(x, w)
    ref_codes = np.asarray(adc.codes(acc))
    y0, lsb_l, _, _ = decode_params(ramp)
    y = np.asarray(k_mm(x, w, ramp), np.float64)
    got_codes = np.rint((y - y0) / lsb_l).astype(np.int64)
    mismatch = np.mean(got_codes != ref_codes)
    # accumulation-order fp differences may flip an accumulator sitting
    # within float-eps of a threshold; anything beyond that is a bug
    assert mismatch == 0.0, f"{mismatch:.2%} code mismatches"


# ---------------------------------------------------------------------------
# Layer-level parity over all AnalogConfig modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_dense_nladc_parity_and_grads(mode, rng):
    x = jnp.asarray(rng.normal(0, 0.4, (9, 40)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.2, (40, 24)).astype(np.float32))
    outs, grads = {}, {}
    for be in BACKENDS:
        act = AnalogActivation("swish", _cfg(mode, be))

        def f(x_, w_):
            return jnp.sum(dense_nladc({"w": w_}, x_, act,
                                       key=_key(mode)) ** 2)

        outs[be] = dense_nladc({"w": w}, x, act, key=_key(mode))
        grads[be] = jax.grad(f, argnums=(0, 1))(x, w)
        lsb = _lsb(act)
    assert float(jnp.max(jnp.abs(outs["ref"] - outs["pallas"]))) < lsb / 2
    for a, b in zip(grads["ref"], grads["pallas"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", MODES)
def test_analog_matmul_act_parity(mode, rng):
    """The crossbar path (PWM inputs + weight noise + fused NL-ADC)."""
    x = jnp.asarray(rng.normal(0, 0.4, (7, 24)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.2, (24, 16)).astype(np.float32))
    outs = {}
    for be in BACKENDS:
        cfg = _cfg(mode, be, input_bits=5)
        act = AnalogActivation("tanh", cfg)
        outs[be] = analog_matmul_act(x, w, cfg, key=_key(mode),
                                     activation=act)
        lsb = _lsb(act)
    assert float(jnp.max(jnp.abs(outs["ref"] - outs["pallas"]))) < lsb / 2


@pytest.mark.parametrize("mode", MODES)
def test_lstm_family_parity_and_grads(mode):
    from repro.nn import lstm as NN

    ys, gs, lsb = {}, {}, None
    for be in BACKENDS:
        spec = NN.LSTMSpec(
            n_in=10, n_hidden=12,
            analog=AnalogConfig(enabled=True, adc_bits=5, input_bits=5,
                                mode=mode, backend=be))
        acts = NN.make_gate_acts(spec.analog)
        lsb = _lsb(acts[0])
        p = NN.lstm_init(jax.random.PRNGKey(1), spec)
        xs = 0.5 * jax.random.normal(jax.random.PRNGKey(2), (4, 5, 10))
        ys[be], _ = NN.lstm_scan(p, xs, spec, acts, key=_key(mode))

        def loss(pp):
            out, _ = NN.lstm_scan(pp, xs, spec, acts, key=_key(mode))
            return jnp.sum(out ** 2)

        gs[be] = jax.grad(loss)(p)
    assert float(jnp.max(jnp.abs(ys["ref"] - ys["pallas"]))) < lsb / 2
    for a, b in zip(jax.tree.leaves(gs["ref"]), jax.tree.leaves(gs["pallas"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Full-model family parity (tiny smoke configs, f32, NL-ADC enabled)
# ---------------------------------------------------------------------------

FAMILY_ARCHS = ["qwen2.5-3b", "deepseek-moe-16b", "recurrentgemma-9b",
                "mamba2-370m", "whisper-base"]


def _family_forward(arch, mode, be):
    from repro import configs
    from repro.configs.base import AnalogSpec
    from repro.nn.frontends import audio_frame_stub
    from repro.nn.model import build

    cfg = configs.get_smoke(arch).replace(
        dtype="float32", capacity_factor=8.0,
        analog=AnalogSpec(enabled=True, adc_bits=5, mode=mode, backend=be))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    extra = None
    if cfg.family == "encdec":
        extra = {"frames": audio_frame_stub(jax.random.PRNGKey(2), 2,
                                            cfg.enc_len, cfg.d_model,
                                            dtype=jnp.float32)}
    return model.forward(params, tokens, extra, key=_key(mode)), model


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_model_family_parity(arch):
    """Every nn/ family reaches the fused kernels through the dispatch and
    matches the ref backend to sub-LSB (= quantization-exact)."""
    out_ref, model = _family_forward(arch, "exact", "ref")
    out_pal, _ = _family_forward(arch, "exact", "pallas")
    lsb = model.act.ramp.lsb
    d = float(jnp.max(jnp.abs(out_ref - out_pal)))
    # logits are a linear readout of NL-ADC'd activations: allow a few
    # output-LSB-scaled units of accumulated float slack, far below one
    # quantization step's effect on any single activation
    assert d < lsb / 2, (arch, d, lsb)


@pytest.mark.parametrize("mode", ["train", "infer"])
def test_model_modes_parity(mode):
    """Noise modes draw identically on both backends (shared orchestration)."""
    out_ref, model = _family_forward("qwen2.5-3b", mode, "ref")
    out_pal, _ = _family_forward("qwen2.5-3b", mode, "pallas")
    lsb = model.act.ramp.lsb
    assert float(jnp.max(jnp.abs(out_ref - out_pal))) < lsb / 2


def test_model_train_grad_parity():
    """STE gradients through a whole train-mode model match across backends."""
    from repro import configs
    from repro.configs.base import AnalogSpec
    from repro.nn.model import build

    grads = {}
    for be in BACKENDS:
        cfg = configs.get_smoke("qwen2.5-3b").replace(
            dtype="float32",
            analog=AnalogSpec(enabled=True, adc_bits=5, mode="train",
                              backend=be))
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                         cfg.vocab),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                         cfg.vocab),
        }

        def loss(p):
            total, _ = model.loss(p, batch, key=jax.random.PRNGKey(3),
                                  remat=False)
            return total

        grads[be] = jax.grad(loss)(params)
    for a, b in zip(jax.tree.leaves(grads["ref"]),
                    jax.tree.leaves(grads["pallas"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# Decode path (int8 KV flash decode through the dispatch)
# ---------------------------------------------------------------------------

def test_int8_decode_backend_parity():
    from repro import configs
    from repro.configs.base import AnalogSpec
    from repro.nn.model import build

    outs = {}
    for be in BACKENDS:
        cfg = configs.get_smoke("qwen2.5-3b").replace(
            dtype="float32", kv_cache_dtype="int8",
            analog=AnalogSpec(enabled=False, backend=be))
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                  cfg.vocab)
        state = model.init_decode_state(2, 32)
        logs = []
        for t in range(8):
            l, state = model.decode_step(params, state, toks[:, t:t + 1])
            logs.append(l)
        outs[be] = jnp.concatenate(logs, axis=1)
    rel = float(jnp.max(jnp.abs(outs["ref"] - outs["pallas"]))) \
        / float(jnp.max(jnp.abs(outs["ref"])))
    assert rel < 1e-5, rel


# ---------------------------------------------------------------------------
# Device-model presets: parity must hold under build-stage nonidealities
# (programmed/drifted thresholds + read noise), not just the ideal ramp
# ---------------------------------------------------------------------------


def test_deployed_ramp_codes_bitwise(rng):
    """Bitwise ADC-code parity on the aged-1day programmed thresholds."""
    from repro.core.device import get_device
    from repro.kernels import ops

    ramp = build_ramp("sigmoid", 5)
    deployed = get_device("aged-1day").deploy_ramp(ramp)
    adc = NLADC(deployed)
    x = jnp.asarray(rng.normal(0, 2, (29, 33)).astype(np.float32))
    ref_codes = np.asarray(adc.codes(x))
    from repro.kernels.ref import decode_params

    y0, lsb_l, _, _ = decode_params(deployed)
    y = np.asarray(ops.nladc(x, deployed), np.float64)
    got_codes = np.rint((y - y0) / lsb_l).astype(np.int64)
    np.testing.assert_array_equal(got_codes, ref_codes)


@pytest.mark.parametrize("preset", ["aged-1day", "stressed"])
def test_dense_nladc_parity_under_noisy_preset(preset, rng):
    """Infer-mode layer parity under build-stage device models."""
    x = jnp.asarray(rng.normal(0, 0.4, (9, 40)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.2, (40, 24)).astype(np.float32))
    outs = {}
    for be in BACKENDS:
        act = AnalogActivation("swish", _cfg("infer", be, device=preset))
        outs[be] = dense_nladc({"w": w}, x, act, key=_key("infer"))
        lsb = _lsb(act)
    assert float(jnp.max(jnp.abs(outs["ref"] - outs["pallas"]))) < lsb / 2


def test_model_noisy_preset_parity():
    """aged-1day end-to-end through a whole LM: both backends see the same
    programmed thresholds and read-noise draws (the acceptance case)."""
    from repro import configs
    from repro.configs.base import AnalogSpec
    from repro.nn.model import build

    outs, lsb = {}, None
    for be in BACKENDS:
        cfg = configs.get_smoke("qwen2.5-3b").replace(
            dtype="float32",
            analog=AnalogSpec(enabled=True, adc_bits=5, mode="infer",
                              backend=be, device="aged-1day"))
        model = build(cfg)
        lsb = model.act.ramp.lsb
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                    cfg.vocab)
        outs[be] = model.forward(params, tokens, key=_key("infer"))
    assert float(jnp.max(jnp.abs(outs["ref"] - outs["pallas"]))) < lsb / 2


def test_lstm_noisy_preset_parity():
    from repro.nn import lstm as NN

    ys, lsb = {}, None
    for be in BACKENDS:
        spec = NN.LSTMSpec(
            n_in=10, n_hidden=12,
            analog=AnalogConfig(enabled=True, adc_bits=5, input_bits=5,
                                mode="infer", backend=be,
                                device="aged-1day"))
        acts = NN.make_gate_acts(spec.analog)
        lsb = _lsb(acts[0])
        p = NN.lstm_init(jax.random.PRNGKey(1), spec)
        xs = 0.5 * jax.random.normal(jax.random.PRNGKey(2), (4, 5, 10))
        ys[be], _ = NN.lstm_scan(p, xs, spec, acts, key=_key("infer"))
    assert float(jnp.max(jnp.abs(ys["ref"] - ys["pallas"]))) < lsb / 2


# ---------------------------------------------------------------------------
# Threshold banks: the (n_col_tiles, P) layout through both backends
# ---------------------------------------------------------------------------


def _banked(adc, n_banks, width, spread=0.0):
    """A BankedThresholds over ``width`` columns (optionally per-bank
    distinct thresholds, as an actually-deployed bank would carry)."""
    from repro.core.nladc import BankedThresholds, bank_map_for

    thr = np.stack([np.asarray(adc.thresholds) + spread * j
                    for j in range(n_banks)])
    return BankedThresholds(jnp.asarray(thr, jnp.float32),
                            bank_map_for(width, -(-width // n_banks)))


@pytest.mark.parametrize("be", BACKENDS)
@pytest.mark.parametrize("name", ["sigmoid", "tanh", "gelu"])
def test_single_bank_bitwise_equals_legacy(be, name, rng):
    """n_col_tiles=1 banked path == the legacy (P,) path, BITWISE — ADC
    codes and STE grads — on ref AND pallas (the acceptance criterion)."""
    ramp = build_ramp(name, 5)
    adc = NLADC(ramp)
    bk = BK.get_backend(be)
    x = jnp.asarray(rng.normal(0, 2, (13, 24)).astype(np.float32))
    b1 = _banked(adc, 1, 24)

    y_leg = np.asarray(bk.nladc(x, adc))
    y_bank = np.asarray(bk.nladc(x, adc, thresholds=b1))
    np.testing.assert_array_equal(y_leg, y_bank)

    def loss(fn):
        return jax.grad(lambda v: jnp.sum(fn(v) ** 2))(x)

    g_leg = np.asarray(loss(lambda v: bk.nladc(v, adc)))
    g_bank = np.asarray(loss(lambda v: bk.nladc(v, adc, thresholds=b1)))
    np.testing.assert_array_equal(g_leg, g_bank)

    # the fused matmul path too
    w = jnp.asarray(rng.normal(0, 0.2, (16, 24)).astype(np.float32))
    m_leg = np.asarray(bk.matmul_nladc(x[:, :16], w, adc))
    m_bank = np.asarray(bk.matmul_nladc(x[:, :16], w, adc, thresholds=b1))
    np.testing.assert_array_equal(m_leg, m_bank)


def test_banked_codes_bitwise_ref_vs_pallas(rng):
    """Multi-bank deployed thresholds: both backends produce bitwise-equal
    ADC codes (each column against its own col-tile's programmed ramp)."""
    from repro.core.device import get_device

    ramp = build_ramp("sigmoid", 5)
    dev = get_device("aged-1day")
    ramps = dev.deploy_ramp_bank(ramp, 4)
    from repro.core.nladc import BankedThresholds, bank_map_for

    bt = BankedThresholds(
        jnp.asarray(np.stack([r.thresholds for r in ramps]), jnp.float32),
        bank_map_for(30, 8))
    adc = NLADC(ramp)
    x = jnp.asarray(rng.normal(0, 2, (21, 30)).astype(np.float32))
    y = {be: np.asarray(BK.get_backend(be).nladc(x, adc, thresholds=bt),
                        np.float64)
         for be in BACKENDS}
    from repro.kernels.ref import decode_params

    y0, lsb_l, _, _ = decode_params(ramp)
    np.testing.assert_array_equal(
        np.rint((y["ref"] - y0) / lsb_l).astype(np.int64),
        np.rint((y["pallas"] - y0) / lsb_l).astype(np.int64))


@pytest.mark.parametrize("mode", MODES)
def test_banked_activation_parity_and_grads(mode, rng):
    """AnalogConfig.bank_cols end-to-end through dense_nladc: outputs
    quantization-exact across backends, STE grads equal — in every mode
    (train draws per-bank ramp noise from the shared key)."""
    x = jnp.asarray(rng.normal(0, 0.4, (9, 40)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.2, (40, 24)).astype(np.float32))
    outs, grads, lsb = {}, {}, None
    for be in BACKENDS:
        act = AnalogActivation(
            "swish", _cfg(mode, be, device="aged-1day", bank_cols=8))
        assert act.bank_for(24).n_banks == 3
        lsb = _lsb(act)
        outs[be] = dense_nladc({"w": w}, x, act, key=_key(mode))

        def loss(xx, ww):
            return jnp.sum(dense_nladc({"w": ww}, xx, act,
                                       key=_key(mode)) ** 2)

        grads[be] = jax.grad(loss, argnums=(0, 1))(x, w)
    assert float(jnp.max(jnp.abs(outs["ref"] - outs["pallas"]))) < lsb / 2
    for a, b in zip(grads["ref"], grads["pallas"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_banked_lstm_parity(rng):
    """Banked gate/cell NL-ADCs through the fused LSTM tail, both backends."""
    from repro.nn import lstm as NN

    ys, lsb = {}, None
    for be in BACKENDS:
        spec = NN.LSTMSpec(
            n_in=10, n_hidden=12,
            analog=AnalogConfig(enabled=True, adc_bits=5, input_bits=5,
                                mode="infer", backend=be,
                                device="aged-1day", bank_cols=4))
        acts = NN.make_gate_acts(spec.analog, width=12)
        assert acts[0].bank_for(12).n_banks == 3
        lsb = _lsb(acts[0])
        p = NN.lstm_init(jax.random.PRNGKey(1), spec)
        xs = 0.5 * jax.random.normal(jax.random.PRNGKey(2), (4, 5, 10))
        ys[be], _ = NN.lstm_scan(p, xs, spec, acts, key=_key("infer"))
    assert float(jnp.max(jnp.abs(ys["ref"] - ys["pallas"]))) < lsb / 2


def test_from_spec_carries_bank_cols():
    from repro.configs.base import AnalogSpec

    cfg = AnalogConfig.from_spec(AnalogSpec(enabled=True, bank_cols=128))
    assert cfg.bank_cols == 128
    cfg2 = AnalogConfig.from_spec(AnalogSpec(enabled=True), bank_cols=64)
    assert cfg2.bank_cols == 64


def test_env_override_selects_backend(monkeypatch):
    from repro.core.backend import PallasBackend, get_backend, resolve_backend

    monkeypatch.setenv("REPRO_ANALOG_BACKEND", "pallas")
    assert resolve_backend("") == "pallas"
    assert isinstance(get_backend(""), PallasBackend)
    assert resolve_backend("ref") == "ref"
    monkeypatch.delenv("REPRO_ANALOG_BACKEND")
    assert resolve_backend("") == "ref"


# ---------------------------------------------------------------------------
# Circuit-level stages (LineResistance / NonlinearIV): parity by construction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("preset", ["paper-ir", "stressed-ir"])
def test_matmul_parity_under_ir_presets(preset, rng):
    """IR-drop correction and nonlinear-IV read are folded into the shared
    seam *before* backend dispatch, so both backends consume identical
    effective weights / driven inputs and codes stay bitwise-equal."""
    x = jnp.asarray(rng.normal(0, 0.4, (7, 48)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.2, (48, 24)).astype(np.float32))
    outs = {}
    for be in BACKENDS:
        cfg = _cfg("infer", be, input_bits=5, device=preset)
        act = AnalogActivation("tanh", cfg)
        outs[be] = analog_matmul_act(x, w, cfg, key=_key("infer"),
                                     activation=act)
        lsb = _lsb(act)
    assert float(jnp.max(jnp.abs(outs["ref"] - outs["pallas"]))) < lsb / 2


@pytest.mark.parametrize("preset", ["paper-ir", "stressed-ir"])
def test_dense_nladc_parity_under_ir_presets(preset, rng):
    """Activations-only path: the line stage still reshapes the deployed
    ramp (programmed thresholds), which both backends must share."""
    x = jnp.asarray(rng.normal(0, 0.4, (9, 40)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.2, (40, 24)).astype(np.float32))
    outs = {}
    for be in BACKENDS:
        act = AnalogActivation("swish", _cfg("infer", be, device=preset))
        outs[be] = dense_nladc({"w": w}, x, act, key=_key("infer"))
        lsb = _lsb(act)
    assert float(jnp.max(jnp.abs(outs["ref"] - outs["pallas"]))) < lsb / 2


def test_ir_stage_changes_output_but_not_parity(rng):
    """Sanity that the stage is actually live on this path: paper-ir output
    differs from paper-infer, while each stays parity-clean."""
    x = jnp.asarray(rng.normal(0, 0.4, (7, 48)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.2, (48, 24)).astype(np.float32))
    got = {}
    for preset in ("paper-infer", "paper-ir"):
        cfg = _cfg("infer", "ref", input_bits=5, device=preset)
        act = AnalogActivation("tanh", cfg)
        got[preset] = analog_matmul_act(x, w, cfg, key=_key("infer"),
                                        activation=act)
    assert float(jnp.max(jnp.abs(got["paper-infer"] - got["paper-ir"]))) > 0


# ---------------------------------------------------------------------------
# PR 10 backend methods: fused MoE einsum + cached attention
# ---------------------------------------------------------------------------

def _moe_inputs(rng, e=3, c=6, d=24, f=32):
    x = jnp.asarray(rng.normal(0, 0.5, (e, c, d)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.3, (e, d, f)).astype(np.float32))
    return x, w


@pytest.mark.parametrize("banked", [False, True])
def test_moe_matmul_nladc_parity_and_grads(banked, rng):
    """Fused MoE expert einsum: codes within LSB/2 across backends, STE
    grads (dx AND dw) matching across backends — plain and banked.

    Grads follow the file convention (allclose at 1e-5, not bitwise):
    the hand-written bwd einsums may contract in a different order than
    the autodiff transpose of the ref composition."""
    ramp = build_ramp("swish", 5)
    adc = NLADC(ramp)
    x, w = _moe_inputs(rng)
    thr = None
    if banked:
        from repro.core.nladc import BankedThresholds, bank_map_for

        n_banks, f = 2, w.shape[-1]
        t = np.stack([np.asarray(adc.thresholds) + 0.01 * j
                      for j in range(n_banks)])
        thr = BankedThresholds(jnp.asarray(t, jnp.float32),
                               bank_map_for(f, f // n_banks))
    outs, gx, gw = {}, {}, {}
    for be in BACKENDS:
        bk = BK.get_backend(be)
        outs[be] = bk.moe_matmul_nladc(x, w, adc, thr)
        gx[be], gw[be] = jax.grad(
            lambda a, b: jnp.sum(bk.moe_matmul_nladc(a, b, adc, thr) ** 2),
            argnums=(0, 1))(x, w)
    lsb = float(ramp.lsb)
    assert float(jnp.max(jnp.abs(outs["ref"] - outs["pallas"]))) < lsb / 2
    np.testing.assert_allclose(np.asarray(gx["ref"]),
                               np.asarray(gx["pallas"]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw["ref"]),
                               np.asarray(gw["pallas"]),
                               rtol=1e-5, atol=1e-5)


def test_moe_matmul_nladc_matches_unfused(rng):
    """Each backend's fused MoE call == its own nladc(einsum) composition
    (the historical moe.py gate path), bitwise."""
    ramp = build_ramp("sigmoid", 5)
    adc = NLADC(ramp)
    x, w = _moe_inputs(rng)
    for be in BACKENDS:
        bk = BK.get_backend(be)
        fused = bk.moe_matmul_nladc(x, w, adc)
        unfused = bk.nladc(
            jnp.einsum("ecd,edf->ecf", x, w.astype(x.dtype)), adc)
        lsb = float(ramp.lsb)
        assert float(jnp.max(jnp.abs(fused - unfused))) < lsb / 2, be


def test_prefill_attention_backend_parity_and_grads(rng):
    """Cached attention: bitwise outputs and grads (q, k, v) across
    backends — the serve stream invariance anchor."""
    b, h, hkv, d, s = 2, 8, 2, 16, 12
    q = jnp.asarray(rng.normal(0, 1, (b, 1, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (b, s, hkv, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (b, s, hkv, d)).astype(np.float32))
    mask = (jnp.arange(s) < 9)[None, None, :]
    outs, grads = {}, {}
    for be in BACKENDS:
        bk = BK.get_backend(be)
        outs[be] = bk.prefill_attention(q, k, v, mask)
        grads[be] = jax.grad(
            lambda a, b2, c: jnp.sum(
                bk.prefill_attention(a, b2, c, mask) ** 2),
            argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_array_equal(np.asarray(outs["ref"]),
                                  np.asarray(outs["pallas"]))
    for g_r, g_p in zip(grads["ref"], grads["pallas"]):
        np.testing.assert_array_equal(np.asarray(g_r), np.asarray(g_p))


def test_prefill_attention_under_jit_and_scan(rng):
    """The kernel must be trace-safe inside the engine's masked prefill
    scan: jit(scan over positions) matches the eager per-step calls."""
    be = BK.get_backend("pallas")
    b, h, hkv, d, s = 1, 4, 2, 8, 6
    q_seq = jnp.asarray(rng.normal(0, 1, (s, b, 1, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (b, s, hkv, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (b, s, hkv, d)).astype(np.float32))

    def step(carry, i):
        mask = (jnp.arange(s) <= i)[None, None, :]
        return carry, be.prefill_attention(q_seq[i], k, v, mask)

    _, scanned = jax.jit(
        lambda: jax.lax.scan(step, 0, jnp.arange(s)))()
    for i in range(s):
        mask = (jnp.arange(s) <= i)[None, None, :]
        np.testing.assert_array_equal(
            np.asarray(scanned[i]),
            np.asarray(be.prefill_attention(q_seq[i], k, v, mask)))
