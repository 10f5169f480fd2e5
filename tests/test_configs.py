"""Config-schema regressions: parameter accounting + analog-spec plumbing."""

import dataclasses

import pytest

from repro import configs
from repro.configs.base import ARCH_NAMES, AnalogSpec

# Pinned (n_params, n_active_params) for every assigned arch.  These froze
# the values at the point the dead duplicate ``blk`` computation in the ssm
# branch was removed (the first assignment was discarded, so the numbers are
# unchanged); any future edit to n_params must update them CONSCIOUSLY.
N_PARAMS_PIN = {
    "pixtral-12b": (12_247_367_680, 12_247_367_680),
    "whisper-base": (97_517_568, 97_517_568),
    "qwen2.5-32b": (32_762_757_120, 32_762_757_120),
    "granite-34b": (47_248_834_560, 47_248_834_560),
    "granite-3-8b": (8_172_601_344, 8_172_601_344),
    "qwen2.5-3b": (3_085_959_168, 3_085_959_168),
    "moonlight-16b-a3b": (15_960_110_208, 2_914_776_192),
    "deepseek-moe-16b": (16_879_452_160, 2_830_630_912),
    "recurrentgemma-9b": (10_007_822_336, 10_007_822_336),
    "mamba2-370m": (355_467_264, 355_467_264),
    "kws_lstm": (9_600, 9_600),
    "ptb_lstm": (6_137_712, 6_137_712),
}


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_n_params_pinned(arch):
    cfg = configs.get(arch)
    want_total, want_active = N_PARAMS_PIN[arch]
    assert cfg.n_params() == want_total, arch
    assert cfg.n_active_params() == want_active, arch


def test_moonlight_counts_match_published_and_the_chips_share():
    """The whole model is the published 15.96B; one chip of the EP8
    deployment holds 8 of the 64 routed experts of each MoE layer."""
    cfg = configs.get("moonlight-16b-a3b")
    assert cfg.n_params() == 15_960_110_208
    assert cfg.replace(experts_held=8).n_params() == 3_364_615_296


def test_moe_active_below_total():
    cfg = configs.get("deepseek-moe-16b")
    assert cfg.n_active_params() < cfg.n_params()


def test_analog_spec_device_defaults_to_auto():
    """Every arch spec leaves the device preset on auto-resolution."""
    for arch in ARCH_NAMES:
        spec = configs.get(arch).analog
        assert isinstance(spec, AnalogSpec)
        assert spec.device == ""


def test_analog_spec_carries_device_name():
    spec = dataclasses.replace(configs.get("qwen2.5-3b").analog,
                               device="aged-1day")
    from repro.core.analog_layer import AnalogConfig

    cfg = AnalogConfig.from_spec(spec)
    assert cfg.device.name == "aged-1day"
    assert cfg.device.drift is not None and cfg.device.drift.t_s == 86_400.0
