"""The check against the plain reference, at CPU size: the served tokens
of every cell agree with the reference, and the check comes out false
under the control (the reference in float8) and under each fault planted
in the timed path."""

import contextlib
import time

import pytest

from bench import run as R
from bench.tests import faults, small

CELLS = ["qwen2.5-3b.decode-heavy", "qwen2.5-3b.long-prompt",
         "mamba2-370m.chat-bursty"]
SECONDS = 3.0


def _run(name, seed, fault=None):
    cell, config = small.load(name)
    ctx = faults.FAULTS[fault]() if fault else contextlib.nullcontext()
    with ctx:
        return R.run(name, cell, config, seed, SECONDS, False,
                     t_start=time.perf_counter(), log=lambda m: None)


@pytest.mark.parametrize("name", CELLS)
def test_served_tokens_agree_with_reference(name):
    res = _run(name, 11)
    assert res["correct"], res["check"]
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]


@pytest.mark.parametrize("name", ["qwen2.5-3b.decode-heavy",
                                  "mamba2-370m.chat-bursty"])
def test_sample_covers_a_later_wave_a_mixed_wave_and_both_halves(name):
    import numpy as np

    cell, config = small.load(name)
    s = R.Session(cell, config, 3, log=lambda m: None)
    traffic, records, _, _, _ = s.window(3, SECONDS, False)
    assert all(r.slot >= 0 for r in records if r.token_times)
    picks = R._sample(records, np.random.default_rng(0),
                      cell["check"]["requests"], traffic.closed)
    assert len(picks) == cell["check"]["requests"]
    assert {r.slot % 2 for r in picks} == {0, 1}
    if traffic.closed:
        assert len({r.admit_step for r in picks}) > 1
    else:
        assert [r for r in picks if len({x.prompt_len for x in records
                                         if x.admit_step == r.admit_step})
                > 1]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_check(name):
    cell, config = small.load(name)
    cell["check"]["requests"] = 16
    s = R.Session(cell, config, 11, log=lambda m: None)
    traffic, records, _, _, _ = s.window(11, SECONDS, False)
    _, got = s.check(records, 11, traffic, control="fp8")
    chk = cell["check"]
    assert R.check_numbers(got["fp8@bf16"], chk)["agree_min"]["value"] \
        < chk["min_agree"] \
        <= R.check_numbers(got["bf16"], chk)["agree_min"]["value"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_fails_the_check(name, fault):
    res = _run(name, 11, fault)
    assert not res["correct"], res["check"]
