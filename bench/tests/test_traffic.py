"""The traffic generators are functions of the seed."""

import numpy as np

from bench import run as R

GAMMA = {"rate_rps": 2.0, "cv": 2.0, "pool_seed": 7, "pool_size": 200,
         "prompt": {"median": 32, "sigma": 0.8, "min": 8, "max": 256},
         "output": {"median": 64, "sigma": 0.7, "min": 16, "max": 256}}
WAVES = {"clients": 4, "prompt_len": 16, "max_new_tokens": 8}


def _drain(t, until=1e9):
    return t.poll(until, True)


def test_open_gamma_same_seed_same_requests():
    gen = R.load_module("traffic", "open_gamma").Traffic
    a, b = _drain(gen(GAMMA, 2**31 + 5, 1000)), _drain(gen(GAMMA, 2**31 + 5,
                                                          1000))
    assert len(a) == 200
    for x, y in zip(a, b):
        assert x[0] == y[0] and x[2:] == y[2:]
        np.testing.assert_array_equal(x[1], y[1])


def test_open_gamma_seeds_share_the_schedule_not_the_ids():
    gen = R.load_module("traffic", "open_gamma").Traffic
    a, b = _drain(gen(GAMMA, 1, 1000)), _drain(gen(GAMMA, 2, 1000))
    assert [(len(x[1]), x[2], x[3]) for x in a] == \
        [(len(x[1]), x[2], x[3]) for x in b]
    assert any((x[1] != y[1]).any() for x, y in zip(a, b))
    lens = [len(r[1]) for r in a]
    assert min(lens) >= 8 and max(lens) <= 256


def test_open_gamma_is_bursty_at_its_rate():
    gen = R.load_module("traffic", "open_gamma").Traffic
    p = dict(GAMMA, pool_size=20000)
    due = np.array([r[3] for r in _drain(gen(p, 3, 10))])
    gaps = np.diff(due)
    assert abs(gaps.mean() - 0.5) < 0.02
    assert abs(gaps.std() / gaps.mean() - 2.0) < 0.15


def test_open_gamma_sends_only_what_is_due():
    t = R.load_module("traffic", "open_gamma").Traffic(GAMMA, 4, 1000)
    first = t.next_due()
    assert t.poll(first * 0.99, True) == []
    assert len(t.poll(first, True)) >= 1


def test_closed_waves_seeded_and_only_when_idle():
    gen = R.load_module("traffic", "closed_waves").Traffic
    a, b = gen(WAVES, 9, 100), gen(WAVES, 9, 100)
    assert a.poll(0.0, False) == []
    wa, wb = a.poll(1.5, True), b.poll(1.5, True)
    assert [r[0] for r in wa] == [0, 1, 2, 3]
    assert all(r[2] == 8 and r[3] == 1.5 and len(r[1]) == 16 for r in wa)
    for x, y in zip(wa, wb):
        np.testing.assert_array_equal(x[1], y[1])
    c = gen(WAVES, 10, 100).poll(0.0, True)
    assert any((x[1] != y[1]).any() for x, y in zip(wa, c))
