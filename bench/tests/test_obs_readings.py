"""Readings of the program's own instrumentation (``bench/obs.py`` and
the readers built on it): the recorded chip trace reads as before and
gives them nothing; hand-built planes give hand-computed values; a CPU
run of decode-heavy reads its compile counter."""

import os
import time
from types import SimpleNamespace as NS

import pytest

from bench import obs
from bench import run as R
from bench import trace as T
from bench.tests import small

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "decode_steps.xplane.pb")
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(red, window=(0.0, 1.0)):
    cell = R.load_json("workloads", "qwen2.5-3b.decode-heavy.json")
    config = R.load_json("configs", cell["config"] + ".json")
    steps = [R.StepRec(0.0, 0.0, [], [c] * 32, True) for c in (129, 130,
                                                                131)]
    return R.Context(cell=cell, cfg=R.reference_config(config), peak=PEAK,
                     n_params=3_085_938_688, setup_s=0.0, window=window,
                     requests=[], steps=steps, trace=red)


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    data = ProfileData.from_file(DATA)
    return T.reduce_planes(data.planes), obs.serve_spans(data.planes)


# the values these readers gave on the recorded trace before the program
# had spans or a compile counter
BEFORE = {"decode_step_ms": 22.897267,
          "decode_mfu": 4.405617667669604,
          "fused_matmul_nladc_roofline": 20.795841659921003,
          "prefill_attention_roofline": 4.301042009582531,
          "step_mfu": 0.3026298121096447,
          "idle_share.throughput": 59.91700964951121}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_existing_readers_read_as_before(recorded, name):
    red, _ = recorded
    value = R.load_module("metrics", name).read(_ctx(red))
    assert value == pytest.approx(BEFORE[name], rel=1e-12)


def test_new_readings_find_nothing_in_the_recorded_trace(recorded):
    red, spans = recorded
    ctx = _ctx(red)
    assert spans == []
    for name in ("compiles_in_window", "setup_compile_s"):
        assert R.load_module("metrics", name).read(ctx) is None
    assert obs.decode_host_ms(red, spans) is None
    assert obs.engine_idle_share(red, spans) is None
    assert obs.idle_by_span(red, spans) is None
    assert {g[0] for g in T.breakdown(red)["idle_gaps"]} <= {"engine.step",
                                                             "none"}


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=line, events=[NS(name=n, start_ns=s, duration_ns=e - s)
                              for n, s, e in events])
        for line, events in lines.items()])


# Two engine steps in a 1000-ns window: a decode-only step, then one that
# admits (prefill on the device) before it decodes.
PLANES = [
    _plane("/device:TPU:0", {"XLA Ops": [("decode", 100, 380),
                                         ("prefill", 600, 690),
                                         ("decode", 710, 890)]}),
    _plane("/host:CPU", {"python": [
        ("bench.traced", 0, 1000),
        ("engine.step", 50, 500),
        ("serve.step", 60, 490),
        ("serve.decode", 70, 480),
        ("serve.decode.inputs", 70, 90),
        ("serve.decode.dispatch", 90, 150),
        ("serve.decode.sync", 150, 400),
        ("serve.decode.bookkeep", 400, 480),
        ("engine.step", 500, 950),
        ("serve.step", 510, 940),
        ("serve.admit", 515, 700),
        ("serve.decode", 700, 930),
        ("serve.decode.sync", 720, 900)]}),
]


def test_span_readings_on_hand_built_planes():
    red = T.reduce_planes(PLANES)
    spans = obs.serve_spans(PLANES)
    assert len(spans) == 10 and red["window_s"] == pytest.approx(1e-6)
    # idle: [0, 100), [380, 600), [690, 710), [890, 1000)
    # the decode-only step: 430 ns less its 250-ns sync
    assert obs.decode_host_ms(red, spans) == pytest.approx(180e-6)
    # idle under serve.step: 40 + 110 + 90 + 20 + 50 ns of 1000
    assert obs.engine_idle_share(red, spans) == pytest.approx(31.0)
    got = {k: v * 1e9 for k, v in obs.idle_by_span(red, spans)}
    want = {"none": 100, "serve.admit": 95, "serve.decode.bookkeep": 80,
            "serve.decode": 40, "engine.step": 40, "serve.step": 35,
            "serve.decode.sync": 30, "serve.decode.inputs": 20,
            "serve.decode.dispatch": 10}
    assert got == pytest.approx(want)
    assert sum(want.values()) * 1e-9 == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert [k for k, _ in obs.idle_by_span(red, spans, top=2)] == \
        ["none", "serve.admit"]


def test_compile_readers_count_a_window():
    import jax
    import jax.numpy as jnp

    from repro.obs import MetricsRegistry
    from repro.obs.trace import watch_compiles

    watch_compiles(MetricsRegistry())       # starts the compile log
    t0 = time.perf_counter()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    t1 = time.perf_counter()
    ctx = _ctx(None, window=(t0, t1))
    assert R.load_module("metrics", "compiles_in_window").read(ctx) >= 1
    assert R.load_module("metrics", "setup_compile_s").read(ctx) >= 0
    ctx.window = (t1, time.perf_counter())
    assert R.load_module("metrics", "compiles_in_window").read(ctx) == 0


def test_traced_run_reads_no_compile_in_its_window():
    """decode-heavy at CPU size, traced: warm-up took every compile."""
    name = "qwen2.5-3b.decode-heavy"
    cell, config = small.load(name)
    res = R.run(name, cell, config, 11, 3.0, True,
                t_start=time.perf_counter(), log=lambda m: None)
    assert res["correct"], res["check"]
    m = res["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert m["setup_compile_s"]["value"] > 0
