"""repro.obs: metrics, tracing, energy accounting, trace determinism.

The load-bearing contract is that traces are *bitwise reproducible*: the
step clock (not wall time) orders entries, so two seeded runs — or a run
interrupted by a checkpoint and resumed in a fresh process — emit the
same JSONL modulo the opt-in wall fields.
"""

import json

import jax
import numpy as np
import pytest

from repro import configs
from repro.configs.base import AnalogSpec
from repro.core import hwcost
from repro.nn.model import build
from repro.obs import (ChipEnergyModel, EnergyMeter, EventBus, Histogram,
                       MetricsRegistry, Obs, Tracer, read_jsonl, strip_wall)
from repro.obs.replay import chips_in, latency_summary, render_timeline
from repro.serve.engine import Request, ServingEngine
from repro.subproc import check_in_subprocess


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32", analog=AnalogSpec(enabled=False))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


# ---------------------------------------------------------------------------
# Histogram: log-scale buckets, percentiles, mergeability
# ---------------------------------------------------------------------------


def test_histogram_summary_exact_extremes():
    h = Histogram("t")
    for v in [1.0, 2.0, 4.0, 100.0]:
        h.record(v)
    s = h.summary()
    assert s["count"] == 4
    assert s["min"] == 1.0 and s["max"] == 100.0
    assert s["mean"] == pytest.approx(26.75)
    # percentiles land on (approximate) bucket values, clamped to the
    # exact observed range
    assert s["min"] <= s["p50"] <= s["p95"] <= s["p99"] <= s["max"]


def test_histogram_bucket_relative_error_bounded():
    # 8 subbuckets per octave -> worst-case relative error 2**(1/8)-1 ~ 9%
    h = Histogram("t")
    for v in [3.7, 11.2, 250.0, 0.004, 1e6]:
        h2 = Histogram("t")
        h2.record(v)
        assert h2.percentile(0.5) == pytest.approx(v, rel=0.10)
        h.record(v)
    assert h.count == 5


def test_histogram_zero_and_negative_underflow():
    h = Histogram("t")
    h.record(0.0)
    h.record(-3.0)
    s = h.summary()
    assert s["count"] == 2 and s["min"] == -3.0
    assert h.percentile(0.01) == -3.0  # clamped to exact min


def test_histogram_merge_is_commutative_associative_with_identity():
    rng = np.random.default_rng(0)
    hs = []
    for _ in range(3):
        h = Histogram("t")
        for v in rng.lognormal(0, 2, 40):
            h.record(float(v))
        hs.append(h)
    a, b, c = hs
    assert a.merge(b) == b.merge(a)
    assert a.merge(b).merge(c) == a.merge(b.merge(c))
    empty = Histogram("t")
    assert a.merge(empty) == a and empty.merge(a) == a
    # merged distribution == recording the union
    ab = a.merge(b)
    assert ab.count == a.count + b.count
    assert ab.sum == pytest.approx(a.sum + b.sum)
    assert ab.min == min(a.min, b.min) and ab.max == max(a.max, b.max)


def test_histogram_merge_property_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    vals = st.lists(st.floats(min_value=-1e9, max_value=1e9,
                              allow_nan=False), max_size=30)

    def mk(vs):
        h = Histogram("t")
        for v in vs:
            h.record(v)
        return h

    @hyp.given(vals, vals, vals)
    @hyp.settings(max_examples=50, deadline=None)
    def prop(xs, ys, zs):
        a, b, c = mk(xs), mk(ys), mk(zs)
        assert a.merge(b) == b.merge(a)
        assert a.merge(b).merge(c) == a.merge(b.merge(c))
        assert a.merge(Histogram("t")) == a

    prop()


def test_histogram_dict_round_trip():
    h = Histogram("t")
    for v in [0.5, 7.0, 7.0, 300.0]:
        h.record(v)
    h2 = Histogram("t")
    h2.restore(h.to_dict())
    assert h2 == h and h2.summary() == h.summary()


# ---------------------------------------------------------------------------
# Registry: get-or-create, snapshot/restore, prometheus text
# ---------------------------------------------------------------------------


def test_registry_get_or_create_by_name_and_labels():
    r = MetricsRegistry()
    c1 = r.counter("serve.tokens_total", chip="chip00")
    c2 = r.counter("serve.tokens_total", chip="chip00")
    c3 = r.counter("serve.tokens_total", chip="chip01")
    assert c1 is c2 and c1 is not c3
    c1.inc(5)
    assert r.find("serve.tokens_total", chip="chip00").value == 5


def test_registry_snapshot_restore_round_trip():
    r = MetricsRegistry()
    r.counter("a.count").inc(3)
    r.gauge("b.level", chip="c0").set(1.5)
    r.histogram("c.lat").record(12.0)
    snap = r.snapshot()
    # snapshot is pure data (json-safe)
    snap = json.loads(json.dumps(snap))
    r2 = MetricsRegistry()
    r2.restore(snap)
    assert r2.find("a.count").value == 3
    assert r2.find("b.level", chip="c0").value == 1.5
    assert r2.find("c.lat").summary()["count"] == 1
    assert r2.snapshot() == snap


def test_registry_merged_histogram_across_chips():
    r = MetricsRegistry()
    r.histogram("serve.ttft_steps", chip="c0").record(2)
    r.histogram("serve.ttft_steps", chip="c1").record(4)
    m = r.merged_histogram("serve.ttft_steps")
    assert m.count == 2 and m.min == 2 and m.max == 4


def test_registry_prometheus_text():
    r = MetricsRegistry()
    r.counter("serve.tokens_total", chip="chip00").inc(7)
    r.gauge("lifecycle.inl_lsb").set(0.25)
    h = r.histogram("serve.ttft_steps")
    h.record(3.0)
    text = r.to_prometheus()
    assert 'serve_tokens_total{chip="chip00"} 7' in text
    assert "lifecycle_inl_lsb 0.25" in text
    assert 'quantile="99"' in text
    assert "serve_ttft_steps_count 1" in text
    assert "serve_ttft_steps_sum 3" in text


# ---------------------------------------------------------------------------
# Tracer + EventBus: step clock, wall stripping, jsonl, src filtering
# ---------------------------------------------------------------------------


def test_tracer_step_clock_and_spans():
    t = Tracer(enabled=True, wall_clock=False)
    t.set_step(4)
    t.event("submit", uid=0)
    with t.span("decode", active=2) as sp:
        t.set_step(5)
        sp.set(extra=1)
    e_ev, e_sp = t.entries
    assert e_ev == {"kind": "event", "seq": 1, "step": 4,
                    "type": "submit", "uid": 0}
    assert e_sp["name"] == "decode" and e_sp["step"] == 4 \
        and e_sp["end_step"] == 5 and e_sp["extra"] == 1
    assert "wall_s" not in e_ev and "wall_dur_s" not in e_sp


def test_tracer_wall_clock_opt_in_and_strip():
    t = Tracer(enabled=True, wall_clock=True)
    t.set_step(0)
    with t.span("decode"):
        pass
    t.event("finish", uid=1)
    assert any("wall_dur_s" in e or "wall_s" in e for e in t.entries)
    stripped = strip_wall(t.entries)
    assert all("wall_s" not in e and "wall_dur_s" not in e
               for e in stripped)
    # stripping is the ONLY difference
    for raw, st in zip(t.entries, stripped):
        assert {k: v for k, v in raw.items()
                if k not in ("wall_s", "wall_dur_s")} == st


def test_tracer_disabled_records_nothing():
    t = Tracer(enabled=False)
    t.event("x")
    with t.span("y"):
        pass
    assert t.entries == []


def test_tracer_jsonl_round_trip(tmp_path):
    t = Tracer(enabled=True)
    t.set_step(1)
    t.event("submit", uid=3, prompt_len=4)
    with t.span("decode", active=1):
        pass
    path = str(tmp_path / "trace.jsonl")
    t.write_jsonl(path)
    assert read_jsonl(path) == t.entries


def test_tracer_counters_resume_continuity():
    t = Tracer(enabled=True)
    t.set_step(7)
    t.event("a")
    t2 = Tracer(enabled=True)
    t2.restore_counters(t.counters())
    t2.event("b")
    assert t2.entries[0]["seq"] == t.entries[0]["seq"] + 1
    assert t2.entries[0]["step"] == 7


def test_event_bus_src_and_chip_filtering():
    t = Tracer(enabled=True)
    bus = EventBus(t)
    bus.emit("rebalance", step=1, src="fleet", chip="chip00")
    bus.emit("probe", step=1, src="sched", chip="chip00")
    bus.emit("rebalance", step=2, src="fleet", chip="chip01")
    assert [e["step"] for e in bus.view(src="fleet")] == [1, 2]
    assert [e["type"] for e in bus.view(chip="chip00")] \
        == ["rebalance", "probe"]
    # unified schema: every entry names step/type/src
    assert all({"step", "type", "src"} <= set(e) for e in bus.events)
    # events mirror onto the tracer
    assert [e["type"] for e in t.entries] == ["rebalance", "probe",
                                              "rebalance"]


def test_obs_child_shares_state_and_tags_chip():
    obs = Obs(trace=True)
    child = obs.child("chip01")
    child.counter("serve.tokens_total").inc(2)
    child.emit("probe", step=0, src="sched")
    assert obs.metrics.find("serve.tokens_total", chip="chip01").value == 2
    assert obs.bus.events[0]["chip"] == "chip01"
    snap = obs.snapshot()
    obs2 = Obs(trace=True)
    obs2.restore(snap)
    assert obs2.metrics.find("serve.tokens_total", chip="chip01").value == 2


# ---------------------------------------------------------------------------
# Energy accounting: hwcost-priced chip model + calibration anchors
# ---------------------------------------------------------------------------


def test_nladc_macro_within_published_calibration_bracket():
    """A representative NL-ADC macro prices inside the measured 65nm
    NL-CIM silicon bracket (arXiv 2512.06362: 33.6-136.2 TOPS/W)."""
    t = hwcost.CALIBRATION_TARGETS["nlcim_65nm"]
    for dims in [(256, 256), (576, 576)]:
        m = hwcost.nladc_macro(*dims)
        assert t["tops_per_w_min"] <= m.tops_per_w <= t["tops_per_w_max"], \
            f"{dims}: {m.tops_per_w}"


def test_digital_lut_baseline_less_efficient_than_nladc():
    n = hwcost.nladc_macro(256, 256)
    d = hwcost.digital_lut_macro(256, 256)
    assert d.tops_per_w < n.tops_per_w
    assert d.energy_pj > n.energy_pj


def test_chip_energy_model_and_meter(smoke_model):
    cfg, model, params = smoke_model
    em = ChipEnergyModel.price(params, bits=5, bank_cols=0, redundancy=1)
    assert set(em.variants) == {"nladc", "digital_lut"}
    assert em.n_macros > 0
    assert em.variants["nladc"]["ops_per_token"] > 0
    reg = MetricsRegistry()
    meter = EnergyMeter(em, reg, chip="chip00")
    meter.add_processed(10)
    meter.add_generated(3)
    rep = meter.report()
    assert rep["processed_tokens"] == 10 and rep["generated_tokens"] == 3
    for variant in ("nladc", "digital_lut"):
        v = rep[variant]
        assert v["energy_j"] > 0
        assert v["tokens_per_joule"] > 0
        assert v["tops_per_w"] > 0
    # the paper's pitch: the NL-ADC chip beats the digital-LUT baseline
    assert rep["nladc_vs_digital_energy"] < 1.0
    assert rep["nladc"]["tokens_per_joule"] \
        > rep["digital_lut"]["tokens_per_joule"]
    # counters live in the registry -> they ride in checkpoints
    assert reg.find("energy.processed_tokens", chip="chip00").value == 10


def test_energy_redundancy_scales_only_array_energy(smoke_model):
    cfg, model, params = smoke_model
    e1 = ChipEnergyModel.price(params, bits=5, bank_cols=0, redundancy=1)
    e2 = ChipEnergyModel.price(params, bits=5, bank_cols=0, redundancy=2)
    pj1 = e1.variants["nladc"]["e_per_token_pj"]
    pj2 = e2.variants["nladc"]["e_per_token_pj"]
    # redundant NL-ADC columns cost more, but less than 2x (only the
    # NL-ADC array module is replicated)
    assert pj1 < pj2 < 2 * pj1


# ---------------------------------------------------------------------------
# Engine integration: latency percentiles + energy in run_offline
# ---------------------------------------------------------------------------


def test_run_offline_reports_latency_and_energy(smoke_model):
    cfg, model, params = smoke_model
    obs = Obs(trace=True)
    eng = ServingEngine(model, params, max_batch=2, max_len=64, obs=obs)
    rng = np.random.default_rng(2)
    reqs = [Request(uid=u, prompt=rng.integers(0, cfg.vocab, 5)
                    .astype(np.int32), max_new_tokens=3)
            for u in range(3)]
    out = eng.run_offline(reqs)
    for key in ("ttft_steps", "itl_steps", "ttft_ms", "itl_ms"):
        s = out[key]
        assert s["count"] > 0
        assert s["p50"] <= s["p95"] <= s["p99"]
    assert out["ttft_steps"]["count"] == 3          # one first token each
    assert out["energy"]["generated_tokens"] == 9
    assert out["energy"]["processed_tokens"] > 0
    assert out["energy"]["nladc"]["tokens_per_joule"] > 0
    # the trace saw every request through to completion
    types = [e.get("type") for e in obs.tracer.entries
             if e.get("kind") == "event"]
    assert types.count("submit") == 3
    assert types.count("first_token") == 3
    assert types.count("finish") == 3


# ---------------------------------------------------------------------------
# Profiler spans, the compile counter, TTFT from the arrival time
# ---------------------------------------------------------------------------


def _host_spans(log_dir):
    """Every ``serve.*`` event on the host planes of the profiler trace
    under ``log_dir``: [(name, start_ns, end_ns)] by start."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("serve.")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_engine_steps_nest_profiler_spans(smoke_model, tmp_path):
    """Each step() is one serve.step span holding the decode step's
    inputs, dispatch, host sync and bookkeeping, nested and in order; the
    default engine records no JSONL but keeps its profiler spans."""
    cfg, model, params = smoke_model
    eng = ServingEngine(model, params, max_batch=2, max_len=48)
    rng = np.random.default_rng(3)
    for uid in range(2):
        eng.submit(Request(uid=uid, prompt=rng.integers(0, cfg.vocab, 5)
                           .astype(np.int32), max_new_tokens=8))
    eng.step()                      # admits and compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            eng.step()
    assert eng.obs.tracer.entries == []
    spans = _host_spans(str(tmp_path))
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == 3
    parts = ["serve.decode.inputs", "serve.decode.dispatch",
             "serve.decode.sync", "serve.decode.bookkeep"]
    for _, t0, t1 in steps:
        inside = [s for s in spans if t0 <= s[1] and s[2] <= t1
                  and s[0] != "serve.step"]
        assert [s[0] for s in inside] == ["serve.decode"] + parts
        (_, d0, d1), *subs = inside
        assert all(d0 <= a and b <= d1 for _, a, b in subs)
        assert all(a[2] <= b[1] for a, b in zip(subs, subs[1:]))


def test_compile_counter_counts_only_new_programs(smoke_model):
    """After warmup() a wave of the warmed shapes builds no program; a
    new prompt bucket builds at least one."""
    cfg, model, params = smoke_model
    eng = ServingEngine(model, params, max_batch=2, max_len=48,
                        prefill_buckets=(8, 16))
    programs = eng.obs.metrics.find("compile.programs")
    seconds = eng.obs.metrics.find("compile.seconds")
    eng.warmup()
    assert programs.value >= 3 and seconds.value > 0
    before = programs.value, seconds.value
    rng = np.random.default_rng(4)
    for uid, n in enumerate((6, 14)):
        eng.submit(Request(uid=uid, prompt=rng.integers(0, cfg.vocab, n)
                           .astype(np.int32), max_new_tokens=3))
    assert eng.run_to_completion() == 6
    assert (programs.value, seconds.value) == before
    eng._ensure_prefill_exec(32)
    assert programs.value >= before[0] + 1 and seconds.value > before[1]
    from repro.obs.trace import compile_totals

    now = compile_totals()
    assert now[0] >= programs.value and now[1] >= seconds.value


def test_ttft_counts_from_arrival(smoke_model):
    """serve.ttft_ms counts from Request.arrival_s when it is set, else
    from submit()."""
    import time

    cfg, model, params = smoke_model
    eng = ServingEngine(model, params, max_batch=2, max_len=48)
    rng = np.random.default_rng(5)
    due = time.perf_counter() - 1000.0
    for uid, arrival in enumerate((due, None)):
        eng.submit(Request(uid=uid, prompt=rng.integers(0, cfg.vocab, 5)
                           .astype(np.int32), max_new_tokens=2,
                           arrival_s=arrival))
    eng.run_to_completion()
    ttft = eng.obs.metrics.find("serve.ttft_ms")
    assert ttft.count == 2
    assert ttft.max >= 1000.0 * 1e3 > ttft.min


# ---------------------------------------------------------------------------
# Replay CLI
# ---------------------------------------------------------------------------


def test_replay_timeline_and_summary(tmp_path, capsys):
    from repro.obs import replay

    obs = Obs(trace=True)
    c0 = obs.child("chip00")
    c0.set_step(0)
    c0.emit("submit", step=0, src="engine", uid=1, prompt_len=4)
    c0.emit("admit", step=1, src="engine", uid=1, slot=0,
            queue_wait_steps=1)
    with c0.span("decode", active=1):
        pass
    c0.emit("first_token", step=2, src="engine", uid=1, ttft_steps=2)
    c0.emit("finish", step=4, src="engine", uid=1, n_tokens=3)
    path = str(tmp_path / "t.jsonl")
    obs.tracer.write_jsonl(path)

    entries = read_jsonl(path)
    assert chips_in(entries) == ["chip00"]
    lines = render_timeline(entries)
    assert len(lines) == 1 + len(entries)
    assert any("[first_token]" in ln for ln in lines)
    s = latency_summary(entries)
    assert s["ttft_steps"]["count"] == 1 and s["ttft_steps"]["max"] == 2
    assert s["tokens_per_request"]["max"] == 3

    assert replay.main([path, "--last", "3"]) == 0
    out = capsys.readouterr().out
    assert "latency summary" in out and "chip00" in out


# ---------------------------------------------------------------------------
# Trace determinism: seeded reruns and checkpoint resume, both backends
# ---------------------------------------------------------------------------

_TRACE_COMMON = """
    import json

    import jax
    import numpy as np

    from repro import configs
    from repro.configs.base import AnalogSpec
    from repro.nn.model import build
    from repro.obs import Obs, strip_wall
    from repro.serve.engine import Request, ServingEngine

    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32",
        analog=AnalogSpec(enabled=True, mode="infer", device="aged-1day",
                          backend={backend!r}))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def fresh_engine():
        eng = ServingEngine(model, params, max_batch=2, max_len=48,
                            noise_seed=7, obs=Obs(trace=True))
        rng = np.random.default_rng(5)
        for uid in range(4):
            eng.submit(Request(
                uid=uid,
                prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32),
                max_new_tokens=3))
        return eng

    def dump(eng):
        reg = eng.obs.metrics
        print(json.dumps({{
            "trace": strip_wall(eng.obs.tracer.entries),
            "tokens": reg.find("serve.tokens_total").value,
            "ttft": reg.find("serve.ttft_steps").to_dict(),
        }}))
"""


def _trace_full(backend):
    return _TRACE_COMMON.format(backend=backend) + """
    eng = fresh_engine()
    for _ in range(8):
        eng.step()
    dump(eng)
"""


def _trace_save(backend, root):
    return _TRACE_COMMON.format(backend=backend) + f"""
    eng = fresh_engine()
    for _ in range(4):
        eng.step()
    eng.save({root!r}, 4)
    dump(eng)
"""


def _trace_resume(backend, root):
    return _TRACE_COMMON.format(backend=backend) + f"""
    eng = ServingEngine.restore(model, {root!r}, obs=Obs(trace=True))
    for _ in range(4):
        eng.step()
    dump(eng)
"""


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_trace_bitwise_reproducible(backend):
    """Two seeded runs in fresh processes emit identical JSONL traces
    (modulo the opt-in wall fields) and identical latency metrics."""
    a = json.loads(check_in_subprocess(
        _trace_full(backend), devices=1,
        timeout=900).strip().splitlines()[-1])
    b = json.loads(check_in_subprocess(
        _trace_full(backend), devices=1,
        timeout=900).strip().splitlines()[-1])
    assert a["trace"], "trace must not be empty"
    assert a == b


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_trace_deterministic_across_resume(backend, tmp_path):
    """checkpoint mid-run -> restore in a FRESH process: the concatenated
    trace (and the continued latency histograms / token counters) match
    the uninterrupted run exactly."""
    root = str(tmp_path / f"obs-{backend}")
    full = json.loads(check_in_subprocess(
        _trace_full(backend), devices=1,
        timeout=900).strip().splitlines()[-1])
    part = json.loads(check_in_subprocess(
        _trace_save(backend, root), devices=1,
        timeout=900).strip().splitlines()[-1])
    resumed = json.loads(check_in_subprocess(
        _trace_resume(backend, root), devices=1,
        timeout=900).strip().splitlines()[-1])

    assert part["trace"] + resumed["trace"] == full["trace"]
    assert resumed["tokens"] == full["tokens"]
    assert resumed["ttft"] == full["ttft"]


# ---------------------------------------------------------------------------
# MoE pair counters and the named scopes of the MLA + MoE block
# ---------------------------------------------------------------------------


def _moonlight_smoke():
    cfg = configs.get_smoke("moonlight-16b-a3b").replace(
        dtype="float32", experts_held=2, expert_offset=3,
        analog=AnalogSpec(enabled=False))
    model = build(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def test_moe_pair_counters_match_a_host_recount(monkeypatch):
    """``moe.pairs_routed`` and ``moe.pairs_local``, over the real rows of
    each decode step and summed over layers, equal a recount of the
    router's choices on the host: a callback hands each decode call's
    routing to the test, which counts the rows the step decoded."""
    from repro.nn import moe as MOE
    from repro.obs import trace as otrace

    cfg, model, params = _moonlight_smoke()
    held = range(cfg.expert_offset, cfg.expert_offset + cfg.experts_held)
    seen = {"rows": None, "routed": 0, "local": 0, "calls": 0}
    route = MOE.route

    def spy(p, xf, **kw):
        out = route(p, xf, **kw)

        def note(idx):
            if seen["rows"] is not None and idx.shape[0] == 3:
                idx = np.asarray(idx)[seen["rows"]]
                seen["calls"] += 1
                seen["routed"] += idx.size
                seen["local"] += int(np.isin(idx, list(held)).sum())
        jax.debug.callback(note, out[1])
        return out

    step_sync = ServingEngine._step_sync

    def step(self, active):
        seen["rows"] = list(active)
        try:
            return step_sync(self, active)
        finally:
            seen["rows"] = None

    monkeypatch.setattr(MOE, "route", spy)
    monkeypatch.setattr(ServingEngine, "_step_sync", step)
    eng = ServingEngine(model, params, max_batch=3, max_len=32)
    rng = np.random.default_rng(5)
    for uid, (n, new) in enumerate(((5, 6), (9, 3))):
        eng.submit(Request(uid=uid, prompt=rng.integers(0, cfg.vocab, n)
                           .astype(np.int32), max_new_tokens=new))
    before = otrace.counter_totals(otrace.MOE_PAIRS_LOCAL) or 0.0
    assert eng.run_to_completion() == 9
    routed = eng.obs.metrics.find(otrace.MOE_PAIRS_ROUTED).value
    local = eng.obs.metrics.find(otrace.MOE_PAIRS_LOCAL).value
    n_moe = cfg.n_layers - cfg.n_dense_layers
    assert seen["calls"] == 6 * n_moe                   # decode steps x layers
    assert routed == seen["routed"] == 9 * n_moe * cfg.top_k      # 9 tokens
    assert local == seen["local"] and 0 < local < routed
    assert otrace.counter_totals(otrace.MOE_PAIRS_LOCAL) == before + local


def test_moe_pair_counters_same_with_detok_thread():
    """The detokenize thread counts the pairs when its batch lands: the
    same totals as the synchronous path."""
    from repro.obs import trace as otrace

    cfg, model, params = _moonlight_smoke()
    got = []
    for detok in (False, True):
        eng = ServingEngine(model, params, max_batch=3, max_len=32,
                            detok_thread=detok)
        rng = np.random.default_rng(6)
        for uid, n in enumerate((4, 7, 6)):
            eng.submit(Request(uid=uid, prompt=rng.integers(0, cfg.vocab, n)
                               .astype(np.int32), max_new_tokens=5))
        eng.run_to_completion()
        eng.detok_flush()
        got.append(tuple(eng.obs.metrics.find(name).value for name in
                         (otrace.MOE_PAIRS_ROUTED, otrace.MOE_PAIRS_LOCAL)))
    n_moe = cfg.n_layers - cfg.n_dense_layers
    assert got[0] == got[1] and got[0][0] == 15 * n_moe * cfg.top_k


def test_dense_model_counts_no_pairs(smoke_model):
    cfg, model, params = smoke_model
    eng = ServingEngine(model, params, max_batch=2, max_len=32)
    eng.submit(Request(uid=0, prompt=np.arange(5, dtype=np.int32),
                       max_new_tokens=3))
    eng.run_to_completion()
    assert "moe_pairs" not in eng.state
    assert eng.obs.metrics.find("moe.pairs_routed") is None
    assert eng.obs.metrics.find("moe.pairs_local") is None


def test_mla_and_moe_scopes_name_device_ops():
    """Every named scope of the block reaches the compiled decode step's
    op names, which a profiler trace shows."""
    from repro.obs import trace as otrace

    cfg, model, params = _moonlight_smoke()
    state = model.init_decode_state(2, 16)
    tokens = np.zeros((2, 1), np.int32)
    text = jax.jit(model.decode_step).lower(params, state, tokens) \
        .compile().as_text()
    for scope in (otrace.SCOPE_MLA, otrace.SCOPE_ROUTER,
                  otrace.SCOPE_HELD_EXPERTS, otrace.SCOPE_SHARED_EXPERTS,
                  otrace.SCOPE_COMBINE):
        assert f"/{scope}/" in text, scope
