"""Fleet orchestration: per-chip device derivation, the request router,
the maintenance planner's capacity floor, canary early warning, per-tile
weight refresh, and bitwise fleet checkpoint restore."""

import json
import math

import jax
import numpy as np
import pytest

from repro import configs
from repro.configs.base import AnalogSpec
from repro.core import crossbar as CB
from repro.core.analog_layer import AnalogActivation, AnalogConfig
from repro.core.device import DeviceModel, WriteNoise, get_device
from repro.ckpt.checkpoint import read_metadata, save_checkpoint
from repro.ft.elastic import plan_request_rebalance
from repro.nn.model import build
from repro.serve.engine import Request, ServingEngine
from repro.serve.fleet import (FleetEngine, FleetPolicy, MaintenancePlanner,
                               chip_device)
from repro.serve.lifecycle import RecalPolicy, RecalScheduler
from repro.subproc import check_in_subprocess

# ---------------------------------------------------------------------------
# Chip derivation
# ---------------------------------------------------------------------------


def test_chip_device_derivation_independent_and_deterministic():
    base = get_device("aged-1day")
    a = chip_device(base, "chip00")
    b = chip_device(base, "chip01")
    assert a.seed != b.seed and a.name != b.name
    assert a.name == "aged-1day@chip00"
    # pure function of (preset, id): rebuilding realizes the same die
    assert chip_device(base, "chip00") == a
    # distinct seeds -> distinct tile-keyed device populations
    w = np.random.default_rng(0).normal(0, 0.5, (64, 48))
    assert np.max(np.abs(a.age_weights_tiled(w, "k")
                         - b.age_weights_tiled(w, "k"))) > 0


# ---------------------------------------------------------------------------
# Maintenance planner: the capacity floor
# ---------------------------------------------------------------------------


def test_planner_fifo_grant_and_cap():
    pl = MaintenancePlanner(4, 0.75)
    assert pl.max_drain == 1
    for cid in ("c0", "c1", "c2", "c3"):
        assert pl.request(cid)
    assert not pl.request("c1")                 # idempotent while queued
    assert pl.grant_next() == "c0"
    assert pl.grant_next() is None              # cap reached
    pl.complete("c0")
    assert pl.grant_next() == "c1"              # FIFO order
    # round-trips
    pl2 = MaintenancePlanner.from_dict(pl.to_dict())
    assert pl2.to_dict() == pl.to_dict()


def _check_planner_invariant(n, floor, ops):
    """Under ANY interleaving of maintenance requests, grants, and
    completions, at most ceil(n*(1-floor)) chips drain at once — so
    accepting capacity never drops below the floor."""
    pl = MaintenancePlanner(n, floor)
    cap = math.ceil(n * (1.0 - floor))
    for op, k in ops:
        if op == "request":
            pl.request(f"c{k % n}")
        elif op == "grant":
            pl.grant_next()
        elif pl.draining:
            pl.complete(pl.draining[k % len(pl.draining)])
        assert len(pl.draining) <= cap
        assert n - len(pl.draining) >= n - cap
        # no chip is double-booked
        assert not set(pl.pending) & set(pl.draining)


def test_planner_capacity_floor_property():
    pytest.importorskip(
        "hypothesis", reason="optional dev dep (pip install hypothesis)")
    from hypothesis import given, settings, strategies as st  # noqa: E402

    @settings(max_examples=120, deadline=None)
    @given(st.integers(2, 9),
           st.sampled_from([0.5, 0.6, 0.75, 0.8, 0.9, 1.0]),
           st.lists(st.tuples(st.sampled_from(["request", "grant",
                                               "complete"]),
                              st.integers(0, 8)),
                    min_size=1, max_size=60))
    def prop(n, floor, ops):
        _check_planner_invariant(n, floor, ops)

    prop()


def test_planner_capacity_floor_seeded_sweep():
    """The same invariant, exercised unconditionally (hypothesis is an
    optional dep) over a seeded pseudo-random op soup."""
    import random

    for seed in range(200):
        rng = random.Random(seed)
        n = rng.randint(2, 9)
        floor = rng.choice([0.5, 0.6, 0.75, 0.8, 0.9, 1.0])
        ops = [(rng.choice(["request", "grant", "complete"]),
                rng.randint(0, 8)) for _ in range(rng.randint(1, 60))]
        _check_planner_invariant(n, floor, ops)


def test_plan_request_rebalance_least_loaded_deterministic():
    reqs = [f"r{i}" for i in range(5)]
    out = plan_request_rebalance(reqs, {"a": 2, "b": 0, "c": 1})
    # least-loaded first, ties break by chip id: b(0)<-r0, b(1)=c -> b<-r1,
    # c(1)<-r2, all at 2 -> a<-r3, then b again
    assert out == {"a": ["r3"], "b": ["r0", "r1", "r4"], "c": ["r2"]}
    assert plan_request_rebalance(reqs, {"a": 2, "b": 0, "c": 1}) == out
    with pytest.raises(ValueError, match="no surviving chips"):
        plan_request_rebalance(reqs, {})


# ---------------------------------------------------------------------------
# Router policies (exact-mode fleet: no device physics, fast)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def exact_fleet():
    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32", analog=AnalogSpec(enabled=False))
    return cfg, FleetEngine.build(cfg, 3, max_batch=2, max_len=48)


def test_round_robin_router_cycles(exact_fleet):
    _, fleet = exact_fleet
    fleet.policy = FleetPolicy(router="round-robin")
    fleet._rr = 0
    assert [fleet._route() for _ in range(4)] == [
        "chip00", "chip01", "chip02", "chip00"]


def test_least_loaded_router_balances(exact_fleet):
    cfg, fleet = exact_fleet
    fleet.policy = FleetPolicy(router="least-loaded")
    rng = np.random.default_rng(0)
    homes = [fleet.submit(Request(
        uid=1000 + i, prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32),
        max_new_tokens=1)) for i in range(3)]
    assert sorted(homes) == ["chip00", "chip01", "chip02"]
    fleet.run_to_completion()


def test_router_skips_draining_chip(exact_fleet):
    _, fleet = exact_fleet
    fleet.policy = FleetPolicy(router="round-robin")
    fleet._rr = 0
    fleet.chips["chip00"].engine.begin_drain()
    try:
        assert set(fleet._route() for _ in range(4)) == {"chip01", "chip02"}
        assert fleet.accepting() == ["chip01", "chip02"]
        assert fleet.capacity() == pytest.approx(2 / 3)
    finally:
        # settle the forced drain so sibling tests see a clean fleet
        fleet.chips["chip00"].engine.step()
        assert not fleet.chips["chip00"].engine.draining


def test_fleet_policy_validation():
    with pytest.raises(ValueError, match="unknown router"):
        FleetPolicy(router="random")
    with pytest.raises(ValueError, match="capacity_floor"):
        FleetPolicy(capacity_floor=1.5)


# ---------------------------------------------------------------------------
# The acceptance scenario: recal storm, canary early warning
# ---------------------------------------------------------------------------


def test_recal_storm_serialized_and_canary_tightens_siblings():
    """N=4, capacity_floor=0.75, every chip out-of-spec at the first probe
    (a recal storm): the planner serializes the maintenance windows so >= 3
    chips accept traffic at EVERY step, and the stressed canary's first
    recal tightens every sibling's probe cadence."""
    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32",
        analog=AnalogSpec(enabled=True, mode="infer", device="aged-1day"))
    pol = RecalPolicy(age_per_step_s=5e4, check_every=2,
                      inl_threshold_lsb=0.05)
    # round-robin so every chip (the canary included) serves traffic —
    # chips age per SERVING step, so an idle canary is no early warning
    fleet = FleetEngine.build(
        cfg, 4,
        policy=FleetPolicy(capacity_floor=0.75, router="round-robin"),
        recal=pol, max_batch=1, max_len=48, canary_presets=("stressed",))
    assert fleet.planner.max_drain == 1
    assert fleet.chips["chip03"].spec.canary
    assert fleet.chips["chip03"].device.name == "stressed@chip03"

    rng = np.random.default_rng(0)
    uid = 0
    for it in range(40):
        if it < 32:
            fleet.submit(Request(
                uid=uid, prompt=rng.integers(0, cfg.vocab, 4)
                .astype(np.int32), max_new_tokens=2))
            uid += 1
        fleet.step()
        # the floor, at every single step
        assert len(fleet.accepting()) >= 3

    kinds = [e["type"] for e in fleet.events]
    # the storm: every chip (canary included) requested maintenance
    req = {e["chip"] for e in fleet.events
           if e["type"] == "maintenance_requested"}
    assert req == set(fleet.chips)
    # windows were granted AND completed one at a time
    assert "drain_start" in kinds and "reprogram_done" in kinds
    open_w = 0
    for ev in fleet.events:
        if ev["type"] == "drain_start":
            open_w += 1
        elif ev["type"] == "reprogram_done":
            open_w -= 1
        assert 0 <= open_w <= 1
    # canary early warning: fired once, tightened every non-canary sibling
    warns = [e for e in fleet.events if e["type"] == "canary_warning"]
    assert len(warns) == 1 and warns[0]["chip"] == "chip03"
    assert set(warns[0]["tightened"]) == {"chip00", "chip01", "chip02"}
    for sid in ("chip00", "chip01", "chip02"):
        assert fleet.chips[sid].engine.scheduler.policy.check_every == 1
    assert fleet.chips["chip03"].engine.scheduler.policy.check_every == 2
    # every admission eventually completes despite the storm
    fleet.run_to_completion()
    assert len(fleet.admission_latency_steps()) == uid


# ---------------------------------------------------------------------------
# Per-tile weight refresh
# ---------------------------------------------------------------------------


def test_age_weights_tiled_col_overrides_scope_and_determinism():
    """A col-tile override rewrites exactly that tile's columns, with the
    same draw a full generation-g rewrite would give that tile."""
    dev = DeviceModel(name="t", write=WriteNoise(), seed=5)
    plan = CB.plan_tiles(64, 96, tile_rows=32, tile_cols=24)
    w = np.random.default_rng(0).normal(0, 0.5, (64, 96))
    base = dev.age_weights_tiled(w, "k", plan)
    part = dev.age_weights_tiled(w, "k", plan,
                                 col_overrides={1: (3, 0.0)})
    np.testing.assert_array_equal(part[:, :24], base[:, :24])
    np.testing.assert_array_equal(part[:, 48:], base[:, 48:])
    assert np.max(np.abs(part[:, 24:48] - base[:, 24:48])) > 0
    g3 = dev.age_weights_tiled(w, "k", plan, generation=3)
    np.testing.assert_array_equal(part[:, 24:48], g3[:, 24:48])
    np.testing.assert_array_equal(
        part, dev.age_weights_tiled(w, "k", plan,
                                    col_overrides={1: (3, 0.0)}))


def test_scheduler_records_stalled_refresh_ramps():
    dev = get_device("aged-1day")
    cfg = AnalogConfig(enabled=True, adc_bits=5, mode="infer", device=dev,
                       bank_cols=8)
    act = AnalogActivation("tanh", cfg)
    act.bank_for(24)
    pol = RecalPolicy(age_per_step_s=1e5, check_every=1,
                      inl_threshold_lsb=0.01,
                      weight_refresh_after_stalls=1)
    sched = RecalScheduler(dev, {"tanh": act}, pol)
    sched.tick()
    assert sched.weight_refresh_pending
    assert sched.weight_refresh_ramps
    # the stalled keys name real ramp states, bank members included
    assert set(sched.weight_refresh_ramps) <= set(sched.ramps)
    assert any(k.startswith("tanh@24:") for k in sched.weight_refresh_ramps)
    assert sched.events[-1]["weight_refresh_ramps"] == \
        sched.weight_refresh_ramps
    # keys survive consume (engine snapshots before consuming) and the
    # serialization round-trip
    d = sched.to_dict()
    assert d["weight_refresh_ramps"] == sched.weight_refresh_ramps
    assert sched.consume_weight_refresh()
    assert sched.weight_refresh_ramps


def _aged_bank_engine():
    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32",
        analog=AnalogSpec(enabled=True, mode="infer", device="aged-1day",
                          bank_cols=64))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pol = RecalPolicy(age_per_step_s=1e5, check_every=4,
                      inl_threshold_lsb=0.05, weight_refresh_after_stalls=1)
    eng = ServingEngine(model, params, max_batch=1, max_len=32,
                        device=get_device("aged-1day"), recal=pol)
    # banks deploy lazily on first application width; realize the d_ff bank
    # the way the first decode trace would, then let the scheduler adopt it
    eng._acts["act"].bank_for(cfg.d_ff)
    eng.scheduler._sync_banks()
    return cfg, model, params, eng


def test_engine_per_tile_refresh_rewrites_only_mapped_leaves(tmp_path):
    """A stalled BANK of the hidden activation re-programs only the
    crossbar col-tiles feeding it: the act's gate/up matrices change, every
    other leaf is bitwise untouched, and the chip-wide generation stays 0."""
    cfg, model, params, eng = _aged_bank_engine()
    sched = eng.scheduler
    key = sched.bank_key("act", cfg.d_ff, 1)
    assert key in sched.ramps                   # eager d_ff bank
    before = jax.tree.map(np.asarray, eng.params)

    sched.weight_refresh_pending = True
    sched.weight_refresh_ramps = [key]
    eng._on_chip_reprogram()

    assert eng._weight_gen == 0                 # no chip-wide rewrite
    assert set(eng._tile_gens) == {key}
    assert eng._tile_gens[key]["gen"] == 1
    after = jax.tree.map(np.asarray, eng.params)
    mlp = lambda t: t["layers"]["mlp"]          # noqa: E731
    assert np.max(np.abs(mlp(after)["wi_gate"]["w"]
                         - mlp(before)["wi_gate"]["w"])) > 0
    np.testing.assert_array_equal(mlp(after)["wo"]["w"],
                                  mlp(before)["wo"]["w"])
    np.testing.assert_array_equal(
        after["layers"]["attn"]["wq"]["w"],
        before["layers"]["attn"]["wq"]["w"])
    np.testing.assert_array_equal(after["embed"]["table"],
                                  before["embed"]["table"])

    # the partial re-program is part of the checkpointed deployment
    root = str(tmp_path / "ck")
    eng.save(root, 1)
    eng2 = ServingEngine.restore(model, root, params_like=params)
    assert eng2._tile_gens == eng._tile_gens
    assert eng2._refresh_ord == eng._refresh_ord
    for a, b in zip(jax.tree.leaves(eng2.params),
                    jax.tree.leaves(eng.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_unmapped_stall_falls_back_to_full_refresh():
    """A stalled ramp with no act->leaf mapping (or an unbanked one) keeps
    the chip-wide re-program semantics."""
    cfg, model, params, eng = _aged_bank_engine()
    sched = eng.scheduler
    sched.weight_refresh_pending = True
    sched.weight_refresh_ramps = ["sigmoid_act"]      # unbanked ramp
    eng._on_chip_reprogram()
    assert eng._weight_gen == 1 and not eng._tile_gens
    # a later per-tile refresh salts with a HIGHER ordinal than the
    # chip-wide one (no rng-stream collision between the two paths)
    key = sched.bank_key("act", cfg.d_ff, 0)
    sched.weight_refresh_pending = True
    sched.weight_refresh_ramps = [key]
    eng._on_chip_reprogram()
    assert eng._weight_gen == 1
    assert eng._tile_gens[key]["gen"] == 2


# ---------------------------------------------------------------------------
# read_metadata hardening + restore cross-hints
# ---------------------------------------------------------------------------


def test_read_metadata_rejects_foreign_payloads(tmp_path):
    d = tmp_path / "step_00000001"
    d.mkdir()
    (d / "manifest.json").write_text(json.dumps({"weights": [1, 2]}))
    with pytest.raises(ValueError, match="not a repro checkpoint manifest"):
        read_metadata(str(tmp_path))
    (d / "manifest.json").write_text("{definitely not json")
    with pytest.raises(ValueError, match="malformed JSON"):
        read_metadata(str(tmp_path))


def test_engine_restore_hints_fleet_manifest(tmp_path):
    save_checkpoint(str(tmp_path), 1, {}, metadata={"fleet": {"schema": 1}})
    with pytest.raises(ValueError, match="FleetEngine.restore"):
        ServingEngine.restore(None, str(tmp_path))


def test_fleet_restore_hints_engine_checkpoint(tmp_path):
    save_checkpoint(str(tmp_path), 1, {},
                    metadata={"engine": {"max_batch": 1, "max_len": 8}})
    cfg = configs.get_smoke("qwen2.5-3b")
    with pytest.raises(ValueError, match="ServingEngine.restore"):
        FleetEngine.restore(cfg, str(tmp_path))
    save_checkpoint(str(tmp_path), 2, {}, metadata={"train_step": 7})
    with pytest.raises(ValueError, match="repro.ckpt directly"):
        FleetEngine.restore(cfg, str(tmp_path))


# ---------------------------------------------------------------------------
# Bitwise fleet restore across a process restart, both backends
# ---------------------------------------------------------------------------

_FLEET_COMMON = """
    import json
    import numpy as np
    import jax
    from repro import configs
    from repro.configs.base import AnalogSpec
    from repro.serve.engine import Request
    from repro.serve.fleet import FleetEngine, FleetPolicy
    from repro.serve.lifecycle import RecalPolicy

    BACKEND = {backend!r}
    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32",
        analog=AnalogSpec(enabled=True, mode="infer", device="aged-1day",
                          backend=BACKEND))
    pol = RecalPolicy(age_per_step_s=2e4, check_every=2,
                      inl_threshold_lsb=0.3)

    def fresh_fleet():
        fleet = FleetEngine.build(cfg, 3, policy=FleetPolicy(),
                                  recal=pol, max_batch=1, max_len=48,
                                  canary_presets=("stressed",))
        rng = np.random.default_rng(3)
        for uid in range(5):
            fleet.submit(Request(
                uid=uid,
                prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32),
                max_new_tokens=4))
        return fleet

    def run(fleet, n, stream):
        for _ in range(n):
            for uid, tok in sorted(fleet.step().items()):
                stream.setdefault(str(uid), []).append(int(tok))

    def dump(fleet, stream):
        print(json.dumps({{
            "stream": stream,
            "events": fleet.events,
            "sched": {{cid: c.engine.scheduler.events
                       for cid, c in sorted(fleet.chips.items())}},
        }}))
"""


def _fleet_full(backend):
    return _FLEET_COMMON.format(backend=backend) + """
    fleet = fresh_fleet()
    stream = {}
    run(fleet, 6, stream)
    dump(fleet, stream)
"""


def _fleet_save(backend, root):
    return _FLEET_COMMON.format(backend=backend) + f"""
    fleet = fresh_fleet()
    stream = {{}}
    run(fleet, 3, stream)
    # the save lands MID-maintenance: the storm has chips pending/draining
    assert any(c.engine.maintenance_pending or c.engine.draining
               for c in fleet.chips.values())
    fleet.save({root!r}, fleet.step_count)
    dump(fleet, stream)
"""


def _fleet_resume(backend, root):
    return _FLEET_COMMON.format(backend=backend) + f"""
    fleet = FleetEngine.restore(cfg, {root!r})
    stream = {{}}
    run(fleet, 3, stream)
    dump(fleet, stream)
"""


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_fleet_restart_bitwise_reproducible(backend, tmp_path):
    """serve N fleet steps -> fleet checkpoint mid-maintenance -> restore
    in a FRESH process -> token streams, fleet events, and every chip's
    lifecycle trace match the uninterrupted run, on both backends."""
    root = str(tmp_path / f"fleet-{backend}")

    full = json.loads(check_in_subprocess(
        _fleet_full(backend), devices=1,
        timeout=900).strip().splitlines()[-1])
    part = json.loads(check_in_subprocess(
        _fleet_save(backend, root), devices=1,
        timeout=900).strip().splitlines()[-1])
    resumed = json.loads(check_in_subprocess(
        _fleet_resume(backend, root), devices=1,
        timeout=900).strip().splitlines()[-1])

    # bitwise token streams: prefix before the save, identical join after
    uids = set(full["stream"]) | set(part["stream"]) | set(resumed["stream"])
    for uid in uids:
        joined = part["stream"].get(uid, []) + resumed["stream"].get(uid, [])
        assert joined == full["stream"][uid], f"uid {uid}"
    # fleet-level event trace (router/planner/canary) continues exactly
    assert resumed["events"] == full["events"]
    # every chip's probe/recal trace is the uninterrupted one
    assert resumed["sched"] == full["sched"]


# ---------------------------------------------------------------------------
# Shelf aging (idle chips keep drifting) + probe-freshness routing
# ---------------------------------------------------------------------------


def test_shelf_aging_wakes_idle_canary():
    """Chips only tick their scheduler on steps where they decode, so an
    unrouted canary never ages and never warns — unless the fleet policy
    applies shelf aging to idle chips."""
    import dataclasses as _dc

    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32",
        analog=AnalogSpec(enabled=True, mode="infer", device="aged-1day"))
    pol = RecalPolicy(age_per_step_s=5e4, check_every=2,
                      inl_threshold_lsb=0.05)
    fleet = FleetEngine.build(
        cfg, 3, policy=FleetPolicy(router="round-robin"), recal=pol,
        max_batch=1, max_len=32, canary_presets=("stressed",))
    # default policy (shelf_age 0): no traffic -> no aging, no probes,
    # no warning — the silent-canary failure mode
    for _ in range(6):
        fleet.step()
    assert fleet.events == []
    assert all(c.engine.scheduler.step_count == 0
               for c in fleet.chips.values())
    # shelf aging on: the still-idle canary drifts, probes, recals, warns
    fleet.policy = _dc.replace(fleet.policy, shelf_age_per_step_s=5e4)
    for _ in range(12):
        fleet.step()
    kinds = [e["type"] for e in fleet.events]
    assert "canary_warning" in kinds
    warn = next(e for e in fleet.events if e["type"] == "canary_warning")
    assert warn["chip"] == "chip02"
    assert all(c.engine.scheduler.age_s > 0 for c in fleet.chips.values())
    # the maintenance loop runs for idle chips too, and reprogram_done
    # carries the bucket-invalidation observability payload
    fleet.run_to_completion()
    for _ in range(8):
        fleet.step()
    done = [e for e in fleet.events if e["type"] == "reprogram_done"]
    assert done and {"buckets_kept", "buckets_dropped"} <= set(done[0])


def test_fleet_policy_rejects_negative_shelf_age():
    with pytest.raises(ValueError, match="shelf_age_per_step_s"):
        FleetPolicy(shelf_age_per_step_s=-1.0)


def test_health_reports_probe_freshness():
    """health() exposes how stale the last INL probe is (in engine steps)
    plus the probe cadence, so routers can discount old readings."""
    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32",
        analog=AnalogSpec(enabled=True, mode="infer", device="aged-1day"))
    model = build(cfg)
    params = jax.tree.map(np.asarray, model.init(jax.random.PRNGKey(0)))
    pol = RecalPolicy(age_per_step_s=1.0, check_every=3,
                      inl_threshold_lsb=100.0)      # probe, never recal
    eng = ServingEngine(model, params, max_batch=1, max_len=32,
                        device=get_device("aged-1day"), recal=pol)
    h = eng.health()
    assert h["inl_age_steps"] == -1 and h["check_every"] == 3
    eng.submit(Request(uid=0, prompt=np.asarray([1, 2, 3], np.int32),
                       max_new_tokens=7))
    eng.run_to_completion()
    sched = eng.scheduler
    assert sched.events                              # probes fired
    h = eng.health()
    assert h["inl_age_steps"] == sched.step_count - sched.events[-1]["step"]
    assert 0 <= h["inl_age_steps"] < 3


def test_health_weighted_router_discounts_stale_probes(exact_fleet):
    """The health-weighted router's INL term decays once the probe is
    older than check_every (linearly to zero over one more cadence) and
    is ignored entirely for a never-probed chip."""
    _, fleet = exact_fleet
    fleet.policy = FleetPolicy(router="health-weighted")
    engines = [fleet.chips[c].engine for c in ("chip00", "chip01", "chip02")]
    saved = [e.health for e in engines]

    def fake(inl, age, ce=4):
        return lambda: {"active": 0, "queued": 0, "inl_lsb": inl,
                        "inl_age_steps": age, "check_every": ce}

    try:
        engines[2].health = fake(1.5, 1)         # fixed mid score (2.5)
        # fresh high-INL chip loses to a fresh clean chip
        engines[0].health = fake(2.0, 1)
        engines[1].health = fake(0.0, 1)
        assert fleet._route() == "chip01"
        # probe staler than 2x cadence: INL fully discounted -> tie on
        # score, lowest id wins despite the (stale) high reading
        engines[0].health = fake(2.0, 9)
        assert fleet._route() == "chip00"
        # half-stale: w = 0.5, so INL 2.0 scores like a fresh 1.0
        engines[0].health = fake(2.0, 6)
        engines[1].health = fake(1.0, 1)
        assert fleet._route() == "chip00"            # tie -> lowest id
        # never probed: no INL signal at all
        engines[0].health = fake(5.0, -1)
        engines[1].health = fake(0.0, 1)
        assert fleet._route() == "chip00"
    finally:
        for eng, h in zip(engines, saved):
            eng.health = h
