"""Device lifecycle: per-tile build-stage draws, the re-calibration
scheduler, and checkpointed (restart-reproducible) aged deployments."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import crossbar as CB
from repro.core.analog_layer import AnalogActivation, AnalogConfig
from repro.core.device import DeviceModel, StuckAt, WriteNoise, get_device
from repro.core.nladc import build_ramp
from repro.serve.lifecycle import RecalPolicy, RecalScheduler
from repro.subproc import check_in_subprocess

# ---------------------------------------------------------------------------
# Per-tile build-stage draws (TilePlan-keyed)
# ---------------------------------------------------------------------------


def test_tileplan_blocks_cover_matrix_once():
    plan = CB.plan_tiles(1300, 600)
    cover = np.zeros((1300, 600), np.int32)
    for (i, j), rs, cs in plan.blocks():
        assert 0 <= i < plan.n_row_tiles and 0 <= j < plan.n_col_tiles
        cover[rs, cs] += 1
    np.testing.assert_array_equal(cover, 1)


def test_two_tiles_of_one_matrix_decorrelated_stuck_masks():
    """The tentpole claim: a matrix split across crossbar tiles carries an
    independent device population per tile — stuck-at masks included."""
    dev = DeviceModel(name="t", stuck=StuckAt(prob=0.3), seed=3)
    plan = CB.plan_tiles(128, 96, tile_rows=64, tile_cols=48)
    w = np.ones((128, 96))
    aged = dev.age_weights_tiled(w, "w_gates", plan)
    masks = [(aged[rs, cs] == 0.0) for _, rs, cs in plan.blocks()]
    assert len(masks) == 4
    for m in masks:
        assert 0.1 < m.mean() < 0.5          # the fault stage visibly acts
    for a in range(len(masks)):
        for b in range(a + 1, len(masks)):
            assert not np.array_equal(masks[a], masks[b])


def test_tile_draws_permutation_independent():
    """Each tile's draw depends only on its key — not on visit order."""
    dev = get_device("aged-1day").replace(stuck=StuckAt(prob=0.05), seed=11)
    plan = CB.plan_tiles(150, 130, tile_rows=64, tile_cols=48)
    w = np.random.default_rng(0).normal(0, 0.5, (150, 130))
    whole = dev.age_weights_tiled(w, "k", plan)
    blocks = list(plan.blocks())
    for order in (blocks[::-1], blocks[2:] + blocks[:2]):
        out = np.empty_like(w)
        for (i, j), rs, cs in order:
            out[rs, cs] = dev.age_weights(w[rs, cs],
                                          dev.tile_rng("k", 0, i, j))
        np.testing.assert_array_equal(out, whole)


def test_age_params_tile_path_keyed_by_leaf_path():
    """rng=None ages per tile keyed by the pytree path: deterministic,
    and independent of what OTHER leaves exist in the tree."""
    dev = get_device("aged-1day")
    w = jnp.asarray(np.random.default_rng(1).normal(0, 0.5, (64, 48)),
                    jnp.float32)
    small = dev.age_params({"lstm": {"w": w}})
    big = dev.age_params({"lstm": {"w": w},
                          "fc": {"w": w * 2, "b": jnp.zeros((4,))}})
    np.testing.assert_array_equal(np.asarray(small["lstm"]["w"]),
                                  np.asarray(big["lstm"]["w"]))
    # biases untouched; distinct paths -> distinct draws
    np.testing.assert_array_equal(np.asarray(big["fc"]["b"]), 0.0)
    assert np.max(np.abs(np.asarray(big["fc"]["w"]) / 2
                         - np.asarray(big["lstm"]["w"]))) > 0
    # explicit-rng (legacy benchmark) path unchanged: sequential stream
    legacy = dev.age_params({"lstm": {"w": w}}, np.random.default_rng(5))
    legacy2 = dev.age_params({"lstm": {"w": w}}, np.random.default_rng(5))
    np.testing.assert_array_equal(np.asarray(legacy["lstm"]["w"]),
                                  np.asarray(legacy2["lstm"]["w"]))


def test_age_weights_tiled_rejects_mismatched_plan():
    dev = DeviceModel(name="t", write=WriteNoise(), seed=1)
    small_plan = CB.plan_tiles(64, 48, tile_rows=64, tile_cols=48)
    with pytest.raises(ValueError, match="plan covers"):
        dev.age_weights_tiled(np.ones((128, 96)), "k", small_plan)


def test_scheduler_batched_ticks_never_skip_probes():
    """tick(n) probes on cadence *crossings*, not exact multiples."""
    dev = get_device("paper-infer")
    sched = RecalScheduler(dev, _acts_for(dev),
                           RecalPolicy(age_per_step_s=0.0, check_every=64,
                                       inl_threshold_lsb=10.0))
    for _ in range(8):                       # 8 x 24 = 192 steps
        sched.tick(24)
    # crossings of 64 at 72 (passes 64), 120->144 (passes 128), 192
    assert [e["step"] for e in sched.events] == [72, 144, 192]


def test_deploy_ramp_instance_salt():
    ramp = build_ramp("tanh", 5)
    dev = get_device("aged-1day")
    base = dev.deploy_ramp(ramp)
    np.testing.assert_array_equal(base.thresholds,
                                  dev.deploy_ramp(ramp).thresholds)
    t0 = dev.deploy_ramp(ramp, instance="tile0")
    t0b = dev.deploy_ramp(ramp, instance="tile0")
    t1 = dev.deploy_ramp(ramp, instance="tile1")
    np.testing.assert_array_equal(t0.thresholds, t0b.thresholds)
    assert np.max(np.abs(t0.thresholds - base.thresholds)) > 0
    assert np.max(np.abs(t0.thresholds - t1.thresholds)) > 0


def test_ref_pallas_parity_on_tile_aged_weights():
    """Aged weights + programmed thresholds are host-side shared state, so
    the two backends produce bitwise-identical ADC codes on them — under
    every preset with a build stage."""
    from repro.core import backend as BK

    x = jnp.asarray(np.random.default_rng(0).normal(0, 0.6, (4, 64)),
                    jnp.float32)
    for preset in ("paper-infer", "aged-1day", "stressed"):
        dev = get_device(preset)
        cfg = AnalogConfig(enabled=True, adc_bits=5, mode="infer",
                           device=dev)
        act = AnalogActivation("sigmoid", cfg)
        w = jnp.asarray(
            dev.age_params({"w": jnp.asarray(
                np.random.default_rng(1).normal(0, 0.4, (64, 32)),
                jnp.float32)})["w"])
        ref = BK.get_backend("ref")
        pal = BK.get_backend("pallas")
        thr = act.thresholds_for()
        y_ref = np.asarray(ref.matmul_nladc(x, w, act.adc, thresholds=thr))
        y_pal = np.asarray(pal.matmul_nladc(x, w, act.adc, thresholds=thr))
        # the backend contract (tests/test_backend_parity.py): bitwise-equal
        # ADC codes; the pallas decode is closed-form (y0 + n*LSB) so raw
        # floats can differ at ~1e-7 — recover the codes and compare those
        ramp = act.ramp
        y0, lsb = ramp.y_table[0], ramp.lsb
        np.testing.assert_array_equal(
            np.rint((y_ref - y0) / lsb).astype(np.int64),
            np.rint((y_pal - y0) / lsb).astype(np.int64))


# ---------------------------------------------------------------------------
# RecalScheduler
# ---------------------------------------------------------------------------


def _acts_for(device, names=("sigmoid", "tanh")):
    cfg = AnalogConfig(enabled=True, adc_bits=5, mode="infer", device=device)
    return {n: AnalogActivation(n, cfg) for n in names}


def test_scheduler_ages_probes_and_recalibrates():
    dev = get_device("aged-1day")
    acts = _acts_for(dev)
    pol = RecalPolicy(age_per_step_s=1e4, check_every=4,
                      inl_threshold_lsb=0.4)
    sched = RecalScheduler(dev, acts, pol)
    assert sched.age_s == pytest.approx(86_400.0)      # preset's drift age
    inl0 = sched.probe_inl()
    assert inl0 > pol.inl_threshold_lsb                # aged chip out of spec
    for _ in range(8):
        sched.tick()
    assert sched.step_count == 8 and sched.n_recals >= 1
    assert sched.age_s == pytest.approx(86_400.0 + 8e4)
    assert len(sched.events) == 2                      # probes at 4 and 8
    ev = sched.events[0]
    assert ev["recalibrated"] and ev["inl_after_lsb"] < ev["inl_lsb"]
    # the recalibrated thresholds are live in the activations
    for name, act in acts.items():
        got = np.asarray(act.ramp.thresholds)
        want = sched.ramps[name].ramp_at(dev, sched.age_s).thresholds
        np.testing.assert_array_equal(got, want)


def test_scheduler_below_threshold_never_recals():
    dev = get_device("paper-infer")                    # fresh, calibrated
    sched = RecalScheduler(dev, _acts_for(dev),
                           RecalPolicy(age_per_step_s=0.0, check_every=2,
                                       inl_threshold_lsb=10.0))
    for _ in range(6):
        assert not sched.tick()                        # no threshold motion
    assert sched.n_recals == 0
    assert all(not e["recalibrated"] for e in sched.events)


def test_scheduler_serialization_roundtrip():
    dev = get_device("aged-1day")
    acts = _acts_for(dev)
    sched = RecalScheduler(dev, acts, RecalPolicy(age_per_step_s=5e3,
                                                  check_every=3,
                                                  inl_threshold_lsb=0.4))
    for _ in range(7):
        sched.tick()
    blob = json.dumps(sched.to_dict())                 # plain JSON
    back = RecalScheduler.from_dict(json.loads(blob), acts)
    assert back.age_s == sched.age_s
    assert back.step_count == sched.step_count
    assert back.n_recals == sched.n_recals
    assert back.events == sched.events
    for name in sched.ramps:
        np.testing.assert_array_equal(back.ramps[name].g0_us,
                                      sched.ramps[name].g0_us)
        assert back.ramps[name].cal_shift == sched.ramps[name].cal_shift
        # deterministic continuation: same thresholds at any future age
        np.testing.assert_array_equal(
            back.ramps[name].ramp_at(dev, sched.age_s + 1e4).thresholds,
            sched.ramps[name].ramp_at(dev, sched.age_s + 1e4).thresholds)


def test_recal_recovers_kws_accuracy():
    """The NEON-style claim on the paper's own workload: an aged-1day
    deployment re-calibrated by the scheduler lands within a pinned delta
    of the freshly-programmed (paper-infer) chip."""
    from benchmarks.device_sweep import _accuracy_under
    from benchmarks.s13_drift import train_kws
    from repro.data.pipeline import SyntheticKWS
    from repro.nn import lstm as NN

    data = SyntheticKWS(seed=0).splits(384, 256)
    params = train_kws(data, 2, get_device("paper"))
    acc_fresh = _accuracy_under(params, data, get_device("paper-infer"))

    aged_dev = get_device("aged-1day")
    spec = NN.LSTMSpec(
        n_in=40, n_hidden=32,
        analog=AnalogConfig(enabled=True, adc_bits=5, input_bits=5,
                            mode="infer", device=aged_dev))
    acts = NN.make_gate_acts(spec.analog)
    sched = RecalScheduler(aged_dev, {"sigmoid": acts[0], "tanh": acts[1]},
                           RecalPolicy(age_per_step_s=0.0, check_every=1,
                                       inl_threshold_lsb=0.4))
    inl_before = sched.probe_inl()
    sched.tick()                                       # probe -> recal
    assert sched.n_recals == 1
    assert sched.probe_inl() < inl_before

    (_, _), (xte, yte) = data
    aged_params = aged_dev.age_params(params)

    @jax.jit
    def predict(p, xb, key):
        return jnp.argmax(NN.classifier_apply(p, xb, spec, acts, key=key),
                          -1)

    pred = predict(aged_params, jnp.asarray(xte), jax.random.PRNGKey(100))
    acc_recal = float(jnp.mean(pred == jnp.asarray(yte)))
    assert acc_recal >= acc_fresh - 0.15, (acc_recal, acc_fresh)


# ---------------------------------------------------------------------------
# Threshold banks: (n_col_tiles, P) deployment + per-bank lifecycle
# ---------------------------------------------------------------------------


def test_bank_deployment_and_single_tile_collapse():
    """bank_cols deploys one programmed ramp per col-tile; a width inside
    one tile keeps the legacy (P,) layout (bitwise the unbanked chip)."""
    dev = get_device("aged-1day")
    cfg = AnalogConfig(enabled=True, adc_bits=5, mode="infer", device=dev,
                       bank_cols=8)
    act = AnalogActivation("tanh", cfg)
    # single tile -> no bank, thresholds ARE the legacy deployment
    assert act.bank_for(8) is None
    legacy = AnalogActivation(
        "tanh", AnalogConfig(enabled=True, adc_bits=5, mode="infer",
                             device=dev))
    np.testing.assert_array_equal(act.ramp.thresholds,
                                  legacy.ramp.thresholds)
    # multi-tile -> per-bank chips, distinct and deterministic
    bank = act.bank_for(32)
    assert bank.n_banks == 4
    again = AnalogActivation("tanh", cfg).bank_for(32)
    np.testing.assert_array_equal(bank.thresholds_f64, again.thresholds_f64)
    for a in range(4):
        for b in range(a + 1, 4):
            assert np.max(np.abs(bank.thresholds_f64[a]
                                 - bank.thresholds_f64[b])) > 0
    # the bank map is the TilePlan column grouping
    np.testing.assert_array_equal(bank.bank_map.idx,
                                  np.arange(32) // 8)


def _banked_acts(device, bank_cols=8, width=32):
    cfg = AnalogConfig(enabled=True, adc_bits=5, mode="infer", device=device,
                       bank_cols=bank_cols)
    acts = {}
    for n in ("sigmoid", "tanh"):
        acts[n] = AnalogActivation(n, cfg)
        acts[n].bank_for(width)
    return acts


def test_scheduler_recals_only_out_of_spec_bank():
    """The acceptance case: force drift on ONE bank — the recal event
    reprograms only that ramp column, every other bank stays untouched."""
    dev = get_device("paper-infer")                    # fresh, in-spec chip
    acts = _banked_acts(dev)
    sched = RecalScheduler(dev, acts,
                           RecalPolicy(age_per_step_s=0.0, check_every=1,
                                       inl_threshold_lsb=0.4))
    assert len(sched.ramps) == 2 + 2 * 4               # legacy + banks
    assert not sched.tick()                            # everything in spec
    assert sched.n_recals == 0

    # knock one bank's programmed devices out of spec (a local drift /
    # disturb event on that physical column)
    victim = sched.bank_key("tanh", 32, 2)
    state = sched.ramps[victim]
    shifts = {k: s.cal_shift for k, s in sched.ramps.items()}
    state.g0_us = np.clip(state.g0_us * 1.25, 0.0, 150.0)
    assert state.inl_at(dev, sched.age_s) > 0.4

    assert sched.tick()                                # redeploy + recal
    ev = sched.events[-1]
    assert ev["recalibrated"] and ev["recal_ramps"] == [victim]
    assert sched.n_recals == 1
    # only the victim's calibration moved
    for k, s in sched.ramps.items():
        if k == victim:
            assert s.cal_shift != shifts[k]
        else:
            assert s.cal_shift == shifts[k]
    # and the victim's recovered thresholds are live in the bank
    bank = acts["tanh"].bank_for(32)
    np.testing.assert_array_equal(
        bank.thresholds_f64[2],
        state.ramp_at(dev, sched.age_s).thresholds)


def test_scheduler_adopts_lazily_realized_banks():
    """A bank realized after scheduler construction (first trace) gets its
    RampStates on the next probe — keyed draws, so adoption order is
    irrelevant."""
    dev = get_device("paper-infer")
    cfg = AnalogConfig(enabled=True, adc_bits=5, mode="infer", device=dev,
                       bank_cols=8)
    act = AnalogActivation("sigmoid", cfg)
    sched = RecalScheduler(dev, {"sigmoid": act},
                           RecalPolicy(check_every=1,
                                       inl_threshold_lsb=10.0))
    assert len(sched.ramps) == 1
    act.bank_for(24)                                   # lazy realization
    sched.tick()
    assert len(sched.ramps) == 1 + 3
    # adopted states drive the bank from now on (scheduler's chip)
    bank = act.bank_for(24)
    for j in range(3):
        st_j = sched.ramps[sched.bank_key("sigmoid", 24, j)]
        np.testing.assert_array_equal(
            bank.thresholds_f64[j],
            st_j.ramp_at(dev, sched.age_s).thresholds)


def test_weight_refresh_generation_salts_tile_draws():
    """generation != 0 re-draws every tile's write noise (a re-program);
    generation 0 is bitwise the legacy stream."""
    dev = DeviceModel(name="t", write=WriteNoise(), seed=9)
    plan = CB.plan_tiles(64, 48, tile_rows=32, tile_cols=24)
    w = np.random.default_rng(0).normal(0, 0.5, (64, 48))
    g0 = dev.age_weights_tiled(w, "k", plan)
    np.testing.assert_array_equal(
        g0, dev.age_weights_tiled(w, "k", plan, generation=0))
    g1 = dev.age_weights_tiled(w, "k", plan, generation=1)
    assert np.max(np.abs(g1 - g0)) > 0
    np.testing.assert_array_equal(
        g1, dev.age_weights_tiled(w, "k", plan, generation=1))


def test_scheduler_weight_refresh_on_recal_stall():
    """When per-bank recal cannot bring INL back under threshold for
    ``weight_refresh_after_stalls`` consecutive events, the scheduler
    requests a weight-crossbar re-program."""
    dev = get_device("aged-1day")
    acts = _banked_acts(dev)
    # threshold far below what a V_init shift can reach on an aged chip
    pol = RecalPolicy(age_per_step_s=1e4, check_every=1,
                      inl_threshold_lsb=0.05, weight_refresh_after_stalls=2)
    sched = RecalScheduler(dev, acts, pol)
    assert not sched.weight_refresh_pending
    sched.tick()                                       # recal 1: stall 1
    assert sched.stall_count == 1 and not sched.weight_refresh_pending
    sched.tick()                                       # recal 2: stall 2
    assert sched.weight_refresh_pending
    assert sched.events[-1].get("weight_refresh") is True
    assert sched.consume_weight_refresh()
    assert not sched.consume_weight_refresh()          # one-shot


def test_engine_weight_refresh_reprograms_crossbars():
    from repro import configs
    from repro.configs.base import AnalogSpec
    from repro.nn.model import build
    from repro.serve.engine import Request, ServingEngine

    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32",
        analog=AnalogSpec(enabled=True, mode="infer", device="aged-1day"))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dev = get_device("aged-1day")
    pol = RecalPolicy(age_per_step_s=1e5, check_every=2,
                      inl_threshold_lsb=0.05, weight_refresh_after_stalls=1)
    eng = ServingEngine(model, params, max_batch=1, max_len=32, device=dev,
                        recal=pol)
    eng.submit(Request(uid=0, prompt=np.asarray([1, 2, 3], np.int32),
                       max_new_tokens=8))
    eng.run_to_completion()
    assert eng._weight_gen >= 1                        # crossbars rewritten
    assert eng._weight_prog_age_s > 0
    assert any(e.get("weight_refresh") for e in eng.scheduler.events)
    # the refresh is part of the checkpointed deployment state
    import tempfile

    root = tempfile.mkdtemp()
    eng.save(root, eng.scheduler.step_count)
    eng2 = ServingEngine.restore(model, root, params_like=params)
    assert eng2._weight_gen == eng._weight_gen
    assert eng2._weight_prog_age_s == eng._weight_prog_age_s


def test_drain_before_rejit_waits_for_wave():
    """Scheduler-aware continuous batching: with drain on, the chip
    re-program (and re-jit) lands only when every decode slot is free."""
    from repro import configs
    from repro.configs.base import AnalogSpec
    from repro.nn.model import build
    from repro.serve.engine import Request, ServingEngine

    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32",
        analog=AnalogSpec(enabled=True, mode="infer", device="aged-1day"))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dev = get_device("aged-1day")
    pol = RecalPolicy(age_per_step_s=3600.0, check_every=2,
                      inl_threshold_lsb=0.4)

    def run(drain):
        eng = ServingEngine(model, params, max_batch=2, max_len=48,
                            device=dev, recal=pol,
                            drain_before_rejit=drain)
        req = Request(uid=0, prompt=np.arange(1, 6, dtype=np.int32),
                      max_new_tokens=9)
        eng.submit(req)
        states = []
        orig = eng._on_chip_reprogram

        def spy():
            states.append(all(eng.slot_free))
            orig()

        eng._on_chip_reprogram = spy
        eng.run_to_completion()
        return req, states

    req, states = run(drain=True)
    assert len(req.generated) == 9                     # traffic unharmed
    assert states and all(states)                      # only at drain points
    _, states_hot = run(drain=False)
    assert not all(states_hot)                         # default: mid-wave


def test_drain_window_checkpoint_resumes_bitwise(tmp_path):
    """A save that lands INSIDE a drain window (re-jit deferred, host-side
    thresholds already moved ahead of the compiled traces) still restores
    to the SERVED chip: the resumed run finishes the wave on the old
    thresholds and re-programs at the drain point, token-for-token equal
    to the uninterrupted run."""
    from repro.serve.engine import Request, ServingEngine

    model, params, _ = _smoke_engine(tmp_path)
    dev = get_device("aged-1day")
    pol = RecalPolicy(age_per_step_s=3600.0, check_every=2,
                      inl_threshold_lsb=0.4)

    def fresh():
        eng = ServingEngine(model, params, max_batch=2, max_len=48,
                            device=dev, noise_seed=7, recal=pol,
                            drain_before_rejit=True)
        req = Request(uid=0, prompt=np.arange(1, 6, dtype=np.int32),
                      max_new_tokens=9)
        eng.submit(req)
        return eng, req

    eng, req = fresh()
    for _ in range(12):
        eng.step()
    full = list(req.generated)

    eng_a, req_a = fresh()
    steps_a = 0
    while not eng_a._rejit_pending:                    # land mid-drain
        eng_a.step()
        steps_a += 1
        assert steps_a < 12
    eng_a.save(str(tmp_path), steps_a)
    eng_b = ServingEngine.restore(model, str(tmp_path), params_like=params,
                                  drain_before_rejit=True)
    assert eng_b._rejit_pending                        # window survives
    req_b = eng_b.slot_req[0]
    assert req_b.generated == full[:len(req_b.generated)]
    for _ in range(12 - steps_a):
        eng_b.step()
    assert req_b.generated == full


def test_restore_rejects_bank_cols_mismatch_both_ways(tmp_path):
    """Resuming with the wrong --bank-cols fails with a bank_cols hint in
    BOTH directions, not a tree-mismatch KeyError deep in repro.ckpt."""
    from repro.serve.engine import ServingEngine

    # banked deployment saved...
    model_b, params_b, fresh_b = _smoke_engine(tmp_path, bank_cols=16)
    eng, _ = fresh_b()
    eng.step()
    eng.save(str(tmp_path / "banked"), 1)
    # ...restored into an unbanked model config
    model_u, params_u, fresh_u = _smoke_engine(tmp_path)
    with pytest.raises(ValueError, match="does not bank that width"):
        ServingEngine.restore(model_u, str(tmp_path / "banked"),
                              params_like=params_u)
    # unbanked deployment saved, restored into a banked model config
    eng_u, _ = fresh_u()
    eng_u.step()
    eng_u.save(str(tmp_path / "flat"), 1)
    with pytest.raises(ValueError, match="checkpoint has none there"):
        ServingEngine.restore(model_b, str(tmp_path / "flat"),
                              params_like=params_b)


# ---------------------------------------------------------------------------
# Checkpoint schema: banks, v1 migration, unknown-version rejection
# ---------------------------------------------------------------------------


def _smoke_engine(tmp_path, bank_cols=0, **spec_kw):
    from repro import configs
    from repro.configs.base import AnalogSpec
    from repro.nn.model import build
    from repro.serve.engine import Request, ServingEngine

    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32",
        analog=AnalogSpec(enabled=True, mode="infer", device="aged-1day",
                          bank_cols=bank_cols, **spec_kw))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dev = get_device("aged-1day")
    pol = RecalPolicy(age_per_step_s=3600.0, check_every=3,
                      inl_threshold_lsb=0.4)

    def fresh():
        eng = ServingEngine(model, params, max_batch=2, max_len=48,
                            device=dev, noise_seed=7, recal=pol)
        req = Request(uid=0, prompt=np.arange(1, 6, dtype=np.int32),
                      max_new_tokens=8)
        eng.submit(req)
        return eng, req

    return model, params, fresh


def test_engine_banked_checkpoint_roundtrip(tmp_path):
    """A banked deployment (d_ff spans several col-tiles) checkpoints and
    resumes bit-identically — schema v2 carries the (n_col_tiles, P)
    banks."""
    from repro.serve.engine import ServingEngine

    model, params, fresh = _smoke_engine(tmp_path, bank_cols=16)
    assert model.act.bank_for(model.cfg.d_ff).n_banks > 1
    eng, req = fresh()
    for _ in range(8):
        eng.step()
    full = list(req.generated)

    eng_a, req_a = fresh()
    for _ in range(4):
        eng_a.step()
    eng_a.save(str(tmp_path), 4)
    eng_b = ServingEngine.restore(model, str(tmp_path), params_like=params)
    req_b = eng_b.slot_req[0]
    assert req_b.generated == full[:4]
    # the restored banks are bitwise the running chip
    for name, act in eng_a._acts.items():
        for width, bank in act.banks().items():
            np.testing.assert_array_equal(
                bank.thresholds_f64,
                eng_b._acts[name].bank_for(width).thresholds_f64)
    for _ in range(4):
        eng_b.step()
    assert req_b.generated == full


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_single_tile_bank_cols_tokens_bitwise_legacy(backend, tmp_path):
    """The acceptance criterion: with every activation width inside one
    col-tile (n_col_tiles=1), a banked deployment serves bitwise-identical
    tokens to bank_cols=0, on both backends."""
    from repro import configs
    from repro.configs.base import AnalogSpec
    from repro.nn.model import build
    from repro.serve.engine import Request, ServingEngine

    tokens = {}
    for bc in (0, 4096):                     # 4096 > every smoke width
        cfg = configs.get_smoke("qwen2.5-3b").replace(
            dtype="float32",
            analog=AnalogSpec(enabled=True, mode="infer",
                              device="aged-1day", backend=backend,
                              bank_cols=bc))
        model = build(cfg)
        assert not any(a.banks() for a in
                       __import__("repro.serve.lifecycle",
                                  fromlist=["analog_activations"])
                       .analog_activations(model).values())
        params = model.init(jax.random.PRNGKey(0))
        eng = ServingEngine(model, params, max_batch=1, max_len=32,
                            device=get_device("aged-1day"), noise_seed=7)
        req = Request(uid=0, prompt=np.asarray([1, 2, 3], np.int32),
                      max_new_tokens=6)
        eng.submit(req)
        eng.run_to_completion()
        tokens[bc] = list(req.generated)
    assert tokens[0] == tokens[4096]


def _rewrite_manifest_meta(root, mutate):
    import os

    from repro.ckpt.checkpoint import list_checkpoints

    step = list_checkpoints(root)[-1]
    path = os.path.join(root, f"step_{step:08d}", "manifest.json")
    with open(path) as f:
        manifest = json.load(f)
    mutate(manifest["metadata"])
    with open(path, "w") as f:
        json.dump(manifest, f)


def test_restore_migrates_schema1_checkpoint(tmp_path):
    """A PR 4-era (schema-1) deployment checkpoint — no schema field, no
    bank inventory, no lifecycle bookkeeping — restores through the
    versioned migration and continues bit-identically."""
    from repro.serve.engine import ServingEngine

    model, params, fresh = _smoke_engine(tmp_path)
    eng, req = fresh()
    for _ in range(8):
        eng.step()
    full = list(req.generated)

    eng_a, req_a = fresh()
    for _ in range(4):
        eng_a.step()
    eng_a.save(str(tmp_path), 4)

    def to_v1(meta):
        for key in ("schema", "banks", "lifecycle"):
            meta.pop(key, None)

    _rewrite_manifest_meta(str(tmp_path), to_v1)
    eng_b = ServingEngine.restore(model, str(tmp_path), params_like=params)
    assert eng_b._weight_gen == 0
    req_b = eng_b.slot_req[0]
    for _ in range(4):
        eng_b.step()
    assert req_b.generated == full


def test_restore_rejects_unknown_schema(tmp_path):
    from repro.serve.engine import ServingEngine

    model, params, fresh = _smoke_engine(tmp_path)
    eng, _ = fresh()
    eng.step()
    eng.save(str(tmp_path), 1)
    _rewrite_manifest_meta(str(tmp_path),
                           lambda m: m.update(schema=99))
    with pytest.raises(ValueError, match="schema 99.*upgrade repro"):
        ServingEngine.restore(model, str(tmp_path), params_like=params)


def test_restore_rejects_non_engine_checkpoint(tmp_path):
    """A train-style checkpoint (no engine metadata) fails with a clear
    message instead of a KeyError deep in repro.ckpt."""
    from repro.ckpt.checkpoint import save_checkpoint
    from repro.serve.engine import ServingEngine

    from repro import configs
    from repro.nn.model import build

    cfg = configs.get_smoke("qwen2.5-3b").replace(dtype="float32")
    model = build(cfg)
    save_checkpoint(str(tmp_path), 0, {"params": np.zeros(3)},
                    metadata={"whatever": 1})
    with pytest.raises(ValueError, match="not a ServingEngine deployment"):
        ServingEngine.restore(model, str(tmp_path))


# ---------------------------------------------------------------------------
# Engine checkpoint/restore (in-process; the cross-process bitwise test
# is below)
# ---------------------------------------------------------------------------


def test_engine_checkpoint_roundtrip_with_lifecycle(tmp_path):
    from repro import configs
    from repro.configs.base import AnalogSpec
    from repro.nn.model import build
    from repro.serve.engine import Request, ServingEngine

    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32",
        analog=AnalogSpec(enabled=True, mode="infer", device="aged-1day"))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dev = get_device("aged-1day")
    pol = RecalPolicy(age_per_step_s=3600.0, check_every=3,
                      inl_threshold_lsb=0.4)

    # uninterrupted run first (the restore below mutates the shared
    # activations, so order matters in-process)
    eng = ServingEngine(model, params, max_batch=2, max_len=48,
                        device=dev, noise_seed=7, recal=pol)
    req_full = Request(uid=0, prompt=np.arange(1, 6, dtype=np.int32),
                       max_new_tokens=8)
    eng.submit(req_full)
    for _ in range(8):
        eng.step()
    full = list(req_full.generated)
    assert len(eng.scheduler.events) == 2

    # 4 steps -> checkpoint -> restore -> 4 more
    eng_a = ServingEngine(model, params, max_batch=2, max_len=48,
                          device=dev, noise_seed=7, recal=pol)
    req_a = Request(uid=0, prompt=np.arange(1, 6, dtype=np.int32),
                    max_new_tokens=8)
    eng_a.submit(req_a)
    for _ in range(4):
        eng_a.step()
    eng_a.save(str(tmp_path), 4)
    eng_b = ServingEngine.restore(model, str(tmp_path), params_like=params)
    assert eng_b.scheduler is not None
    assert eng_b.scheduler.age_s == eng_a.scheduler.age_s
    req_b = eng_b.slot_req[0]
    assert req_b is not None and req_b.generated == full[:4]
    for _ in range(4):
        eng_b.step()
    assert req_b.generated == full
    assert eng_b.scheduler.events == eng.scheduler.events


def test_engine_checkpoint_roundtrip_no_scheduler(tmp_path):
    """device-only deployment (no recal policy) also checkpoints."""
    from repro import configs
    from repro.configs.base import AnalogSpec
    from repro.nn.model import build
    from repro.serve.engine import Request, ServingEngine

    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32",
        analog=AnalogSpec(enabled=True, mode="infer", device="paper-infer"))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dev = get_device("paper-infer")
    eng = ServingEngine(model, params, max_batch=1, max_len=32, device=dev,
                        noise_seed=3)
    req = Request(uid=5, prompt=np.asarray([2, 9, 4], np.int32),
                  max_new_tokens=6)
    eng.submit(req)
    for _ in range(3):
        eng.step()
    eng.save(str(tmp_path), 3)
    eng2 = ServingEngine.restore(model, str(tmp_path), params_like=params)
    assert eng2.scheduler is None and eng2.device is not None
    assert eng2.device.to_dict() == dev.to_dict()
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(eng2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    r2 = eng2.slot_req[0]
    for _ in range(3):
        eng.step()
        eng2.step()
    assert r2.generated == req.generated and len(r2.generated) == 6
    # fresh traffic on the restored engine: admission re-merges a prefill
    # into the (restored, device-resident) decode state
    new = Request(uid=6, prompt=np.asarray([1, 2, 3], np.int32),
                  max_new_tokens=2)
    eng2.submit(new)
    eng2.run_to_completion()
    assert len(new.generated) == 2


# ---------------------------------------------------------------------------
# Engine-restart reproducibility across PROCESSES (bitwise ADC codes)
# ---------------------------------------------------------------------------

_RESTART_COMMON = """
    import json, zlib
    import numpy as np
    import jax, jax.numpy as jnp
    from repro import configs
    from repro.configs.base import AnalogSpec
    from repro.nn.model import build
    from repro.core.device import get_device
    from repro.serve.engine import Request, ServingEngine
    from repro.serve.lifecycle import RecalPolicy

    BACKEND = {backend!r}
    cfg = configs.get_smoke("qwen2.5-3b").replace(
        dtype="float32",
        analog=AnalogSpec(enabled=True, mode="infer", device="aged-1day",
                          backend=BACKEND))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dev = get_device("aged-1day")
    pol = RecalPolicy(age_per_step_s=3600.0, check_every=2,
                      inl_threshold_lsb=0.4)

    def probe(eng):
        # bitwise fingerprint of every deployed NL-ADC's codes on a grid
        grid = jnp.linspace(-4.0, 4.0, 257, dtype=jnp.float32)
        out = {{}}
        for name, act in sorted(eng._acts.items()):
            codes = np.ascontiguousarray(np.asarray(act.adc.codes(grid)))
            out[name] = zlib.crc32(codes.tobytes())
        return out

    def fresh_engine():
        eng = ServingEngine(model, params, max_batch=2, max_len=48,
                            device=dev, noise_seed=7, recal=pol)
        req = Request(uid=0, prompt=np.arange(1, 5, dtype=np.int32),
                      max_new_tokens=6)
        eng.submit(req)
        return eng, req
"""


def _restart_part1(backend):
    return _RESTART_COMMON.format(backend=backend) + """
    # uninterrupted run: 6 steps
    eng, req = fresh_engine()
    for _ in range(6):
        eng.step()
    print(json.dumps({"tokens": list(req.generated), "codes": probe(eng),
                      "events": eng.scheduler.events}))
"""


def _restart_part2_save(backend, root):
    return _RESTART_COMMON.format(backend=backend) + f"""
    eng, req = fresh_engine()
    for _ in range(3):
        eng.step()
    eng.save({root!r}, 3)
    print(json.dumps({{"tokens": list(req.generated)}}))
"""


def _restart_part3_resume(backend, root):
    return _RESTART_COMMON.format(backend=backend) + f"""
    eng = ServingEngine.restore(model, {root!r}, params_like=params)
    req = eng.slot_req[0]
    for _ in range(3):
        eng.step()
    print(json.dumps({{"tokens": list(req.generated),
                       "codes": probe(eng),
                       "events": eng.scheduler.events}}))
"""


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_engine_restart_bitwise_reproducible(backend, tmp_path):
    """serve N -> checkpoint -> restore in a FRESH process -> the resumed
    deployment produces bitwise-identical ADC codes and tokens vs the
    uninterrupted run, on both analog backends."""
    root = str(tmp_path / f"ck-{backend}")

    full = json.loads(
        check_in_subprocess(_restart_part1(backend), devices=1,
                            timeout=900).strip().splitlines()[-1])
    part = json.loads(
        check_in_subprocess(_restart_part2_save(backend, root), devices=1,
                            timeout=900).strip().splitlines()[-1])
    resumed = json.loads(
        check_in_subprocess(_restart_part3_resume(backend, root), devices=1,
                            timeout=900).strip().splitlines()[-1])

    # the generation: prefix before the save, identical total afterwards
    assert part["tokens"] == full["tokens"][:3]
    assert resumed["tokens"] == full["tokens"]
    # the chip: every deployed NL-ADC's thermometer codes, bit for bit
    assert resumed["codes"] == full["codes"]
    # the lifecycle: same probe/recal trace
    assert resumed["events"] == full["events"]
