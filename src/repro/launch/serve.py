"""Serving driver: ``python -m repro.launch.serve --arch <id> --smoke``.

Spins up the batched serving engine, submits a wave of synthetic requests,
and reports tokens/s + per-request outputs.

Throughput knobs: ``--prefill-buckets`` (the AOT-compiled prefill lengths
each admission wave rounds up to; 'auto', the default, or an explicit
list), ``--detok-thread`` (background detokenize pipeline), and
``--offline`` (MLPerf-offline style: ``warmup()`` pre-compiles everything,
then one measured burst).

Device-lifecycle knobs (``--age-per-step-s`` / ``--recal-every`` /
``--recal-inl-lsb``) attach a :class:`repro.serve.lifecycle.RecalScheduler`
to the engine: device age advances every step, INL probes run on the
cadence, and one-point re-calibration fires past the threshold (trace
printed at exit).  ``--ckpt-dir`` checkpoints the whole deployment at the
end of the run; with ``--resume`` the engine restores from the latest
checkpoint there instead of programming a fresh chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro import configs
from repro.core.backend import backend_names
from repro.core.device import device_names, resolve_device
from repro.kernels import tune
from repro.launch.compile_cache import enable_compile_cache
from repro.nn.model import build
from repro.serve.engine import Request, ServingEngine, serving_params
from repro.serve.lifecycle import RecalPolicy


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--backend", choices=("",) + backend_names(), default="",
                    help="analog execution backend (default: env or 'ref')")
    ap.add_argument("--device", choices=("",) + device_names(), default="",
                    help="device-model preset (default: REPRO_DEVICE env or "
                         "'paper'); in infer mode its build-stage "
                         "nonidealities (write noise, faults, drift) are "
                         "applied to the loaded params once, before serving")
    ap.add_argument("--analog-mode", choices=("", "exact", "train", "infer"),
                    default="", help="override AnalogSpec.mode (most LM "
                    "configs default to 'exact'; pass 'infer' for the full "
                    "deployment simulation so --device actually acts)")
    ap.add_argument("--bank-cols", type=int, default=0,
                    help="threshold banks: output columns per NL-ADC ramp "
                         "(one ramp per crossbar col-tile; 0 = one shared "
                         "ramp per activation, the legacy layout)")
    ap.add_argument("--prefill-buckets", default="auto",
                    help="comma-separated AOT prefill bucket lengths "
                         "(e.g. '8,16,32') or 'auto' for powers of two up "
                         "to max_len-1")
    ap.add_argument("--detok-thread", action="store_true",
                    help="background detokenize/backlog thread: host "
                         "transfer + bookkeeping overlap the next device "
                         "step")
    ap.add_argument("--offline", action="store_true",
                    help="MLPerf-offline style run: warmup() pre-compiles "
                         "every bucket + the decode step, then the whole "
                         "request burst is submitted and drained under one "
                         "wall-clock measurement")
    ap.add_argument("--shelf-age-per-step-s", type=float, default=0.0,
                    help="fleet: device seconds added per fleet step to "
                         "chips serving no traffic (idle chips keep "
                         "drifting and probing; 0 disables)")
    ap.add_argument("--drain-before-rejit", action="store_true",
                    help="scheduler-aware continuous batching: drain the "
                         "in-flight decode wave before a planned chip "
                         "re-program/re-jit instead of recompiling mid-wave")
    ap.add_argument("--age-per-step-s", type=float, default=0.0,
                    help="device seconds added per engine step; > 0 turns "
                         "on the re-calibration scheduler (infer mode only)")
    ap.add_argument("--recal-every", type=int, default=64,
                    help="engine steps between INL probes")
    ap.add_argument("--recal-inl-lsb", type=float, default=1.0,
                    help="mean deployed INL (LSB) that triggers one-point "
                         "re-calibration")
    ap.add_argument("--ckpt-dir", default="",
                    help="checkpoint the deployment here at end of run")
    ap.add_argument("--resume", action="store_true",
                    help="restore the deployment from --ckpt-dir instead "
                         "of programming a fresh chip")
    ap.add_argument("--fleet", type=int, default=0,
                    help="serve a fleet of N chips (each its own device "
                         "draws, drift clock, and recal schedule) behind "
                         "the fleet router/planner; 0 = single chip")
    ap.add_argument("--capacity-floor", type=float, default=0.75,
                    help="fleet: fraction of chips that must keep "
                         "accepting traffic; bounds concurrent drains")
    ap.add_argument("--router", default="least-loaded",
                    help="fleet admission policy: round-robin | "
                         "least-loaded | health-weighted")
    ap.add_argument("--canary", action="append", default=[],
                    help="fleet: pin one chip to this device preset as a "
                         "canary (repeatable; canaries age ahead and "
                         "tighten sibling recal cadence on first recal)")
    ap.add_argument("--force-drain-step", type=int, default=0,
                    help="fleet: force a maintenance request on the first "
                         "chip at this step (CI smoke for the drain path)")
    ap.add_argument("--metrics-dir", default="",
                    help="observability: write the metrics registry here at "
                         "exit (metrics.json snapshot + metrics.prom "
                         "Prometheus text)")
    ap.add_argument("--trace", default="",
                    help="observability: record the span/event trace and "
                         "write it to this JSONL path at exit (step-clock "
                         "primary — seeded runs emit bitwise-identical "
                         "traces; replay with python -m repro.obs.replay)")
    ap.add_argument("--trace-wall-clock", action="store_true",
                    help="add wall_s/wall_dur_s fields to --trace entries "
                         "(off by default: wall fields break trace "
                         "bitwise-reproducibility)")
    ap.add_argument("--prom", action="store_true",
                    help="observability: print the Prometheus text "
                         "exposition at exit")
    ap.add_argument("--kernel-cache", default="",
                    help="path to a kernel tune-cache JSON "
                         "(benchmarks.kernel_tune output); Pallas block "
                         "sizes then resolve per shape from it (also: "
                         "REPRO_KERNEL_CACHE env)")
    ap.add_argument("--kernel-blocks", default="",
                    help="force per-kernel Pallas blocks, e.g. "
                         "'fused_matmul_nladc=128x128x512,nladc=256x512' "
                         "— overrides the tune cache (also: "
                         "REPRO_KERNEL_BLOCKS env)")
    args = ap.parse_args()
    enable_compile_cache()

    try:
        tune.configure(args.kernel_blocks, args.kernel_cache)
    except (ValueError, OSError) as e:
        ap.error(f"--kernel-blocks/--kernel-cache: {e}")

    prefill_kw = {"detok_thread": args.detok_thread}
    if args.prefill_buckets != "auto":
        try:
            prefill_kw["prefill_buckets"] = tuple(
                int(b) for b in args.prefill_buckets.split(","))
        except ValueError:
            ap.error("--prefill-buckets must be 'auto' or a "
                     "comma-separated list of ints")

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get(args.arch)
    spec_kw = {}
    if args.backend:
        spec_kw["backend"] = args.backend
    if args.device:
        spec_kw["device"] = args.device
    if args.analog_mode:
        spec_kw["mode"] = args.analog_mode
    if args.bank_cols:
        spec_kw["bank_cols"] = args.bank_cols
    if spec_kw:
        cfg = cfg.replace(analog=dataclasses.replace(cfg.analog, **spec_kw))
    from repro.obs import Obs

    obs = Obs(trace=bool(args.trace), wall_clock=args.trace_wall_clock)
    if args.fleet:
        _serve_fleet(ap, args, cfg, prefill_kw, obs)
        _export_obs(args, obs)
        return
    model = build(cfg)
    params = serving_params(model, jax.random.PRNGKey(0))
    # Build-stage aging only composes with infer mode: exact mode would pair
    # aged weights with a pristine NL-ADC and no read noise — a chip that
    # cannot physically exist — so the driver gates it rather than the engine.
    device = None
    if cfg.analog.mode == "infer":
        device = resolve_device(cfg.analog.device)
        if device.has_build_stage and not args.resume:
            print(f"[serve] applying device model {device.name!r} build "
                  "stage to params (write noise / faults / drift; "
                  "per-tile TilePlan-keyed draws)")
    elif args.device:
        print(f"[serve] note: --device {args.device} is inert in analog "
              f"mode {cfg.analog.mode!r}; pass --analog-mode infer for the "
              "deployment simulation")
    recal = None
    if args.age_per_step_s > 0:
        if device is None:
            ap.error("--age-per-step-s requires --analog-mode infer (the "
                     "lifecycle acts on a deployed device model)")
        recal = RecalPolicy(age_per_step_s=args.age_per_step_s,
                            check_every=args.recal_every,
                            inl_threshold_lsb=args.recal_inl_lsb)
    if args.resume:
        if not args.ckpt_dir:
            ap.error("--resume requires --ckpt-dir")
        engine = ServingEngine.restore(
            model, args.ckpt_dir, params_like=params,
            drain_before_rejit=args.drain_before_rejit, obs=obs,
            **prefill_kw)
        sched = engine.scheduler
        if recal is not None:
            if sched is None:
                ap.error("--age-per-step-s with --resume needs a checkpoint "
                         "that was serving with a scheduler (this one has "
                         "none, and re-programming its ramps would discard "
                         "the restored chip state)")
            # knob changes are safe on resume; the chip state is not touched
            sched.policy = recal
        print(f"[serve] resumed deployment from {args.ckpt_dir}"
              + (f" (age {sched.age_s:.0f}s, {sched.n_recals} recals)"
                 if sched is not None else ""))
    else:
        engine = ServingEngine(model, params, max_batch=args.max_batch,
                               max_len=args.max_len, device=device,
                               recal=recal,
                               drain_before_rejit=args.drain_before_rejit,
                               obs=obs, **prefill_kw)

    rng = np.random.default_rng(0)
    reqs = []
    for uid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab,
                              size=rng.integers(4, 12)).astype(np.int32)
        reqs.append(Request(uid=uid, prompt=prompt,
                            max_new_tokens=args.max_new))

    if args.offline:
        w = engine.warmup()
        print(f"[serve] warmup: {len(w['prefill_buckets'])} prefill bucket "
              f"executables {tuple(w['prefill_buckets'])} + decode step "
              "compiled")
        stats = engine.run_offline(reqs)
        n_tokens, dt = stats["tokens"], stats["seconds"]
        print(f"[serve] offline: {args.requests} requests, "
              f"{n_tokens} tokens in {dt:.2f}s "
              f"({stats['tokens_per_s']:.1f} tok/s, warmup excluded)")
        for key, unit in (("ttft_steps", "steps"), ("ttft_ms", "ms"),
                          ("itl_steps", "steps"), ("itl_ms", "ms")):
            s = stats[key]
            what = "TTFT" if key.startswith("ttft") else "ITL"
            print(f"[serve] {what:4s} ({unit}): p50 {s['p50']:.3f}  "
                  f"p95 {s['p95']:.3f}  p99 {s['p99']:.3f}  "
                  f"(n={s['count']})")
        e = stats["energy"]
        for variant in ("nladc", "digital_lut"):
            v = e[variant]
            print(f"[serve] energy[{variant}]: {v['energy_j']:.3e} J, "
                  f"{v['tokens_per_joule']:.3e} tok/J, "
                  f"{v['tops_per_w']:.1f} TOPS/W")
        if "nladc_vs_digital_energy" in e:
            print(f"[serve] nladc / digital-LUT energy: "
                  f"{e['nladc_vs_digital_energy']:.3f}x")
    else:
        for req in reqs:
            engine.submit(req)
        t0 = time.time()
        n_tokens = 0
        while engine.queue or not all(engine.slot_free):
            out = engine.step()
            n_tokens += len(out)
        n_tokens += sum(len(b) for b in engine.detok_flush())
        dt = time.time() - t0
        print(f"[serve] {args.requests} requests, {n_tokens} tokens "
              f"in {dt:.2f}s ({n_tokens / max(dt, 1e-9):.1f} tok/s)")
    if engine.scheduler is not None:
        s = engine.scheduler
        print(f"[serve] lifecycle: age {s.age_s:.0f}s, "
              f"{len(s.events)} probes, {s.n_recals} recalibrations")
        for ev in s.events:
            line = (f"  step {ev['step']:>5}  age {ev['age_s']:.0f}s  "
                    f"INL {ev['inl_lsb']:.3f} LSB")
            if ev["recalibrated"]:
                line += f" -> recal -> {ev['inl_after_lsb']:.3f} LSB"
            print(line)
    if args.ckpt_dir:
        if engine.scheduler is not None:
            # the scheduler's step clock is cumulative across resumes
            step = engine.scheduler.step_count
        else:
            # keep steps monotonic across resumed runs so read_metadata's
            # latest-checkpoint pick never resurrects an older deployment
            from repro.ckpt.checkpoint import list_checkpoints
            prev = list_checkpoints(args.ckpt_dir)
            step = (prev[-1] if prev else 0) + n_tokens
        out = engine.save(args.ckpt_dir, step=step)
        print(f"[serve] deployment checkpointed to {out}")
    _export_obs(args, obs)


def _export_obs(args, obs) -> None:
    """Flush the run's observability per the CLI flags (trace JSONL,
    metrics dir, Prometheus stdout)."""
    import os

    if args.trace:
        os.makedirs(os.path.dirname(os.path.abspath(args.trace)),
                    exist_ok=True)
        obs.tracer.write_jsonl(args.trace)
        print(f"[serve] trace: {len(obs.tracer.entries)} entries -> "
              f"{args.trace}")
    if args.metrics_dir:
        os.makedirs(args.metrics_dir, exist_ok=True)
        jpath = os.path.join(args.metrics_dir, "metrics.json")
        with open(jpath, "w") as f:
            f.write(obs.metrics.dump_json())
        ppath = os.path.join(args.metrics_dir, "metrics.prom")
        with open(ppath, "w") as f:
            f.write(obs.metrics.to_prometheus())
        print(f"[serve] metrics -> {jpath} + {ppath}")
    if args.prom:
        print(obs.metrics.to_prometheus(), end="")


def _serve_fleet(ap, args, cfg, prefill_kw, obs):
    """The --fleet path: N chips, router, planner, canaries, manifest."""
    from repro.serve.fleet import ROUTERS, FleetEngine, FleetPolicy

    if args.router not in ROUTERS:
        ap.error(f"--router must be one of {ROUTERS}")
    recal = None
    if args.age_per_step_s > 0:
        if cfg.analog.mode != "infer":
            ap.error("--age-per-step-s requires --analog-mode infer (the "
                     "lifecycle acts on deployed device models)")
        recal = RecalPolicy(age_per_step_s=args.age_per_step_s,
                            check_every=args.recal_every,
                            inl_threshold_lsb=args.recal_inl_lsb)
    if args.canary and cfg.analog.mode != "infer":
        ap.error("--canary requires --analog-mode infer (canaries are "
                 "pinned to deployed device presets)")
    policy = FleetPolicy(capacity_floor=args.capacity_floor,
                         router=args.router,
                         shelf_age_per_step_s=args.shelf_age_per_step_s)
    if args.resume:
        if not args.ckpt_dir:
            ap.error("--resume requires --ckpt-dir")
        fleet = FleetEngine.restore(cfg, args.ckpt_dir, obs=obs)
        print(f"[serve] resumed fleet of {len(fleet.chips)} chips from "
              f"{args.ckpt_dir} (step {fleet.step_count}, "
              f"{len(fleet.events)} events)")
    else:
        fleet = FleetEngine.build(
            cfg, args.fleet, policy=policy, recal=recal,
            max_batch=args.max_batch, max_len=args.max_len,
            canary_presets=tuple(args.canary), obs=obs, **prefill_kw)
        roles = ", ".join(
            f"{cid}{' (canary: ' + c.device.name + ')' if c.spec.canary else ''}"
            for cid, c in fleet.chips.items())
        print(f"[serve] fleet up: {roles}")
        print(f"[serve] router={policy.router} "
              f"capacity_floor={policy.capacity_floor} "
              f"(max {fleet.planner.max_drain} draining)")

    if args.offline:
        fleet.warmup()
        print("[serve] fleet warmup: bucket executables + decode steps "
              "compiled on every chip")
    rng = np.random.default_rng(0)
    for uid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab,
                              size=rng.integers(4, 12)).astype(np.int32)
        cid = fleet.submit(Request(uid=uid, prompt=prompt,
                                   max_new_tokens=args.max_new))
        print(f"[serve] request {uid} -> {cid}")

    t0 = time.time()
    n_tokens = 0
    min_accepting = len(fleet.chips)
    while any(c.engine.queue or not all(c.engine.slot_free)
              for c in fleet.chips.values()):
        if args.force_drain_step \
                and fleet.step_count + 1 == args.force_drain_step:
            first = sorted(fleet.chips)[0]
            print(f"[serve] forcing maintenance on {first}")
            fleet.force_maintenance(first)
        n_tokens += len(fleet.step())
        min_accepting = min(min_accepting, len(fleet.accepting()))
    n_tokens += sum(len(b) for c in fleet.chips.values()
                    for b in c.engine.detok_flush())
    dt = time.time() - t0
    lat = fleet.admission_latency_steps()
    p95 = float(np.percentile(lat, 95)) if lat else 0.0
    print(f"[serve] fleet: {args.requests} requests, {n_tokens} tokens "
          f"in {dt:.2f}s ({n_tokens / max(dt, 1e-9):.1f} tok/s), "
          f"p95 first-token {p95:.1f} steps, "
          f"min accepting {min_accepting}/{len(fleet.chips)}")
    for ev in fleet.events:
        extra = {k: v for k, v in ev.items() if k not in ("step", "type")}
        print(f"  step {ev['step']:>5}  {ev['type']}"
              + (f"  {extra}" if extra else ""))
    for cid, h in fleet.health().items():
        print(f"  {cid}: age {h['age_s']:.0f}s  INL {h['inl_lsb']:.3f} LSB  "
              f"weight_gen {h['weight_gen']}")
    for cid, e in fleet.energy_report().items():
        nl = e["nladc"]
        print(f"  {cid}: energy {nl['energy_j']:.3e} J  "
              f"{nl['tokens_per_joule']:.3e} tok/J  "
              f"{nl['tops_per_w']:.1f} TOPS/W (nl-adc; digital-LUT "
              f"{e['digital_lut']['tops_per_w']:.1f} TOPS/W)")
    if args.ckpt_dir:
        out = fleet.save(args.ckpt_dir, fleet.step_count)
        print(f"[serve] fleet checkpointed to {out}")


if __name__ == "__main__":
    main()
