#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload qwen2.5-3b.decode-heavy --seed 7 \
        --seconds 30 --trace 0

A cell is ``bench/workloads/<name>.json``: its configuration
(``bench/configs/<config>.json``), its traffic generator
(``bench/traffic/<generator>.py``) with the generator's parameters, the
``ServingEngine`` arguments, the traced sub-window and the check.  The
metrics it reports are those ``BENCHMARK.json`` gives the cell, each
read by ``bench/metrics/<metric>.py``: with ``--trace 0`` the end-to-end
metrics, with ``--trace 1`` the per-layer ones, from a profiler trace of
part of the window.

A run: weights made on the device from the seed by the configuration's
reference module, the engine built and warmed up on every shape the
traffic uses (set-up, ``setup_s``), then ``--seconds`` of traffic driven
through ``ServingEngine.submit`` and ``ServingEngine.step``, then the
check: a sample, drawn from the seed, of the requests finished in the
window is run through the plain reference (``bench/reference/<family>.py``),
and the share of served tokens that are the reference's first choice at
their position must reach the cell's limit.

It refuses to run without a TPU, or with fewer chips than the cell asks
for, and then prints no result.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` with ``--trace 1``), then ``check``, the
numbers compared with their limits.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
OUT = os.path.join(ROOT, ".bench_out")
CACHE = os.path.join(ROOT, ".jax_cache")


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Rec:
    """One request as the harness saw it (host clock, seconds)."""
    req: object
    prompt_len: int
    max_new: int
    due: float
    sent: float
    admit_ts: Optional[float] = None
    admit_step: int = -1
    slot: int = -1
    token_times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class StepRec:
    """One ``engine.step()``: when it ran, the prompt lengths it
    prefilled (tokens cached, i.e. prompt - 1) and the context length of
    each row it decoded."""
    ts: float
    te: float
    prefill: List[int]
    decode_ctx: List[int]
    traced: bool


@dataclasses.dataclass
class Context:
    """What a metric reader gets."""
    cell: dict
    cfg: dict                   # the configuration's model + weight keys
    peak: dict                  # flops_per_s, hbm_bytes_per_s
    n_params: int
    setup_s: float
    window: tuple               # (t0, t1) host clock
    requests: List[Rec]
    steps: List[StepRec]
    trace: Optional[dict] = None


def seed_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def require_chips(devices, chips: int) -> None:
    """Refuse to run without a TPU, or with fewer chips than the cell
    asks for."""
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")


def peaks_for(kind: str) -> dict:
    table = load_json("peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} has no entry in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[kind]


def program_config(config: dict):
    from repro import configs
    from repro.configs.base import AnalogSpec

    base = configs.get(config["program_config"])
    return base.replace(**config["model"],
                        analog=AnalogSpec(**config["analog"]))


def reference_config(config: dict) -> dict:
    return {**config["model"], **config["weights"],
            "analog_activation": config["analog"]["activation"],
            "adc_bits": config["analog"]["adc_bits"]}


def make_params(model, refmod, ref_cfg, seed: int):
    """Seeded weights, made on the device in one jitted call in the
    served dtype, checked against the program's own parameter tree."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(model.cfg.serve_params_dtype)
    params = jax.jit(lambda k: refmod.init_params(ref_cfg, k, dt))(
        seed_key(seed))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    want = jax.tree.map(lambda a: (a.shape, dt if a.dtype == jnp.float32
                                   else a.dtype), want)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError(f"reference weights do not match the program's "
                         f"parameter tree:\n{got}\n!=\n{want}")
    return jax.block_until_ready(params)


def select_metrics(cell_name: str, trace: bool) -> List[dict]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    e2e = [m for m in bm["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if cell_name in m["workloads"] or ("workloads" not in m
                                               and m["moves"] in moved)]


def _sample(records, rng, n: int, closed: bool):
    """``n`` finished requests to check: the longest; one admitted in a
    later wave (closed loop) or in a wave of mixed prompt lengths (open
    loop); the rest drawn from the seed, taking turns between even and
    odd batch slots so that both halves of the batch are checked."""
    done = [r for r in records if len(r.token_times) == r.max_new]
    if not done:
        return []
    picks = [max(done, key=lambda r: (r.prompt_len + r.max_new,
                                      -r.admit_step))]
    if closed:
        first = min(r.admit_step for r in done)
        later = [r for r in done if r.admit_step > first]
        if later:
            picks.append(later[int(rng.integers(len(later)))])
    else:
        waves = {}
        for r in done:
            waves.setdefault(r.admit_step, []).append(r)
        mixed = [r for w in waves.values() if len({x.prompt_len for x in w})
                 > 1 for r in w]
        if mixed:
            picks.append(mixed[int(rng.integers(len(mixed)))])
    rest = [r for r in done if all(r is not p for p in picks)]
    pools = [[rest[int(i)] for i in rng.permutation(len(rest))
              if rest[int(i)].slot % 2 == k] for k in (0, 1)]
    while len(picks) < n and (pools[0] or pools[1]):
        odd = sum(p.slot % 2 for p in picks)
        k = int(odd < len(picks) - odd)
        picks.append((pools[k] or pools[1 - k]).pop(0))
    return picks[:n]


def _gap_rank(logits, picked):
    """Per position: the reference's best logit minus the picked token's,
    and how many tokens the reference puts above the picked one."""
    import jax.numpy as jnp

    got = jnp.take_along_axis(logits, picked[:, None], axis=1)
    return jnp.max(logits, axis=1) - got[:, 0], jnp.sum(logits > got, axis=1)


BLOCK = 512             # positions per call of the reference's head


def check_outputs(refmod, ref_cfg, params, sample, shape, modes=("bf16",),
                  control=None):
    """The reference over each sampled prompt with its served tokens, as
    one batch of the fixed ``shape`` (rows, width), so that its programs
    are the same in every run.  -> {mode: [(gaps, ranks) per request]} of
    the served tokens under that reference (``_gap_rank``), and with
    ``control`` (a lower-precision mode) also {"<control>@<mode>": the
    same of the tokens the control puts first}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tokens = np.zeros(shape, np.int32)
    rows, pos, served, bounds = [], [], [], [0]
    for i, r in enumerate(sample):
        seq = np.concatenate([np.asarray(r.req.prompt),
                              np.asarray(r.req.generated[:-1], np.int32)])
        tokens[i, :len(seq)] = seq
        n = len(r.req.generated)
        rows += [i] * n
        pos += list(range(r.prompt_len - 1, r.prompt_len - 1 + n))
        served += list(r.req.generated)
        bounds.append(bounds[-1] + n)
    pad = -len(served) % BLOCK
    rows, pos, served = (np.asarray(a + [0] * pad, np.int32)
                         for a in (rows, pos, served))
    blocks = [slice(i, i + BLOCK) for i in range(0, len(served), BLOCK)]
    gap_rank = jax.jit(_gap_rank)

    picks = {mode: served for mode in modes}
    if control is not None:
        ref = refmod.Reference(ref_cfg, control)
        x = ref.hidden(params, tokens)
        low = np.concatenate([np.asarray(jnp.argmax(
            ref.head(params, x, rows[b], pos[b]), axis=1)) for b in blocks])
        del x
        picks.update({f"{control}@{mode}": low for mode in modes})
    out = {}
    for mode in modes:
        ref = refmod.Reference(ref_cfg, mode)
        x = ref.hidden(params, tokens)
        got = {k: ([], []) for k in picks if k.endswith(mode)}
        for b in blocks:
            logits = ref.head(params, x, rows[b], pos[b])
            for k, (gaps, ranks) in got.items():
                g, n = gap_rank(logits, jnp.asarray(picks[k][b]))
                gaps.append(np.asarray(g))
                ranks.append(np.asarray(n))
        del x, logits
        for k, (gaps, ranks) in got.items():
            gaps, ranks = np.concatenate(gaps), np.concatenate(ranks)
            out[k] = [(gaps[a:b], ranks[a:b])
                      for a, b in zip(bounds, bounds[1:])]
    return out


def check_numbers(per_request, chk: dict) -> dict:
    """The numbers compared, each with its limit.  ``agree_min``: of the
    checked requests, the smallest share of a request's served tokens
    that the reference (in the cell's ``check.reference`` mode) ranks
    among its ``check.top_k`` first choices, at least ``min_agree``;
    ``tokens_checked``: at least ``min_tokens``."""
    import numpy as np

    k = chk["top_k"]
    shares = [float(np.mean(n < k)) for _, n in per_request if n.size]
    return {
        "agree_min": {"value": min(shares) if shares else 0.0,
                      "limit": chk["min_agree"], "at_least": True,
                      "top_k": k},
        "tokens_checked": {"value": int(sum(n.size for _, n in per_request)),
                           "limit": chk["min_tokens"], "at_least": True},
    }


class Session:
    """The program under test, set up for one cell: weights from the
    seed, the engine built and warmed up on every shape the traffic
    uses, and a jitted fresh decode state for the per-wave reset."""

    def __init__(self, cell: dict, config: dict, seed: int, *, log=print):
        import jax

        devices = jax.devices()
        self.kind = devices[0].device_kind
        log(f"devices: platform {devices[0].platform}, device_kind "
            f"{self.kind!r}, count {len(devices)}")
        require_chips(devices, cell["chips"])
        self.devices = devices
        self.peak = peaks_for(self.kind)

        from repro.nn.model import build
        from repro.serve.engine import Request, ServingEngine

        self.Request = Request
        self.cell, self.log = cell, log
        self.pcfg = program_config(config)
        self.model = build(self.pcfg)
        self.refmod = load_module("reference", config["reference"])
        self.ref_cfg = reference_config(config)
        self.params = make_params(self.model, self.refmod, self.ref_cfg,
                                  seed)
        eng_kw = dict(cell["engine"])
        if "prefill_buckets" in eng_kw:
            eng_kw["prefill_buckets"] = tuple(eng_kw["prefill_buckets"])
        self.engine = ServingEngine(self.model, self.params, **eng_kw)
        self.engine.warmup()
        eng = self.engine
        self.reset = jax.jit(lambda: self.model.init_decode_state(
            eng.max_batch, eng.max_len))
        self.tparams = cell["traffic"]
        self.gen = load_module("traffic", self.tparams["generator"])
        for wave in self.traffic(seed).warmup(eng.max_batch):
            eng.state = self.reset()
            for uid, prompt, max_new in wave:
                eng.submit(Request(uid=uid, prompt=prompt,
                                   max_new_tokens=max_new))
            while eng.queue or not all(eng.slot_free):
                eng.step()
        eng.state = jax.block_until_ready(self.reset())

    def traffic(self, seed: int):
        return self.gen.Traffic(self.tparams, seed, self.pcfg.vocab)

    def reseed(self, seed: int) -> None:
        """New weights from ``seed`` in the same engine (the executables
        take the weights as arguments, so nothing compiles again)."""
        self.params = make_params(self.model, self.refmod, self.ref_cfg,
                                  seed)
        self.engine.params = self.engine._pristine_params = self.params

    def window(self, seed: int, seconds: float, trace: bool):
        """Drive ``seconds`` of the cell's traffic through the engine.
        -> (traffic, records, steps, (t0, t1), trace dir or None).

        With ``trace``, a profiler trace of ``trace.seconds`` is taken from
        the first step boundary after ``trace.start_s`` that suits the
        cell's ``trace.phase``: ``decode``, right after a step that
        admitted nothing, with requests in flight and none queued; ``wave``, with the engine idle, so the trace
        opens on a wave's prefill; ``any``, at once."""
        import jax

        eng, Request = self.engine, self.Request
        traffic = self.traffic(seed)
        fresh = bool(self.tparams.get("fresh_state_per_wave", False))
        ann = jax.profiler.TraceAnnotation
        tr = self.cell["trace"]
        trace_dir = os.path.join(OUT, "trace")
        records, steps = {}, []
        tracing = trace_done = False
        trace_start = 0.0
        window_note = None
        t0 = time.perf_counter()
        t_end = t0 + seconds
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            idle = not eng.queue and all(eng.slot_free)
            phase_ok = {"any": True, "wave": idle,
                        "decode": bool(steps) and not steps[-1].prefill
                        and not eng.queue and not idle}[tr["phase"]]
            if trace and not tracing and not trace_done \
                    and now - t0 >= tr["start_s"] and phase_ok:
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(trace_dir)
                tracing, trace_start = True, time.perf_counter()
                window_note = ann("bench.traced")
                window_note.__enter__()
            with ann("bench.generate"):
                new = traffic.poll(now - t0, idle)
            if new:
                if fresh and idle:
                    with ann("bench.reset"):
                        eng.state = self.reset()
                with ann("bench.submit"):
                    for uid, prompt, max_new, due in new:
                        req = Request(uid=uid, prompt=prompt,
                                      max_new_tokens=max_new)
                        eng.submit(req)
                        records[uid] = Rec(req, len(prompt), max_new,
                                           t0 + due, time.perf_counter())
            if eng.queue or not all(eng.slot_free):
                ts = time.perf_counter()
                with ann("engine.step"):
                    out = eng.step()
                te = time.perf_counter()
                pre, ctx = [], []
                for uid in out:
                    rec = records[uid]
                    n_prev = len(rec.token_times)
                    if n_prev == 0:
                        rec.admit_ts, rec.admit_step = ts, len(steps)
                        pre.append(rec.prompt_len - 1)
                    ctx.append(rec.prompt_len + n_prev)
                    rec.token_times.append(te)
                if pre:
                    for slot, req in enumerate(eng.slot_req):
                        if req is not None and req.uid in out \
                                and records[req.uid].admit_step == len(steps):
                            records[req.uid].slot = slot
                steps.append(StepRec(ts, te, pre, ctx, tracing))
            else:
                nd = traffic.next_due()
                wake = t_end if nd is None else min(t0 + nd, t_end)
                time.sleep(max(wake - time.perf_counter(), 0.0))
            if tracing and time.perf_counter() - trace_start >= tr["seconds"]:
                window_note.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing, trace_done = False, True
        t1 = max(t_end, steps[-1].te if steps else t_end)
        if tracing:
            window_note.__exit__(None, None, None)
            jax.profiler.stop_trace()
        n_tok = sum(len(s.decode_ctx) for s in steps)
        self.log(f"window {t1 - t0:.3f} s: {len(records)} requests sent, "
                 f"{len(steps)} engine steps, {n_tok} tokens")
        self.log("steps that admitted requests (start s, seconds): "
                 + ", ".join(f"({s.ts - t0:.3f}, {s.te - s.ts:.3f})"
                             for s in steps if s.prefill))
        return (traffic, list(records.values()), steps, (t0, t1),
                trace_dir if trace else None)

    def check(self, records, seed: int, traffic, modes=None, control=None,
              requests=None):
        """The reference check of a sample of the finished requests
        (``check_outputs``) -> (sample, {mode: [(gaps, ranks) per
        request]}); ``modes`` defaults to the cell's reference mode,
        ``requests`` to the cell's sample size."""
        import numpy as np

        modes = modes or (self.cell["check"]["reference"],)
        n = requests or self.cell["check"]["requests"]
        rng = np.random.default_rng([seed, 1])
        sample = _sample(records, rng, n, traffic.closed)
        if not sample:
            return sample, {m: [] for m in modes}
        t = time.perf_counter()
        shape = (n, -(-traffic.longest() // 128) * 128)
        out = check_outputs(self.refmod, self.ref_cfg, self.params, sample,
                            shape, modes, control)
        self.log(f"reference check: {len(sample)} requests, "
                 f"{sum(g.size for g, _ in out[modes[0]])} served tokens, "
                 f"{time.perf_counter() - t:.3f} s")
        return sample, out


def run(cell_name: str, cell: dict, config: dict, seed: int,
        seconds: float, trace: bool, *, t_start: float, log=print) -> dict:
    import jax

    s = Session(cell, config, seed, log=log)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; window {seconds} s")
    traffic, records, steps, window, trace_dir = s.window(seed, seconds,
                                                          trace)
    used = s.devices[:cell["chips"]]
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in used]
    device = {"platform": s.devices[0].platform, "kind": s.kind,
              "count": len(s.devices), "memory_peak_bytes": int(max(mem))}
    red = None
    if trace_dir is not None:
        from bench import trace as T

        red = T.reduce_file(T.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]

    # The program's state is freed before the reference runs: only the
    # weights stay on the device.
    s.engine.state = s.engine._pack_tmpl = None
    s.engine = s.reset = None
    gc.collect()
    chk = cell["check"]
    with jax.profiler.TraceAnnotation("bench.reference"):
        sample, got = s.check(records, seed, traffic)
    per_request = got[chk["reference"]]
    check = check_numbers(per_request, chk)
    correct = all(c["value"] >= c["limit"] for c in check.values())
    for r, (_, n) in zip(sample, per_request):
        log(f"request {r.req.uid} (slot {r.slot}, admitted at step "
            f"{r.admit_step}): {n.size} tokens; shares the reference ranks "
            f"in its top 1, 2, 5, 10, 20, 50: "
            f"{[float((n < k).mean()) for k in (1, 2, 5, 10, 20, 50)]}")

    leaves = jax.tree.leaves(s.params)
    ctx = Context(cell=cell, cfg=s.ref_cfg, peak=s.peak,
                  n_params=int(sum(a.size for a in leaves)),
                  setup_s=setup_s, window=window, requests=records,
                  steps=steps, trace=red)
    metrics = {}
    for m in select_metrics(cell_name, trace):
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": correct, "attempted": len(records), "failed": 0,
              "metrics": metrics, "device": device}
    if red is not None:
        from bench import trace as T

        result["breakdown"] = T.breakdown(red)
    result["check"] = check
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # the persistent compile cache at one fixed path in the checkout,
    # handed to the program too
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    cell = load_json("workloads", args.workload + ".json")
    config = load_json("configs", cell["config"] + ".json")

    def log(msg):
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    result = run(args.workload, cell, config, args.seed, args.seconds,
                 bool(args.trace), t_start=T_START, log=log)
    for name, c in result["check"].items():
        log(f"check {name}: {c['value']} (at least {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
