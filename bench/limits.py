#!/usr/bin/env python3
"""Readings that set a cell's check limits: the program's numbers on many
seeds, the control's, and those of faults planted under the timed path,
in one process.

    python3 bench/limits.py --workload qwen2.5-3b.decode-heavy \
        --seeds 11,12,13 --control-seeds 11,12 \
        --faults state_unchanged,half_batch --fault-seeds 21,22 \
        --seconds 36 --dump limits.jsonl

For each seed the weights are made anew in the same engine (nothing
compiles again), the cell's traffic runs for ``--seconds`` at the cell's
own load, and the check's sample of finished requests is compared with
the reference in the cell's mode, as a benchmark run does.  On the
control seeds the reference computed with float8 operands (``fp8``, the
precision below the configuration's bfloat16) is read at the same
positions: the token it puts first.  Each fault of ``bench/tests/faults.py``
is planted before a fresh engine is built and compiled, and runs on the
fault seeds.  One summary line per seed and reading on standard output;
with ``--dump``, every checked token's gap and rank, per request, one
JSON line per seed and reading.  The benchmark's own runs never run the
control or a fault.

    python3 bench/limits.py --workload <cell> --choose limits.jsonl

reads such a dump and prints the cell's ``top_k`` and ``min_agree``:
for each k, the lower reading is the smallest ``agree_min`` of the
program over its seeds, the upper reading the largest of the control;
the k kept is the one whose two readings lie furthest apart, by at
least 3x, and whose limit every fault reading falls below; the limit is
C * (S / C) ** 0.4 between the control's C (at least 0.005) and the
program's S, so that it leaves more room below the program than above
the control.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def summary(per_request) -> dict:
    import numpy as np

    gaps = np.concatenate([g for g, _ in per_request])
    ranks = np.concatenate([n for _, n in per_request])
    return {"top1_agree": float(np.mean(gaps == 0)),
            **{f"top{k}_agree_min": min(float(np.mean(n < k))
                                        for _, n in per_request)
               for k in (1, 5, 20, 100)},
            "rank_median": float(np.median(ranks)),
            "mean_logit_gap": float(gaps.mean()),
            "max_logit_gap": float(gaps.max()),
            "tokens": int(gaps.size)}


KS = (1, 2, 3, 5, 10, 20, 50)


def choose(path: str, n_requests: int) -> dict:
    """The check's ``top_k`` and ``min_agree`` from a dump (see above);
    each reading's ``agree_min`` is taken over its first ``n_requests``
    requests, the cell's own sample size."""
    import numpy as np

    readings = {}
    with open(path) as f:
        for line in f:
            d = json.loads(line)
            ranks = [np.asarray(r["ranks"])
                     for r in d["requests"][:n_requests]]
            readings.setdefault(d["reading"], []).append(ranks)

    def agree_min(ranks, k):
        return min(float(np.mean(n < k)) for n in ranks)

    best = None
    for k in KS:
        s = min(agree_min(r, k) for r in readings["program"])
        c = max(max(agree_min(r, k) for r in readings["control"]), 0.005)
        faults = {f: max(agree_min(r, k) for r in rs)
                  for f, rs in readings.items()
                  if f not in ("program", "control")}
        if s < 3 * c:
            continue
        limit = float(f"{c * (s / c) ** 0.4:.3g}")
        if all(v < limit for v in faults.values()) and \
                (best is None or s / c > best["program"] / best["control"]):
            best = {"top_k": k, "min_agree": limit, "program": s,
                    "control": c, "faults": faults}
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--choose", default=None)
    ap.add_argument("--requests", type=int, default=None,
                    help="requests to check (default: the cell's)")
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run as R
    from bench.tests import faults

    if args.choose:
        cell = R.load_json("workloads", args.workload + ".json")
        print(json.dumps(choose(args.choose, cell["check"]["requests"])))
        return 0

    os.environ["JAX_COMPILATION_CACHE_DIR"] = R.CACHE
    import jax

    jax.config.update("jax_compilation_cache_dir", R.CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = R.load_json("workloads", args.workload + ".json")
    config = R.load_json("configs", cell["config"] + ".json")

    def ints(text):
        return [int(x) for x in text.split(",") if x]

    control = set(ints(args.control_seeds))
    dump = open(args.dump, "a") if args.dump else None

    def log(msg):
        print(f"[limits] {msg}", file=sys.stderr, flush=True)

    def drive(s, seed, reading):
        s.reseed(seed)
        s.engine.state = s.reset()
        traffic, records, _, _, _ = s.window(seed, args.seconds, False)
        sample, got = s.check(records, seed, traffic,
                              control="fp8" if seed in control
                              and reading == "program" else None,
                              requests=args.requests)
        for key, per in got.items():
            name = reading if "@" not in key else "control"
            print(json.dumps({"seed": seed, "reading": name, "mode": key,
                              **summary(per)}), flush=True)
            if dump:
                dump.write(json.dumps({
                    "seed": seed, "reading": name, "mode": key,
                    "requests": [{"uid": r.req.uid, "slot": r.slot,
                                  "admit_step": r.admit_step,
                                  "prompt_len": r.prompt_len,
                                  "gaps": [round(float(x), 4) for x in g],
                                  "ranks": [int(x) for x in n]}
                                 for r, (g, n) in zip(sample, per)]}) + "\n")
                dump.flush()
        # drop what is still in flight: the next seed starts idle
        eng = s.engine
        eng.queue.clear()
        eng.slot_free = [True] * eng.max_batch
        eng.slot_req = [None] * eng.max_batch

    readings = [("program", ints(args.seeds), contextlib.nullcontext)]
    readings += [(f, ints(args.fault_seeds), faults.FAULTS[f])
                 for f in args.faults.split(",") if f]
    for reading, seeds, plant in readings:
        if not seeds:
            continue
        with plant():
            s = R.Session(cell, config, seeds[0], log=log)
            log(f"{reading}: set-up {time.perf_counter() - T_START:.1f} s")
            for seed in seeds:
                drive(s, seed, reading)
            del s
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
