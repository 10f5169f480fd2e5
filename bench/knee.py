#!/usr/bin/env python3
"""Sweep an open-loop cell's arrival rate once, to find its knee: the
highest mean rate at which the queue does not grow over the window.

    python3 bench/knee.py --workload mamba2-370m.chat-bursty \
        --rates 1,2,3,4 --pool-seeds 7,8 --seconds 30 --seed 5

One process, one engine; each rate runs the cell's traffic with only
``rate_rps`` changed, from an idle engine, once on each schedule of
``--pool-seeds`` (default: the cell's own).  Per rate one JSON line: the
requests sent, finished and still waiting at the window's end, the queue
length over the window's last third against its first, and TTFT p50/p90
(ms) of the requests that got a first token.  The cell's rate is then
written into its file by hand, at about 0.8 of the knee.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pool-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np

    from bench import run as R

    os.environ["JAX_COMPILATION_CACHE_DIR"] = R.CACHE
    import jax

    jax.config.update("jax_compilation_cache_dir", R.CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = R.load_json("workloads", args.workload + ".json")
    config = R.load_json("configs", cell["config"] + ".json")

    def log(msg):
        print(f"[knee] {msg}", file=sys.stderr, flush=True)

    s = R.Session(cell, config, args.seed, log=log)
    pools = [int(x) for x in args.pool_seeds.split(",") if x] \
        or [cell["traffic"]["pool_seed"]]
    for rate, pool in [(float(x), p) for x in args.rates.split(",")
                       for p in pools]:
        s.tparams = dict(s.tparams, rate_rps=rate, pool_seed=pool)
        _, records, steps, (t0, t1), _ = s.window(args.seed, args.seconds,
                                                  False)
        eng = s.engine

        def waiting(t):       # sent by t, no first token by t
            return sum(1 for r in records if r.sent <= t
                       and not (r.token_times and r.token_times[0] <= t))

        ttft = [r.token_times[0] - r.due for r in records if r.token_times]
        third = (t1 - t0) / 3.0
        print(json.dumps({
            "rate_rps": rate, "pool_seed": pool, "sent": len(records),
            "finished": sum(1 for r in records
                            if len(r.token_times) == r.max_new),
            "queued_at_end": len(eng.queue),
            "waiting_first_third": float(np.mean(
                [waiting(t0 + f * third) for f in (0.25, 0.5, 0.75, 1.0)])),
            "waiting_last_third": float(np.mean(
                [waiting(t0 + (2 + f) * third) for f in (0.25, 0.5, 0.75,
                                                         1.0)])),
            "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
            "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3,
            "steps": len(steps)}), flush=True)
        eng.queue.clear()
        eng.slot_free = [True] * eng.max_batch
        eng.slot_req = [None] * eng.max_batch
        eng.state = s.reset()
        time.sleep(1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
