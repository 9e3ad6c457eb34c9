#!/usr/bin/env python3
"""Find a serving cell's knee: the highest offered rate at which at
least 90% of requests meet TTFT <= 1 s and a mean inter-token gap
<= 100 ms, with no growing backlog. One engine is built (from the cell's
file and one seed) and replays each rate for ``--seconds``.

    python3 bench/tools/knee.py --workload serve.gpt3s.chat \\
        --rates 2,3,4,5,6 --seconds 30 --seed 1

Prints one JSON line per rate: the share meeting both limits, TTFT p50 /
p90, ITL p95, and the waiting queue at the middle and end of the
window. Runs on the chip only.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

TTFT_LIMIT_S, GAP_LIMIT_S = 1.0, 0.100


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import jax
    import numpy as np

    from bench import spec
    from bench.drivers import serve
    from bench.run import Context
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("knee: no TPU")
    enable_compile_cache()
    cell = spec.workload(args.workload)
    ctx = Context(cell, spec.config(cell["config"]), args.seed,
                  args.seconds, False)
    engine = serve.build(ctx)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        ctx.seed = args.seed + 1 + i
        counted, tail = serve.requests(ctx, rate, args.seconds)
        queue = []
        step0 = engine.step

        def step():                     # sample the waiting queue
            out = step0()
            queue.append((time.perf_counter(),
                          len(engine.scheduler.waiting)))
            return out
        engine.step = step
        run = serve.replay(ctx, engine, counted, tail, args.seconds,
                           window=False)
        engine.step = step0
        met = serve.e2e(ctx, run, len(counted), args.seconds)
        ok = 0
        for j in range(len(counted)):
            req = run["counted"].get(j)
            if req is None or not req.itl_s or req.ttft_s > TTFT_LIMIT_S:
                continue
            ok += float(np.mean(req.itl_s)) <= GAP_LIMIT_S
        def waiting_at(t):
            t += run["t0"]
            return min(queue, key=lambda q: abs(q[0] - t))[1] \
                if queue else 0
        print(json.dumps({
            "rate": rate, "requests": len(counted),
            "met_share": ok / len(counted),
            "ttft_p50_s": float(np.percentile(met["ttft"], 50)),
            "ttft_p90_s": met["ttft_p90_s"], "itl_p95_ms": met["itl_p95_ms"],
            "waiting_mid": waiting_at(args.seconds / 2),
            "waiting_end": waiting_at(args.seconds),
            "drain_s": run["t_end"] - args.seconds}), flush=True)
        engine.run_until_idle()


if __name__ == "__main__":
    main()
