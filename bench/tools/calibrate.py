#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (chip only).

Serving cells: runs the cell (set-up, a window of ``--seconds`` at the
cell's own load, the reference check) once per seed, in one process, and
prints the program's served gap beside the fp8 control's gap on the
same sample of prompts and served tokens.

Training cells: no program run is needed. Per seed, the reference's
three steps in float32 are compared with (a) the same reference in fp8,
the control, and (b) the float32 reference on half of each batch, the
mean taken over the rest (a fault the program could have); each prints
the three compared numbers. A state left unchanged reads 1 on the
gradient and change numbers by construction.

    python3 bench/tools/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--seconds 10]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def serve_readings(ctx):
    from bench.drivers import serve

    serve.run(ctx, control=True)
    return {"served_gap": ctx.checks["served_gap"][0],
            "control_gap": max(ctx.control_gaps),
            "sample_tokens": ctx.layer["sample_tokens"]}


def train_readings(ctx, n_groups: int):
    from bench import reference, traffic, weights
    from bench.drivers import train

    t, m = ctx.cell["params"], ctx.model
    seq, batch = t["seq"], t["batch"]
    ep = t["mesh"]["model"]
    group = batch * seq // ep // n_groups
    batches = [traffic.token_batch(ctx.seed, s, batch, seq,
                                   m["vocab_size"]) for s in range(3)]
    half = [{k: v[:batch // 2] for k, v in b.items()} for b in batches]
    out = {}
    for name, prec, bs in (("f32", "f32", batches), ("fp8", "fp8", batches),
                           ("half", "f32", half)):
        # the faulty step's dispatch groups are those of the rows it kept
        grp = min(group, len(bs[0]["tokens"]) * seq // ep)
        rows = train._rows_per_block(len(bs[0]["tokens"]), seq, ep, grp)
        losses, grads, p3 = reference.train_readings(
            weights.make(m, ctx.seed), bs, m, t["optimizer"],
            group_tokens=grp, ep=ep, rows_per_block=rows, prec=prec)
        out[name] = (losses, grads,
                     train._delta_norms(p3, weights.make(m, ctx.seed)))
        del p3
        gc.collect()
    res = {}
    for name in ("fp8", "half"):
        r = train.readings(*out[name], *out["f32"])
        res[name] = {k: (v[0] if isinstance(v, tuple) else v)
                     for k, v in r.items()}
    return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--groups", type=int, default=1,
                    help="training: dispatch groups per chip (the "
                         "resolved n)")
    args = ap.parse_args()

    import jax

    from bench import spec
    from bench.run import Context
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: no TPU")
    enable_compile_cache()
    cell = spec.workload(args.workload)
    conf = spec.config(cell["config"])
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = Context(cell, conf, seed, args.seconds, False)
        if cell["driver"] == "serve":
            res = serve_readings(ctx)
        else:
            res = train_readings(ctx, args.groups)
        print(json.dumps({"seed": seed, **res}), flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
