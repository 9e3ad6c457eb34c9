import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS_EXTRA", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh; record memory analysis, FLOPs/bytes and collective
traffic for the roofline table.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k [--multi-pod] [--out experiments/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, ASSIGNED, SHAPES, applicable, get_config
from repro.core import TPU_V5E, resolve
from repro.distributed.context import DistContext
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis, specs as specs_lib
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models.api import get_model
from repro.runtime.train_loop import TrainOptions, abstract_state, \
    make_train_step, train_state_shardings


def _dist_for(mesh) -> DistContext:
    return DistContext(mesh=mesh, dp_axes=dp_axes(mesh), ep_axis="model",
                       tp_axis="model")


def _arch_cfg_for_cell(name: str, shape_name: str, mesh) -> "ArchConfig":
    cfg = get_config(name)
    shape = SHAPES[shape_name]
    # layer-level remat for training (activation fit at 4k x 256 batch)
    if shape.kind == "train":
        cfg = dataclasses.replace(cfg, remat_policy="full")
    if cfg.moe is not None:
        dp = 1
        for a in dp_axes(mesh):
            dp *= mesh.shape[a]
        ep = mesh.shape["model"]
        if shape.kind == "train":
            local_tokens = max(1, shape.global_batch // dp) \
                * max(1, shape.seq_len // ep)
        else:
            local_tokens = max(1, shape.global_batch // dp) * shape.seq_len
        cfg = resolve(cfg, local_tokens=local_tokens, ep_size=ep,
                      hw=TPU_V5E, allow_offload=False, dp=dp)
    return cfg


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               mesh=None, compile_: bool = True, cfg_override=None,
               seq_parallel: bool = False) -> dict:
    """Lower (and compile) one cell; return the record dict.

    ``cfg_override(cfg) -> cfg`` lets the perf-iteration harness tweak a
    single knob (pipeline mode, n, remat policy, ...) against the same
    lowering path; ``seq_parallel`` flips the residual-stream layout.
    """
    shape = SHAPES[shape_name]
    base = get_config(arch)
    ok, why = applicable(base, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    cfg = _arch_cfg_for_cell(arch, shape_name, mesh)
    if cfg_override is not None:
        cfg = cfg_override(cfg)
    model = get_model(cfg)
    dist = dataclasses.replace(_dist_for(mesh), seq_parallel=seq_parallel)
    rules = shd.make_rules(
        mesh, shape.kind,
        fsdp=(shape.kind == "train") or cfg.param_count() > 3e10,
        seq_shard_cache=("data", "model") if shape.global_batch == 1
        else "model")
    record = {"arch": arch, "shape": shape_name,
              "mesh": dict(zip(mesh.axis_names,
                               [int(s) for s in mesh.devices.shape])),
              "params": cfg.param_count(),
              "params_active": cfg.active_param_count()}
    if cfg.moe:
        record["moe"] = {"n_partitions": cfg.moe.num_partitions,
                         "strategy": cfg.moe.memory_reuse_strategy}

    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        pshard = shd.param_shardings(cfg, rules, model)
        if shape.kind == "train":
            opts = TrainOptions()
            astate = abstract_state(cfg, opts)
            sshard = train_state_shardings(cfg, opts, mesh)
            abatch = specs_lib.train_batch_specs(cfg, shape)
            bshard = shd.batch_shardings(cfg, shape, rules, abatch)
            step = make_train_step(cfg, opts, dist)
            jitted = jax.jit(step, in_shardings=(sshard, bshard),
                             donate_argnums=(0,))
            lowered = jitted.lower(astate, abatch)
        elif shape.kind == "prefill":
            abatch = specs_lib.prefill_batch_specs(cfg, shape)
            bshard = shd.batch_shardings(cfg, shape, rules, abatch)

            def prefill_step(params, batch):
                logits, cache = model.prefill(params, batch, cfg,
                                              max_len=shape.seq_len,
                                              dist=dist)
                return logits, cache
            jitted = jax.jit(prefill_step, in_shardings=(pshard, bshard))
            lowered = jitted.lower(model.abstract_params(cfg), abatch)
        else:  # decode
            tokens, acache = specs_lib.decode_inputs(cfg, shape)
            cshard = shd.cache_shardings(cfg, rules, acache)
            tshard = rules.sharding_for(tokens.shape, ("batch", None),
                                        "tokens")

            def serve_step(params, cache, toks):
                return model.decode_step(params, cache, toks, cfg,
                                         dist=dist)
            jitted = jax.jit(serve_step,
                             in_shardings=(pshard, cshard, tshard),
                             donate_argnums=(1,))
            lowered = jitted.lower(model.abstract_params(cfg), acache,
                                   tokens)
        record["lower_s"] = round(time.perf_counter() - t0, 2)
        record["fallbacks"] = rules.fallbacks[:20]

        if not compile_:
            return record
        t1 = time.perf_counter()
        compiled = lowered.compile()
        record["compile_s"] = round(time.perf_counter() - t1, 2)

    mem = compiled.memory_analysis()
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                record[k] = int(v)
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    if cost:  # raw XLA numbers (counts loop bodies ONCE — see hlo_analysis)
        record["xla_flops_once"] = float(cost.get("flops", 0.0))
        record["xla_bytes_once"] = float(cost.get("bytes accessed", 0.0))
    txt = compiled.as_text()
    ana = hlo_analysis.analyze(txt)
    record["flops"] = ana["flops"]
    record["hbm_bytes"] = ana["hbm_bytes"]
    record["collectives"] = {k: float(v)
                             for k, v in ana["collectives"].items()}
    chips = 1
    for s in mesh.devices.shape:
        chips *= int(s)
    record["roofline"] = hlo_analysis.roofline_terms(
        ana["flops"], ana["hbm_bytes"], ana["collectives"], chips=chips)
    record["roofline"]["dominant"] = hlo_analysis.dominant_term(
        record["roofline"])
    # MODEL_FLOPS per device: 6*N*D train (fwd+bwd), 2*N*D prefill (fwd
    # over B*S tokens), 2*N*D decode (one new token per sequence); N =
    # active params for MoE
    if shape.kind == "decode":
        tokens_global = shape.global_batch
    else:
        tokens_global = shape.global_batch * shape.seq_len
    mult = 6.0 if shape.kind == "train" else 2.0
    model_flops = (mult * record["params_active"] * tokens_global) / chips
    record["model_flops"] = model_flops
    record["useful_ratio"] = (model_flops / ana["flops"]
                              if ana["flops"] else 0.0)
    record["top_collectives"] = hlo_analysis.per_collective_report(txt, 8)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ASSIGNED:
            for s in SHAPES:
                cells.append((a, s))
    else:
        archs = [args.arch] if args.arch else list(ASSIGNED)
        shapes = [args.shape] if args.shape else list(SHAPES)
        for a in archs:
            for s in shapes:
                cells.append((a, s))

    os.makedirs(args.out, exist_ok=True)
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    tag = "multipod" if args.multi_pod else "singlepod"
    failures = 0
    for arch, shape in cells:
        out_path = os.path.join(args.out,
                                f"{tag}__{arch}__{shape}.json")
        try:
            rec = lower_cell(arch, shape, mesh=mesh,
                             compile_=not args.no_compile)
        except Exception as e:                      # record, keep going
            failures += 1
            rec = {"arch": arch, "shape": shape, "error": repr(e),
                   "traceback": traceback.format_exc()[-4000:]}
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
        status = ("SKIP " + rec["skipped"] if "skipped" in rec else
                  "ERROR" if "error" in rec else
                  f"ok lower={rec.get('lower_s')}s "
                  f"compile={rec.get('compile_s')}s "
                  f"dom={rec.get('roofline', {}).get('dominant', '?')}")
        print(f"[{tag}] {arch:24s} {shape:12s} {status}", flush=True)
    print(f"done; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
