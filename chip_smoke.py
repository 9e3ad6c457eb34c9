#!/usr/bin/env python3
"""Chip smoke test: the main paths of this repo on a TPU, at published
widths, through the entry points a user calls.

    python chip_smoke.py             # one chip: serve moe-gpt3-s
    python chip_smoke.py --chips 4   # four chips: expert-parallel train

One chip (default). ``moe-gpt3-s`` (12 layers, d_model 768, 64 experts
of d_expert 3072, top-1, vocab 50304: 1.95 B parameters, seeded random
weights, bf16 compute) is served by ``repro.serve.Engine`` with the
options ``launch/serve.py --engine --full`` builds, replaying 8 seeded
Poisson requests (16-48 new tokens each) through ``run_poisson``. The
engine's defaults pick the decode attention path and the prefill
``measure`` mode. Fails unless every request finishes with its full
token count and no served logit row holds a NaN/inf. Greedy agreement
with ``dense_greedy_reference`` on two requests is printed as a rate,
not gated: bf16 rounding moves the argmax of random-weight logits.

Four chips (``--chips 4``, this phase only). A few ``repro.runtime.train``
steps of ``moe-gpt3-s`` at 8 x 1024 tokens on a ``data=1 x model=4``
mesh: each chip holds 16 of the 64 experts and the MoE layers run the
pipelined dispatch/combine All-to-All. Step 0's loss at the resolved
``n`` is compared with the same step at ``n=1`` (see ``LOSS_TOL``).

Every phase runs in this one process (a chip belongs to one process).
Without a TPU — e.g. under ``JAX_PLATFORMS=cpu`` — it exits non-zero
and prints no result. The last stdout line is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "moe-gpt3-s"
REQUESTS, GEN_MIN, GEN_MAX, PROMPT_MAX = 8, 16, 48, 128
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 3
# |loss(n) - loss(1)| at step 0: the two programs differ only in how the
# 2048 local tokens are chunked before dispatch. A smaller chunk rounds
# its expert capacity up differently, so a few different tokens overflow
# and skip their expert (the residual carries them), and bf16 sums run
# in another order. Both move a mean over 8192 tokens by far less than
# 0.02 nats; a wrong dispatch/combine or a chunk dropped from the
# pipeline moves it by more.
LOSS_TOL = 0.02


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def serve_phase(seed: int) -> None:
    import jax

    from repro.core import resolve_hw
    from repro.launch import serve as serve_cli
    from repro.serve import dense_greedy_reference, run_poisson

    args = serve_cli.build_parser().parse_args([
        "--engine", "--full", "--arch", ARCH, "--batch", "8",
        "--prompt-len", str(PROMPT_MAX), "--gen", str(512 - PROMPT_MAX),
        "--requests", str(REQUESTS), "--seed", str(seed)])
    cfg = serve_cli.config_for(args)
    hw = resolve_hw(args.hw)
    _, opts = serve_cli.engine_setup(args, cfg, hw)
    print(f"serve: {cfg.name} params={cfg.param_count()} "
          f"d_model={cfg.d_model} experts={cfg.moe.num_experts} "
          f"compute={cfg.compute_dtype} hw={hw.name} "
          f"slots={opts.max_slots} max_seq_len={opts.max_seq_len} "
          f"page={opts.page_size} chunk={opts.chunk}", flush=True)

    t0 = time.perf_counter()
    startup = {}

    def on_engine(_engine):
        startup["s"] = time.perf_counter() - t0

    engine, wall = run_poisson(
        cfg, opts, requests=args.requests, rate=args.rate,
        prompt_max=PROMPT_MAX, gen_min=GEN_MIN, gen_max=GEN_MAX,
        seed=args.seed, on_engine=on_engine)
    stats = engine.stats()
    done = sorted(engine.done, key=lambda r: r.rid)
    reg = engine.obs.registry
    resolve_s = sum(h.sum for h in reg.get("repro_retune_seconds")
                    .children())
    measured = sum(c.value for c in reg.get(
        "repro_candidates_measured_total").children())
    print(f"serve: start-up {startup['s']:.1f}s (engine build + warmup, "
          f"compiles included; {resolve_s:.1f}s of it resolving prefill "
          f"buckets over {int(measured)} measured candidates), replay "
          f"{wall:.2f}s", flush=True)
    print(f"serve: peak_bytes_in_use={peak_bytes(jax.devices()[0])}",
          flush=True)
    print("serve: stats " + json.dumps(
        {k: stats[k] for k in (
            "requests_done", "tokens_generated", "attn_kernel",
            "engine_steps", "prefill_compiles", "decode_traces",
            "nonfinite_logit_rows", "p50_ttft_s", "p99_ttft_s",
            "p50_itl_s", "p99_itl_s", "p50_latency_s", "p99_latency_s",
            "resolutions")}), flush=True)
    hlo = engine.compiled_decode_text()
    print(f"serve: attention path={stats['attn_kernel']} "
          f"decode tpu_custom_call={'tpu_custom_call' in hlo}", flush=True)

    if len(done) != REQUESTS:
        fail(f"{len(done)} of {REQUESTS} requests finished")
    short = [(r.rid, len(r.output), r.max_new_tokens) for r in done
             if len(r.output) != r.max_new_tokens]
    if short:
        fail(f"requests without their full token count: {short}")
    if stats["nonfinite_logit_rows"]:
        fail(f"{stats['nonfinite_logit_rows']} served logit rows hold "
             f"NaN/inf")
    if stats["attn_kernel"] == "pallas" and "tpu_custom_call" not in hlo:
        fail("attention path is pallas but the decode program holds no "
             "tpu_custom_call")

    agree, total = 0, 0
    for r in done[:2]:
        ref = dense_greedy_reference(engine.params, cfg, r.prompt,
                                     len(r.output))
        lead = next((i for i, (a, b) in enumerate(zip(r.output, ref))
                     if a != b), len(ref))
        agree, total = agree + lead, total + len(ref)
        print(f"serve: rid {r.rid} greedy agreement with the dense "
              f"reference: {lead}/{len(ref)} leading tokens", flush=True)
    print(f"serve: greedy agreement rate {agree / total:.3f} "
          f"(not gated)", flush=True)


def train_phase(seed: int) -> None:
    import jax

    from repro.configs import get_config
    from repro.core import resolve, resolve_hw
    from repro.data import SyntheticTokens
    from repro.distributed.context import DistContext
    from repro.launch.mesh import dp_axes, make_host_mesh
    from repro.runtime import TrainOptions, train

    cfg = get_config(ARCH)
    mesh = make_host_mesh(1, 4)
    dist = DistContext(mesh=mesh, dp_axes=dp_axes(mesh), ep_axis="model",
                       tp_axis="model")
    local_tokens = (TRAIN_BATCH // dist.dp_size) * (TRAIN_SEQ
                                                    // dist.ep_size)
    rcfg = resolve(cfg, local_tokens=local_tokens, ep_size=dist.ep_size,
                   hw=resolve_hw("auto"), dp=dist.dp_size)
    n = rcfg.moe.num_partitions
    print(f"train: {cfg.name} params={cfg.param_count()} mesh "
          f"data={dist.dp_size} x model={dist.ep_size} "
          f"({cfg.moe.num_experts // dist.ep_size} experts per chip), "
          f"batch {TRAIN_BATCH}x{TRAIN_SEQ}, resolved n={n} "
          f"strategy={rcfg.moe.memory_reuse_strategy}", flush=True)
    ds = SyntheticTokens(cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ, seed=seed)
    opts = TrainOptions(lr=1e-3, warmup=1, total_steps=TRAIN_STEPS)

    t0 = time.perf_counter()
    state, hist = train(rcfg, steps=TRAIN_STEPS, batch_source=ds,
                        opts=opts, dist=dist, adaptive=False)
    expert = state["params"]["periods"]["l1"]["moe"]["experts"]["w_up"]
    shard_shapes = sorted({tuple(s.data.shape)
                           for s in expert.addressable_shards})
    del state
    for h in hist:
        print(f"train: step {h['step']} loss={h['loss']:.5f} "
              f"t={h['step_time_s']:.3f}s", flush=True)
    print(f"train: {TRAIN_STEPS} steps in {time.perf_counter() - t0:.1f}s "
          f"(compile included); expert w_up shards {shard_shapes}",
          flush=True)

    cfg1 = dataclasses.replace(rcfg, moe=dataclasses.replace(
        rcfg.moe, num_partitions=1))
    _, hist1 = train(cfg1, steps=1, batch_source=ds, opts=opts, dist=dist,
                     adaptive=False)
    loss_n, loss_1 = hist[0]["loss"], hist1[0]["loss"]
    diff = abs(loss_n - loss_1)
    print(f"train: step-0 loss n={n}: {loss_n:.6f}  n=1: {loss_1:.6f}  "
          f"|diff|={diff:.6f} (tol {LOSS_TOL})", flush=True)
    for d in jax.devices():
        print(f"train: device {d.id} peak_bytes_in_use={peak_bytes(d)}",
              flush=True)

    losses = [h["loss"] for h in hist + hist1]
    if not all(l == l and abs(l) < float("inf") for l in losses):
        fail(f"non-finite loss: {losses}")
    if diff > LOSS_TOL:
        fail(f"step-0 loss at n={n} and n=1 differ by {diff} > {LOSS_TOL}")


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Run the main path of this repo on the TPU and "
                    "check its outputs; the last stdout line is JSON.")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serve moe-gpt3-s on one chip; 4: only the "
                         "expert-parallel training step on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"the repro sources are not next to this script ({src})")
    sys.path.insert(0, src)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: jax sees {len(devices)} {dev.platform} "
             f"device(s)")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPU chips; "
             f"{len(devices)} attached")

    from repro.launch.compile_cache import enable_compile_cache

    print(f"device_kind={dev.device_kind} count={len(devices)} "
          f"jax={jax.__version__}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 1:
        serve_phase(args.seed)
    else:
        train_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
