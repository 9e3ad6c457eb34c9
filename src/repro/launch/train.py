"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --shape train_4k --steps 100 [--mesh-data 16 --mesh-model 16]

By default it runs the reduced (tiny, same-family) config, which is what
CPU runs use; ``--full`` builds the published widths and refuses to
start without an accelerator. With ``--mesh-data``/``--mesh-model`` the
train state is created sharded over that mesh (experts over ``model``).
The persistent compilation cache follows ``launch.compile_cache``.
Fault tolerance: checkpoint/restart + seekable data + heartbeats
(DESIGN §8).
"""
from __future__ import annotations

import argparse
import dataclasses
import logging

import jax

from repro.ckpt import Checkpointer
from repro.configs import SHAPES, get_config
from repro.core import resolve, resolve_hw
from repro.data import SyntheticTokens
from repro.distributed.context import DistContext
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import dp_axes, make_host_mesh
from repro.runtime import AdaptiveOptions, TrainOptions, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="moe-gpt3-s")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--use-kernel", action="store_true",
                    help="run expert FFNs through the fused Pallas "
                         "grouped-FFN kernel (pure-jax fallback off-TPU)")
    ap.add_argument("--adaptive", action="store_true",
                    help="online (n, strategy) controller instead of a "
                         "one-shot offline resolve")
    ap.add_argument("--retune-every", type=int, default=0,
                    help="with --adaptive: also re-resolve every K steps "
                         "(0 = only on batch-shape change)")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="serve live Prometheus /metrics (controller "
                         "retunes + training heartbeat gauges) on this "
                         "port (0 = free port; -1 = disabled)")
    ap.add_argument("--trace-out", default="",
                    help="write resolver retune spans as a "
                         "Perfetto-loadable trace here at exit "
                         "('' = off)")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    if not args.reduced and jax.default_backend() == "cpu":
        raise SystemExit("--full builds the published widths and needs an "
                         "accelerator; jax found only CPU devices")
    print(f"compile cache: {enable_compile_cache()}")
    hw = resolve_hw("auto")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = SHAPES[args.shape]

    mesh = None
    dist = None
    if args.mesh_data * args.mesh_model > 1:
        mesh = make_host_mesh(args.mesh_data, args.mesh_model)
        dist = DistContext(mesh=mesh, dp_axes=dp_axes(mesh),
                           ep_axis="model", tp_axis="model")
    # one telemetry surface for training: resolver retune spans/counters
    # land in the same repro.obs registry the serving engine uses
    from repro.obs import MetricsServer, Recorder, Tracer
    obs = Recorder(tracer=Tracer()) if args.trace_out else Recorder()
    server = None
    if args.metrics_port >= 0:
        server = MetricsServer(obs.registry,
                               port=args.metrics_port).start()
        print(f"metrics: {server.url}/metrics")

    adaptive = False
    if cfg.moe is not None:
        if args.adaptive:
            # leave the adaptive placeholders in place: train() grows an
            # AdaptiveController that resolves (n, strategy) online
            cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, num_partitions=0,
                memory_reuse_strategy="adaptive"))
            adaptive = AdaptiveOptions(retune_every=args.retune_every,
                                       ep_size=max(1, args.mesh_model),
                                       dp=max(1, args.mesh_data),
                                       hw=hw, obs=obs)
            print("MPipeMoE: online adaptive (n, strategy) "
                  f"(retune_every={args.retune_every})")
        else:
            cfg = resolve(cfg, local_tokens=args.batch * args.seq,
                          ep_size=args.mesh_model, hw=hw)
            print(f"MPipeMoE: n={cfg.moe.num_partitions} "
                  f"strategy={cfg.moe.memory_reuse_strategy}")

    ds = SyntheticTokens(cfg, batch=args.batch, seq=args.seq, seed=0)
    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    opts = TrainOptions(lr=args.lr, warmup=min(20, args.steps // 5),
                        total_steps=args.steps,
                        compress_grads=args.compress_grads,
                        use_kernel=args.use_kernel)

    g_step = obs.registry.gauge("repro_train_step", "last training step")
    g_loss = obs.registry.gauge("repro_train_loss", "last training loss")
    h_step = obs.registry.histogram("repro_train_step_seconds",
                                    "training step wall time")

    def heartbeat(step, metrics):
        g_step.set(step)
        g_loss.set(float(metrics["loss"]))
        h_step.observe(float(metrics["step_time_s"]))
        if step % 10 == 0:
            extra = (f" n={metrics['n']} strat={metrics['strategy']}"
                     if "n" in metrics else "")
            print(f"step {step:5d} loss={metrics['loss']:.4f} "
                  f"t={metrics['step_time_s']*1e3:.0f}ms{extra}",
                  flush=True)

    try:
        state, hist = train(cfg, steps=args.steps, batch_source=ds,
                            opts=opts, dist=dist, checkpointer=ck,
                            ckpt_every=args.ckpt_every,
                            heartbeat=heartbeat, adaptive=adaptive)
    finally:
        if server is not None:
            server.stop()
        if args.trace_out:
            obs.tracer.write(args.trace_out)
            print(f"trace: {args.trace_out}")
    print(f"final loss {hist[-1]['loss']:.4f} at step {hist[-1]['step']}")
    if "n" in hist[-1]:                   # controller engaged (MoE arch)
        print(f"adaptive: n={hist[-1]['n']} "
              f"strategy={hist[-1]['strategy']}")


if __name__ == "__main__":
    main()
