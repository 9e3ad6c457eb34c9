"""Serving launcher.

Two paths share one CLI:

* ``--engine``: the continuous-batching engine (``repro.serve``) replays
  a Poisson arrival trace of mixed-length requests with the state cache
  the architecture needs (paged KV for attention — full K/V or the MLA
  latent —, slot-indexed constant state for recurrent mamba/xLSTM
  mixers, a composite of both for jamba; decided by
  ``models/api.serving_support`` and printed at startup), per-bucket
  adaptive (n, strategy) prefill, preemptive scheduling under capacity
  pressure (``--preempt``, ``--num-pages``) and temperature /
  top-k / top-p sampling (``--temperature`` …) —

      PYTHONPATH=src python -m repro.launch.serve --engine --requests 16

  Unservable configs (encoder-decoder, vision/audio frontends, m-rope)
  exit with the stable reason string from ``serving_support``.

  ``--devices N`` serves over an N-device dp x ep mesh (EP-sharded
  prefill, replicated psum decode — see docs/distributed.md); on CPU
  the launcher re-execs itself with virtual host devices when fewer
  than N are attached. ``--attn-kernel`` selects the paged-decode
  attention path (fused Pallas page walk vs the gather baseline —
  bit-identical tokens, see docs/serving.md). ``--prefix-cache on``
  shares published KV pages across requests with the same prompt
  prefix (refcounted trie + copy-on-write; also bit-identical — see
  docs/serving.md).

* default: the legacy fixed-batch loop (kept as the golden reference the
  engine is tested against), now with per-request ``max_new_tokens`` and
  EOS early exit — stopping is masked host-side so jitted shapes stay
  static.

``--hw`` names the :class:`HardwareSpec` the MPipeMoE resolver plans
for; ``auto`` detects it from the first device's ``device_kind``.
``--full`` serves the config at its published widths and refuses to
start without an accelerator; the default is the reduced same-family
config, which CPU runs use. The persistent compilation cache follows
``launch.compile_cache``.

Telemetry (engine path only; see docs/observability.md):
``--metrics-port`` serves live Prometheus ``/metrics`` + ``/healthz``
for the duration of the run, and ``--trace-out PATH`` records
engine/request/resolver spans and writes a Perfetto-loadable Chrome
trace-event JSON at shutdown.

``--http-port`` (engine path only) swaps the Poisson replay for the
asyncio HTTP/SSE ingress tier (``repro.serve.ingress``): real clients
``POST /generate`` and stream tokens back per decode step; client
disconnects cancel their request; ``--shed-policy`` /
``--admission-queue`` configure overload shedding. Serves until
interrupted —

    PYTHONPATH=src python -m repro.launch.serve --engine \\
        --http-port 8080 --shed-policy degrade --admission-queue 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import HW_SPECS, resolve, resolve_hw
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import get_model


def legacy_loop(args, cfg, hw):
    """Fixed-batch request loop over a dense [batch, max_len] cache."""
    if cfg.moe is not None:
        # concrete (n, strategy) for the prefill token count (decode
        # itself always runs n=1 — see pipeline_moe._resolve_partitions)
        cfg = resolve(cfg, local_tokens=args.batch * args.prompt_len,
                      ep_size=1, hw=hw)
        print(f"MPipeMoE prefill: n={cfg.moe.num_partitions} "
              f"strategy={cfg.moe.memory_reuse_strategy}")
    model = get_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(cfg, key)
    max_len = args.prompt_len + args.gen
    step = jax.jit(lambda p, c, t: model.decode_step(p, c, t, cfg))

    rng = np.random.Generator(np.random.Philox(key=123))
    for req in range(args.requests):
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(req), (args.batch, args.prompt_len), 0,
            cfg.vocab_size)}
        if cfg.frontend == "audio_stub":
            e = cfg.encoder
            batch["frames"] = 0.02 * jax.random.normal(
                key, (args.batch, e.context_len, e.d_model))
        # per-sequence generation budget (<= --gen); EOS stops earlier
        max_new = rng.integers(max(1, args.gen // 2), args.gen + 1,
                               size=args.batch)
        t0 = time.perf_counter()
        logits, cache = model.prefill(params, batch, cfg, max_len=max_len,
                                      dtype=jnp.float32)
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        done = np.zeros(args.batch, bool)
        n_gen = np.ones(args.batch, np.int64)
        if args.eos >= 0:
            done |= np.asarray(tok[:, 0]) == args.eos
        done |= n_gen >= max_new
        steps = 1
        while not done.all() and steps < args.gen:
            logits, cache = step(params, cache, tok)
            nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            # masked stop: finished sequences keep re-feeding their last
            # token, so the jitted step shape never changes
            tok = jnp.where(jnp.asarray(done)[:, None], tok, nxt)
            n_gen += ~done
            if args.eos >= 0:
                done |= np.asarray(tok[:, 0]) == args.eos
            done |= n_gen >= max_new
            steps += 1
        dt = time.perf_counter() - t0
        total = int(n_gen.sum())
        print(f"request-batch {req}: {args.batch} seqs x "
              f"({args.prompt_len} prompt + <= {args.gen} gen) "
              f"{total} tokens in {dt*1e3:.0f}ms -> {total/dt:.1f} tok/s "
              f"(stopped early: {int(done.sum())})")


def engine_setup(args, cfg, hw):
    """Shared by the replay and ingress engine paths (and
    ``chip_smoke.py``): servability check, recorder, EngineOptions from
    the CLI's arguments."""
    from repro.models.api import serving_support
    from repro.obs import Recorder, Tracer
    from repro.serve import EngineOptions

    kind, why = serving_support(cfg)
    if kind is None:
        raise SystemExit(f"{cfg.name} is not servable: {why}")
    print(f"state cache: {kind}")
    obs = Recorder(tracer=Tracer()) if args.trace_out else Recorder()
    opts = EngineOptions(page_size=args.page_size, max_slots=args.batch,
                         max_seq_len=args.prompt_len + args.gen,
                         chunk=args.chunk, hw=hw, preempt=args.preempt,
                         num_pages=args.num_pages, measure=args.measure,
                         devices=args.devices,
                         kv_sharding=args.kv_sharding,
                         attn_kernel=args.attn_kernel,
                         prefix_cache=args.prefix_cache, obs=obs)
    return obs, opts


def ingress_loop(args, cfg, hw):
    """Serve real HTTP/SSE clients until interrupted (no trace replay)."""
    from repro.obs import MetricsServer
    from repro.serve import Engine, IngressOptions, IngressServer

    obs, opts = engine_setup(args, cfg, hw)
    engine = Engine(cfg, None, options=opts)
    engine.warmup()
    server = None
    if args.metrics_port >= 0:
        server = MetricsServer(obs.registry, port=args.metrics_port,
                               refresh=engine._refresh_gauges).start()
        print(f"metrics: {server.url}/metrics")
    ingress = IngressServer(engine, options=IngressOptions(
        port=args.http_port, shed_policy=args.shed_policy,
        admission_queue=args.admission_queue)).start()
    print(f"ingress: {ingress.url} — POST /generate streams SSE "
          f"(shed={args.shed_policy}, "
          f"admission_queue={args.admission_queue}); ^C to stop")
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        print("draining...")
    finally:
        ingress.stop()
        if server is not None:
            server.stop()
    if args.trace_out:
        obs.tracer.write(args.trace_out)
        print(f"trace: {args.trace_out} "
              f"(load in https://ui.perfetto.dev)")
    s = engine.stats()
    print(f"served {s['requests_done']} requests "
          f"({s['requests_cancelled']} cancelled: "
          f"{s['cancelled_by_stage']}), "
          f"{s['tokens_generated']} tokens in {s['engine_steps']} steps")


def engine_loop(args, cfg, hw):
    from repro.obs import MetricsServer
    from repro.serve import SamplingParams, run_poisson

    obs, opts = engine_setup(args, cfg, hw)
    sampling = None
    if args.temperature > 0:
        sampling = SamplingParams(temperature=args.temperature,
                                  top_k=args.top_k, top_p=args.top_p,
                                  seed=args.sample_seed)

    server = None

    def on_engine(engine):
        # live /metrics: scrape-time refresh through the engine's gauge
        # updater so mid-run curls match what stats() would report
        nonlocal server
        if args.metrics_port >= 0:
            server = MetricsServer(obs.registry, port=args.metrics_port,
                                   refresh=engine._refresh_gauges).start()
            print(f"metrics: {server.url}/metrics")

    try:
        engine, dt = run_poisson(
            cfg, opts, requests=args.requests, rate=args.rate,
            prompt_max=args.prompt_len, gen_max=args.gen, seed=args.seed,
            eos_id=args.eos if args.eos >= 0 else None,
            time_scale=args.time_scale, sampling=sampling,
            on_engine=on_engine)
    finally:
        if server is not None:
            server.stop()
    if args.trace_out:
        obs.tracer.write(args.trace_out)
        print(f"trace: {args.trace_out} "
              f"(load in https://ui.perfetto.dev)")
    s = engine.stats()
    if s["devices"] > 1:
        kvs = (f"DP-sharded KV x{s['kv_shards']}"
               if s["kv_shards"] > 1 else "replicated KV")
        print(f"mesh: {s['devices']} devices = dp {s['dp_size']} x "
              f"ep {s['ep_size']} (EP-sharded prefill, {kvs}; "
              f"{s['per_device_cache_bytes']/2**20:.2f}MiB KV pool "
              f"per device)")
    print(f"engine: {s['requests_done']} requests, "
          f"{s['tokens_generated']} tokens in {dt:.2f}s "
          f"({s['requests_done']/dt:.2f} req/s, "
          f"{s['tokens_generated']/dt:.1f} tok/s)")
    print(f"latency p50={s['p50_latency_s']*1e3:.0f}ms "
          f"p99={s['p99_latency_s']*1e3:.0f}ms | "
          f"TTFT p50={s['p50_ttft_s']*1e3:.0f}ms | "
          f"ITL p50={s['p50_itl_s']*1e3:.1f}ms "
          f"p99={s['p99_itl_s']*1e3:.1f}ms")
    print(f"KV pool {s['cache_bytes']/2**20:.2f}MiB, "
          f"peak used {s['peak_kv_used_bytes']/2**20:.2f}MiB | "
          f"{s['engine_steps']} steps, "
          f"{s['prefill_compiles']} prefill compiles")
    if s["preempt_recompute"] or s["preempt_offload"]:
        print(f"preemptions: {s['preempt_recompute']} recompute, "
              f"{s['preempt_offload']} offload, {s['resumes']} resumes, "
              f"swap {s['swap_out_bytes']/2**20:.2f}MiB out / "
              f"{s['swap_in_bytes']/2**20:.2f}MiB in")
    if s.get("prefix_cache") == "on":
        print(f"prefix cache: {s['prefix_hits']} hits / "
              f"{s['prefix_misses']} misses "
              f"({100 * s['prefix_hit_rate']:.0f}%), "
              f"{s['prefix_hit_tokens']} prompt tokens skipped, "
              f"{s['prefix_cow_copies']} CoW copies, "
              f"{s['prefix_evicted_pages']} pages evicted")
    for bucket, (n, strat) in sorted(engine.adaptive.resolutions.items()):
        print(f"  bucket {bucket:4d} -> n={n} strategy={strat}")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--full", action="store_true",
                    help="build the config at its published widths "
                         "(needs an accelerator); default is the reduced "
                         "same-family config")
    ap.add_argument("--batch", type=int, default=4,
                    help="legacy: batch size; engine: decode slots")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--hw", default="auto",
                    choices=["auto"] + sorted(HW_SPECS),
                    help="hardware spec for the MPipeMoE resolver "
                         "(auto = detect from the jax backend)")
    ap.add_argument("--eos", type=int, default=-1,
                    help="EOS token id for early exit (-1 = disabled)")
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine over a Poisson trace")
    ap.add_argument("--chunk", type=int, default=32,
                    help="engine: prefill chunk size (tokens)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="engine: KV page size (tokens)")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="engine: Poisson arrival rate (req/s)")
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="engine: arrival time multiplier (0 = all at once)")
    ap.add_argument("--preempt", default="auto",
                    choices=["auto", "recompute", "offload", "never"],
                    help="engine: overload policy — on-demand pages with "
                         "preemption (auto picks offload vs recompute per "
                         "victim by cost), or 'never' for the conservative "
                         "full-budget admission-blocking baseline")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="engine: KV pool size in pages (0 = worst case; "
                         "smaller values exercise preemption)")
    ap.add_argument("--measure", default="auto",
                    choices=["auto", "wallclock", "simulate"],
                    help="engine: bucket (n, strategy) resolution measure "
                         "(auto = wallclock on non-CPU backends)")
    ap.add_argument("--devices", type=int, default=0,
                    help="engine: serve over an N-device dp x ep mesh "
                         "(0 = single device); CPU re-execs with virtual "
                         "host devices when fewer are attached")
    ap.add_argument("--kv-sharding", default="replicated",
                    choices=["replicated", "dp"],
                    help="engine: paged-KV pool layout over the mesh — "
                         "'replicated' (every device holds the whole "
                         "pool) or 'dp' (pages sharded over the data "
                         "axis: per-device KV drops dp-fold, per-shard "
                         "free lists, sticky least-loaded placement); "
                         "'dp' needs --devices > 1")
    ap.add_argument("--attn-kernel", default="auto",
                    choices=["auto", "pallas", "gather"],
                    help="engine: paged-decode attention path — 'pallas' "
                         "walks the page table inside a fused kernel "
                         "(shard-local page reads under --kv-sharding "
                         "dp), 'gather' materializes pages first (the "
                         "exactness baseline; both emit bit-identical "
                         "tokens), 'auto' picks pallas on TPU")
    ap.add_argument("--prefix-cache", default="off",
                    choices=["on", "off"],
                    help="engine: cross-request prefix caching — 'on' "
                         "publishes full KV pages of finished prefixes "
                         "into a per-shard refcounted trie so later "
                         "requests sharing a prompt prefix skip its "
                         "prefill (copy-on-write on divergence, LRU "
                         "eviction under pressure; bit-identical "
                         "tokens, see docs/serving.md); non-paged "
                         "caches degrade to 'off'")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="engine: sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="engine: top-k filter (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="engine: nucleus (top-p) filter (1 = disabled)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="engine: per-request sampling seed")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="engine: serve live Prometheus /metrics (and "
                         "/healthz) on this port for the duration of "
                         "the run (0 = pick a free port, printed at "
                         "startup; -1 = disabled)")
    ap.add_argument("--trace-out", default="",
                    help="engine: record spans and write a "
                         "Perfetto-loadable Chrome trace-event JSON "
                         "here at shutdown ('' = tracing off)")
    ap.add_argument("--http-port", type=int, default=-1,
                    help="engine: serve the HTTP/SSE ingress tier on "
                         "this port instead of replaying a Poisson "
                         "trace — POST /generate streams one SSE event "
                         "per generated token, client disconnects "
                         "cancel their request (0 = pick a free port, "
                         "printed at startup; -1 = disabled)")
    ap.add_argument("--shed-policy", default="reject",
                    choices=["reject", "degrade"],
                    help="ingress: behaviour past --admission-queue — "
                         "'reject' answers 429 with Retry-After, "
                         "'degrade' admits with max_new_tokens clamped")
    ap.add_argument("--admission-queue", type=int, default=8,
                    help="ingress: bound on requests accepted but not "
                         "yet finished before load shedding kicks in")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def config_for(args):
    """The served config: published widths with ``--full``, else the
    reduced same-family config."""
    cfg = get_config(args.arch)
    return cfg if args.full else cfg.reduced()


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)

    if args.devices > 1:
        if not args.engine:
            ap.error("--devices requires --engine (the legacy loop is "
                     "single-device)")
        from repro.compat import ensure_host_device_count
        ensure_host_device_count(args.devices)
    elif args.kv_sharding == "dp":
        ap.error("--kv-sharding dp shards the KV pools over the mesh "
                 "data axis; it requires --devices > 1")
    if (args.metrics_port >= 0 or args.trace_out) and not args.engine:
        ap.error("--metrics-port / --trace-out instrument the "
                 "continuous-batching engine; add --engine")
    if args.attn_kernel != "auto" and not args.engine:
        ap.error("--attn-kernel selects the engine's paged-decode "
                 "attention path; add --engine")
    if args.prefix_cache != "off" and not args.engine:
        ap.error("--prefix-cache enables the engine's cross-request "
                 "prefix cache; add --engine")
    if args.http_port >= 0 and not args.engine:
        ap.error("--http-port serves the continuous-batching engine "
                 "over HTTP/SSE; add --engine")
    if args.http_port < 0 and (args.shed_policy != "reject"
                               or args.admission_queue != 8):
        ap.error("--shed-policy / --admission-queue configure the "
                 "HTTP ingress tier; add --http-port")
    if args.full and jax.default_backend() == "cpu":
        ap.error("--full builds the published widths and needs an "
                 "accelerator; jax found only CPU devices")
    print(f"compile cache: {enable_compile_cache()}")
    hw = resolve_hw(args.hw)
    print(f"hw spec: {hw.name}")
    cfg = config_for(args)
    if args.engine and args.http_port >= 0:
        ingress_loop(args, cfg, hw)
    elif args.engine:
        engine_loop(args, cfg, hw)
    else:
        legacy_loop(args, cfg, hw)


if __name__ == "__main__":
    main()
