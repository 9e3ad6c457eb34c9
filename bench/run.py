#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's file (``bench/workloads/<cell>.json``) names its configuration,
its chips, its driver (``bench/drivers/<kind>.py``) and every traffic
parameter. The driver makes weights and inputs from ``--seed``, warms up
the cell's own shapes (set-up), measures for ``--seconds``, and checks
what the timed path produced against ``bench/reference.py``.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
its per-layer metrics (``bench/metrics/<name>.py``), device busy time and
a breakdown. The last stdout line is one JSON object; the numbers that
decided ``correct`` are printed beside their limits as the last lines of
stderr and under the result's last key, ``checks``.

Without a TPU, or with fewer chips than the cell needs, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, "bench_out")
# the TPU runtime logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def fail(msg: str, code: int = 2) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


class CompileCounter:
    """Counts executables compiled or loaded from the persistent cache,
    through JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.programs += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class Context:
    """What a driver is given, and what it hands back."""

    def __init__(self, cell, conf, seed, seconds, trace):
        self.cell, self.conf = cell, conf
        self.model = conf["model"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_start = T_START
        self.compiles = CompileCounter()
        self.trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
        # filled by the driver
        self.window = None          # (t0, t1) on perf_counter
        self.e2e = {}               # end-to-end metric name -> value
        self.layer = {}             # facts the per-layer readers use
        self.checks = {}            # name -> (value, limit)
        self.attempted = 0
        self.failed = 0
        self.window_programs = 0
        self.devices = []
        self.memory_peak_bytes = 0

    # -- profiler around the window -------------------------------------
    def start_window(self):
        import jax

        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            # no Python call tracing: it slows the host loop the window
            # times, and the harness's own spans mark what the host does
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._programs0 = self.compiles.programs
        t0 = time.perf_counter()
        self.window = (t0, None)
        self._annotation = jax.profiler.TraceAnnotation("bench.window")
        self._annotation.__enter__()
        return t0

    def end_window(self, t1=None, stop_trace=True):
        """Close the window. A driver whose requests are still in flight
        passes ``stop_trace=False`` and calls ``stop_trace()`` once they
        are served: writing the trace stalls the host for seconds."""
        t1 = time.perf_counter() if t1 is None else t1
        self._annotation.__exit__(None, None, None)
        self.window = (self.window[0], t1)
        self.window_programs = self.compiles.programs - self._programs0
        if stop_trace:
            self.stop_trace()

    def stop_trace(self):
        import jax

        if self.trace:
            jax.profiler.stop_trace()

    def read_memory_peak(self):
        """Peak HBM of the fullest chip, program temporaries included
        (``peak_bytes_in_use`` alone leaves them out)."""
        from bench.util import peak_bytes

        self.memory_peak_bytes = max(peak_bytes(d) for d in self.devices)
        return self.memory_peak_bytes


def per_layer(ctx: Context, reduced) -> dict:
    from bench import spec

    out = {}
    for m in spec.per_layer_for(ctx.cell["name"]):
        value = spec.metric_module(m["name"]).read(ctx, reduced)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result(ctx: Context, correct: bool) -> dict:
    import jax

    from bench import spec

    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    out = {"correct": bool(correct), "attempted": int(ctx.attempted),
           "failed": int(ctx.failed + ctx.window_programs)}
    if ctx.trace:
        from bench import trace_reduce

        red = trace_reduce.reduce_dir(ctx.trace_dir)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["metrics"] = per_layer(ctx, red)
        out["device"] = device
        out["breakdown"] = {"device_ops": red["top_ops"][:10],
                            "idle_gaps": red["idle_gaps"][:10]}
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"]
                 for m in spec.end_to_end_for(ctx.cell["name"])}
        out["metrics"] = {k: {"value": ctx.e2e[k], "unit": u}
                          for k, u in units.items()}
        out["device"] = device
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in ctx.checks.items()}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"the program's sources are not in this checkout ({src})")
    sys.path[:0] = [ROOT, src]

    from bench import spec

    cell = spec.workload(args.workload)
    conf = spec.config(cell["config"])

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: jax sees {len(devices)} {devices[0].platform} "
             f"device(s)")
    if len(devices) < cell["chips"]:
        fail(f"{args.workload} needs {cell['chips']} chips; "
             f"{len(devices)} attached")

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    ctx = Context(cell, conf, args.seed, args.seconds, bool(args.trace))
    driver = importlib.import_module(f"bench.drivers.{cell['driver']}")
    correct = driver.run(ctx)
    out = result(ctx, correct)
    print(f"programs compiled or loaded inside the window: "
          f"{ctx.window_programs} (counted as failed)", file=sys.stderr)
    for k, (v, lim) in ctx.checks.items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
