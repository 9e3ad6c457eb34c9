"""repro.obs — engine-wide telemetry: metrics registry, span tracer,
Perfetto export, live /metrics exporter. Stdlib only; nothing in this
package imports jax.

Module map:

- ``metrics``  — ``Registry`` of counters/gauges/ring-buffer
  histograms; shared nearest-rank ``quantile``; Prometheus ``render()``
  and JSON ``snapshot()``.
- ``trace``    — ``Tracer`` (Chrome trace-event spans/instants,
  Perfetto-loadable export) and the no-op ``NullTracer``.
- ``export``   — ``MetricsServer``: background HTTP thread serving
  ``/metrics`` + ``/healthz``.
- ``scopes``   — the process-wide scope table: for each compiled
  program, the innermost ``repro`` device scope (``sample``,
  ``moe.expert_ffn``, ``optimizer``, ...) of every HLO instruction, so
  a profiler trace's device ops can be put down to a layer.

The unit the rest of the codebase passes around is :class:`Recorder`:
a registry (always real, so ``Engine.stats()`` and ``/metrics`` read
one source of truth) plus a tracer (``NullTracer`` unless span
recording was requested). Spans of either tracer reach the JAX
profiler while a profiler session is active, and every Recorder
installs the process's one GC hook (``host.gc`` spans,
``repro_gc_pause_seconds``). "Telemetry disabled" — the default — means
the null tracer and no exporter thread; the registry itself is plain
counter arithmetic on the host and is never consulted inside jitted
code, so the disabled path adds no jit traces and no measurable
per-token cost (pinned by the conformance compile-count matrix and
``benchmarks/trajectory/pr7_obs_overhead.json``).
"""
from __future__ import annotations

from typing import Optional

from .export import MetricsServer
from .metrics import Counter, Gauge, Histogram, Registry, quantile
from .trace import (NullTracer, PID_ENGINE, PID_INGRESS, PID_REQUESTS,
                    PID_RESOLVER, Tracer, install_gc_hook)

__all__ = ["Counter", "Gauge", "Histogram", "MetricsServer",
           "NullTracer", "PID_ENGINE", "PID_INGRESS", "PID_REQUESTS",
           "PID_RESOLVER", "Recorder", "Registry", "Tracer", "quantile"]


class Recorder:
    """Registry + tracer bundle threaded through engine, scheduler,
    caches, resolver and train controller. Construct with
    ``Recorder(tracer=Tracer())`` to record spans; the default is
    metrics-only with the no-op tracer."""

    def __init__(self, registry: Optional[Registry] = None,
                 tracer: Optional[NullTracer] = None):
        self.registry = registry if registry is not None else Registry()
        self.tracer = tracer if tracer is not None else NullTracer()
        self._m_gc = self.registry.histogram(
            "repro_gc_pause_seconds", "garbage-collection pauses",
            ["generation"])
        install_gc_hook(self)

    def gc_pause(self, generation: int, t0: float, t1: float) -> None:
        """One collection, ``t0``..``t1`` on ``time.perf_counter``."""
        self._m_gc.labels(generation=generation).observe(t1 - t0)
        if self.tracer.enabled:
            self.tracer._emit({"ph": "X", "name": "host.gc",
                               "pid": PID_ENGINE, "tid": 1,
                               "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6,
                               "args": {"generation": generation}})

    @property
    def tracing(self) -> bool:
        return self.tracer.enabled
