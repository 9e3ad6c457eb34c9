"""Span tracer: monotonic-clock spans and instant events exported as
Chrome trace-event JSON (the format Perfetto / chrome://tracing load).

Taxonomy (pinned by tests/test_obs.py's golden-schema test):

* pid 1 "engine"   / tid 1 "steps"    — one ``engine.step`` X span per
  host step, with its phase spans nested inside (``engine.admit``,
  ``engine.kv_grow``, ``engine.inputs``, ``engine.dispatch``,
  ``engine.sync``, ``engine.emit``; args: kind), ``host.gc`` X spans
  (args: generation) for each garbage collection, plus ``jit.trace``
  instants whenever XLA re-traces a jitted body.
* pid 2 "requests" / tid = request id — the request lifecycle:
  ``ADMIT``/``RESUME``/``PREEMPT``/``RETIRE`` instants,
  ``PREFILL`` chunk X spans (args: chunk/bucket/pos) and a ``DECODE``
  B/E pair that opens when the request enters decode and closes at
  preemption or retirement.
* pid 3 "resolver" / tid 1 "retune"   — ``resolver.resolve`` X spans
  (args: tokens/n/strategy) with ``candidate`` instants for each
  measured (n, strategy) timing inside the granularity search.

Every ``span()`` has two sinks. While a JAX profiler session is
active it is also a ``jax.profiler.TraceAnnotation`` (a TraceMe on the
host plane of the same ``.xplane.pb`` as the device ops, so on their
clock); with no session that costs one enabled-check. The Chrome-JSON
event is recorded only by :class:`Tracer`. Instants and ``B``/``E``
pairs are Chrome-only.

Two recorders share the interface: :class:`Tracer` buffers events in
memory and ``export()``s ``{"traceEvents": [...]}``;
:class:`NullTracer` records nothing itself — ``span()`` returns a
shared inert context manager unless a profiler session is active. This
module does not import jax: it uses ``jax.profiler`` once the program
has loaded it. Events emitted inside jitted Python bodies run at trace
time only, so telemetry on/off cannot change compiled HLO (pinned by
the conformance compile-count matrix).

:func:`install_gc_hook` puts a ``host.gc`` span around every garbage
collection of the process, with the same two sinks, and feeds each
live :class:`~repro.obs.Recorder`'s ``repro_gc_pause_seconds``.
"""
from __future__ import annotations

import gc
import json
import sys
import time
import weakref
from typing import Any, Dict, List, Optional

__all__ = ["NullTracer", "Tracer", "PID_ENGINE", "PID_INGRESS",
           "PID_REQUESTS", "PID_RESOLVER", "install_gc_hook"]

PID_ENGINE = 1
PID_REQUESTS = 2
PID_RESOLVER = 3
PID_INGRESS = 4

_PROCESS_NAMES = {PID_ENGINE: "engine", PID_REQUESTS: "requests",
                  PID_RESOLVER: "resolver", PID_INGRESS: "ingress"}


def _now_us() -> float:
    return time.perf_counter() * 1e6


_annotation_cls = None


def _annotation(name: str, args: Optional[Dict[str, Any]]):
    """An un-entered ``jax.profiler.TraceAnnotation`` for ``name`` while
    a profiler session is active; None otherwise (also before the
    program has imported jax)."""
    global _annotation_cls
    if _annotation_cls is None:
        prof = sys.modules.get("jax.profiler")
        if prof is None:
            return None
        _annotation_cls = prof.TraceAnnotation
    if not _annotation_cls.is_enabled():
        return None
    return _annotation_cls(name, **(args or {}))


class _ProfilerSpan:
    """A span on the profiler's clock alone (the disabled tracer's span
    while a profiler session is active)."""

    __slots__ = ("_ann",)

    def __init__(self, ann):
        self._ann = ann

    def __setitem__(self, key: str, value: Any) -> None:
        self._ann.set_metadata(**{key: value})

    def __enter__(self) -> "_ProfilerSpan":
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(None, None, None)


class _Span:
    """Context manager for an X (complete) event. Mutable mapping-ish:
    ``span["key"] = value`` attaches args discovered mid-span (the
    resolver's chosen (n, strategy) is only known at exit)."""

    __slots__ = ("_tracer", "name", "pid", "tid", "args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, pid: int, tid: int,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self.name = name
        self.pid = pid
        self.tid = tid
        self.args = dict(args) if args else {}
        self._t0 = 0.0
        self._ann = None

    def __setitem__(self, key: str, value: Any) -> None:
        self.args[key] = value
        if self._ann is not None:
            self._ann.set_metadata(**{key: value})

    def __enter__(self) -> "_Span":
        self._ann = _annotation(self.name, self.args)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = _now_us()
        return self

    def __exit__(self, *exc) -> None:
        t1 = _now_us()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._tracer._emit({
            "ph": "X", "name": self.name, "pid": self.pid,
            "tid": self.tid, "ts": self._t0,
            "dur": max(0.0, t1 - self._t0),
            **({"args": self.args} if self.args else {})})


class _NullSpan:
    """Inert span: accepts item assignment, does nothing."""

    __slots__ = ()

    def __setitem__(self, key: str, value: Any) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


class NullTracer:
    """Disabled tracer — the default. It records nothing; ``span()``
    reaches the profiler while a session is active. ``enabled`` is
    False so call sites can skip arg construction for instants."""

    enabled = False
    _SPAN = _NullSpan()

    def span(self, name: str, *, pid: int = PID_ENGINE, tid: int = 1,
             args: Optional[Dict[str, Any]] = None):
        ann = _annotation(name, args)
        return self._SPAN if ann is None else _ProfilerSpan(ann)

    def instant(self, name: str, *, pid: int = PID_ENGINE, tid: int = 1,
                args: Optional[Dict[str, Any]] = None) -> None:
        pass

    def begin(self, name: str, *, pid: int = PID_ENGINE, tid: int = 1,
              args: Optional[Dict[str, Any]] = None) -> None:
        pass

    def end(self, name: str, *, pid: int = PID_ENGINE,
            tid: int = 1) -> None:
        pass

    def thread_name(self, pid: int, tid: int, name: str) -> None:
        pass

    def export(self) -> Dict[str, Any]:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        pass


class Tracer(NullTracer):
    """In-memory Chrome trace-event recorder.

    Events carry microsecond ``ts`` from ``time.perf_counter()`` (one
    monotonic clock for the whole process, so spans from different
    pids interleave correctly on the Perfetto timeline).
    """

    enabled = True

    def __init__(self):
        self._events: List[Dict[str, Any]] = []
        self._thread_names: Dict[tuple, str] = {}

    # -- emission --------------------------------------------------------
    def _emit(self, ev: Dict[str, Any]) -> None:
        self._events.append(ev)

    def span(self, name, *, pid=PID_ENGINE, tid=1, args=None) -> _Span:
        return _Span(self, name, pid, tid, args)

    def instant(self, name, *, pid=PID_ENGINE, tid=1, args=None) -> None:
        self._emit({"ph": "i", "name": name, "pid": pid, "tid": tid,
                    "ts": _now_us(), "s": "t",
                    **({"args": dict(args)} if args else {})})

    def begin(self, name, *, pid=PID_ENGINE, tid=1, args=None) -> None:
        self._emit({"ph": "B", "name": name, "pid": pid, "tid": tid,
                    "ts": _now_us(),
                    **({"args": dict(args)} if args else {})})

    def end(self, name, *, pid=PID_ENGINE, tid=1) -> None:
        self._emit({"ph": "E", "name": name, "pid": pid, "tid": tid,
                    "ts": _now_us()})

    def thread_name(self, pid: int, tid: int, name: str) -> None:
        self._thread_names[(pid, tid)] = name

    # -- export ----------------------------------------------------------
    def export(self) -> Dict[str, Any]:
        """Chrome trace-event JSON object. Real events sorted by ts
        (B before E at equal ts so zero-duration pairs stay nested);
        process/thread metadata (ph=M) prepended."""
        meta: List[Dict[str, Any]] = []
        pids = sorted({e["pid"] for e in self._events}
                      | set(_PROCESS_NAMES)
                      | {p for p, _ in self._thread_names})
        for pid in pids:
            meta.append({"ph": "M", "name": "process_name", "pid": pid,
                         "tid": 0, "ts": 0,
                         "args": {"name": _PROCESS_NAMES.get(
                             pid, f"pid{pid}")}})
        for (pid, tid), name in sorted(self._thread_names.items()):
            meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                         "tid": tid, "ts": 0, "args": {"name": name}})
        order = {"B": 0, "X": 0, "i": 1, "E": 2}
        events = sorted(self._events,
                        key=lambda e: (e["ts"], order.get(e["ph"], 1)))
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.export(), f)


class _GcPauses:
    """``gc.callbacks`` hook: a ``host.gc`` span from each collection's
    start to its stop (arg: generation) on the profiler's clock, and in
    the Chrome trace and ``repro_gc_pause_seconds{generation}`` of each
    live recorder."""

    def __init__(self):
        self.recorders = weakref.WeakSet()
        self._t0 = None
        self._ann = None

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._ann = _annotation("host.gc",
                                    {"generation": info["generation"]})
            if self._ann is not None:
                self._ann.__enter__()
            self._t0 = time.perf_counter()
            return
        if self._t0 is None:
            return
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        t0, self._t0 = self._t0, None
        for rec in list(self.recorders):
            rec.gc_pause(info["generation"], t0, t1)


_GC_HOOK: Optional[_GcPauses] = None


def install_gc_hook(recorder) -> None:
    """Install the process's one GC hook (first call) and feed it to
    ``recorder`` (held weakly) — ``recorder.gc_pause(generation, t0,
    t1)`` runs after every collection."""
    global _GC_HOOK
    if _GC_HOOK is None:
        _GC_HOOK = _GcPauses()
        gc.callbacks.append(_GC_HOOK)
    _GC_HOOK.recorders.add(recorder)
