"""Read a traced run's device time by the program's own names.

Two things the program puts on the profiler's clock are read here:

- its device scopes (``jax.named_scope``: ``sample``, ``moe.expert_ffn``,
  ``optimizer``, ...). A TPU trace names a device op by its HLO text
  alone, so each op is looked up in the program's scope table
  (``repro.obs.scopes``: module name -> instruction name -> innermost
  scope), through the module that the trace's ``XLA Modules`` line shows
  running at the op's middle;
- its host spans (``engine.*`` phases of ``Engine.step`` and ``host.gc``
  pauses): each device-idle gap of the window is put down to the
  innermost such span around its middle.

The xplane is loaded once per run (``summary``), while the trace is
still on disk. Readers return None, never 0, where the program publishes
no scope table (a program older than the table), where programs the
table lacks hold more than ``UNKNOWN_LIMIT`` of the busy time, or where
no program span lies in the window, so that a lost table or span cannot
read as a gain. What each summary found is printed on stderr.
"""
from __future__ import annotations

import bisect
import os
import sys
from typing import Dict, List, Optional, Tuple

from bench import trace_reduce as tr

SPAN_PREFIXES = ("engine.", "host.gc")
A2A_PREFIX = "moe.a2a_"
UNKNOWN_LIMIT = 0.01          # share of busy time in programs with no
                              # table entry above which scopes read None

_CACHE: Dict[Tuple[str, float], dict] = {}


def _table() -> Optional[dict]:
    try:
        from repro.obs import scopes
    except ImportError:
        return None
    return scopes.table()


def _innermost(spans, starts, longest, t) -> Optional[str]:
    """Name of the latest-starting span that holds ``t``: with spans
    that nest, the innermost. ``spans`` sorted by start; none lasts
    more than ``longest``."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0 and t - spans[i][0] <= longest:
        if spans[i][1] >= t:
            return spans[i][2]
        i -= 1
    return None


def summarize(devices: Dict[str, list], modules: Dict[str, list],
              host_spans: List[Tuple[float, float, str]], w0: float,
              w1: float, table: Optional[dict]) -> dict:
    """``devices``: plane -> [(start_ns, end_ns, HLO text)]; ``modules``:
    plane -> [(start_ns, end_ns, program name)]; ``host_spans``: program
    spans [(start_ns, end_ns, name)]; the window ``[w0, w1]``; the scope
    table (None: the program has none). Seconds are summed over
    devices."""
    spans = sorted(s for s in host_spans if s[1] > w0 and s[0] < w1)
    starts = [s[0] for s in spans]
    longest = max((b - a for a, b, _ in spans), default=0)
    out = {"window_s": (w1 - w0) * 1e-9, "devices": len(devices),
           "busy_s": 0.0, "scope_s": {}, "unscoped_s": 0.0,
           "unscoped_ops": {},
           "unknown_s": {}, "a2a_exposed_s": {}, "gap_s": {},
           "table": table is not None, "spans": len(spans)}
    for dev, evs in sorted(devices.items()):
        mods = sorted(modules.get(dev, []))
        mstarts = [m[0] for m in mods]
        ops, a2a, rest = [], [], []
        for a, b, text in evs:
            kind = tr.kind_of(text)
            if b <= w0 or a >= w1 or kind == "container":
                continue
            a, b = max(a, w0), min(b, w1)
            module = tr._module_at(mods, mstarts, (a + b) / 2)
            scope = (table or {}).get(module, {}).get(tr.parse_op(text)[0])
            ops.append((a, b, text, module, scope))
            (a2a if kind == "collective" and scope
             and scope.startswith(A2A_PREFIX) else rest).append((a, b))
        busy = tr.union(a2a + rest)
        out["busy_s"] += tr.length(busy) * 1e-9
        for a, b, text, module, scope in ops:
            dt = (b - a) * 1e-9
            if table is not None and module not in table:
                out["unknown_s"][module] = out["unknown_s"].get(module,
                                                                0.0) + dt
            elif scope is None:
                out["unscoped_s"] += dt
                key = f"{module} {tr.label(text)}"
                out["unscoped_ops"][key] = out["unscoped_ops"].get(key,
                                                                   0.0) + dt
            else:
                out["scope_s"][scope] = out["scope_s"].get(scope, 0.0) + dt
        exposed = tr.subtract(tr.union(a2a), tr.union(rest))
        out["a2a_exposed_s"][dev] = tr.length(exposed) * 1e-9
        for a, b in tr.gaps(busy, w0, w1):
            who = _innermost(spans, starts, longest, (a + b) / 2)
            if who is not None:
                gap = (b - a) * 1e-9
                out["gap_s"][who] = out["gap_s"].get(who, 0.0) + gap
    return out


def read_xplane(path: str, table: Optional[dict]) -> dict:
    host, devices, modules = [], {}, {}
    win = None
    for plane in tr.load(path).planes:
        if tr.DEVICE_PLANE.match(plane.name):
            evs, mods = [], []
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    evs = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name) for ev in line.events]
                elif line.name == tr.MODULES_LINE:
                    mods = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                             tr.module_base(ev.name)) for ev in line.events]
            devices[plane.name], modules[plane.name] = evs, mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name.startswith(SPAN_PREFIXES):
                        host.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns, name))
                    elif name == tr.WINDOW_SPAN and win is None:
                        win = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if win is None:
        raise ValueError("the trace holds no bench.window span")
    return summarize(devices, modules, host, win[0], win[1], table)


def summary(ctx) -> dict:
    """This run's summary (loaded once, then cached)."""
    path = tr.find_xplane(ctx.trace_dir)
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = s = read_xplane(path, _table())
        _report(s)
    return _CACHE[key]


def _report(s: dict) -> None:
    busy = s["busy_s"] or 1e-30
    print(f"program_trace: busy {s['busy_s']:.6g} s over {s['devices']} "
          f"device(s); scope table {'found' if s['table'] else 'absent'}",
          file=sys.stderr)
    for k, v in sorted(s["scope_s"].items(), key=lambda kv: -kv[1]):
        print(f"program_trace: scope {k}: {v:.6g} s "
              f"({100 * v / busy:.4g}% of busy)", file=sys.stderr)
    print(f"program_trace: ops with no scope: {s['unscoped_s']:.6g} s "
          f"({100 * s['unscoped_s'] / busy:.4g}% of busy)", file=sys.stderr)
    top = sorted(s["unscoped_ops"].items(), key=lambda kv: -kv[1])[:8]
    for k, v in top:
        print(f"program_trace:   no scope: {k}: {v:.6g} s", file=sys.stderr)
    for k, v in s["unknown_s"].items():
        print(f"program_trace: program {k!r} not in the scope table: "
              f"{v:.6g} s ({100 * v / busy:.4g}% of busy)", file=sys.stderr)
    for k, v in sorted(s["gap_s"].items(), key=lambda kv: -kv[1]):
        print(f"program_trace: device idle in {k}: {v:.6g} s",
              file=sys.stderr)


def _scoped(s: dict) -> bool:
    """The table covers what ran: it exists, it knows the programs that
    hold all but ``UNKNOWN_LIMIT`` of the busy time, and ops were looked
    up in it."""
    looked_up = sum(s["scope_s"].values()) + s["unscoped_s"]
    unknown = sum(s["unknown_s"].values())
    return (s["table"] and looked_up > 0
            and unknown <= UNKNOWN_LIMIT * s["busy_s"])


def scope_share(s: dict, scope: str) -> Optional[float]:
    """Device time of the ops in ``scope``, % of device busy time."""
    if not _scoped(s):
        return None
    return 100.0 * s["scope_s"].get(scope, 0.0) / s["busy_s"]


def a2a_exposed_share(s: dict) -> Optional[float]:
    """Per chip, All-to-All time in ``moe.a2a_*`` during which no other
    op runs, % of the window; the worst chip."""
    if not _scoped(s):
        return None
    return 100.0 * max(s["a2a_exposed_s"].values()) / s["window_s"]


def host_gap_share(s: dict) -> Optional[float]:
    """Device idle time put down to a program span, % of the window
    (averaged over devices)."""
    if not s["spans"] or not s["devices"]:
        return None
    return (100.0 * sum(s["gap_s"].values())
            / (s["window_s"] * s["devices"]))
