"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

- busy: the union of the intervals in which an operation runs on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), within
  the window the harness's ``bench.window`` host span marks; per device
  and averaged over the devices;
- time by kind of operation: collectives (all-to-all, all-reduce,
  all-gather, reduce-scatter, collective-permute), Pallas custom calls,
  and the rest;
- exposed collective time: per device, the part of its collectives'
  intervals during which no other operation runs on it;
- the operations that took most time, and the longest idle gaps, each
  gap named by the innermost ``bench.*`` host span around its middle.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(r"^(all-to-all|all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute)")
# ops whose event spans the ops they run, which the trace lists too
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals, lo, hi) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """``a`` minus ``b``; both sorted and disjoint."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


def parse_op(text: str) -> Tuple[str, str, str]:
    """An ``XLA Ops`` event is named by its HLO text, ``%name = type
    opcode(operands), attributes``. Returns ``(name, result type without
    layouts, opcode)``."""
    name, _, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else ""
    rtype = rest[:m.start()].strip() if m else rest
    rtype = re.sub(r"\{[^{}]*\}", "", rtype)
    return name.lstrip("%"), rtype, opcode


def label(text: str) -> str:
    """A short row name for the breakdown: ``name type opcode``."""
    name, rtype, opcode = parse_op(text)
    if len(rtype) > 48:
        rtype = rtype[:45] + "..."
    return f"{name} {rtype} {opcode}"


def kind_of(text: str) -> str:
    name, _, opcode = parse_op(text)
    if COLLECTIVE.match(opcode) or COLLECTIVE.match(name):
        return "collective"
    if opcode == "custom-call" and "tpu_custom_call" in text:
        return "custom_call"
    if opcode in CONTAINERS:
        return "container"
    return "other"


def module_base(name: str) -> str:
    """``jit__decode_step(1336...)`` -> ``jit__decode_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_dir(trace_dir: str) -> dict:
    return reduce(load(find_xplane(trace_dir)))


def reduce(pd) -> dict:
    host_spans: List[Tuple[float, float, str]] = []
    devices: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name) for ev in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                             module_base(ev.name)) for ev in line.events]
            devices[plane.name], modules[plane.name] = evs, mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_spans.append((ev.start_ns,
                                           ev.start_ns + ev.duration_ns,
                                           ev.name))
    win = [s for s in host_spans if s[2] == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = win[0][0], win[0][1]
    return reduce_events(devices, host_spans, w0, w1, modules)


def _module_at(mods, starts, t):
    i = bisect.bisect_right(starts, t) - 1
    return mods[i][2] if i >= 0 and t <= mods[i][1] else ""


def reduce_events(devices: Dict[str, list], host_spans, w0, w1,
                  modules=None) -> dict:
    """``devices``: plane name -> [(start_ns, end_ns, HLO text)];
    ``host_spans``: [(start_ns, end_ns, name)]; window ``[w0, w1]``;
    ``modules``: plane name -> [(start_ns, end_ns, program name)]."""
    window = (w1 - w0) * 1e-9
    per_dev = {}
    op_time: Dict[str, float] = {}
    by_kind: Dict[str, float] = {}
    custom_by_module: Dict[str, float] = {}
    gap_time: Dict[str, float] = {}
    inner = sorted((s for s in host_spans if s[2] != WINDOW_SPAN),
                   key=lambda s: s[1] - s[0])
    for dev, evs in sorted(devices.items()):
        mods = sorted((modules or {}).get(dev, []))
        starts = [m[0] for m in mods]
        evs = [(max(a, w0), min(b, w1), n, kind_of(n)) for a, b, n in evs
               if b > w0 and a < w1]
        busy = union([(a, b) for a, b, _, _ in evs])
        coll = union([(a, b) for a, b, _, k in evs if k == "collective"])
        rest = union([(a, b) for a, b, _, k in evs
                      if k not in ("collective", "container")])
        per_dev[dev] = {"busy_s": length(busy) * 1e-9,
                        "collective_s": length(coll) * 1e-9,
                        "exposed_collective_s":
                        length(subtract(coll, rest)) * 1e-9}
        for a, b, n, k in evs:
            if k == "container":
                continue
            dt = (b - a) * 1e-9
            op_time[label(n)] = op_time.get(label(n), 0.0) + dt
            by_kind[k] = by_kind.get(k, 0.0) + dt
            if k == "custom_call":
                mod = _module_at(mods, starts, (a + b) / 2)
                custom_by_module[mod] = custom_by_module.get(mod, 0.0) + dt
        for a, b in gaps(busy, w0, w1):
            mid = (a + b) / 2
            who = next((s[2] for s in inner if s[0] <= mid <= s[1]),
                       "no bench span")
            gap_time[who] = gap_time.get(who, 0.0) + (b - a) * 1e-9
    n = max(1, len(per_dev))
    busy_s = sum(d["busy_s"] for d in per_dev.values()) / n
    return {
        "window_s": window,
        "busy_s": busy_s,
        "devices": per_dev,
        "by_kind_s": {k: v / n for k, v in by_kind.items()},
        "custom_call_s_by_program": {k: v / n for k, v in
                                     custom_by_module.items()},
        "top_ops": sorted(([k, v / n] for k, v in op_time.items()),
                          key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([k, v / n] for k, v in gap_time.items()),
                            key=lambda kv: -kv[1]),
    }
