"""Scope table: which layer of the program each device op belongs to.

The jitted bodies name their layers with ``jax.named_scope`` (the names
in :data:`SCOPES`). A scope reaches the compiled program only as the
``metadata={op_name="..."}`` of its HLO instructions, and a profiler
trace of a TPU names each device op by its instruction text alone. So
the program publishes, per compiled module, the map from instruction
name to the innermost scope around it, and a reader of the trace looks
ops up by the module the trace prints (``jit__decode_step``) and the
instruction's name (``fusion.7``).

The table is process-wide and holds strings only. :func:`register`
parses a compiled program's text (every computation, so the bodies of
``while`` loops and scans are in it); :func:`register_compiled` takes
any object with ``as_text()``, such as a ``jax.stages.Compiled``.

An instruction the compiler made carries no ``op_name``, or a bare one
that is no path of the traced program (``reduce_window_sum``): a weight
cast hoisted out of the layer loop, a prefetch copy, a piece of a split
reduction. It takes the scope of the nearest scoped op that consumes its
value, followed through data movement (copies, bitcasts, slices, tuples)
and into the loop body that receives it. So the per-step casts of the
expert weights count as ``moe.expert_ffn`` wherever the compiler moved
them.

A fusion with several outputs counts in the scope of most of them (the
elements of its fused computation's tuple root); any other op in that
of its own op_name. A weight gradient's GEMM fused with the optimizer
update that consumes it writes the parameter and both moments, so it
counts as ``optimizer``. Stdlib only.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

__all__ = ["SCOPES", "innermost", "register", "register_compiled",
           "table"]

# the program's device scopes, one per layer it names
SCOPES = ("embed", "norm", "attention", "ffn", "moe.route", "moe.dispatch",
          "moe.a2a_dispatch", "moe.expert_ffn", "moe.a2a_combine",
          "moe.combine", "lm_head", "sample", "optimizer")

_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_CALL = re.compile(r" ([a-z][a-z0-9\-]*)\(([^)]*)\)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?op_name="([^"]*)"')
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_INDEX = re.compile(r"\bindex=(\d+)")
_SCOPE = re.compile(r"(?<![\w.])(" + "|".join(
    re.escape(s) for s in sorted(SCOPES, key=len, reverse=True))
    + r")(?!\w)")
# ops that move a value without computing on it: a compiler-made op's
# value is followed through them to the op that uses it
_MOVES = {"bitcast", "copy", "copy-start", "copy-done", "dynamic-slice",
          "get-tuple-element", "optimization-barrier", "parameter",
          "reshape", "slice", "transpose", "tuple", "while"}
_HOPS = 16

_TABLE: Dict[str, Dict[str, str]] = {}


def innermost(op_name: str) -> Optional[str]:
    """The last of :data:`SCOPES` in an ``op_name`` path, which is the
    innermost (``jit(f)/transpose(jvp(moe.expert_ffn))/dot_general``
    gives ``moe.expert_ffn``)."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


class _Instr:
    """One instruction of the module's text; ``traced``: its op_name is
    a path of the traced program (``jit(f)/...``), so not the
    compiler's own."""

    __slots__ = ("opcode", "operands", "op_name", "body", "calls",
                 "index", "computation", "traced")

    def __init__(self, rest: str, computation: str):
        call = _CALL.search(" " + rest)
        self.opcode = call.group(1) if call else ""
        self.operands = _OPERAND.findall(call.group(2)) if call else []
        m = _OP_NAME.search(rest)
        self.op_name = m.group(1) if m else None
        self.traced = self.op_name is not None and "/" in self.op_name
        m = _BODY.search(rest)
        self.body = m.group(1) if m else None
        m = _CALLS.search(rest)
        self.calls = m.group(1) if m else None
        m = _INDEX.search(rest)
        self.index = int(m.group(1)) if m else None
        self.computation = computation


def _parse(hlo_text: str) -> Tuple[Dict[str, _Instr], Dict[str, str]]:
    """(name -> instruction, computation -> its root's name)."""
    instrs: Dict[str, _Instr] = {}
    roots: Dict[str, str] = {}
    comp = ""
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            instrs[m.group(1)] = _Instr(m.group(2), comp)
            if line.lstrip().startswith("ROOT "):
                roots[comp] = m.group(1)
            continue
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
    return instrs, roots


def _majority(found: List[str]) -> Optional[str]:
    return max(sorted(set(found)), key=found.count) if found else None


def _scopes(instrs: Dict[str, _Instr], roots: Dict[str, str]):
    """name -> scope of each traced instruction and each multi-output
    fusion whose outputs have one (None: in no scope)."""
    own = {n: innermost(i.op_name) for n, i in instrs.items() if i.traced}
    scope = dict(own)
    for n, ins in instrs.items():
        root = roots.get(ins.calls) if ins.opcode == "fusion" else None
        if root is None or instrs[root].opcode != "tuple":
            continue
        made = _majority([own[o] for o in instrs[root].operands
                          if own.get(o)])
        if made is not None:
            scope[n] = made
    return scope


def _consumers(instrs: Dict[str, _Instr]):
    """name -> the instructions that take its value: its users, except
    that a value put into a loop's state tuple goes to the loop body's
    read of that tuple element."""
    users: Dict[str, List[Tuple[str, int]]] = {}
    for name, ins in instrs.items():
        for k, op in enumerate(ins.operands):
            users.setdefault(op, []).append((name, k))
    reads: Dict[Tuple[str, int], List[str]] = {}    # (body, index) -> gtes
    params = {ins.computation: name for name, ins in instrs.items()
              if ins.opcode == "parameter"}
    for name, ins in instrs.items():
        if (ins.opcode == "get-tuple-element" and ins.operands
                and params.get(ins.computation) == ins.operands[0]):
            reads.setdefault((ins.computation, ins.index), []).append(name)

    def consumers(name: str) -> List[str]:
        out = []
        for user, k in users.get(name, ()):
            if instrs[user].opcode != "tuple":
                out.append(user)
                continue
            for loop, _ in users.get(user, ()):
                body = instrs[loop].body
                if instrs[loop].opcode == "while" and body:
                    out.extend(reads.get((body, k), ()))
        return out

    return consumers


def _consumer_scope(name: str, instrs, scope, consumers) -> Optional[str]:
    seen, frontier = {name}, [name]
    for _ in range(_HOPS):
        found, nxt = [], []
        for n in frontier:
            for c in consumers(n):
                if c in seen:
                    continue
                seen.add(c)
                if scope.get(c):
                    found.append(scope[c])
                elif not instrs[c].traced or instrs[c].opcode in _MOVES:
                    nxt.append(c)
        if found:
            return _majority(found)
        if not nxt:
            return None
        frontier = nxt
    return None


def register(hlo_text: str) -> str:
    """Add one compiled module's instructions to the table, replacing
    an earlier module of the same name. Returns the module's name."""
    m = _MODULE.search(hlo_text)
    if m is None:
        raise ValueError("not an HLO module's text")
    instrs, roots = _parse(hlo_text)
    scope = _scopes(instrs, roots)
    consumers = _consumers(instrs)
    made = {n: _consumer_scope(n, instrs, scope, consumers)
            for n, i in instrs.items() if n not in scope}
    ops = {n: s for n, s in scope.items() if s is not None}
    ops.update((n, s) for n, s in made.items() if s is not None)
    _TABLE[m.group(1)] = ops
    return m.group(1)


def register_compiled(compiled) -> str:
    return register(compiled.as_text())


def table() -> Dict[str, Dict[str, str]]:
    """The table itself: module name -> {instruction name -> scope}."""
    return _TABLE
