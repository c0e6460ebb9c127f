"""Spans in host code, and which physical operator each compiled op runs.

``span(name, **ids)`` marks a stretch of host code in the JAX profiler's
trace (``jax.profiler.TraceAnnotation``), on the same clock as the device's
ops; outside a trace it costs a flag check. Nesting on one thread gives a
span its parent; spans of one request share an integer id (``rid=12``).
The names are dotted (``serving.dispatch``) so that they never equal a
benchmark harness's own span names. A span in ``PUBLISHED`` also reports
its duration on JAX's monitoring bus as ``/repro/<name>`` and adds it to
``published_s``, so that its total is known without a trace.

Device code names its operators with ``jax.named_scope`` (``OPERATORS``,
set by ``core.physical``). ``operators(hlo_text)`` reads them back from a
compiled executable's HLO text, where each instruction keeps its own
``op_name`` path. ``core.plan_cache`` records that map for each executable
it builds (``record_executable``), so that a profiler's per-op times, which
carry HLO instruction names only, can be summed by operator afterwards.
"""
from __future__ import annotations

import re
import time
from collections import OrderedDict, defaultdict
from typing import Dict, NamedTuple, Optional

import jax

OPERATORS = ("scan", "filter", "project", "compact", "join", "cross_join",
             "aggregate", "blocked_matmul", "forest", "repartition")
PUBLISHED = frozenset({"optimizer.cost"})

# seconds inside each published span, over the process's life
published_s: Dict[str, float] = defaultdict(float)


class _Published:
    """A span whose duration also goes to the monitoring bus."""

    def __init__(self, name: str, ids: dict):
        self.name = name
        self.annotation = jax.profiler.TraceAnnotation(name, **ids)

    def __enter__(self):
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.annotation.__exit__(*exc)
        published_s[self.name] += dt
        jax.monitoring.record_event_duration_secs("/repro/" + self.name, dt)
        return False


def span(name: str, **ids: int):
    """A host span named ``name``; ``ids`` are single integers."""
    if name in PUBLISHED:
        return _Published(name, ids)
    return jax.profiler.TraceAnnotation(name, **ids)


# ---------------------------------------------------------------------------
# operator scopes of compiled executables
# ---------------------------------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_NAME = re.compile(r"%([\w.\-]+)")
_INNER = re.compile(r"(?:calls|to_apply|called_computations)=\{?([^}]*?)\}?"
                    r"(?:, [a-z_]+=|$)")
_WRAPPER = re.compile(r"^[\w\-]+\((.*)\)$")


def operator_of(op_name: str) -> Optional[str]:
    """The innermost operator scope of an ``op_name`` path, or None.
    Transform wrappers are stripped (``vmap(aggregate)`` is ``aggregate``);
    the last component is the primitive and never a scope."""
    found = None
    for part in op_name.split("/")[:-1]:
        while (m := _WRAPPER.match(part)):
            part = m.group(1)
        if part in OPERATORS:
            found = part
    return found


class OpScope(NamedTuple):
    """What one HLO instruction runs: its opcode, its operator, and whether
    it is a fusion whose fused instructions belong to more than one
    operator."""
    opcode: str
    operator: Optional[str]
    cross: bool


class _Instr(NamedTuple):
    name: str
    opcode: str
    op_name: Optional[str]
    own: Optional[str]   # operator of its own op_name
    inner: list          # computations it calls as part of itself
    names: list          # every %name it refers to: operands, computations
    root: bool


def operators(hlo_text: str) -> Dict[str, OpScope]:
    """The ops a profiler reports for a compiled module (the instructions of
    the entry computation and of loop bodies and branches, not those inside
    fusions or reducers), by name, with the operator each ran for.

    An op's operator is its own ``op_name``'s. An op that XLA added with no
    ``op_name`` (copies, broadcasts of constants, wrapping fusions) takes,
    as XLA names a fusion after its root, that of the root of a computation
    it calls; else that of an operand, or else of a user, that has one."""
    comps: Dict[str, list] = {}
    current = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and current is not None:
            root, name, rhs = m.groups()
            head = rhs.split(", metadata=")[0]
            op = _OP_NAME.search(rhs)
            code = _OPCODE.search(" " + head)
            inner = [n for g in _INNER.findall(head) for n in _NAME.findall(g)]
            comps[current].append(_Instr(
                name, code.group(1) if code else "", op and op.group(1),
                operator_of(op.group(1)) if op else None, inner,
                [n for n in _NAME.findall(head) if n != name], bool(root)))
            continue
        m = _COMPUTATION.match(line)
        if m:
            current = m.group(1)
            comps[current] = []
        elif line.startswith("}"):
            current = None
    inner = {c for instrs in comps.values() for i in instrs for c in i.inner}

    def root_of(comp: str, depth: int = 0) -> Optional[str]:
        for i in comps.get(comp, ()):
            if i.root and depth < 8:
                return i.own or next((r for c in i.inner
                                      if (r := root_of(c, depth + 1))), None)
        return None

    def inside(comp: str, depth: int = 0) -> set:
        found = set()
        for i in comps.get(comp, ()):
            found |= {i.own} - {None}
            for c in i.inner:
                if depth < 8:
                    found |= inside(c, depth + 1)
        return found

    ops = [i for c, instrs in comps.items() if c not in inner for i in instrs]
    found = {i.name: i.own or (None if i.op_name else next(
        (r for c in i.inner if (r := root_of(c))), None)) for i in ops}
    users: Dict[str, list] = {}
    for i in ops:
        for n in i.names:
            users.setdefault(n, []).append(i.name)
    for _ in range(4):  # copies of copies: follow a short chain
        for i in ops:
            if found[i.name] is None and not i.op_name:
                found[i.name] = next(
                    (found[n] for n in i.names + users.get(i.name, [])
                     if found.get(n)), None)
    out = {}
    for i in ops:
        fused = set().union(*(inside(c) for c in i.inner)) | {i.own} - {None}
        out[i.name] = OpScope(i.opcode, found[i.name],
                              i.opcode == "fusion" and len(fused) > 1)
    return out


class Executable(NamedTuple):
    """A compiled plan executable as ``record_executable`` keeps it."""
    module: str
    batch_size: int
    ops: Dict[str, OpScope]


_EXECUTABLES: "OrderedDict[str, Executable]" = OrderedDict()
MAX_EXECUTABLES = 64


def record_executable(key: str, batch_size: int, compiled) -> None:
    """Keep the operator map of a compiled executable under its cache key.
    The map is per process, as the HLO names a profiler reports are; the
    oldest of more than ``MAX_EXECUTABLES`` entries is dropped."""
    text = compiled.as_text()
    m = re.match(r"HloModule\s+([\w.\-]+)", text)
    _EXECUTABLES[key] = Executable(m.group(1) if m else "", batch_size,
                                   operators(text))
    _EXECUTABLES.move_to_end(key)
    while len(_EXECUTABLES) > MAX_EXECUTABLES:
        _EXECUTABLES.popitem(last=False)


def executables() -> list:
    """The recorded executables, oldest first."""
    return list(_EXECUTABLES.values())
