"""The plan program's phases and the host spans on the profiler's clock,
from a profiler trace (``.xplane.pb``).

The plan program names its two phases with ``jax.named_scope``:
``plan.fill`` and ``plan.traceback`` appear in the ``op_name`` metadata
of every op the compiler makes of them.  A TPU v5e trace carries no
``op_name`` on its ops, so an op's scope is read from the compiled
program's text (``hlo_scopes``), by the op's instruction name in its
module (the enclosing ``XLA Modules`` event).  An op that names no phase
inherits the phase of the op it is nested in (a copy inside the fill's
loop is fill time), and every instant of busy time goes to exactly one
phase, or to ``unscoped``.

With the ``repro.obs`` profiler bridge on, every span is also a host
event of the trace with the same ``span_id``: ``clock_skew_us`` is the
largest gap between a span's start put on the trace's clock by the
harness's one offset and its start on the profiler's own clock.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

PHASES = ("plan.fill", "plan.traceback")
UNSCOPED = "unscoped"

Op = Tuple[Optional[str], float, float]          # (phase or None, start, end)

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"')
_OP_NAME = re.compile(r"^%?([\w.\-]+)")


def phase_of(op_name: str) -> Optional[str]:
    """The innermost plan phase an ``op_name`` path names, or None."""
    for part in reversed(op_name.split("/")):
        if part in PHASES:
            return part
    return None


def module_name(name: str) -> str:
    """``jit_fn(12)`` (an ``XLA Modules`` event) -> ``jit_fn``."""
    return name.split("(", 1)[0]


def hlo_scopes(texts: Iterable[str]) -> Dict[Tuple[str, str], Optional[str]]:
    """``(module, instruction) -> phase`` from compiled programs' text
    (``compile().as_text()``).  Programs that share a module name share
    a map; an instruction whose programs disagree maps to None."""
    out: Dict[Tuple[str, str], Optional[str]] = {}
    for text in texts:
        lines = text.splitlines()
        if not lines or not lines[0].startswith("HloModule "):
            raise ValueError("not the text of an HLO module")
        module = lines[0].split()[1].rstrip(",")
        for line in lines[1:]:
            m = _INSTR.match(line)
            if m:
                key, phase = (module, m.group(1)), phase_of(m.group(2))
                out[key] = phase if out.get(key, phase) == phase else None
    return out


def scoped(events, modules=(), hlo: Optional[Dict] = None) -> List[Op]:
    """Op events (``.name``, ``.start_ns``, ``.duration_ns``, ``.stats``)
    with their phase from ``hlo``.  An op's module is its ``hlo_module``
    stat (a CPU trace has one), else the ``modules`` event enclosing it;
    its instruction is its ``hlo_op`` stat, else the head of its name."""
    hlo = hlo or {}
    mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                   module_name(ev.name)) for ev in modules)
    out, k = [], 0
    for ev in sorted(events, key=lambda ev: ev.start_ns):
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        while k < len(mods) and mods[k][1] < s:
            k += 1
        stats = dict(ev.stats)
        module = stats.get("hlo_module") or (
            mods[k][2] if k < len(mods) and mods[k][0] <= s else None)
        m = _OP_NAME.match(ev.name)
        instr = stats.get("hlo_op") or (m.group(1) if m else ev.name)
        out.append((hlo.get((module, instr)), s * 1e-9, e * 1e-9))
    return out


def device_ops(pd, hlo: Optional[Dict] = None) -> Dict[str, List[Op]]:
    """Every TPU plane's ops (``XLA Ops``) with their phase."""
    devices: Dict[str, List[Op]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        ops = scoped(lines.get("XLA Ops", ()), lines.get("XLA Modules", ()),
                     hlo)
        if ops:
            devices[plane.name] = ops
    return devices


def timeline(ops: Sequence[Op]) -> List[Tuple[float, float, str]]:
    """Ops flattened to segments that do not overlap, each under the
    phase of the innermost op open in it that names one (``unscoped``
    where none does); the segments cover the union of the ops once."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []       # (end, phase), innermost last
    t = float("-inf")

    def pop_until(limit: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, phase = stack.pop()
            if end > t:
                out.append((t, end, phase))
                t = end

    for phase, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        pop_until(s)
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        t = max(t, s)
        stack.append((e, phase or (stack[-1][1] if stack else UNSCOPED)))
    pop_until(float("inf"))
    return out


def split(devices: Dict[str, Sequence[Op]], lo: float, hi: float
          ) -> Dict[str, float]:
    """Seconds of busy time in ``[lo, hi]`` under each phase and
    ``unscoped``, averaged over devices: they add up to the busy time."""
    if not devices:
        raise ValueError("the trace holds no device plane")
    out = {p: 0.0 for p in PHASES + (UNSCOPED,)}
    for ops in devices.values():
        for s, e, phase in timeline(ops):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                out[phase] += d / len(devices)
    return out


def profiler_spans(pd) -> Dict[int, Tuple[str, float, float]]:
    """Host events that carry a ``span_id`` (the ``repro.obs`` bridge):
    ``span_id -> (name, start, end)`` on the trace's clock."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                for k, v in ev.stats:
                    if k == "span_id":
                        s = ev.start_ns * 1e-9
                        out[int(v)] = (ev.name, s, s + ev.duration_ns * 1e-9)
    return out


def clock_skew_us(spans, offset: float,
                  host: Dict[int, Tuple[str, float, float]]
                  ) -> Optional[float]:
    """The largest gap, in microseconds, between a span's start on the
    trace's clock by ``offset`` (trace clock minus monotonic clock) and
    the start of its own profiler event; None where no span has one."""
    gaps = [abs(s.t0 + offset - host[k][1]) for s in spans
            if s.args and (k := s.args.get("span_id")) in host]
    return max(gaps) * 1e6 if gaps else None
