"""From a profiler trace (``.xplane.pb``) to device busy time, idle share,
plan device time and the breakdown of a traced window.

The harness brackets the traced part of its window with a host
annotation (``WINDOW``) and records ``time.monotonic()`` as it opens,
which puts the host spans of ``repro.obs`` (monotonic clock) on the
trace's clock.  Device planes are ``/device:TPU:<n>``; on each, ops come
from the ``XLA Ops`` line and whole programs from ``XLA Modules``.
Busy time is the union of the op intervals (of the module intervals
where a plane has no op line), clipped to the window.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"

Interval = Tuple[float, float]          # seconds on the trace's clock


@dataclasses.dataclass
class DeviceEvents:
    ops: List[Tuple[str, float, float]]        # (name, start, end)
    modules: List[Tuple[str, float, float]]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _events(line) -> List[Tuple[str, float, float, object]]:
    out = []
    for ev in line.events:
        s = ev.start_ns * 1e-9
        out.append((ev.name, s, s + ev.duration_ns * 1e-9, ev))
    return out


def tpu_devices(pd) -> Dict[str, DeviceEvents]:
    """Ops and programs of every TPU plane in the trace."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops, mods = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [(n, s, e) for n, s, e, _ in _events(line)]
            elif line.name == "XLA Modules":
                mods = [(n, s, e) for n, s, e, _ in _events(line)]
        if ops or mods:
            out[plane.name] = DeviceEvents(ops=ops, modules=mods)
    return out


def host_annotation(pd, name: str) -> Optional[Interval]:
    """The first host event called ``name`` (a ``TraceAnnotation``)."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for n, s, e, _ in _events(line):
                if n == name:
                    return (s, e)
    return None


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[list] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_timeline(spans, offset: float) -> List[Tuple[float, float, str]]:
    """The ``repro.obs`` spans flattened to ``(start, end, name)``
    segments that do not overlap, each named by the innermost span open
    in it, on the trace's clock (``offset`` = trace clock minus
    monotonic clock)."""
    iv = sorted(((s.t0 + offset, s.t1 + offset, s.name) for s in spans
                 if s.t1 is not None), key=lambda x: (x[0], -x[1]))
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []       # (end, name), innermost last
    t = float("-inf")

    def pop_until(limit: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for s, e, name in iv:
        pop_until(s)
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        t = max(t, s)
        stack.append((e, name))
    pop_until(float("inf"))
    return out


def label_time(timeline, a: float, b: float) -> Dict[str, float]:
    """Seconds of ``[a, b]`` under each host span name; the rest under
    ``"no host span"``."""
    starts = [s for s, _, _ in timeline]
    out: Dict[str, float] = {}
    covered = 0.0
    k = max(0, bisect.bisect_right(starts, a) - 1)
    while k < len(timeline) and timeline[k][0] < b:
        s, e, name = timeline[k]
        d = min(e, b) - max(s, a)
        if d > 0:
            out[name] = out.get(name, 0.0) + d
            covered += d
        k += 1
    if b - a - covered > 0:
        out["no host span"] = out.get("no host span", 0.0) + (b - a - covered)
    return out


def reduce(devices: Dict[str, DeviceEvents], lo: float, hi: float,
           timeline: Sequence[Tuple[float, float, str]] = (),
           top: int = 10) -> dict:
    """Busy and program time per device over ``[lo, hi]``, averaged over
    devices, and the breakdown: the device ops that took most time and
    the idle time by what the host was doing.  ``ended_module_s`` is the
    whole device time of the programs that finished inside the window
    (``ended_modules`` of them): the time of the batches whose results
    landed in it."""
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy_s, module_s, ended_s, ended_n = [], [], [], []
    op_time: Dict[str, float] = {}
    idle_by: Dict[str, float] = {}
    for dev in devices.values():
        src = dev.ops or dev.modules
        busy = union(clip(((s, e) for _, s, e in src), lo, hi))
        busy_s.append(total(busy))
        module_s.append(total(union(clip(((s, e) for _, s, e in dev.modules),
                                         lo, hi))))
        ended = [e - s for _, s, e in dev.modules if lo <= e <= hi]
        ended_s.append(sum(ended))
        ended_n.append(len(ended))
        for name, s, e in src:
            c = clip([(s, e)], lo, hi)
            if c:
                op_time[name] = op_time.get(name, 0.0) + total(c)
        for s, e in gaps(busy, lo, hi):
            for key, d in label_time(timeline, s, e).items():
                idle_by[key] = idle_by.get(key, 0.0) + d
    n = len(devices)
    rank = lambda d: sorted(([k, v / n] for k, v in d.items()),  # noqa: E731
                            key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy_s) / n, "window_s": hi - lo,
            "module_s": sum(module_s) / n, "devices": n,
            "ended_module_s": sum(ended_s) / n,
            "ended_modules": sum(ended_n) / n,
            "breakdown": {"device_ops": rank(op_time),
                          "idle_gaps": rank(idle_by)}}
