"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are ``/device:TPU:<n>``; on each, the "XLA Ops" line holds one
event per operation that ran, named by its HLO instruction
(``%grouped_dequant_matmul_pallas.1 = f32[...] custom-call(...)``; a Pallas
kernel's instruction is named after its jitted wrapper), and the "XLA
Modules" line one event per jitted program (``jit__grouped_ffn(<hash>)``).
The host plane ``/host:CPU`` holds the benchmark's own spans
(``jax.profiler.TraceAnnotation`` names starting ``bench.``) on the same
clock.  The window starts where the ``bench.window`` span starts and lasts
the measured window's length (the span itself ends a little later, at the
first call after the window closed).
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
HOST_FALLBACK = "scheduler host code"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals (seconds), sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def covered(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no interval of ``busy`` covers."""
    out, t = [], lo
    for s, e in union(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The innermost span (the latest-starting one) that covers the gap's
    middle, or HOST_FALLBACK."""
    mid = 0.5 * (gap[0] + gap[1])
    best: Optional[Tuple[float, str]] = None
    for name, s, e in spans:
        if s <= mid <= e and (best is None or s > best[0]):
            best = (s, name)
    return best[1] if best else HOST_FALLBACK


def op_name(text: str) -> str:
    """``%name.3 = type op(...)`` -> ``name.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def module_name(text: str) -> str:
    """``jit_fn(12345)`` -> ``jit_fn``."""
    return text.split("(", 1)[0]


def _enclosing(mods: List[Tuple[str, float, float]], t: float) -> str:
    i = bisect.bisect_right([s for _, s, _ in mods], t) - 1
    return mods[i][0] if i >= 0 and t < mods[i][2] else "?"


class Trace:
    """Events of one trace, times in seconds."""

    def __init__(self, ops: Dict[str, List[Tuple[str, str, float, float]]],
                 modules: Dict[str, List[Tuple[str, float, float]]],
                 spans: List[Tuple[str, float, float]],
                 seconds: Optional[float] = None):
        self.ops = ops            # device -> [(op, module, start, end)]
        self.modules = modules    # device -> [(module, start, end)]
        self.spans = spans        # [(name, start, end)] host spans
        self.seconds = seconds    # measured window length, if known

    @classmethod
    def load(cls, path: str, seconds: Optional[float] = None) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(str(path))
        ops, modules, spans = {}, {}, []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                lines = {line.name: list(line.events) for line in plane.lines}
                mods = sorted(((module_name(ev.name), ev.start_ns * 1e-9,
                                ev.end_ns * 1e-9)
                               for ev in lines.get("XLA Modules", [])),
                              key=lambda m: m[1])
                modules[plane.name] = mods
                ops[plane.name] = [
                    (op_name(ev.name), _enclosing(mods, ev.start_ns * 1e-9),
                     ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    for ev in lines.get("XLA Ops", [])]
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    spans.extend((ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                                 for ev in line.events
                                 if ev.name.startswith("bench."))
        return cls(ops, modules, spans, seconds)

    def window(self) -> Interval:
        w = [(s, e) for n, s, e in self.spans if n == "bench.window"]
        if not w:
            raise ValueError("the trace holds no bench.window span")
        s, e = w[0]
        return (s, s + self.seconds) if self.seconds else (s, e)

    def busy_s(self) -> float:
        """Seconds in the window in which some operation ran, averaged over
        the devices that ran any."""
        lo, hi = self.window()
        per = [covered(clip([(s, e) for _, _, s, e in evs], lo, hi))
               for evs in self.ops.values() if evs]
        return sum(per) / len(per) if per else 0.0

    def op_time(self, pattern: str) -> Tuple[float, int]:
        """(seconds, events) of the window's operations whose instruction
        name matches ``pattern``, summed over devices."""
        lo, hi = self.window()
        rx = re.compile(pattern)
        t, n = 0.0, 0
        for evs in self.ops.values():
            for name, _, s, e in evs:
                if lo <= s < hi and rx.search(name):
                    t += e - s
                    n += 1
        return t, n

    def module_time(self, pattern: str) -> Tuple[float, int]:
        """(seconds, calls) of the window's jitted programs matching."""
        lo, hi = self.window()
        rx = re.compile(pattern)
        t, n = 0.0, 0
        for evs in self.modules.values():
            for name, s, e in evs:
                if lo <= s < hi and rx.search(name):
                    t += e - s
                    n += 1
        return t, n

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` operations (``program/instruction``) that took the most
        device time."""
        lo, hi = self.window()
        tot: Dict[str, float] = {}
        for evs in self.ops.values():
            for name, mod, s, e in evs:
                if lo <= s < hi:
                    key = f"{mod}/{name}"
                    tot[key] = tot.get(key, 0.0) + (e - s)
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest gaps in which no device ran an operation, each
        named by the host span it falls in."""
        lo, hi = self.window()
        busy = [(s, e) for evs in self.ops.values() for _, _, s, e in evs]
        inner = [sp for sp in self.spans if sp[0] != "bench.window"]
        gs = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:n]
        return [[label_gap(g, inner), g[1] - g[0]] for g in gs]
