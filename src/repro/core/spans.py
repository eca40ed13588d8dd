"""Named host spans: one profiler annotation and one running total per
interval of the serving hot path.

``Spans(names)`` declares every name up front with zeros, so ``totals()``
always holds every key and a difference of two snapshots never misses one.
``with spans("hobbit.layer.gate", layer=3):`` adds the interval's
``perf_counter`` seconds and one count to the name's total and, while a
profiler is tracing, opens a ``jax.profiler.TraceAnnotation`` with the
metadata; ``spans.step(name, n)`` opens a step's root as a
``StepTraceAnnotation``.  With no profiler running a span costs two clock
reads and no annotation (one opened then would record nothing).  A name
does not nest inside itself.
"""

from __future__ import annotations

import time
from typing import Dict, Iterable, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

_tracing = TraceAnnotation.is_enabled
_clock = time.perf_counter


class _Span:
    __slots__ = ("total", "tm", "t0")

    def __init__(self):
        self.total = [0.0, 0]           # seconds, count
        self.tm: Optional[TraceAnnotation] = None
        self.t0 = 0.0

    def __enter__(self):
        if self.tm is not None:
            self.tm.__enter__()
        self.t0 = _clock()
        return self

    def __exit__(self, *exc):
        total = self.total
        total[0] += _clock() - self.t0
        total[1] += 1
        if self.tm is not None:
            self.tm.__exit__(*exc)
            self.tm = None
        return False


class Spans:
    """A registry of named spans and their totals."""

    def __init__(self, names: Iterable[str]):
        # owner: main-thread
        self._spans: Dict[str, _Span] = {n: _Span() for n in names}

    def __call__(self, name: str, **meta) -> _Span:
        span = self._spans[name]
        if _tracing():
            span.tm = TraceAnnotation(name, **meta)
        return span

    def step(self, name: str, step: int) -> _Span:
        span = self._spans[name]
        if _tracing():
            span.tm = StepTraceAnnotation(name, step_num=step)
        return span

    def seconds(self, name: str) -> float:
        return self._spans[name].total[0]

    def totals(self) -> Dict[str, Dict[str, float]]:
        """JSON-ready ``{name: {"s": seconds, "n": count}}``."""
        return {n: {"s": sp.total[0], "n": sp.total[1]}
                for n, sp in self._spans.items()}
