"""Spans the benchmark records around its own calls into each layer.

Kept in memory (name, start, end on `time.perf_counter`); in a traced run
each span is also a `jax.profiler.TraceAnnotation` named `bm:<name>`, so
that it sits on the device trace's clock and an idle gap on the chip can
be named by what the host was doing.
"""

from __future__ import annotations

import contextlib
import time

PREFIX = "bm:"


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        if self.annotate:
            import jax

            note = jax.profiler.TraceAnnotation(PREFIX + name)
        else:
            note = contextlib.nullcontext()
        with note:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def add(self, name: str, start: float, end: float) -> None:
        """An interval that is not a stretch of the client thread (a
        call's time on the wire): recorded, never annotated."""
        self.records.append((name, start, end))

    def durations(self, name: str, since: float = 0.0,
                  until: float = float("inf")) -> list[float]:
        return [e - s for n, s, e in self.records
                if n == name and s >= since and e <= until]

    def total(self, name: str, since: float = 0.0,
              until: float = float("inf")) -> float:
        return sum(self.durations(name, since, until))
