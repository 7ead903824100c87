"""What the harness hands a driver, and what a driver hands back."""

from __future__ import annotations

import dataclasses

from benchmark.spans import Spans


@dataclasses.dataclass
class RunContext:
    """One run of one cell.  `interpret` is the caller's statement of how
    Pallas kernels run (the CPU rehearsal passes True), never derived
    from the platform."""

    cell: object                 # manifest.Cell
    seed: int
    seconds: float
    trace: bool
    devices: list                # the chips this cell may use
    interpret: bool
    spans: Spans
    compiles: object             # run.CompileCounter
    trace_dir: str               # raw profiler output, outside the checkout

    def start_trace(self) -> None:
        """Device trace plus the benchmark's own spans; the Python tracer
        is off, since it would be most of the trace and of its cost."""
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.spans.annotate = True

    def stop_trace(self) -> None:
        import jax

        self.spans.annotate = False
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Evidence:
    """Everything the metrics are computed from.  Times are on
    `time.perf_counter`; the window is [t_open, t_close]."""

    t_open: float
    t_close: float
    call_s: list[float]          # one sample per verified call in the window
    call_end: list[float]        # when each of those calls ended
    bytes_per_call: int          # payload bytes counted once per call
    attempted: int
    failed: int
    correct: bool
    compiles_in_window: int
    spans: Spans
    counters: dict               # program counters, delta over the window
    trace: dict | None = None    # trace_reduce's plain form, traced run only
    traced: tuple[float, float] | None = None  # perf_counter interval
    notes: dict = dataclasses.field(default_factory=dict)
    device_kind: str = ""        # filled in by the harness for the readers

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open
