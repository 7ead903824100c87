"""The end-to-end metrics: what a caller of the system sees.  A cell
reports those BENCHMARK.json lists for it; `setup_s` is the harness's."""

from __future__ import annotations

from benchmark import stats


def goodput(ev) -> float:
    """GB/s of payload that completed and verified, counted once per call."""
    return stats.per_second(len(ev.call_s) * ev.bytes_per_call,
                            ev.window_s) / 1e9


def call_rate(ev) -> float:
    return stats.per_second(len(ev.call_s), ev.window_s)


def call_p50(ev) -> float:
    return stats.median(ev.call_s) * 1e6


def call_p99(ev) -> float:
    return stats.tail(ev.call_s, 99.0) * 1e6


METRICS = {"goodput": goodput, "call_rate": call_rate,
           "call_p50": call_p50, "call_p99": call_p99}
