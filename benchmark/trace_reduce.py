"""From a profiler trace to numbers: device busy and idle time, kernel
time by name, and each idle gap named by the span the host was in.

The reduction works on a plain form of the trace, so that the tests can
run it on a small recorded one (`testdata/`):

    {"planes": [{"name": str, "lines": [{"name": str,
        "events": [[name, start_ns, duration_ns], ...]}]}]}

Device planes are `/device:TPU:<n>`; what runs on a chip's compute units
is on its "XLA Ops" line and whole programs are on "XLA Modules".  The
host's spans are the `bm:<name>` annotations of `spans.py`, on whichever
host line carries them.  All lines of one trace share a clock.
"""

from __future__ import annotations

import glob
import os
import re

from benchmark.spans import PREFIX

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
BETWEEN = "between_spans"


def load_xplane(trace_dir: str) -> dict:
    """The newest .xplane.pb under `trace_dir`, in the plain form: the
    device planes' op and module lines, and the host's `bm:` spans."""
    import jax

    found = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(found[-1])
    planes = []
    for plane in data.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if on_device and line.name not in (OP_LINE, MODULE_LINE):
                continue
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if on_device or e.name.startswith(PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def chips_traced(trace: dict) -> int:
    return len(_device_planes(trace))


def _line_events(plane: dict, line_name: str) -> list[list]:
    return [e for ln in plane["lines"] if ln["name"] == line_name
            for e in ln["events"]]


def host_spans(trace: dict) -> list[tuple[str, float, float]]:
    """(name without prefix, start_ns, end_ns) of every `bm:` span,
    sorted by start."""
    out = [(e[0][len(PREFIX):], e[1], e[1] + e[2])
           for p in trace["planes"] if not DEVICE_PLANE.match(p["name"])
           for ln in p["lines"] for e in ln["events"]
           if e[0].startswith(PREFIX)]
    return sorted(out, key=lambda s: s[1])


def window_ns(trace: dict) -> tuple[float, float]:
    """The traced window: from the first host span's start to the last
    one's end."""
    spans = host_spans(trace)
    if not spans:
        raise ValueError("the trace holds no bm: span")
    return spans[0][1], max(s[2] for s in spans)


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_intervals(plane: dict, lo: float, hi: float):
    ops = [(e[1], e[1] + e[2]) for e in _line_events(plane, OP_LINE)]
    return merge(_clip(ops, lo, hi))


def busy_s(trace: dict) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    chips traced."""
    lo, hi = window_ns(trace)
    planes = _device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no device plane")
    total = sum(e - s for p in planes
                for s, e in busy_intervals(p, lo, hi))
    return total / len(planes) / 1e9


def window_s(trace: dict) -> float:
    lo, hi = window_ns(trace)
    return (hi - lo) / 1e9


def idle_share(trace: dict) -> float:
    return 1.0 - busy_s(trace) / window_s(trace)


def _matching_ns(trace: dict, line_name: str, pattern: str) -> list:
    """Durations of the events on `line_name`, on any chip, that lie in
    the traced window and whose name matches `pattern`."""
    rx = re.compile(pattern)
    lo, hi = window_ns(trace)
    return [e[2] for p in _device_planes(trace)
            for e in _line_events(p, line_name)
            if rx.search(e[0]) and lo <= e[1] and e[1] + e[2] <= hi]


def seconds_by_name(trace: dict, line_name: str, pattern: str) -> float:
    """Summed device seconds of the matching events, averaged over the
    chips traced."""
    return sum(_matching_ns(trace, line_name, pattern)) / max(
        chips_traced(trace), 1) / 1e9


def count_by_name(trace: dict, line_name: str, pattern: str) -> float:
    """How many such events ran, averaged over the chips traced."""
    return len(_matching_ns(trace, line_name, pattern)) / max(
        chips_traced(trace), 1)


def short_op_name(name: str) -> str:
    """An op line's event is named by its whole HLO text; what is before
    the " = " names it."""
    return name.split(" = ", 1)[0].lstrip("%")


def top_device_ops(trace: dict, n: int = 10) -> list[list]:
    """[[name, seconds]]: the operations that took most device time,
    averaged over the chips traced."""
    lo, hi = window_ns(trace)
    planes = _device_planes(trace)
    by_name: dict[str, float] = {}
    for p in planes:
        for name, start, dur in _line_events(p, OP_LINE):
            if start + dur > lo and start < hi:
                name = short_op_name(name)
                by_name[name] = by_name.get(name, 0.0) + dur
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / len(planes) / 1e9] for name, ns in ranked]


def idle_by_span(trace: dict, n: int = 10) -> list[list]:
    """[[span, seconds]]: the idle time of the first chip's compute line,
    split by the host span that covered it; what no span covered is
    `between_spans`.  Most idle seconds first."""
    lo, hi = window_ns(trace)
    plane = _device_planes(trace)[0]
    busy = busy_intervals(plane, lo, hi)
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    spans = host_spans(trace)
    by_name: dict[str, float] = {}
    first = 0
    for gs, ge in gaps:
        while first < len(spans) and spans[first][2] <= gs:
            first += 1
        covered = 0.0
        for name, ss, se in spans[first:]:
            if ss >= ge:
                break
            part = min(se, ge) - max(ss, gs)
            if part > 0:
                by_name[name] = by_name.get(name, 0.0) + part
                covered += part
        rest = (ge - gs) - covered
        if rest > 0:
            by_name[BETWEEN] = by_name.get(BETWEEN, 0.0) + rest
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def span_count(trace: dict, name: str) -> int:
    return sum(1 for s in host_spans(trace) if s[0] == name)


def seconds_per_event(trace: dict, line_name: str,
                      pattern: str) -> float | None:
    """Mean device seconds of one matching event; None where the trace
    holds none."""
    n = count_by_name(trace, line_name, pattern)
    if not n:
        return None
    return seconds_by_name(trace, line_name, pattern) / n


def seconds_per_span(trace: dict, line_name: str, pattern: str,
                     span: str) -> float | None:
    """Device seconds of the matching events per host span named `span`
    (one exchange, one call); None where the trace holds no such span."""
    n = span_count(trace, span)
    if not n:
        return None
    return seconds_by_name(trace, line_name, pattern) / n
