#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is one JSON object with exactly the keys
`correct`, `attempted`, `failed`, `metrics`, `device`, in a traced run
`breakdown`, and last `compared`: each number that `correct` rests on
beside its limit, which are also the last lines of stderr.  With
`--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics.  Everything else a reader may want is
on earlier lines, each a JSON object with a `note`.
Without the chips the cell asks for the run exits non-zero and prints no
result; no failure is turned into a null.

Nothing here names a cell, a size or a transport: the cell's files say
what runs (manifest.py).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

_T0 = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import end_to_end, stats, trace_reduce  # noqa: E402
from benchmark.evidence import RunContext  # noqa: E402
from benchmark.manifest import Manifest, ManifestError  # noqa: E402
from benchmark.spans import Spans  # noqa: E402


def process_age_s() -> float:
    """Seconds since this process was started, from /proc: set-up is
    counted from the process's start, interpreter and imports included."""
    with open("/proc/self/stat") as f:
        # Field 22, counted after the parenthesised command name.
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    started = start_ticks / os.sysconf("SC_CLK_TCK")
    return time.clock_gettime(time.CLOCK_BOOTTIME) - started


_AGE_AT_T0 = process_age_s()


class CompileCounter:
    """Counts what JAX traces, lowers or compiles, by its own monitoring
    events; a window in which the count moves compiled something."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _seconds: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.count += 1


def _device_facts(devices, evidence) -> dict:
    import jax

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    facts = {"platform": devices[0].platform,
             "kind": devices[0].device_kind,
             "count": len(jax.devices()),
             "memory_peak_bytes": int(peak)}
    if evidence.trace is not None:
        facts["busy_s"] = trace_reduce.busy_s(evidence.trace)
        facts["window_s"] = trace_reduce.window_s(evidence.trace)
        if not facts["busy_s"] > 0:
            raise RuntimeError("the traced window shows no operation on "
                               "the device")
    return facts


def _span_summary(ev) -> dict:
    """Per span name, over the window: how many, their median and sum."""
    out = {}
    for name in sorted({r[0] for r in ev.spans.records}):
        d = ev.spans.durations(name, ev.t_open, ev.t_close)
        if d:
            out[name] = {"n": len(d), "median_us": stats.median(d) * 1e6,
                         "total_s": sum(d)}
    return out


def _tracing_cost(ev) -> dict:
    """Calls per second while the profiler ran against before it ran, in
    the same window: what tracing costs when it is on."""
    start, stop = ev.traced
    before = sum(1 for end in ev.call_end if end <= start)
    during = sum(1 for end in ev.call_end if start < end <= stop)
    return {"calls_per_s_untraced": before / (start - ev.t_open),
            "calls_per_s_traced": during / (stop - start),
            "traced_s": stop - start}


def _compared(ev) -> dict:
    """Each number `correct` rests on, beside its limit.  The compares
    are exact (a byte-exact echo, an exchange against its reference), so
    every limit is 0: the calls that failed, timed out or mismatched, and
    1 for each fact a driver notes beside what the configuration expects
    of it (`transport` beside `transport_expected`) where the two
    differ."""
    out = {"failed_calls": {"value": int(ev.failed), "limit": 0}}
    for key, expected in ev.notes.items():
        if key.endswith("_expected"):
            fact = key[:-len("_expected")]
            out[f"{fact}_differs"] = {
                "value": int(ev.notes.get(fact) != expected), "limit": 0}
    return out


def run_cell(manifest: Manifest, cell_name: str, seed: int, seconds: float,
             trace: bool, platform: str = "tpu", interpret: bool = False,
             load_trace=trace_reduce.load_xplane) -> tuple[dict, list[dict]]:
    """(the result line, the notes that go before it).  `platform` is the
    one the devices must be of, `interpret` how Pallas kernels run and
    `load_trace` what reads the profiler's output: the command line
    passes none of them; the CPU rehearsal passes "cpu", True and, as the
    CPU has no device plane to read, a loader of a recorded trace."""
    cell = manifest.cell(cell_name)
    driver = manifest.driver(cell.driver_name)
    readers = {m["name"]: manifest.reader(m["name"])
               for m in cell.per_layer} if trace else {}
    for name, reader in readers.items():
        # DRIVERS = None: the reader needs only what every driver hands
        # back (the trace, the call samples), so it reads any driver.
        if (reader.DRIVERS is not None
                and cell.driver_name not in reader.DRIVERS):
            raise ManifestError(
                f"{name} is listed for {cell.name} but reads "
                f"{reader.DRIVERS}, not {cell.driver_name!r}")

    import jax

    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < cell.chips:
        raise SystemExit(
            f"{cell.name} needs {cell.chips} {platform} chip(s); JAX found "
            f"{len(devices)} device(s) of platform "
            f"{devices[0].platform!r} ({devices[0].device_kind!r})")
    devices = devices[:cell.chips]

    from brpc_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()  # before the first compilation
    trace_dir = tempfile.mkdtemp(prefix="bm_trace_")
    try:
        ctx = RunContext(cell=cell, seed=seed, seconds=seconds, trace=trace,
                         devices=devices, interpret=interpret,
                         spans=Spans(), compiles=CompileCounter(),
                         trace_dir=trace_dir)
        ev = driver.run(ctx)
        if trace:
            ev.trace = load_trace(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if ev.compiles_in_window:
        raise RuntimeError(
            f"{ev.compiles_in_window} compilation event(s) inside the "
            "measured window; warm up every shape the window uses")
    ev.device_kind = devices[0].device_kind

    metrics: dict[str, dict] = {}
    if trace:
        for m in cell.per_layer:
            value = readers[m["name"]].read(ev)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                value = ev.t_open - _T0 + _AGE_AT_T0
            else:
                value = end_to_end.METRICS[m["name"]](ev)
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    result = {"correct": bool(ev.correct), "attempted": int(ev.attempted),
              "failed": int(ev.failed), "metrics": metrics,
              "device": _device_facts(devices, ev)}
    if ev.trace is not None:
        result["breakdown"] = {
            "device_ops": trace_reduce.top_device_ops(ev.trace),
            "idle_gaps": trace_reduce.idle_by_span(ev.trace)}
    result["compared"] = _compared(ev)
    top = stats.highest_supported(len(ev.call_s))
    notes = [{"note": "run", "workload": cell.name, "seed": seed,
              "seconds": seconds, "trace": trace,
              "window_s": ev.window_s, "samples": len(ev.call_s),
              "median_us": stats.median(ev.call_s) * 1e6,
              "highest_percentile_with_ten_beyond": top,
              "its_value_us": (None if top is None
                               else stats.tail(ev.call_s, top) * 1e6),
              "compile_cache_dir": cache_dir, "jax": jax.__version__},
             {"note": "driver", **ev.notes},
             {"note": "spans", **_span_summary(ev)},
             {"note": "counters",
              **{k: v for k, v in sorted(ev.counters.items()) if v}}]
    if ev.traced is not None:
        notes.append({"note": "tracing", **_tracing_cost(ev)})
    return result, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    result, notes = run_cell(Manifest(ROOT), args.workload, args.seed,
                             args.seconds, bool(args.trace))
    for note in notes:
        print(json.dumps(note), flush=True)
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
