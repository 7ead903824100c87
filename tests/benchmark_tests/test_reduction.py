"""The reduction from a trace and from samples to numbers: checked by
hand on a trace small enough to work out on paper, on fixed samples, and
on a trace recorded on the chip (benchmark/testdata/)."""

import gzip
import json
import pathlib

import pytest

from benchmark import end_to_end, stats, trace_reduce
from benchmark.evidence import Evidence
from benchmark.spans import Spans

DATA = pathlib.Path(__file__).resolve().parent.parent.parent / (
    "benchmark/testdata")

# Times in ns.  Ops: [100,150] and [120,170] overlap, then [300,400]:
# busy 170 of a 500 ns window.
PAPER = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_bm_produce(1)", 100, 70], ["jit_other(2)", 300, 100]]},
        {"name": "XLA Ops", "events": [
            ["%fusion.1 = u32[8] fusion(...)", 100, 50],
            ["%copy.2 = u32[8] copy(...)", 120, 50],
            ["%all-to-all.3 = u32[8] all-to-all(...)", 300, 100]]},
        {"name": "Async XLA Ops", "events": [["%copy-start", 0, 500]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [
            ["bm:produce", 0, 90], ["bm:d2h", 100, 150],
            ["bm:wait", 260, 200], ["bm:h2d", 470, 30],
            ["PjitFunction(bm_produce)", 0, 600]]}]},
]}


def test_busy_is_the_union_of_the_op_lines_intervals():
    assert trace_reduce.merge([(120, 170), (100, 150), (300, 400)]) == [
        (100, 170), (300, 400)]
    assert trace_reduce.window_ns(PAPER) == (0, 500)
    assert trace_reduce.busy_s(PAPER) == pytest.approx(170e-9)
    assert trace_reduce.window_s(PAPER) == pytest.approx(500e-9)
    assert trace_reduce.idle_share(PAPER) == pytest.approx(330 / 500)


def test_busy_is_averaged_over_the_chips():
    second = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["%fusion.1 = x", 0, 500]]}]}
    both = {"planes": PAPER["planes"] + [second]}
    assert trace_reduce.chips_traced(both) == 2
    assert trace_reduce.busy_s(both) == pytest.approx((170 + 500) / 2 * 1e-9)


def test_kernel_time_by_name():
    assert trace_reduce.seconds_by_name(
        PAPER, "XLA Modules", r"^jit_bm_produce") == pytest.approx(70e-9)
    assert trace_reduce.seconds_per_event(
        PAPER, "XLA Modules", r"^jit_bm_produce") == pytest.approx(70e-9)
    assert trace_reduce.seconds_per_event(
        PAPER, "XLA Modules", r"^jit_absent") is None
    assert trace_reduce.seconds_per_span(
        PAPER, "XLA Ops", r"all-to-all", "d2h") == pytest.approx(100e-9)
    assert trace_reduce.seconds_per_span(
        PAPER, "XLA Ops", r"all-to-all", "exchange") is None
    assert trace_reduce.top_device_ops(PAPER, 2) == [
        ["all-to-all.3", pytest.approx(100e-9)],
        ["fusion.1", pytest.approx(50e-9)]]


def test_each_idle_gap_is_named_by_the_span_the_host_was_in():
    # Gaps [0,100], [170,300], [400,500] against the four spans.
    got = dict(trace_reduce.idle_by_span(PAPER))
    assert got == {
        "wait": pytest.approx(100e-9), "produce": pytest.approx(90e-9),
        "d2h": pytest.approx(80e-9), "h2d": pytest.approx(30e-9),
        "between_spans": pytest.approx(30e-9)}
    assert sum(got.values()) == pytest.approx(
        trace_reduce.window_s(PAPER) - trace_reduce.busy_s(PAPER))
    assert trace_reduce.idle_by_span(PAPER)[0][0] == "wait"


def test_a_trace_without_spans_or_device_is_an_error_not_a_zero():
    with pytest.raises(ValueError, match="no bm: span"):
        trace_reduce.window_ns({"planes": [PAPER["planes"][0]]})
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.busy_s({"planes": [PAPER["planes"][1]]})


@pytest.mark.parametrize("name,chips", [("trace_served_echo", 1),
                                        ("trace_mesh_exchange", 4)])
def test_the_recorded_traces_reduce(name, chips):
    with gzip.open(DATA / f"{name}.json.gz", "rt") as f:
        trace = json.load(f)
    assert trace_reduce.chips_traced(trace) == chips
    busy, window = trace_reduce.busy_s(trace), trace_reduce.window_s(trace)
    assert 0 < busy < window
    idle = trace_reduce.idle_by_span(trace)
    assert sum(s for _, s in idle) <= window - busy / chips + 1e-9
    assert trace_reduce.top_device_ops(trace)[0][1] > 0


def _samples():
    doc = json.loads((DATA / "samples.json").read_text())
    n, stride = doc["count"], doc["call_s_ms_stride"]
    call_s = [((i * stride) % n + 1) / 1e3 for i in range(n)]
    assert sorted(call_s) == [i / 1e3 for i in range(1, n + 1)]
    return doc, call_s


def test_median_and_tails_of_the_fixed_samples():
    doc, call_s = _samples()
    want = doc["expect"]
    assert stats.median(call_s) * 1e3 == pytest.approx(want["median_ms"])
    assert stats.tail(call_s, 90.0) * 1e3 == pytest.approx(want["p90_ms"])
    assert stats.tail(call_s, 95.0) * 1e3 == pytest.approx(want["p95_ms"])
    assert stats.percentile([3.0], 99.0) == 3.0


def test_the_highest_percentile_is_the_one_with_ten_samples_beyond_it():
    doc, call_s = _samples()
    assert stats.highest_supported(len(call_s)) == doc["expect"][
        "highest_supported"]
    assert stats.highest_supported(19) is None
    assert stats.highest_supported(20) == 50.0
    assert stats.highest_supported(100) == 90.0
    assert stats.highest_supported(1000) == 99.0
    assert stats.highest_supported(10000) == 99.9
    with pytest.raises(ValueError, match="p99 needs 10 samples beyond"):
        stats.tail(call_s, 99.0)


def test_throughput_arithmetic_and_spread():
    doc, call_s = _samples()
    ev = Evidence(t_open=10.0, t_close=10.0 + doc["window_s"],
                  call_s=call_s, call_end=[10.0 + s for s in call_s],
                  bytes_per_call=doc["bytes_per_call"],
                  attempted=len(call_s), failed=0, correct=True,
                  compiles_in_window=0, spans=Spans(), counters={})
    assert end_to_end.goodput(ev) == pytest.approx(
        doc["expect"]["goodput_gbps"])
    assert end_to_end.call_rate(ev) == pytest.approx(
        doc["expect"]["call_rate"])
    assert end_to_end.call_p50(ev) == pytest.approx(100.5e3)
    with pytest.raises(ValueError):
        end_to_end.call_p99(ev)
    with pytest.raises(ValueError):
        stats.per_second(1, 0.0)
    assert stats.spread([9.0, 10.0, 11.0, 10.0]) == pytest.approx(0.05)


def test_spans_are_cut_to_the_window():
    spans = Spans()
    spans.add("d2h", 1.0, 2.0)
    spans.add("d2h", 5.0, 5.5)
    spans.add("h2d", 5.5, 6.0)
    assert spans.durations("d2h") == [1.0, 0.5]
    assert spans.durations("d2h", since=4.0, until=7.0) == [0.5]
    assert spans.total("h2d", 0.0, 5.9) == 0.0
    with spans.span("verify"):
        pass
    assert spans.records[-1][0] == "verify"
