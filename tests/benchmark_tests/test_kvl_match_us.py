"""The reader of `kvl_match_us`: the median time of a turn's
`KvClient.match_prefix`, on made-up evidence and through the manifest.
Nothing here is a measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from benchmark.spans import Spans
from test_rehearsal import ROOT

NAME = "kvl_match_us"
CELL = "kv_prefix_linear.agent4_128k_replay"


def _read(**fields):
    ev = dict(counters={}, spans=Spans(), trace=None, t_open=0.0,
              t_close=10.0, call_s=[], bytes_per_call=0.0,
              device_kind="TPU v5 lite", notes={})
    ev.update(fields)
    return Manifest(ROOT).reader(NAME).read(types.SimpleNamespace(**ev))


def test_the_manifest_lists_it_in_the_hybrid_prefix_cell():
    manifest = Manifest(ROOT)
    entry = next(m for m in manifest.doc["per_layer"] if m["name"] == NAME)
    assert entry["workloads"] == [CELL]
    assert (entry["layer"], entry["moves"], entry["better"],
            entry["source"]) == (
        "Channel/Socket/dispatcher", "call_p50", "lower", "program_span")
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT == "us"
    assert reader.DRIVERS == ("kv_prefix_linear",)


def _spans(*durations):
    spans = Spans()
    for i, d in enumerate(durations):
        spans.add("match", i, i + d)
    return spans


def test_without_a_match_in_the_window_it_reads_nothing():
    assert _read() is None
    assert _read(spans=_spans(0.01), t_open=5.0) is None


def test_it_reads_the_median_match():
    assert _read(spans=_spans(0.004, 0.006, 0.02)) == pytest.approx(6000.0)
