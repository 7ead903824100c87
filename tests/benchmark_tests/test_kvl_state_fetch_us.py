"""The reader of `kvl_state_fetch_us`: the median time from a turn's
first fetch round to its state's landing, on made-up evidence and
through the manifest. Nothing here is a measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from benchmark.spans import Spans
from test_rehearsal import ROOT

NAME = "kvl_state_fetch_us"
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
        "Transport", "call_p50", "lower", "program_span")
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT == "us"
    assert reader.DRIVERS == ("kv_prefix_linear",)


def test_without_a_state_fetched_it_reads_nothing():
    assert _read() is None


def test_it_reads_the_median_interval():
    spans = Spans()
    for i, d in enumerate((0.015, 0.016, 0.03)):
        spans.add("state_fetch", i, i + d)
    spans.add("fetch", 0.0, 0.5)
    assert _read(spans=spans) == pytest.approx(16000.0)
