"""The reader of `kvl_h2d_rate`: the restored bytes over the time of
their `device_put`s, on made-up evidence and through the manifest.
Nothing here is a measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from benchmark.spans import Spans
from test_rehearsal import ROOT

NAME = "kvl_h2d_rate"
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
        "D2H/H2D staging", "goodput", "higher", "program_span")
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT == "GB/s"
    assert reader.DRIVERS == ("kv_prefix_linear",)


def test_without_turns_or_transfers_it_reads_nothing():
    assert _read() is None
    spans = Spans()
    spans.add("h2d", 1.0, 2.0)
    assert _read(spans=spans) is None


def test_it_counts_the_pages_and_the_states_transfers():
    spans = Spans()
    spans.add("h2d", 1.0, 1.15)
    spans.add("h2d", 2.0, 2.15)
    spans.add("state_h2d", 3.0, 3.1)
    got = _read(spans=spans, bytes_per_call=1.2e9, call_s=[0.5, 0.5])
    assert got == pytest.approx(2.4e9 / 0.4 / 1e9)
