"""The reader of `kvl_publish_us`: the time to publish one prefilled
page, on made-up evidence and through the manifest. Nothing here is a
measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from benchmark.spans import Spans
from test_rehearsal import ROOT

NAME = "kvl_publish_us"
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
        "D2H/H2D staging", "goodput", "lower", "program_span")
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT == "us"
    assert reader.DRIVERS == ("kv_prefix_linear",)


def _spans():
    spans = Spans()
    spans.add("d2h_wait", 1.0, 1.001)
    spans.add("publish", 1.001, 1.009)
    spans.add("state_publish", 2.0, 2.02)
    return spans


@pytest.mark.parametrize("counters", [
    {},
    {"kv_prefix_publish_total": 5.0},
    {"kv_prefix_publish_total": 1.0, "kv_prefix_state_publish_total": 1.0},
], ids=["no_counter", "no_state_counter", "only_a_state"])
def test_without_a_page_offered_it_reads_nothing(counters):
    assert _read(counters=counters, spans=_spans()) is None


def test_it_divides_by_the_pages_offered_not_the_states():
    got = _read(spans=_spans(),
                counters={"kv_prefix_publish_total": 5.0,
                          "kv_prefix_state_publish_total": 1.0})
    assert got == pytest.approx(9000.0 / 4)
