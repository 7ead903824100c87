"""The reader of `kvl_fetch_us`: the fetch time per page restored, on
made-up evidence and through the manifest. Nothing here is a
measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from benchmark.spans import Spans
from test_rehearsal import ROOT

NAME = "kvl_fetch_us"
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


def _spans(total):
    spans = Spans()
    spans.add("fetch", 1.0, 1.0 + total / 2)
    spans.add("fetch", 2.0, 2.0 + total / 2)
    return spans


@pytest.mark.parametrize("counters", [
    {},
    # A parent without the state counter.
    {"kv_prefix_fetch_total": 1001.0},
    # Only the state was served.
    {"kv_prefix_fetch_total": 1.0, "kv_prefix_state_fetch_total": 1.0},
], ids=["no_counter", "no_state_counter", "no_page"])
def test_without_a_page_restored_it_reads_nothing(counters):
    assert _read(counters=counters, spans=_spans(0.4)) is None


def test_it_divides_the_fetch_time_by_the_pages_not_the_states():
    got = _read(spans=_spans(0.34),
                counters={"kv_prefix_fetch_total": 1001.0,
                          "kv_prefix_state_fetch_total": 1.0})
    assert got == pytest.approx(340.0)
