"""The reader of `kvl_land_copy_share`: the share of the fetched bytes
copied out on landing, on made-up evidence and through the manifest.
Nothing here is a measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from benchmark.spans import Spans
from test_rehearsal import ROOT

NAME = "kvl_land_copy_share"
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
        "Transport", "goodput", "lower", "program_counter")
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT == "%"
    assert reader.DRIVERS == ("kv_prefix_linear",)


def test_without_responses_it_reads_nothing():
    assert _read() is None
    assert _read(counters={"batch_resp_bytes": 0.0}) is None


def test_it_divides_the_copied_bytes_by_the_response_bytes():
    got = _read(counters={"batch_resp_bytes": 1000.0,
                          "batch_land_copy_bytes": 960.0})
    assert got == pytest.approx(96.0)
