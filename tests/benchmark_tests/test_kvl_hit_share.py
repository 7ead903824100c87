"""The reader of `kvl_hit_share`: the pages served over the prompt pages
asked, on made-up evidence and through the manifest. Nothing here is a
measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from benchmark.spans import Spans
from test_rehearsal import ROOT

NAME = "kvl_hit_share"
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
        "Prefix store", "goodput", "higher", "program_counter")
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT == "%"
    assert reader.DRIVERS == ("kv_prefix_linear",)


@pytest.mark.parametrize("counters", [
    {},
    {"kv_prefix_match_keys": 1004.0, "kv_prefix_fetch_total": 1001.0},
], ids=["no_counter", "no_state_counter"])
def test_without_the_counters_it_reads_nothing(counters):
    assert _read(counters=counters) is None


def test_it_leaves_the_states_out_of_the_pages_served():
    got = _read(counters={"kv_prefix_match_keys": 1004.0,
                          "kv_prefix_fetch_total": 1001.0,
                          "kv_prefix_state_fetch_total": 1.0})
    assert got == pytest.approx(100 * 1000 / 1004)
