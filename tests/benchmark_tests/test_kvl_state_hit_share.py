"""The reader of `kvl_state_hit_share`: the share of Match answers with
a state at the run's end that was served, on made-up evidence and
through the manifest. Nothing here is a measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from benchmark.spans import Spans
from test_rehearsal import ROOT

NAME = "kvl_state_hit_share"
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
    {"kv_prefix_match_total": 80.0},
], ids=["no_counter", "no_state_counter"])
def test_without_the_counters_it_reads_nothing(counters):
    assert _read(counters=counters) is None


@pytest.mark.parametrize("counters, expected", [
    ({"kv_prefix_match_total": 80.0, "kv_prefix_match_states": 80.0,
      "kv_prefix_state_fetch_total": 80.0}, 100.0),
    # Two answers short of the run, one state not served.
    ({"kv_prefix_match_total": 80.0, "kv_prefix_match_states": 80.0,
      "kv_prefix_match_states_shallow": 2.0,
      "kv_prefix_state_fetch_total": 77.0}, 100 * 77 / 80),
    ({"kv_prefix_match_total": 80.0, "kv_prefix_match_states": 78.0,
      "kv_prefix_match_states_shallow": 2.0,
      "kv_prefix_state_fetch_total": 79.0}, 100 * 76 / 80),
], ids=["every_turn", "states_not_served", "answers_short"])
def test_it_reads_the_deepest_states_served(counters, expected):
    assert _read(counters=counters) == pytest.approx(expected)
