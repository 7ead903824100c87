"""The reader of `kvp_hash_lane_share` (PR 38): of the prefix pages
offered, the share whose content hash was walked side by side with
others of their run, on made-up counters, through the manifest and in
the cell's CPU rehearsal.  Nothing here is a measurement."""

import json
import types

import pytest

from benchmark import peaks
from benchmark.manifest import Manifest
from test_rehearsal import ROOT, _rehearse, copy_tree, shrink_traffic

NAME = "kvp_hash_lane_share"
CELL = "kv_prefix.sessions6_zipf"


def _read(counters: dict):
    reader = Manifest(ROOT).reader(NAME)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("counters", [
    {},
    # The parent: pages offered and hashed, and no lanes counter.
    {"kv_prefix_publish_total": 2400.0, "kv_prefix_publish_renewed": 60.0,
     "kv_prefix_hash_us": 12.3e6},
    # The counter and no page offered in the window.
    {"kv_prefix_hash_lanes": 0.0, "kv_prefix_publish_total": 0.0},
], ids=["no_counter", "the_parent", "nothing_offered"])
def test_without_the_lanes_counter_or_a_page_it_reads_nothing(counters):
    assert _read(counters) is None


@pytest.mark.parametrize("counters, expected", [
    # Every piece a lone page: nothing grouped.
    ({"kv_prefix_hash_lanes": 0.0, "kv_prefix_publish_total": 300.0}, 0.0),
    # Pieces of 16 and 4 grouped, the two pages a turn appends alone.
    ({"kv_prefix_hash_lanes": 2200.0, "kv_prefix_publish_total": 2400.0,
      "kv_prefix_publish_renewed": 100.0}, 88.0),
    ({"kv_prefix_hash_lanes": 64.0, "kv_prefix_publish_total": 64.0}, 100.0),
], ids=["none_grouped", "the_cell", "all_grouped"])
def test_the_reader_divides_the_pages_grouped_by_the_pages_offered(
        counters, expected):
    got = _read(counters)
    assert got == pytest.approx(expected)
    assert 0.0 <= got <= 100.0


def test_the_manifest_lists_it_in_the_prefix_cell():
    manifest = Manifest(ROOT)
    by_name = {m["name"]: m for m in manifest.doc["per_layer"]}
    entry = by_name[NAME]
    assert entry["workloads"] == [CELL]
    assert entry["layer"] == by_name["kvp_hash_us"]["layer"] == (
        "Prefix store")
    assert (entry["moves"], entry["better"]) == ("goodput", "higher")
    assert entry["source"] == "program_counter"
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT == "%"
    assert {manifest.cell(name).driver_name
            for name in entry["workloads"]} == set(reader.DRIVERS)


def test_the_rehearsal_prints_both_and_its_groups_are_its_pieces(
        tmp_path, monkeypatch):
    """On the CPU the cell publishes pieces of 4 and 1 pages: the pieces
    of 4 are grouped, the lone pages not, and the hash's time is read."""
    copy_tree(tmp_path)
    shrink_traffic(tmp_path)
    table = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    table["cpu"] = table["TPU v5 lite"]
    (tmp_path / "peaks.json").write_text(json.dumps(table))
    monkeypatch.setattr(peaks, "_TABLE", tmp_path / "peaks.json")
    result, notes = _rehearse(Manifest(tmp_path), CELL, seed=2**31 + 38,
                              trace=True)
    counted = next(n for n in notes if n["note"] == "counters")
    assert result["correct"] is True
    offered = (counted["kv_prefix_publish_total"]
               + counted.get("kv_prefix_publish_renewed", 0))
    grouped = counted.get("kv_prefix_hash_lanes", 0)
    assert 0 < grouped < offered and grouped % 4 == 0
    share = result["metrics"][NAME]
    assert share == {"value": pytest.approx(100 * grouped / offered),
                     "unit": "%"}
    assert result["metrics"]["kvp_hash_us"]["value"] > 0
