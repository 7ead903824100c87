"""The reader of `kvp_hash_us` (PR 38): the publisher's time in the
content hash per prefix page offered, on made-up counters and through
the manifest.  Nothing here is a measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from test_rehearsal import ROOT

NAME = "kvp_hash_us"
CELL = "kv_prefix.sessions6_zipf"


def _read(counters: dict):
    reader = Manifest(ROOT).reader(NAME)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("counters", [
    {},
    # Pages offered and no hash counter.
    {"kv_prefix_publish_total": 2400.0, "kv_prefix_publish_renewed": 60.0},
    # The hash counter and no page offered in the window.
    {"kv_prefix_hash_us": 0.0, "kv_prefix_publish_total": 0.0},
], ids=["no_counter", "no_hash_counter", "nothing_offered"])
def test_without_a_page_and_its_hash_it_reads_nothing(counters):
    assert _read(counters) is None


@pytest.mark.parametrize("counters, expected", [
    # The parent: every page hashed alone, 5 ms each.
    ({"kv_prefix_hash_us": 2460 * 5000.0,
      "kv_prefix_publish_total": 2400.0,
      "kv_prefix_publish_renewed": 60.0}, 5000.0),
    # Four at a time: a group's time once, for four pages.
    ({"kv_prefix_hash_us": 600 * 7600.0 + 60 * 5000.0,
      "kv_prefix_publish_total": 2460.0,
      "kv_prefix_hash_lanes": 2400.0}, (600 * 7600.0 + 60 * 5000.0) / 2460),
], ids=["the_parent", "four_lanes"])
def test_the_reader_divides_the_hash_time_by_the_pages_offered(
        counters, expected):
    assert _read(counters) == pytest.approx(expected)


def test_the_manifest_lists_it_in_the_prefix_cell():
    manifest = Manifest(ROOT)
    by_name = {m["name"]: m for m in manifest.doc["per_layer"]}
    entry = by_name[NAME]
    assert entry["workloads"] == [CELL]
    assert entry["layer"] == by_name["kvp_hit_share"]["layer"] == (
        "Prefix store")
    assert (entry["moves"], entry["better"]) == ("goodput", "lower")
    assert entry["source"] == "program_counter"
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT == "us"
    assert {manifest.cell(name).driver_name
            for name in entry["workloads"]} == set(reader.DRIVERS)
