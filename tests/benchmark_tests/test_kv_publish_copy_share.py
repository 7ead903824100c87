"""The reader of `kv_publish_copy_share` (PR 34): of the bytes of the
pages and sequences a window published, the share that was copied into
the slab first, on made-up counters and through the manifest.  Nothing
here is a measurement."""

import json
import types

import pytest

from benchmark import peaks
from benchmark.manifest import Manifest
from test_rehearsal import ROOT, _rehearse, copy_tree, shrink_traffic

NAME = "kv_publish_copy_share"
BLOCK = 8994816.0        # one page of `kv_disagg`
PAGES = 8257536.0        # a hand-over of `kv_hybrid`: its pages,
STATES = 43417600.0      # and its state snapshots


def _read(counters: dict):
    reader = Manifest(ROOT).reader(NAME)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("counters", [
    {},
    # The parent: the store's counters, and none of where a publish's
    # bytes were served from.
    {"kv_publish_total": 300 * 61.0, "host_view_bytes": 300 * BLOCK},
    # Half an account is none.
    {"kv_publish_copy_bytes": 300 * BLOCK},
    {"kv_publish_in_place_bytes": 300 * BLOCK},
], ids=["no_counter", "the_parent", "copied_only", "in_place_only"])
def test_a_program_without_the_counters_reads_nothing(counters):
    assert _read(counters) is None


def test_a_window_in_which_nothing_was_published_reads_zero():
    assert _read({"kv_publish_copy_bytes": 0.0,
                  "kv_publish_in_place_bytes": 0.0}) == 0.0


@pytest.mark.parametrize("copied, in_place, expected", [
    # The chip: every source is the block its transfer landed in.
    (0.0, 2800 * BLOCK, 0.0),
    # The CPU rehearsal: no view is pending, every source is copied.
    (2800 * BLOCK, 0.0, 100.0),
    # A hand-over's pages came from a view and its states from numpy.
    (1000 * STATES, 1000 * PAGES, 100.0 * STATES / (PAGES + STATES)),
], ids=["all_in_place", "all_copied", "the_states_copied"])
def test_the_reader_divides_the_bytes_copied_by_the_bytes_published(
        copied, in_place, expected):
    got = _read({"kv_publish_copy_bytes": copied,
                 "kv_publish_in_place_bytes": in_place})
    assert got == pytest.approx(expected)
    assert 0.0 <= got <= 100.0


def test_the_manifest_lists_it_in_the_two_kv_cells():
    """The cells that publish; its layer is spelt as the layer's other
    metrics spell it, and its drivers are those cells'."""
    manifest = Manifest(ROOT)
    by_name = {m["name"]: m for m in manifest.doc["per_layer"]}
    entry = by_name[NAME]
    assert entry["workloads"] == ["kv_disagg.layerwise_d4",
                                  "kv_hybrid.handover1k_d2"]
    assert entry["layer"] == by_name["kv_publish_us"]["layer"]
    assert (entry["moves"], entry["better"]) == ("goodput", "lower")
    assert entry["source"] == "program_counter"
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT
    assert {manifest.cell(name).driver_name
            for name in entry["workloads"]} == set(reader.DRIVERS)


@pytest.mark.parametrize("cell", ["kv_disagg.layerwise_d4",
                                  "kv_hybrid.handover1k_d2"])
def test_the_cpu_rehearsal_copies_every_byte_it_publishes(
        cell, tmp_path, monkeypatch):
    """On the CPU dlpack imports every array: no view is pending, no
    source is a block of the host pool, and every page and sequence goes
    through the slab as before."""
    copy_tree(tmp_path)
    shrink_traffic(tmp_path)
    table = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    table["cpu"] = table["TPU v5 lite"]
    (tmp_path / "peaks.json").write_text(json.dumps(table))
    monkeypatch.setattr(peaks, "_TABLE", tmp_path / "peaks.json")
    result, _ = _rehearse(Manifest(tmp_path), cell, trace=True)
    assert result["correct"] is True
    assert result["metrics"][NAME] == {"value": 100.0, "unit": "%"}
