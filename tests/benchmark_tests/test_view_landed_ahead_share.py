"""The reader of `view_landed_ahead_share` (PR 32): of the bytes of the
device-to-host views a window's callers asked for, the share that had
landed already, on made-up counters and through the manifest.  Nothing
here is a measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from test_rehearsal import ROOT

NAME = "view_landed_ahead_share"
BLOCK = 8994816.0        # one page of `kv_disagg`
SEQUENCE = 51675136.0    # one hand-over of `kv_hybrid`, two views


def _read(counters: dict):
    reader = Manifest(ROOT).reader(NAME)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("counters", [
    {},
    # The parent: a pool, a stager, and no account of the views.
    {"batch_stage_fetch_bytes": 300 * BLOCK, "process_faults_minor": 5e6},
    # Half an account is none.
    {"host_view_bytes": 300 * BLOCK},
], ids=["no_counter", "the_parent", "half_an_account"])
def test_a_program_without_the_counters_reads_nothing(counters):
    assert _read(counters) is None


def test_a_window_in_which_no_view_was_asked_for_reads_zero():
    # The CPU rehearsal: dlpack imports every array, so nothing is a view;
    # the counters are there from the library's load on.
    assert _read({"host_view_bytes": 0.0, "host_view_ahead_bytes": 0.0,
                  "host_view_wait_us": 0.0,
                  "host_view_transfer_us": 0.0}) == 0.0


@pytest.mark.parametrize("asked, ahead, expected", [
    # Every transfer was seen through before its caller came back.
    (2800 * BLOCK, 2800 * BLOCK, 100.0),
    # Nobody waited: every first resolve() found the bytes on their way.
    (2800 * BLOCK, 0.0, 0.0),
    # A hand-over's pages were there and its states were not.
    (1000 * SEQUENCE, 1000 * 8257536.0, 100.0 * 8257536 / 51675136),
], ids=["all_ahead", "none_ahead", "the_pages_only"])
def test_the_reader_divides_the_bytes_ahead_by_the_bytes_asked_for(
        asked, ahead, expected):
    got = _read({"host_view_bytes": asked, "host_view_ahead_bytes": ahead,
                 "host_view_wait_us": 1.0, "host_view_transfer_us": 1.0})
    assert got == pytest.approx(expected)
    assert 0.0 <= got <= 100.0


def test_the_manifest_lists_it_in_the_two_kv_cells():
    """The cells whose client thread starts a transfer ahead and comes
    back for it; the served cells' stager waits at once and lists it not.
    Its layer is spelt as the layer's other metrics spell it, and its
    drivers are those cells'."""
    manifest = Manifest(ROOT)
    doc = manifest.doc
    by_name = {m["name"]: m for m in doc["per_layer"]}
    entry = by_name[NAME]
    assert entry["workloads"] == ["kv_disagg.layerwise_d4",
                                  "kv_hybrid.handover1k_d2"]
    assert entry["layer"] == by_name["kv_d2h_rate"]["layer"]
    assert (entry["moves"], entry["better"]) == ("goodput", "higher")
    assert entry["source"] == "program_counter"
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT
    assert {manifest.cell(name).driver_name
            for name in entry["workloads"]} == set(reader.DRIVERS)
