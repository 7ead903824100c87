"""The reader of `wire_srv_queue_us` (PR 35): a call's wire time between its request being whole at the server and its handler being entered, on made-up counters and
through the manifest.  Nothing here is a measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from test_rehearsal import ROOT

NAME = "wire_srv_queue_us"


def _read(counters: dict):
    reader = Manifest(ROOT).reader(NAME)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("counters", [
    {},
    {"batch_calls_polled": 700.0, "batch_wire_us": 700 * 44000.0},
    # An older peer all window: polled calls, none of them split.
    {"batch_calls_polled": 700.0, "batch_split_calls": 0.0,
     "batch_srv_queue_us": 0.0},
], ids=["no_counter", "the_parent", "an_older_peer"])
def test_without_a_split_call_it_reads_nothing(counters):
    assert _read(counters) is None


@pytest.mark.parametrize("calls, queue_us, expected", [
    (4200 * 61, 4200 * 61 * 300, 300.0),   # 61 requests arrive together
    (29000, 29000 * 12, 12.0),
    (7, 0, 0.0),                           # handlers entered at once
], ids=["a_burst", "one_at_a_time", "no_queue"])
def test_the_reader_divides_the_queue_time_by_the_split_calls(
        calls, queue_us, expected):
    got = _read({"batch_split_calls": float(calls),
                 "batch_srv_queue_us": float(queue_us),
                 "batch_calls_polled": calls + 9.0})   # not its divisor
    assert got == pytest.approx(expected)


def test_the_manifest_lists_it_in_the_cells_whose_calls_ride_the_pipeline():
    """The eight served cells that print `call_wire_us` and the two KV
    cells; its layer is spelt as the layer's other metrics spell it, and
    its drivers are those cells'."""
    manifest = Manifest(ROOT)
    by_name = {m["name"]: m for m in manifest.doc["per_layer"]}
    entry = by_name[NAME]
    assert entry["workloads"] == (
        by_name["call_wire_us"]["workloads"]
        + ["kv_disagg.layerwise_d4", "kv_hybrid.handover1k_d2"])
    assert not {"mesh_nton.exchange64M", "stream_echo.chunk4M_o6"} & set(
        entry["workloads"])
    assert entry["layer"] == by_name["call_queue_us"]["layer"]
    assert (entry["moves"], entry["better"]) == ("call_p50", "lower")
    assert entry["source"] == "program_counter"
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT == "us"
    assert {manifest.cell(name).driver_name
            for name in entry["workloads"]} == set(reader.DRIVERS)
