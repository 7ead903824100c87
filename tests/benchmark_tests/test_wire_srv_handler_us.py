"""The reader of `wire_srv_handler_us` (PR 35): a call's wire time inside the server's handler, on made-up counters and
through the manifest.  Nothing here is a measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from test_rehearsal import ROOT

NAME = "wire_srv_handler_us"


def _read(counters: dict):
    reader = Manifest(ROOT).reader(NAME)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("counters", [
    {},
    {"batch_calls_polled": 700.0, "batch_wire_us": 700 * 44000.0},
    {"batch_calls_polled": 700.0, "batch_split_calls": 0.0,
     "batch_srv_handler_us": 0.0},
], ids=["no_counter", "the_parent", "an_older_peer"])
def test_without_a_split_call_it_reads_nothing(counters):
    assert _read(counters) is None


@pytest.mark.parametrize("calls, handler_us, expected", [
    (29000, 29000 * 3, 3.0),             # the native echo
    (4200 * 61, 4200 * 61 * 40, 40.0),   # Kv.Fetch's lookup and pin
    (5, 0, 0.0),
], ids=["the_echo", "a_fetch", "under_a_microsecond"])
def test_the_reader_divides_the_handlers_time_by_the_split_calls(
        calls, handler_us, expected):
    got = _read({"batch_split_calls": float(calls),
                 "batch_srv_handler_us": float(handler_us),
                 "batch_leg_calls": 0.0})   # the legs are not its business
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
