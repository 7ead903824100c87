"""The reader of `wire_req_leg_us` (PR 35): a call's wire time from its issue to its request being whole at the server, on made-up counters and
through the manifest.  Nothing here is a measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from test_rehearsal import ROOT

NAME = "wire_req_leg_us"


def _read(counters: dict):
    reader = Manifest(ROOT).reader(NAME)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("counters", [
    {},
    # The parent: the phase clocks, and no stamp from the server.
    {"batch_calls_polled": 700.0, "batch_wire_us": 700 * 44000.0},
    # A peer on another clock: split, and no leg.
    {"batch_split_calls": 700.0, "batch_net_us": 700 * 43000.0,
     "batch_leg_calls": 0.0, "batch_req_leg_us": 0.0},
], ids=["no_counter", "the_parent", "another_clock"])
def test_without_a_call_that_had_its_legs_it_reads_nothing(counters):
    assert _read(counters) is None


@pytest.mark.parametrize("calls, leg_us, expected", [
    (700, 700 * 21500, 21500.0),     # 64 MB over tcp, one at a time
    (29000, 29000 * 25, 25.0),       # 1 KB over the ring
    (3, 10, 10 / 3),
], ids=["a_large_body", "a_small_one", "not_whole_microseconds"])
def test_the_reader_divides_the_legs_time_by_the_calls_that_had_one(
        calls, leg_us, expected):
    got = _read({"batch_leg_calls": float(calls),
                 "batch_req_leg_us": float(leg_us),
                 "batch_split_calls": calls + 5.0})   # not its divisor
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
    assert entry["layer"] == by_name["call_wire_us"]["layer"]
    assert (entry["moves"], entry["better"]) == ("call_p50", "lower")
    assert entry["source"] == "program_counter"
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT == "us"
    assert {manifest.cell(name).driver_name
            for name in entry["workloads"]} == set(reader.DRIVERS)
