"""The reader of `land_fanout_share` (PR 30): the response bytes whose
landing copy ran on more than one rail over all response bytes, on
made-up counters and through the manifest.  Nothing here is a
measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from test_rehearsal import ROOT

NAME = "land_fanout_share"
MB64 = float(64 << 20)


def _read(counters: dict):
    reader = Manifest(ROOT).reader(NAME)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("counters", [
    {},
    # A window in which no call was polled with status 0.
    {"batch_resp_bytes": 0.0, "batch_land_fanout_bytes": 0.0},
    # A process that serves nothing through the batch pipeline.
    {"rma_tx_bytes": 5e9},
], ids=["no_counter", "no_response_bytes", "no_batch_pipeline"])
def test_without_response_bytes_it_reads_nothing(counters):
    assert _read(counters) is None


def test_a_program_without_the_counter_reads_zero():
    # The parent: responses, a landing copy, and no rails under it.
    assert _read({"batch_resp_bytes": 300 * MB64,
                  "batch_land_copy_bytes": 300 * MB64}) == 0.0


@pytest.mark.parametrize("fanned, calls, expected", [
    # shm, one call at a time: every response is a window span.
    (300, 300, 100.0),
    # shm at depth 8: one response in five found the window full and
    # came striped, in place.
    (2400, 3000, 80.0),
    # tcp, or the rehearsal's 1 MB bodies: no span, no rails.
    (0, 300, 0.0),
], ids=["every_response", "window_full_now_and_then", "no_span"])
def test_the_reader_divides_fanned_bytes_by_response_bytes(
        fanned, calls, expected):
    got = _read({"batch_land_fanout_bytes": fanned * MB64,
                 "batch_resp_bytes": calls * MB64})
    assert got == pytest.approx(expected)


def test_the_manifest_lists_it_where_the_landing_copy_share_is_listed():
    """Its cells are those of `land_copy_share` (the fan-out is a way of
    making that copy), it moves what that moves, and its layer is spelt
    as the layer's other metrics spell it."""
    doc = Manifest(ROOT).doc
    by_name = {m["name"]: m for m in doc["per_layer"]}
    entry, copy = by_name[NAME], by_name["land_copy_share"]
    assert entry["workloads"] == copy["workloads"]
    assert (entry["layer"], entry["moves"]) == (copy["layer"], copy["moves"])
    assert entry["unit"] == Manifest(ROOT).reader(NAME).UNIT
    assert entry["better"] == "higher"
