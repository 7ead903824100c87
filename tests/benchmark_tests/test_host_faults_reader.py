"""The reader of `host_faults_per_mb` (PR 28): the process's minor page
faults over the MB of request bytes the stager waited for, on made-up
counters and through the manifest.  Nothing here is a measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from test_rehearsal import ROOT

NAME = "host_faults_per_mb"
MB64 = float(64 << 20)


def _read(counters: dict):
    reader = Manifest(ROOT).reader(NAME)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("counters", [
    {},
    # The parent: the stager's counters without the process's.
    {"batch_stage_fetch_bytes": 300 * MB64, "batch_stage_fetch_us": 2e7},
    # A process that serves nothing through the batch pipeline.
    {"process_faults_minor": 5e6},
], ids=["neither", "no_process_faults_minor", "no_batch_stage_fetch_bytes"])
def test_a_program_without_either_counter_reads_nothing(counters):
    assert _read(counters) is None


def test_nothing_staged_reads_zero_as_the_fetch_stream_rate_does():
    # The CPU rehearsal: dlpack imports every array, the stager waits for
    # no byte, and the faults of the window are not a request's.
    assert _read({"process_faults_minor": 1234.0,
                  "batch_stage_fetch_bytes": 0.0}) == 0.0


@pytest.mark.parametrize("faults, calls, expected", [
    # Every 4 KB page of every 64 MB request touched for the first time.
    (16384.0 * 300, 300, 244.140625),
    # Recycled blocks: what is left is someone else's.
    (1500.0, 300, 1500 / (300 * 67.108864)),
], ids=["fresh_pages", "recycled"])
def test_the_reader_divides_faults_by_megabytes_staged(
        faults, calls, expected):
    got = _read({"process_faults_minor": faults,
                 "batch_stage_fetch_bytes": calls * MB64})
    assert got == pytest.approx(expected)


def test_the_manifest_lists_it_where_the_fetch_stream_rate_is_listed():
    """Its cells are those of `stage_d2h_rate` (the served cells whose
    requests are wide enough for a block to matter), it moves what that
    moves, and its layer is spelt as the layer's other metrics spell it."""
    doc = Manifest(ROOT).doc
    by_name = {m["name"]: m for m in doc["per_layer"]}
    entry, rate = by_name[NAME], by_name["stage_d2h_rate"]
    assert entry["workloads"] == rate["workloads"]
    assert (entry["layer"], entry["moves"]) == (rate["layer"], rate["moves"])
    assert entry["unit"] == Manifest(ROOT).reader(NAME).UNIT
    assert entry["better"] == "lower"
    assert doc["per_layer"][-1] is entry
