"""The reader of `strm_one_sided_share` (PR 36): the stream's payload
bytes whose chunk rode the connection's one-sided window over all it
wrote, on made-up counters and through the manifest.  Nothing here is a
measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from test_rehearsal import ROOT

NAME = "strm_one_sided_share"
CHUNK = 4194304.0


def _read(counters: dict):
    reader = Manifest(ROOT).reader(NAME)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("counters", [
    {},
    # Registered, and no chunk written in the window.
    {"stream_bytes_written": 0.0, "stream_one_sided_bytes": 0.0},
    # The unary path's own counter says nothing of a stream.
    {"rma_tx_bytes": 64 * CHUNK},
], ids=["no_counter", "nothing_written", "unary_bodies_only"])
def test_without_a_written_chunk_it_reads_nothing(counters):
    assert _read(counters) is None


@pytest.mark.parametrize("counters, expected", [
    # The parent: it counts what it wrote and has no one-sided counter.
    ({"stream_bytes_written": 26000 * CHUNK,
      "stream_chunks_written": 26000.0}, 0.0),
    # tcp, or every chunk under the threshold: the counter is there, at 0.
    ({"stream_bytes_written": 26000 * CHUNK,
      "stream_one_sided_bytes": 0.0}, 0.0),
    # Every chunk of both directions through the window.
    ({"stream_bytes_written": 26000 * CHUNK,
      "stream_one_sided_bytes": 26000 * CHUNK}, 100.0),
    # One chunk in eight found the window full and went in band; the
    # unary plane's bytes are not the stream's.
    ({"stream_bytes_written": 8000 * CHUNK,
      "stream_one_sided_bytes": 7000 * CHUNK,
      "rma_tx_bytes": 9000 * CHUNK, "rma_window_full": 1000.0}, 87.5),
], ids=["the_parent", "none_one_sided", "all_one_sided", "window_full"])
def test_the_reader_divides_what_the_window_took_by_what_was_written(
        counters, expected):
    assert _read(counters) == pytest.approx(expected)


def test_the_manifest_lists_it_in_the_stream_cell():
    manifest = Manifest(ROOT)
    by_name = {m["name"]: m for m in manifest.doc["per_layer"]}
    entry = by_name[NAME]
    assert entry["workloads"] == ["stream_echo.chunk4M_o6"]
    assert entry["layer"] == by_name["strm_write_us"]["layer"] == "Transport"
    assert (entry["moves"], entry["better"]) == ("goodput", "higher")
    assert entry["source"] == "program_counter"
    reader = manifest.reader(NAME)
    assert entry["unit"] == reader.UNIT == "%"
    assert {manifest.cell(name).driver_name
            for name in entry["workloads"]} == set(reader.DRIVERS)
