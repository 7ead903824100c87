"""The reader of `wire_resp_leg_us` (PR 35): a call's wire time from the
entry of the handler's `done()` at the server to the entry of its
completion at the caller, on made-up counters and through the manifest;
and the identity the four parts of the wire obey, in the CPU rehearsal of
one served cell on each transport and of one KV cell.  Nothing here is a
measurement."""

import json
import types

import pytest

from benchmark import peaks
from benchmark.manifest import Manifest
from test_rehearsal import ROOT, _rehearse, copy_tree, shrink_traffic

NAME = "wire_resp_leg_us"
PARTS = ("wire_req_leg_us", "wire_srv_queue_us", "wire_srv_handler_us",
         "wire_resp_leg_us")


def _read(counters: dict):
    reader = Manifest(ROOT).reader(NAME)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("counters", [
    {},
    {"batch_calls_polled": 700.0, "batch_wire_us": 700 * 44000.0},
    # A peer on another clock: net is known, its division is not.
    {"batch_split_calls": 700.0, "batch_net_us": 700 * 43000.0,
     "batch_leg_calls": 0.0, "batch_req_leg_us": 0.0},
    # Some calls of the window had their legs and some had not: net and
    # the request's leg are sums over different calls.
    {"batch_split_calls": 700.0, "batch_net_us": 700 * 43000.0,
     "batch_leg_calls": 650.0, "batch_req_leg_us": 650 * 21000.0},
], ids=["no_counter", "the_parent", "another_clock", "legs_for_some"])
def test_unless_every_split_call_had_its_legs_it_reads_nothing(counters):
    assert _read(counters) is None


@pytest.mark.parametrize("calls, net_us, req_us, expected", [
    (700, 700 * 43000, 700 * 21000, 22000.0),
    (29000, 29000 * 60, 29000 * 25, 35.0),
    (4, 9, 9, 0.0),
], ids=["a_large_body", "a_small_one", "all_of_net_was_the_request"])
def test_the_reader_takes_the_requests_leg_out_of_net(
        calls, net_us, req_us, expected):
    got = _read({"batch_split_calls": float(calls),
                 "batch_leg_calls": float(calls),
                 "batch_net_us": float(net_us),
                 "batch_req_leg_us": float(req_us)})
    assert got == pytest.approx(expected)


def test_the_manifest_lists_it_in_the_cells_whose_calls_ride_the_pipeline():
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


@pytest.mark.parametrize("cell, whole", [
    ("echo_tcp.sync1K", "call_wire_us"),
    ("echo_shm.small1K", "call_wire_us"),
    ("kv_disagg.layerwise_d4", "kv_record_wire_us"),
])
def test_in_the_rehearsal_the_four_parts_sum_to_the_wire_phase(
        cell, whole, tmp_path, monkeypatch):
    """Loopback and the ring are one host: every polled call is split
    and has its legs, so the four means share a divisor with the wire
    phase's and sum to it."""
    copy_tree(tmp_path)
    shrink_traffic(tmp_path)
    table = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    table["cpu"] = table["TPU v5 lite"]
    (tmp_path / "peaks.json").write_text(json.dumps(table))
    monkeypatch.setattr(peaks, "_TABLE", tmp_path / "peaks.json")
    result, notes = _rehearse(Manifest(tmp_path), cell, trace=True)
    assert result["correct"] is True
    counters = next(n for n in notes if n["note"] == "counters")
    assert (counters["batch_split_calls"] == counters["batch_leg_calls"]
            == counters["batch_calls_polled"] > 0)
    metrics = result["metrics"]
    assert all(metrics[part]["value"] >= 0 for part in PARTS)
    assert sum(metrics[part]["value"] for part in PARTS) == pytest.approx(
        metrics[whole]["value"], rel=1e-9)
    # The server's send is inside the response's leg or the handler.
    assert metrics["wire_srv_send_us"]["value"] >= 0
