"""The reader of `wire_srv_send_us` (PR 35): the server's own time inside its send call, a call of the method the cell's pipeline calls, on made-up counters and
through the manifest.  Nothing here is a measurement."""

import types

import pytest

from benchmark.manifest import Manifest
from test_rehearsal import ROOT

NAME = "wire_srv_send_us"


def _read(counters: dict):
    reader = Manifest(ROOT).reader(NAME)
    return reader.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("counters", [
    {},
    # The parent: the method's latency recorder, and no phase fold.
    {"rpc_server_Echo.Echo_count": 700.0, "batch_calls_polled": 700.0},
    # The method is registered and answered nothing in the window.
    {"rpc_server_Echo.Echo_calls": 0.0, "rpc_server_Echo.Echo_send_us": 0.0},
    # Only the registry's methods answered: not the pipeline's.
    {"rpc_server_KvReg.LookupMany_calls": 90.0,
     "rpc_server_KvReg.LookupMany_send_us": 900.0},
], ids=["no_counter", "the_parent", "nothing_answered", "another_method"])
def test_without_an_answered_call_of_the_method_it_reads_nothing(counters):
    assert _read(counters) is None


@pytest.mark.parametrize("method, calls, send_us, expected", [
    ("Echo.Echo", 1500, 1500 * 6500, 6500.0),   # 64 MB into the window
    ("Echo.Echo", 29000, 29000 * 4, 4.0),       # a 1 KB frame
    ("Kv.Fetch", 4200 * 61, 4200 * 61 * 20, 20.0),
], ids=["a_one_sided_put", "a_small_frame", "a_record"])
def test_the_reader_divides_the_methods_send_time_by_its_calls(
        method, calls, send_us, expected):
    got = _read({f"rpc_server_{method}_calls": float(calls),
                 f"rpc_server_{method}_send_us": float(send_us),
                 f"rpc_server_{method}_queue_us": 1e9,   # not its business
                 "rpc_server_KvReg.EvictMany_calls": 50.0,
                 "rpc_server_KvReg.EvictMany_send_us": 5e6})
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
